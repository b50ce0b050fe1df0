"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call = wall time of the
producing run; derived = the paper-comparable metric).

  PYTHONPATH=src python -m benchmarks.run [--short] [--only fig6,tab4,...]
"""
from __future__ import annotations

import argparse
import sys

from repro.launch.compile_cache import enable_compile_cache

#: Row-name prefix emitted by each paper_tables benchmark; used to decide
#: whether a --only filter can skip the (expensive) benchmark entirely.
_ROW_PREFIX = {
    "fig1_fig6_mixed_throughput": "fig6",
    "fig2_placement": "fig2",
    "tab3_latency": "tab3",
    "fig7_oversubscription": "fig7",
    "fig8_weighted_groups": "fig8",
    "tab4_priority_inversion": "tab4",
    "fig9_schbench": "fig9",
    "sec67_hint_overhead": "sec67",
}


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--short", action="store_true",
                    help="shorter sim windows (CI mode)")
    ap.add_argument("--only", default=None,
                    help="comma-separated prefixes (fig6, fig2, tab3, fig7, "
                         "fig8, fig9, tab4, sec67, fig10)")
    ap.add_argument("--skip-live", action="store_true",
                    help="skip the live-JAX fig10 benchmark")
    ap.add_argument("--trace-sample", default=None, metavar="PATH",
                    help="also export a schema-validated Chrome trace of a "
                         "small mixed sim run to PATH (CI artifact)")
    args = ap.parse_args()

    if args.trace_sample:
        from repro.core import trace as trace_mod
        trace_mod.main(["--out", args.trace_sample])

    from . import paper_tables
    # (bench fn, row-name prefix): --only skips non-matching benchmarks
    # *before* running them, not just when printing their rows.
    benches = [(fn, _ROW_PREFIX.get(fn.__name__)) for fn in paper_tables.ALL]
    if not args.skip_live:
        from . import fig10_ml, parity
        benches.append((fig10_ml.run, "fig10"))
        benches.append((parity.run, "parity"))

    only = args.only.split(",") if args.only else None
    print("name,us_per_call,derived")
    for fn, prefix in benches:
        # Unknown prefix (new benchmark not yet registered): always run it
        # and let the row-level filter decide.
        if only and prefix is not None and not any(
                p.startswith(prefix) or prefix.startswith(p) for p in only):
            continue
        try:
            rows = fn(short=args.short)
        except Exception as e:  # noqa: BLE001
            print(f"{fn.__name__},0,ERROR:{type(e).__name__}:{e}", flush=True)
            continue
        for name, us, derived in rows:
            if only and not any(name.startswith(p) for p in only):
                continue
            print(f"{name},{us:.0f},{derived}", flush=True)


if __name__ == "__main__":
    main()
