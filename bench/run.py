#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. Prints, as the last line of standard output,
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``: each number compared with the reference beside its limit,
which are also the last lines on standard error. Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints nothing
on standard output.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np  # noqa: E402

import cells  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def end_to_end(rec: dict) -> dict:
    """The values a user of the system sees, by the host's clock."""
    t0, t1 = rec["t0"], rec["t1"]
    gaps = [(b - a) * 1e3 for r in rec["requests"]
            for a, b in zip(r["token_times"], r["token_times"][1:])
            if t0 <= b <= t1]
    out = {"setup_s": rec["setup_s"]}
    # Time to first token from when each request was due, over every
    # interactive request due in the window (drained after it).
    ttft = [(r["first_token"] - r["due"]) * 1e3 for r in rec["requests"]
            if r["ok"]]
    if ttft:
        out["ttft_p95_ms"] = float(np.percentile(ttft, 95))
    if gaps:
        out["itl_p50_ms"] = float(np.percentile(gaps, 50))
        out["itl_p95_ms"] = float(np.percentile(gaps, 95))
    if rec["background_tokens"] is not None:
        out["background_tokens_per_s"] = rec["background_tokens"] / rec["seconds"]
    return out


def record(cell: dict, res: dict) -> dict:
    """What a per-layer metric's reader gets: the window's record, the
    architecture module (its counts) with its sizes, the chip's peaks and
    the traffic mix."""
    return dict(res["rec"], arch=cells.arch_module(cell["config"]),
                dm=res["dm"], peaks=res["peaks"], traffic=cell["traffic"])


def result(cell: dict, res: dict, trace: bool) -> dict:
    rec, checks = res["rec"], res["checks"]
    reqs = rec["requests"]
    failed = sum(1 for r in reqs if not r["ok"])
    correct = failed == 0 and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    dev = res["device"]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": res["n_devices"],
              "memory_peak_bytes": res["memory_peak_bytes"]}
    out = {"correct": correct, "attempted": len(reqs), "failed": failed}
    if not trace:
        values = end_to_end(rec)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"] if m["name"] in values}
    else:
        red = rec["trace"]["reduced"]
        # Both on the device's clock, over the whole programs the trace
        # holds (``xplane.reduce`` leaves out the two it may have cut).
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["span_s"]
        rd = record(cell, res)
        metrics = {}
        for m in cell["per_layer"]:
            v = cells.metric_reader(m["name"])(rd)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out.update(metrics=metrics, device=device)
    if trace:
        out["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = cells.resolve(args.workload)
    import serve_cell
    res = serve_cell.run(cell, args.seed, args.seconds, bool(args.trace),
                         T_START)
    out = result(cell, res, bool(args.trace))
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
