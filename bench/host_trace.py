#!/usr/bin/env python3
"""The cell's host path on the scheduler tracer's clock, on the chip.

    python3 bench/host_trace.py --workload <name> --seeds 1,2,3 \\
        [--seconds 51] [--profile 1]

Runs the cell's window as ``bench/run.py`` does (the reference check
skipped), with a ``SchedTracer`` attached to the kernel, so that the
core's events and the engine's spans of each decode chunk are recorded.

``--profile 1``: a ``--trace 1`` run's profiler window, and just inside
either end of it a host-plane annotation around a read of the tracer's
clock. Right after the profiler stops, the tracer's events are copied out
of its ring, which has to reach back to the first anchor. Prints one JSON
line per seed: the per-layer metrics of the engine's spans beside those
``bench/run.py --trace 1`` prints, the device's idle time by what the
slot's worker was doing (``idle_by_host``, with the longest idle gap),
the clock checks and the parts of a token gap against the gap. Writes the
tracer's events, the anchors and the host-plane events to
``bench/_out/host_trace.json`` for ``tests/record_host_trace.py``.

``--profile 0``: what the tracer costs. Per seed, the window twice with
the profiler off, with and without the tracer, the order alternating from
seed to seed; one JSON line per window with the end-to-end metrics, then
each seed's ``itl_p50_ms`` with the tracer less without, and their median.

``bench/run.py`` attaches no tracer: this tool puts one on the kernel
that ``serve_cell.run`` builds, and the anchors on the profiler's start
and stop, by wrapping ``repro.core.build_kernel`` and
``jax.profiler.start_trace`` / ``stop_trace`` while the window runs.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import glob
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import cells  # noqa: E402
import hostspans  # noqa: E402
import run as bench_run  # noqa: E402
import serve_cell  # noqa: E402
import xplane  # noqa: E402

HOST_TRACE = cells.BENCH / "_out" / "host_trace.json"
READ = ("engine.host_ms_per_step", "engine.dispatch_ms_per_step",
        "engine.sync_ms_per_step", "engine.merge_ms_per_step",
        "sched.decode_requeue_p95_ms", "device.idle_share.mix",
        "device.idle_share.decode_host")


def kept_events(tracer, t0: float) -> list:
    """The tracer's events, as ``TraceEvent.to_dict`` gives them; raises
    where the ring no longer reaches back to ``t0``."""
    events = tracer.events
    if events and events[0].t > t0:
        raise RuntimeError(f"the scheduler tracer's ring ({tracer.capacity} "
                           f"events) wrapped past the traced window's start")
    return [e.to_dict() for e in events]


@contextlib.contextmanager
def attached(jax, state: dict, tracer: bool):
    """While open, kernels that ``repro.core.build_kernel`` builds get a
    ``SchedTracer`` where ``tracer`` says, and the profiler's start and
    stop take the clock anchors and copy the tracer's events into
    ``state`` (``anchors``, ``events``; ``error`` if the copy failed)."""
    import repro.core as core
    from repro.core import SchedTracer
    build, start, stop = (core.build_kernel, jax.profiler.start_trace,
                          jax.profiler.stop_trace)

    def anchor() -> float:
        with jax.profiler.TraceAnnotation(hostspans.ANCHOR):
            return state["kernel"].now

    def build_kernel(*a, **kw):
        kw["tracer"] = SchedTracer() if tracer else None
        state["kernel"] = build(*a, **kw)
        return state["kernel"]

    def start_trace(*a, **kw):
        start(*a, **kw)
        state["anchors"] = [anchor()]

    def stop_trace():
        state["anchors"].append(anchor())
        stop()
        try:
            state["events"] = kept_events(state["kernel"].tracer,
                                          state["anchors"][0])
        except RuntimeError as e:     # raised again by the caller
            state["error"] = e

    core.build_kernel = build_kernel
    jax.profiler.start_trace, jax.profiler.stop_trace = start_trace, stop_trace
    try:
        yield state
    finally:
        core.build_kernel = build
        jax.profiler.start_trace, jax.profiler.stop_trace = start, stop
        # The kernel reaches the engine's caches and weights through
        # reference cycles: free them before the next window's set-up.
        state.pop("kernel", None)
        gc.collect()


def profiled(jax, cell: dict, seed: int, seconds: float) -> dict:
    state: dict = {}
    with attached(jax, state, tracer=True):
        res = serve_cell.run(cell, seed, seconds, True, time.monotonic(),
                             check=False)
    if "error" in state:
        raise state["error"]
    path = glob.glob(str(serve_cell.TRACE_DIR / "**" / "*.xplane.pb"),
                     recursive=True)[0]
    mods = xplane.events_from_xplane(path)["modules"]
    host = hostspans.host_events(path)
    rec = res["rec"]
    tr = rec["trace"]
    tr.update(events=state["events"], anchors=state["anchors"])
    red = tr["reduced"]
    red["busy"] = hostspans.busy(mods)
    red["clock"] = hostspans.clock(mods, host, state["anchors"])
    with open(HOST_TRACE, "w") as f:
        json.dump({"reads": state["anchors"], "events": state["events"],
                   **host}, f)
    record = dict(rec, dm=res["dm"], peaks=res["peaks"],
                  traffic=cell["traffic"])
    starts = [s * 1e-12 for name, s, _ in mods
              if xplane.module_name(name) == "jit_decode_step"]
    span = (red["busy"][0][0] * 1e-12, red["busy"][-1][1] * 1e-12)
    return {"seed": seed,
            "metrics": {m: cells.metric_reader(m)(record) for m in READ},
            **(hostspans.idle_by_host(record) or {}),
            "clock": hostspans.alignment(
                record, [s for s in starts if span[0] <= s <= span[1]]),
            "closure_ms": hostspans.closure(record)}


def cost(jax, cell: dict, seed: int, seconds: float, first: bool) -> float:
    itl = {}
    for tracer in (first, not first):
        with attached(jax, {}, tracer=tracer):
            rec = serve_cell.run(cell, seed, seconds, False, time.monotonic(),
                                 check=False)["rec"]
        values = bench_run.end_to_end(rec)
        itl[tracer] = values["itl_p50_ms"]
        print(json.dumps({"seed": seed, "sched_tracer": tracer, **values}),
              flush=True)
    return itl[True] - itl[False]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--profile", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    import jax
    cell = cells.resolve(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.profile:
        for seed in seeds:
            print(json.dumps(profiled(jax, cell, seed, args.seconds)),
                  flush=True)
        return 0
    diffs = [cost(jax, cell, seed, args.seconds, i % 2 == 0)
             for i, seed in enumerate(seeds)]
    print(json.dumps({"itl_p50_ms_traced_less_untraced": diffs,
                      "median": statistics.median(diffs)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
