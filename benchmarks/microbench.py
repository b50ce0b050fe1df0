"""Microbenchmark harness for the sim scheduling core (BENCH_*.json).

Measures raw simulator throughput -- the hard ceiling on how large a
workload the repro can study (ROADMAP: "run as fast as the hardware
allows") -- across the three policy families and three queue-depth scales:

* ``shallow`` -- the paper's own MIN:MAX shape (8 slots, 8+8 workers);
* ``mixed``   -- oversubscribed mixed tiers (8 slots, 64 bursty + 512 bound);
* ``deep``    -- the deep-queue stress: >= 1k queued jobs per slot plus
  lock-churn driving the hint boost/unboost path, so the per-event cost of
  keyed queue removal, run-end cancellation, and trace overhead dominates.

For each (policy, scale) the sim horizon is split into chunks; each chunk
contributes one wall-time-per-event sample, giving a p50/p99 "dispatch
cost" distribution alongside total events/sec, plus clock-heap and
DSQ-occupancy high-water marks.

Output schema (``BENCH_8.json``, stable field names -- future PRs append
``BENCH_<n>.json`` files to form a trajectory)::

    {
      "schema": "repro.microbench/v1",
      "short": bool,               # CI mode (shorter horizons, smaller deep scale)
      "calib_us": float,           # fixed pure-Python loop wall time: the
                                   # regression gate scales baseline ev/s by
                                   # calib ratio, so a slower CI machine is
                                   # not mistaken for a code regression
      "results": [{
        "name": "ufs.deep",        # <policy>.<scale>
        "policy": "ufs", "scale": "deep",
        "n_slots": int, "horizon": float,
        "events": int,             # clock events processed in the measured span
        "wall_s": float,
        "events_per_sec": float,   # events / wall_s  (the regression-gated figure)
        "dispatch_us": {"p50": float, "p99": float, "mean": float},
        "clock": {"max_live": int, "max_raw": int},   # event-heap occupancy
        "queues": {"max_local": int, "max_group": int},
        "summary_sha256": "...",   # sha256 of Metrics.summary() JSON: must be
      }, ...]                      # machine-independent (sim is deterministic)
    }

Regression gating (used by CI)::

    python -m benchmarks.microbench --short --out BENCH_8.json \
        --baseline BENCH_8.json --max-regression 0.30

compares ``events_per_sec`` per result name against the committed baseline
and exits non-zero if any benchmark regressed by more than the threshold.
``summary_sha256`` values are compared exactly when the baseline was
produced at the same scale settings (same ``short`` flag): the sim is
deterministic, so any drift is a behaviour change, not noise.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import random
import sys
import time
from typing import Iterator, Optional

from repro.core import Job, Tier, build_kernel
from repro.core.metrics import percentile
from repro.core.task import AcquireLock, Block, Burst, ReleaseLock
from repro.core.workloads import bound_worker, bursty_worker
from repro.launch.compile_cache import enable_compile_cache

POLICIES = ("ufs", "vdf", "fifo")
SCALES = ("shallow", "mixed", "deep")
CHUNKS = 50

HOLD_CPU = 0.4e-3     # lock hold burst (background holder)
USE_CPU = 0.1e-3      # lock use burst (time-sensitive waiter)
THINK = 0.5e-3        # waiter think time between acquisitions


# ---------------------------------------------------------------------------
# Lock-churn workloads (the Table-4 inversion micro-experiment, looped):
# each waiter acquisition while the background holder owns the lock fires a
# hint boost, which must *remove* the holder from a deep group DSQ -- the
# keyed-removal hot path.
# ---------------------------------------------------------------------------

def _churn_holder(lock) -> Iterator:
    while True:
        yield AcquireLock(lock)
        yield Burst(HOLD_CPU)
        yield ReleaseLock(lock)


def _churn_waiter(lock, seed: int) -> Iterator:
    rng = random.Random(seed)
    while True:
        yield Block(rng.uniform(0.5 * THINK, 1.5 * THINK))
        yield AcquireLock(lock)
        yield Burst(USE_CPU)
        yield ReleaseLock(lock)


# ---------------------------------------------------------------------------
# Scenario builders
# ---------------------------------------------------------------------------

def _add_jobs(kernel, group, n, mk_behavior, kind, prefix):
    for i in range(n):
        kernel.add_job(Job(group, behavior=mk_behavior(i),
                           name=f"{prefix}-{i}", kind=kind))


def build_scenario(policy: str, scale: str, short: bool):
    """Returns (kernel, n_slots, horizon, warmup)."""
    if scale == "shallow":
        n_slots, horizon = 8, (0.8 if short else 2.0)
        k = build_kernel("sim", policy=policy, n_slots=n_slots)
        ts = k.create_group("ts", Tier.TIME_SENSITIVE, 10_000.0)
        bg = k.create_group("bg", Tier.BACKGROUND, 1.0)
        _add_jobs(k, ts, 8, bursty_worker, "bursty", "ts")
        _add_jobs(k, bg, 8,
                  lambda i: bound_worker(100 + i, query_cpu=0.05), "bound", "bg")
    elif scale == "mixed":
        n_slots, horizon = 8, (0.6 if short else 1.5)
        k = build_kernel("sim", policy=policy, n_slots=n_slots)
        ts = k.create_group("ts", Tier.TIME_SENSITIVE, 10_000.0)
        bg = k.create_group("bg", Tier.BACKGROUND, 1.0)
        _add_jobs(k, ts, 64, bursty_worker, "bursty", "ts")
        _add_jobs(k, bg, 512,
                  lambda i: bound_worker(1000 + i, query_cpu=0.05), "bound", "bg")
    elif scale == "deep":
        # >= 1k queued jobs per slot: a saturating background backlog that
        # every boost must remove from, plus 8 lock-churn pairs driving the
        # boost/unboost path and a light TS foreground keeping wakes alive.
        n_slots = 2
        n_bg = 2048 if short else 8192
        horizon = 0.5 if short else 1.0
        k = build_kernel("sim", policy=policy, n_slots=n_slots)
        ts = k.create_group("ts", Tier.TIME_SENSITIVE, 10_000.0)
        bg = k.create_group("bg", Tier.BACKGROUND, 1.0)
        _add_jobs(k, ts, 4, bursty_worker, "bursty", "ts")
        _add_jobs(k, bg, n_bg,
                  lambda i: bound_worker(2000 + i, query_cpu=0.05), "bound", "bg")
        for p in range(8):
            lock = k.create_lock(f"churn{p}")
            k.add_job(Job(bg, behavior=_churn_holder(lock),
                          name=f"holder-{p}", kind="holder"))
            k.add_job(Job(ts, behavior=_churn_waiter(lock, 9000 + p),
                          name=f"waiter-{p}", kind="waiter"))
    else:
        raise ValueError(f"unknown scale {scale!r}")
    warmup = 0.1 * horizon
    return k, n_slots, horizon, warmup


# ---------------------------------------------------------------------------
# Instrumentation helpers (tolerant of cores without the counters)
# ---------------------------------------------------------------------------

def _events_processed(clock) -> int:
    return getattr(clock, "processed", 0)


def _clock_occupancy(clock) -> tuple:
    raw = getattr(clock, "heap_size", None)
    if raw is None:
        raw = len(getattr(clock, "_heap", ()))
    try:
        live = len(clock)
    except TypeError:
        live = raw
    return live, raw


def _queue_occupancy(kernel) -> tuple:
    max_local = max((len(s.local_dsq) for s in kernel.slots), default=0)
    max_group = max((len(g.dsq) for g in kernel.groups.values()
                     if getattr(g, "dsq", None) is not None), default=0)
    return max_local, max_group


# ---------------------------------------------------------------------------
# One benchmark run
# ---------------------------------------------------------------------------

def bench_one(policy: str, scale: str, short: bool, chunks: int = CHUNKS) -> dict:
    kernel, n_slots, horizon, warmup = build_scenario(policy, scale, short)
    clock = kernel.clock
    kernel.metrics.window_start = warmup
    kernel.metrics.window_end = horizon
    clock.run_until(warmup)                      # admit everything; fill queues

    samples = []
    max_live = max_raw = max_local = max_group = 0
    e_start = _events_processed(clock)
    t_start = time.perf_counter()
    for c in range(1, chunks + 1):
        target = warmup + (horizon - warmup) * c / chunks
        e0 = _events_processed(clock)
        w0 = time.perf_counter()
        clock.run_until(target)
        dw = time.perf_counter() - w0
        de = _events_processed(clock) - e0
        if de > 0:
            samples.append(dw / de * 1e6)
        live, raw = _clock_occupancy(clock)
        ml, mg = _queue_occupancy(kernel)
        max_live, max_raw = max(max_live, live), max(max_raw, raw)
        max_local, max_group = max(max_local, ml), max(max_group, mg)
    wall = time.perf_counter() - t_start
    events = _events_processed(clock) - e_start
    kernel._settle_accounting()

    summary = kernel.metrics.summary(n_slots=n_slots)
    sha = hashlib.sha256(
        json.dumps(summary, sort_keys=True).encode()).hexdigest()
    return {
        "name": f"{policy}.{scale}",
        "policy": policy, "scale": scale,
        "n_slots": n_slots, "horizon": horizon,
        "events": events, "wall_s": round(wall, 6),
        "events_per_sec": round(events / wall, 1) if wall > 0 else 0.0,
        "dispatch_us": {
            "p50": round(percentile(samples, 50), 3) if samples else None,
            "p99": round(percentile(samples, 99), 3) if samples else None,
            "mean": round(sum(samples) / len(samples), 3) if samples else None,
        },
        "clock": {"max_live": max_live, "max_raw": max_raw},
        "queues": {"max_local": max_local, "max_group": max_group},
        "summary_sha256": sha,
    }


# ---------------------------------------------------------------------------
# Baseline comparison (CI regression gate)
# ---------------------------------------------------------------------------

def _calibration_us() -> float:
    """Wall time of a fixed pure-Python loop (best of 3): a proxy for this
    machine's interpreter speed, so the regression gate compares code, not
    hardware."""
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        x = 0
        for i in range(200_000):
            x += i ^ (x >> 3)
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def compare_to_baseline(doc: dict, baseline: dict,
                        max_regression: float) -> list:
    """Returns a list of human-readable failures (empty = gate passes)."""
    failures = []
    base_rows = {r["name"]: r for r in baseline.get("results", [])}
    same_settings = baseline.get("short") == doc.get("short")
    # Scale the baseline to this machine: a box half as fast as the one
    # that produced the baseline halves the expected events/sec.
    scale = 1.0
    if baseline.get("calib_us") and doc.get("calib_us"):
        scale = baseline["calib_us"] / doc["calib_us"]
    for row in doc["results"]:
        base = base_rows.get(row["name"])
        if base is None:
            continue
        b, n = base["events_per_sec"] * scale, row["events_per_sec"]
        if b > 0 and n < b * (1.0 - max_regression):
            failures.append(
                f"{row['name']}: events/sec {n:.0f} < "
                f"{(1.0 - max_regression):.2f} * machine-scaled baseline "
                f"{b:.0f}")
        if same_settings and base.get("summary_sha256") != row["summary_sha256"]:
            failures.append(
                f"{row['name']}: Metrics.summary() hash drifted "
                f"({base.get('summary_sha256', '?')[:12]} -> "
                f"{row['summary_sha256'][:12]}) -- determinism break")
    return failures


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def run_all(short: bool, only: Optional[list] = None) -> dict:
    results = []
    for scale in SCALES:
        for policy in POLICIES:
            name = f"{policy}.{scale}"
            if only and not any(name.startswith(p) or p.startswith(name)
                                or scale.startswith(p) for p in only):
                continue
            row = bench_one(policy, scale, short)
            print(f"{row['name']}: {row['events']} events in "
                  f"{row['wall_s']:.2f}s = {row['events_per_sec']:.0f} ev/s, "
                  f"p50={row['dispatch_us']['p50']}us "
                  f"p99={row['dispatch_us']['p99']}us, "
                  f"clock[live/raw]={row['clock']['max_live']}/"
                  f"{row['clock']['max_raw']}, "
                  f"q[local/group]={row['queues']['max_local']}/"
                  f"{row['queues']['max_group']}", flush=True)
            results.append(row)
    return {"schema": "repro.microbench/v1", "short": short,
            "calib_us": round(_calibration_us(), 2), "results": results}


def main(argv: Optional[list] = None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--short", action="store_true",
                    help="CI mode: shorter horizons, smaller deep scale")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="write the JSON document to PATH (e.g. BENCH_8.json)")
    ap.add_argument("--only", default=None,
                    help="comma-separated scenario prefixes (ufs.deep, deep, vdf)")
    ap.add_argument("--baseline", default=None, metavar="PATH",
                    help="committed baseline JSON to gate regressions against")
    ap.add_argument("--max-regression", type=float, default=0.30,
                    help="fail if events/sec drops more than this fraction "
                         "below baseline (default 0.30)")
    args = ap.parse_args(argv)

    baseline = None
    if args.baseline:
        with open(args.baseline) as f:
            baseline = json.load(f)

    only = args.only.split(",") if args.only else None
    doc = run_all(args.short, only=only)

    if args.out:
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.out} ({len(doc['results'])} results)")

    if baseline is not None:
        failures = compare_to_baseline(doc, baseline, args.max_regression)
        if failures:
            for fail in failures:
                print(f"REGRESSION: {fail}", file=sys.stderr)
            return 1
        print(f"baseline gate passed "
              f"(max regression {args.max_regression:.0%})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
