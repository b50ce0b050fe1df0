"""Serving benchmark harness for the live hot path (BENCH_10.json).

Measures end-to-end serving throughput and latency of the live
``InferenceEngine`` + ``ThreadExecutor`` stack under a mixed
interactive+bulk open-loop load -- the workload the paper's scheduling is
*for* -- in two configurations run side by side in one invocation:

* ``baseline`` -- the pre-overhaul path, kept in-tree behind flags:
  ``LiveKernel(dispatch="polling")`` (global condvar, ``notify_all`` herd,
  50 ms idle tick) and ``InferenceEngine(overlap_decode=False,
  batched_admission=False)`` (engine lock held across device compute,
  per-request prefill inside the admission loop);
* ``hotpath`` -- the defaults: per-slot event parking with targeted
  wakeups, snapshot/merge decode outside the lock, batched padded
  admission prefill, one jitted row-publish scatter.

Because both rows land in the same JSON document, the committed
``BENCH_10.json`` *is* the pre-change baseline recording the acceptance
deltas (tokens/sec, p99 worker-wakeup latency, decode-lock hold).

Models: a ``TinyStubModel`` (microsecond steps -- isolates scheduler and
engine overhead) always; the real reduced transformer additionally in full
(non ``--short``) mode.

Output schema (``BENCH_10.json``, stable field names)::

    {
      "schema": "repro.serving_bench/v1",
      "short": bool,
      "calib_us": float,             # same machine-speed proxy as microbench
      "results": [{
        "name": "stub.hotpath",      # <model>.<mode>
        "model": "stub", "mode": "hotpath",
        "n_slots": int, "max_batch": int, "duration_s": float,
        "requests": {"submitted": int, "completed": int, "failed": int},
        "tokens": int,
        "tokens_per_sec": float,     # the regression-gated figure
        "ttft_ms": {"p50": float, "p99": float},        # interactive tier
        "bulk_ttft_ms": {"p50": float, "p99": float},   # background tier
        "itl_ms": {"p50": float, "p99": float},
        "lock_hold_us": {"p50": float, "p99": float, "max": float},
        "wakeup_us": {"p50": float, "p99": float, "n": int},  # probe phase
        "engine": {...},             # EngineStats.summary()
      }, ...],
      "speedup": {"stub": {"tokens_per_sec": x, "wakeup_p99": x}, ...}
    }

Regression gating (used by CI)::

    python -m benchmarks.serving_bench --short --out BENCH_10.short.json \
        --baseline BENCH_10.json --max-regression 0.50

compares ``tokens_per_sec`` per result name against the committed baseline
scaled by the calibration ratio.  Live timing is noisier than the sim, so
the default threshold is looser than microbench's.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from typing import Optional

import numpy as np

from repro.core.live import LiveJob, LiveKernel
from repro.core.metrics import percentile
from repro.core.policies import make_policy
from repro.core.task import Tier
from repro.core.trace import SchedTracer, wakeup_delays
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.engine import EngineStats, InferenceEngine, Request
from repro.serving.stub import TinyStubModel

MODES = {
    # mode -> (kernel dispatch, overlap_decode, batched_admission)
    "baseline": ("polling", False, False),
    "hotpath": ("event", True, True),
}
# A serving-realistic worker fleet: the dispatch designs differ in how
# wakeups scale with fleet size (polling: notify_all wakes every idle
# worker for a full dispatch scan on every guard exit; event: exactly the
# kicked slot), so the fleet must be big enough for that to show.
N_SLOTS = 48
MAX_BATCH = 8
INTERACTIVE_GAP_S = 0.002      # open-loop interactive arrival gap
BULK_EVERY = 5                 # every Nth submission is a background bulk


def _build_real_model():
    import jax

    from repro.configs import get_arch
    from repro.models.transformer import Model

    cfg = get_arch("llama3.2-1b").reduced()
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return model, params


def _build(model_name: str, mode: str):
    dispatch, overlap, batched = MODES[mode]
    if model_name == "stub":
        model = TinyStubModel()
        params = model.init_params(0)
        max_len = 128
    else:
        model, params = _build_real_model()
        max_len = 96
    # Retain only the wakeup-analysis kinds: at serving rates the full
    # stream would wrap a reasonable ring long before the run ends.
    tracer = SchedTracer(capacity=1 << 18,
                         kinds={"wake", "start_job", "park", "unpark"})
    kernel = LiveKernel(N_SLOTS, make_policy("ufs"), tracer=tracer,
                        dispatch=dispatch)
    # The paper's setting is a multi-tenant box: several workload groups
    # share one fleet.  Idle groups still get walked by every dispatch
    # scan, which is exactly why futile scans (a notify_all herd waking
    # the whole fleet to find nothing) are not free at realistic scale.
    for i in range(6):
        kernel.create_group(f"tenant{i}", Tier.BACKGROUND, 100.0)
    engine = InferenceEngine(model, params, kernel,
                             max_batch=MAX_BATCH, max_len=max_len,
                             overlap_decode=overlap,
                             batched_admission=batched)
    return kernel, engine, tracer, max_len


def _mk_request(i: int, rng: np.random.Generator, vocab: int,
                interactive_tokens: int, bulk_tokens: int) -> Request:
    if i % BULK_EVERY == BULK_EVERY - 1:
        return Request(prompt=rng.integers(1, vocab, 24).astype(np.int32),
                       tier="background", max_new_tokens=bulk_tokens)
    return Request(prompt=rng.integers(1, vocab, 12).astype(np.int32),
                   max_new_tokens=interactive_tokens)


def bench_one(model_name: str, mode: str, duration_s: float) -> dict:
    kernel, engine, tracer, _ = _build(model_name, mode)
    vocab = getattr(engine.model, "vocab", 32)
    interactive_tokens = 8 if model_name == "stub" else 4
    bulk_tokens = 4 if model_name == "stub" else 2
    rng = np.random.default_rng(0)
    kernel.start()
    engine.start()

    # Warmup: compile every jit bucket (admission, decode, bulk, scatter)
    # and settle the worker fleet before the measured window opens.
    warm = [engine.submit(_mk_request(i, rng, vocab, interactive_tokens,
                                      bulk_tokens))
            for i in range(2 * BULK_EVERY)]
    for r in warm:
        r.done_event.wait(timeout=120)
    engine.stats = EngineStats()         # drop warmup samples

    reqs = []
    t_start = time.monotonic()
    trace_t0 = kernel.executor.now
    deadline = t_start + duration_s
    i = 0
    while time.monotonic() < deadline:
        reqs.append(engine.submit(_mk_request(i, rng, vocab,
                                              interactive_tokens,
                                              bulk_tokens)))
        i += 1
        time.sleep(INTERACTIVE_GAP_S)
    for r in reqs:
        r.done_event.wait(timeout=120)
    t_end = time.monotonic()
    trace_t1 = kernel.executor.now
    stats = engine.stats.summary()

    # --- wakeup-latency probe (closed loop) ----------------------------
    # Under the sustained open-loop load the decode loop almost never
    # parks, so the load window yields few wake->start edges.  Probe
    # explicitly: let the engine drain so the loop parks, then each
    # single submit must wake it -- that wake->start_job delay IS the
    # worker wakeup latency (notify_all herd + lock convoy in polling
    # mode vs. a targeted per-slot event in event mode).
    # One sleepy background job keeps the executor guard mildly active
    # during the probe (~600 chunk epilogues/s, GIL released while it
    # sleeps).  In polling mode every epilogue is a notify_all broadcast:
    # all idle workers wake, re-acquire the guard and run a futile
    # dispatch scan, so a ping's wake queues behind the herd.  In event
    # mode parked workers are untouched.  That asymmetry -- O(fleet)
    # wakeups per guard exit vs. O(1) targeted -- is what this metric
    # exists to expose; an utterly idle fleet would hide it.
    churn_stop = [False]

    def _make_churn(sleep_s):
        def _churn(now):
            if churn_stop[0]:
                return "done"
            time.sleep(sleep_s)
            return "yield"
        return _churn

    # Pin churn away from the serve loop's slot (cpuset analogue): live
    # preemption is cooperative, so a ping that lands behind a mid-chunk
    # background sleep waits it out *identically in both modes* -- that
    # queueing delay is placement noise, not the dispatch cost under test.
    churn_sleeps = (2e-3, 3e-3, 4e-3, 5e-3)      # staggered epilogue rate
    churn_group = kernel.create_group(
        "churn", Tier.BACKGROUND, 100.0,
        slot_affinity=frozenset(range(N_SLOTS - len(churn_sleeps), N_SLOTS)))
    for i, sleep_s in enumerate(churn_sleeps):
        kernel.wake(LiveJob(churn_group, _make_churn(sleep_s),
                            name=f"churn{i}"))

    # GC off for the probe: a gen-2 collection pause lands on whichever
    # ping is unlucky and would report the allocator, not the dispatch
    # path, at p99.  (Identical treatment for both modes.)
    n_pings = 600 if model_name == "stub" else 80
    gc_was_enabled = gc.isenabled()
    gc.collect()
    gc.disable()
    try:
        ping_t0 = kernel.executor.now
        for _ in range(n_pings):
            time.sleep(0.005)                # let the decode loop park
            ping = engine.submit(
                Request(prompt=rng.integers(1, vocab, 4).astype(np.int32),
                        max_new_tokens=2))
            ping.done_event.wait(timeout=10)
        ping_t1 = kernel.executor.now
    finally:
        if gc_was_enabled:
            gc.enable()
    churn_stop[0] = True
    time.sleep(0.01)                         # churn job observes the stop
    engine.stop()
    kernel.stop()

    done = [r for r in reqs if r.ok]
    failed = [r for r in reqs if r.finished is not None and not r.ok]
    tokens = sum(len(r.tokens) for r in done)
    wall = t_end - t_start
    inter = [r for r in done if r.tier != "background"]
    bulk = [r for r in done if r.tier == "background"]
    ttft = [(r.first_token - r.submitted) * 1e3 for r in inter
            if r.first_token is not None]
    bulk_ttft = [(r.first_token - r.submitted) * 1e3 for r in bulk
                 if r.first_token is not None]
    itl = [(b - a) * 1e3 for r in inter
           for a, b in zip(r.token_times, r.token_times[1:])]
    # Worker wakeup latency: wake -> first dispatch of the *time-sensitive*
    # serve group only (the decode loop parking and being woken by probe
    # arrivals), measured over the probe window.  Bulk-group delays are
    # tier queueing -- background jobs wait for slack by design -- not
    # dispatch latency.
    delays = wakeup_delays([e for e in tracer.events
                            if ping_t0 <= e.t <= ping_t1])
    wakes = [d * 1e6 for d in delays.get(engine.group.name, [])]
    return {
        "name": f"{model_name}.{mode}",
        "model": model_name, "mode": mode,
        "n_slots": N_SLOTS, "max_batch": MAX_BATCH,
        "duration_s": round(wall, 3),
        "requests": {"submitted": len(reqs), "completed": len(done),
                     "failed": len(failed)},
        "tokens": tokens,
        "tokens_per_sec": round(tokens / wall, 1) if wall > 0 else 0.0,
        "ttft_ms": {"p50": round(percentile(ttft, 50), 3) if ttft else None,
                    "p99": round(percentile(ttft, 99), 3) if ttft else None},
        "bulk_ttft_ms": {
            "p50": round(percentile(bulk_ttft, 50), 3) if bulk_ttft else None,
            "p99": round(percentile(bulk_ttft, 99), 3) if bulk_ttft else None},
        "itl_ms": {"p50": round(percentile(itl, 50), 3) if itl else None,
                   "p99": round(percentile(itl, 99), 3) if itl else None},
        "lock_hold_us": {"p50": round(stats["lock_hold_p50_us"], 2),
                         "p99": round(stats["lock_hold_p99_us"], 2),
                         "max": round(stats["lock_hold_max_us"], 2)},
        "wakeup_us": {"p50": round(percentile(wakes, 50), 2) if wakes else None,
                      "p99": round(percentile(wakes, 99), 2) if wakes else None,
                      "n": len(wakes)},
        "engine": stats,
    }


# ---------------------------------------------------------------------------
# Baseline comparison (CI regression gate) -- microbench convention
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
    scale = 1.0
    if baseline.get("calib_us") and doc.get("calib_us"):
        scale = baseline["calib_us"] / doc["calib_us"]
    for row in doc["results"]:
        base = base_rows.get(row["name"])
        if base is None:
            continue
        b, n = base["tokens_per_sec"] * scale, row["tokens_per_sec"]
        if b > 0 and n < b * (1.0 - max_regression):
            failures.append(
                f"{row['name']}: tokens/sec {n:.0f} < "
                f"{(1.0 - max_regression):.2f} * machine-scaled baseline "
                f"{b:.0f}")
    return failures


def _speedups(results: list) -> dict:
    rows = {r["name"]: r for r in results}
    out = {}
    for model in {r["model"] for r in results}:
        base = rows.get(f"{model}.baseline")
        hot = rows.get(f"{model}.hotpath")
        if not base or not hot:
            continue
        entry = {}
        if base["tokens_per_sec"]:
            entry["tokens_per_sec"] = round(
                hot["tokens_per_sec"] / base["tokens_per_sec"], 2)
        bp, hp = base["wakeup_us"]["p99"], hot["wakeup_us"]["p99"]
        if bp and hp:
            entry["wakeup_p99"] = round(bp / hp, 2)
        bl, hl = base["lock_hold_us"]["p99"], hot["lock_hold_us"]["p99"]
        if bl and hl:
            entry["lock_hold_p99"] = round(bl / hl, 2)
        out[model] = entry
    return out


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def run_all(short: bool, only: Optional[list] = None) -> dict:
    duration = 3.0 if short else 8.0
    models = ["stub"] if short else ["stub", "real"]
    results = []
    for model in models:
        for mode in ("baseline", "hotpath"):
            name = f"{model}.{mode}"
            if only and not any(name.startswith(p) or p.startswith(name)
                                or mode.startswith(p) for p in only):
                continue
            row = bench_one(model, mode, duration)
            print(f"{row['name']}: {row['tokens']} tokens in "
                  f"{row['duration_s']:.2f}s = {row['tokens_per_sec']:.0f} "
                  f"tok/s, ttft p99={row['ttft_ms']['p99']}ms, "
                  f"itl p99={row['itl_ms']['p99']}ms, "
                  f"lock p99={row['lock_hold_us']['p99']}us, "
                  f"wakeup p99={row['wakeup_us']['p99']}us "
                  f"(n={row['wakeup_us']['n']})", flush=True)
            results.append(row)
    doc = {"schema": "repro.serving_bench/v1", "short": short,
           "calib_us": round(_calibration_us(), 2), "results": results,
           "speedup": _speedups(results)}
    if doc["speedup"]:
        print(f"speedup: {json.dumps(doc['speedup'])}", flush=True)
    return doc


def main(argv: Optional[list] = None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--short", action="store_true",
                    help="CI mode: stub model only, shorter load window")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="write the JSON document to PATH (e.g. BENCH_10.json)")
    ap.add_argument("--only", default=None,
                    help="comma-separated scenario prefixes "
                         "(stub.hotpath, real, baseline)")
    ap.add_argument("--baseline", default=None, metavar="PATH",
                    help="committed baseline JSON to gate regressions against")
    ap.add_argument("--max-regression", type=float, default=0.50,
                    help="fail if tokens/sec drops more than this fraction "
                         "below baseline (default 0.50; live timing is noisy)")
    args = ap.parse_args(argv)

    # Latency benchmark on a small box: the default 5 ms GIL switch
    # interval means a freshly woken worker can sit a full quantum
    # behind another thread's bytecode burst, which swamps the tails
    # we are trying to measure.  Pin it low for both modes equally.
    sys.setswitchinterval(0.0001)

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
