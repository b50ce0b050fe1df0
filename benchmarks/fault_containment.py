"""Fault-containment benchmark: a crash-looping background lock holder
must not hurt time-sensitive tail latency (DESIGN.md section 12).

Two sim runs of the same mixed workload (time-sensitive lock users +
background analytics) on the UFS policy:

* ``baseline``  -- fault-free;
* ``crashloop`` -- plus one background job that repeatedly acquires the
  shared lock, burns CPU, and crashes while holding it.  Its
  `RetryPolicy` restarts it after every panic, so it crash-loops for the
  whole horizon (or until quarantine if ``--retries`` is finite).

Reported per run: TS latency stats, fault counters, and -- for the
crash-loop run -- the **containment latency** distribution: virtual time
from each ``panic`` trace event to the next time-sensitive
``lock_acquire`` on the contested lock, i.e. how quickly the force-release
path returns the lock to foreground work.

    PYTHONPATH=src python -m benchmarks.fault_containment [--short]
        [--out fault_containment.json]

Prints ``name,value`` CSV rows; ``--out`` writes the full JSON.
"""
from __future__ import annotations

import argparse
import json
import random

from repro.core import (Job, RetryPolicy, SchedKernel, SchedTracer, Tier,
                        make_policy, percentile)
from repro.core.faults import crashing_holder
from repro.core.task import (AcquireLock, Block, Burst, ReleaseLock,
                             RequestBegin, RequestEnd)
from repro.launch.compile_cache import enable_compile_cache

THINK = 0.3e-3
TS_CS = 0.2e-3          # TS critical section (short, paper-style OLTP)
BG_QUERY = 0.05         # background analytics burst
HOLD_CPU = 2e-3         # crasher's CPU while holding the lock


def ts_locker(seed: int, lock):
    """Closed-loop TS worker whose transaction needs the shared lock."""
    rng = random.Random(seed)
    while True:
        yield Block(rng.expovariate(1.0 / THINK))
        yield RequestBegin()
        yield AcquireLock(lock)
        yield Burst(TS_CS)
        yield ReleaseLock(lock)
        yield RequestEnd()


def bg_analytics(seed: int):
    rng = random.Random(seed)
    while True:
        yield RequestBegin()
        yield Burst(BG_QUERY * rng.uniform(0.95, 1.05))
        yield RequestEnd()


def run_once(horizon: float, crash: bool, retries: int) -> dict:
    tracer = SchedTracer(capacity=1 << 20)
    k = SchedKernel(2, make_policy("ufs"), tracer=tracer)
    ts = k.create_group("ts", Tier.TIME_SENSITIVE, 10_000)
    bg = k.create_group("bg", Tier.BACKGROUND, 1)
    lock = k.create_lock("shared")

    ts_jids = []
    for i in range(4):
        j = Job(ts, behavior=ts_locker(i, lock), name=f"ts{i}", kind="bursty")
        ts_jids.append(j.jid)
        k.add_job(j)
    for i in range(2):
        k.add_job(Job(bg, behavior=bg_analytics(100 + i), name=f"bg{i}",
                      kind="bound"))
    if crash:
        # backoff_growth=1.0: constant 1 ms restart delay, a steady crash
        # loop instead of an exponentially self-silencing one.
        k.add_job(Job(bg, behavior_factory=crashing_holder(
                          lock, hold_cpu=HOLD_CPU),
                      name="crashy", kind="bound",
                      retry_policy=RetryPolicy(max_retries=retries,
                                               backoff=1e-3,
                                               backoff_growth=1.0)))
    m = k.run(horizon, warmup=0.2)

    out = {
        "ts_latency": m.latency_stats("ts"),
        "ts_completed": m.completed["ts"],
        "panics": len(m.panics),
        "retries": m.retries,
        "quarantines": m.quarantines,
    }
    if crash:
        # panic -> next TS lock_acquire on the contested lock
        ts_set = set(ts_jids)
        deltas, pending = [], None
        for e in tracer.events:
            if e.kind == "panic":
                pending = e.t if pending is None else pending
            elif (pending is not None and e.kind == "lock_acquire"
                  and e.jid in ts_set):
                deltas.append(e.t - pending)
                pending = None
        out["containment"] = {
            "n": len(deltas),
            "p50_ms": percentile(deltas, 50) * 1e3 if deltas else None,
            "p99_ms": percentile(deltas, 99) * 1e3 if deltas else None,
            "max_ms": max(deltas) * 1e3 if deltas else None,
        }
    return out


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--short", action="store_true", help="CI-sized horizon")
    ap.add_argument("--horizon", type=float, default=None)
    ap.add_argument("--retries", type=int, default=1_000_000,
                    help="crasher retry budget (default: never quarantines)")
    ap.add_argument("--out", default=None, help="write full JSON here")
    args = ap.parse_args()
    horizon = args.horizon or (4.0 if args.short else 20.0)

    results = {
        "horizon_s": horizon,
        "baseline": run_once(horizon, crash=False, retries=0),
        "crashloop": run_once(horizon, crash=True, retries=args.retries),
    }
    base, fault = results["baseline"], results["crashloop"]
    for name, r in (("baseline", base), ("crashloop", fault)):
        lat = r["ts_latency"]
        print(f"{name}.ts_p50_ms,{lat['p50'] * 1e3:.3f}")
        print(f"{name}.ts_p99_ms,{lat['p99'] * 1e3:.3f}")
        print(f"{name}.ts_completed,{r['ts_completed']}")
    print(f"crashloop.panics,{fault['panics']}")
    print(f"crashloop.retries,{fault['retries']}")
    cont = fault["containment"]
    if cont["n"]:
        print(f"crashloop.containment_p50_ms,{cont['p50_ms']:.3f}")
        print(f"crashloop.containment_p99_ms,{cont['p99_ms']:.3f}")
    # the headline: TS p99 under a crash-looping BG holder vs fault-free
    ratio = fault["ts_latency"]["p99"] / base["ts_latency"]["p99"]
    results["ts_p99_ratio"] = ratio
    print(f"ts_p99_ratio,{ratio:.3f}")

    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=2, sort_keys=True)
        print(f"# wrote {args.out}")


if __name__ == "__main__":
    main()
