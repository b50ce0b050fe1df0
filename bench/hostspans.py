"""The scheduler tracer's events of a traced run against the device trace.

``bench/host_trace.py`` runs a cell with a ``SchedTracer`` attached to the
kernel and adds to the run's record, beside the profiler's reduction:
``rec["trace"]["events"]`` (the tracer's events in ``TraceEvent.to_dict``
form, seconds on the tracer's clock: the decode job's runs on its slot,
the engine's ``span`` of each phase of its chunks, the train job's runs
and the worker parking), ``rec["trace"]["anchors"]`` (the tracer's clock
read at the traced window's start and end, each inside a host-plane
annotation of the profiler's trace), and under ``rec["trace"]["reduced"]``
the device's ``busy`` intervals and the ``clock`` from the tracer's clock
to the device's (``clock`` below).

The readers of the ``engine.*_ms_per_step``,
``sched.decode_requeue_p95_ms`` and ``device.idle_share.decode_host``
metrics, and the ``idle_by_host`` breakdown, are built from the functions
here. Each returns None where what it reads is missing (a program without
the spans, a run without a tracer, as in ``bench/run.py``'s record).
"""
from __future__ import annotations

import numpy as np

DECODE_JOB = "decode-loop"       # the engine's decode job (serving/engine.py)
TRAIN_GROUP = "train"            # the cell's background training job
# Host-plane events of the profiler's trace: the clock anchors around reads
# of the tracer's clock, and the runtime's launch of each device program and
# its host-side completion.
ANCHOR = "bench.clock_anchor"
LAUNCH = "tpu::System::Execute"
DONE = "tpu::System::Execute=>Done"
# What the slot's worker can be in, in the order a moment is given to the
# first that holds it.
HOST_STATES = ("engine.prep", "engine.admit", "engine.dispatch",
               "engine.sync", "engine.merge", "decode.requeue",
               "train.chunk", "parked")


def events(rec: dict):
    return rec["trace"].get("events")


def window(rec: dict) -> tuple:
    """The traced window on the tracer's clock: the two anchor reads."""
    a = rec["trace"]["anchors"]
    return a[0], a[-1]


def decode_chunks(evs: list) -> list:
    """Each run of the decode job on its slot, in order: ``start``,
    ``stop``, ``reason`` and its ``spans`` as (name, t0, t)."""
    out, cur = [], None
    for e in evs:
        if e.get("job") != DECODE_JOB:
            continue
        kind = e["kind"]
        if kind == "start_job":
            cur = {"start": e["t"], "spans": []}
        elif cur is None:
            continue
        elif kind == "span":
            cur["spans"].append((e["args"]["name"], e["args"]["t0"], e["t"]))
        elif kind == "stop_job":
            cur.update(stop=e["t"], reason=e["args"]["reason"])
            out.append(cur)
            cur = None
    return out


def step_chunks(rec: dict) -> list:
    """The decode chunks that committed a step in the traced window (their
    ``engine.merge`` starts in it) and admitted nothing."""
    evs = events(rec)
    if not evs:
        return []
    w0, w1 = window(rec)
    out = []
    for c in decode_chunks(evs):
        names = [n for n, _, _ in c["spans"]]
        if "engine.admit" in names or "engine.merge" not in names:
            continue
        merge_t0 = next(t0 for n, t0, _ in c["spans"] if n == "engine.merge")
        if w0 <= merge_t0 <= w1:
            out.append(c)
    return out


def per_step_ms(rec: dict, names: tuple):
    """Mean time a committed step spends in the spans ``names``, in ms."""
    chunks = step_chunks(rec)
    if not chunks:
        return None
    return 1e3 * float(np.mean([sum(t - t0 for n, t0, t in c["spans"]
                                    if n in names) for c in chunks]))


def requeues(evs: list) -> list:
    """(stop, next start) of the decode job after each chunk that yielded:
    the scheduler's turnaround from its requeue to its next run."""
    out, stopped = [], None
    for e in evs:
        if e.get("job") != DECODE_JOB:
            continue
        if e["kind"] == "stop_job":
            stopped = e["t"] if e["args"]["reason"] == "yield" else None
        elif e["kind"] == "start_job" and stopped is not None:
            out.append((stopped, e["t"]))
            stopped = None
    return out


def requeue_ms(rec: dict) -> list:
    """The decode job's requeue waits that start in the traced window."""
    evs = events(rec)
    if not evs:
        return []
    w0, w1 = window(rec)
    return [(b - a) * 1e3 for a, b in requeues(evs) if w0 <= a <= w1]


def union(iv) -> list:
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        elif e > s:
            out.append([s, e])
    return out


def intersect(a: list, b: list) -> list:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append([s, e])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: list, b: list) -> list:
    """``a`` less ``b``, both sorted lists of disjoint intervals."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append([s, b[k][0]])
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append([s, e])
    return out


def length(iv: list) -> float:
    return sum(e - s for s, e in iv)


def host_states(evs: list) -> dict:
    """For each of ``HOST_STATES``, the tracer-clock intervals in which the
    slot's worker was in it: an engine span, the decode job waiting out its
    requeue, the train job's run, or parked with nothing to run."""
    out = {k: [] for k in HOST_STATES}
    for c in decode_chunks(evs):
        for n, t0, t in c["spans"]:
            out[n].append([t0, t])
    out["decode.requeue"] = [list(r) for r in requeues(evs)]
    started = parked = None
    for e in evs:
        if e["kind"] == "start_job" and e.get("group") == TRAIN_GROUP:
            started = e["t"]
        elif (e["kind"] == "stop_job" and e.get("group") == TRAIN_GROUP
              and started is not None):
            out["train.chunk"].append([started, e["t"]])
            started = None
        elif e["kind"] == "park":
            parked = e["t"]
        elif e["kind"] == "unpark" and parked is not None:
            out["parked"].append([parked, e["t"]])
            parked = None
    return {k: union(v) for k, v in out.items()}


def device_idle(red: dict) -> list:
    """The device's idle gaps inside the traced span, in seconds on the
    device's clock."""
    busy = red.get("busy") or []
    return [[a[1] * 1e-12, b[0] * 1e-12] for a, b in zip(busy, busy[1:])]


def to_device(iv: list, offset: float) -> list:
    return [[s + offset, e + offset] for s, e in iv]


def attribute(gaps: list, states: dict, offset: float) -> list:
    """Seconds of ``gaps`` by host state, each moment given to the first of
    ``HOST_STATES`` that holds it and to ``none`` if no state does; longest
    first."""
    left, out = gaps, {}
    for k in HOST_STATES:
        iv = to_device(states[k], offset)
        out[k] = length(intersect(left, iv))
        left = subtract(left, iv)
    out["none"] = length(left)
    return sorted(([k, v] for k, v in out.items()), key=lambda kv: -kv[1])


def aligned(rec: dict):
    """(events, device offset) of a traced run whose tracer events and
    clock are both there; None otherwise."""
    evs = events(rec)
    clock = rec["trace"]["reduced"].get("clock")
    if not evs or not clock:
        return None
    return evs, clock["offset_s"]


def idle_by_host(rec: dict):
    """The breakdown: the device's idle seconds in the traced span by host
    state, and the longest idle gap with the state that held most of it."""
    got = aligned(rec)
    if got is None:
        return None
    evs, offset = got
    gaps = device_idle(rec["trace"]["reduced"])
    if not gaps:
        return None
    states = host_states(evs)
    longest = max(gaps, key=lambda g: g[1] - g[0])
    parts = attribute([longest], states, offset)
    return {"idle_by_host": attribute(gaps, states, offset),
            "longest_idle_gap": {"s": longest[1] - longest[0],
                                 "at_s": longest[0] - gaps[0][0],
                                 "host": parts[0][0], "by_host": parts}}


def decode_host_idle_share(rec: dict):
    """Share of the traced span, in %, in which the device is idle while
    the decode job is on its slot or waiting out its requeue."""
    got = aligned(rec)
    if got is None:
        return None
    evs, offset = got
    red = rec["trace"]["reduced"]
    chunks = decode_chunks(evs)
    if red["span_s"] <= 0 or not chunks:
        return None
    held = union([[c["start"], c["stop"]] for c in chunks]
                 + [list(r) for r in requeues(evs)])
    idle = intersect(device_idle(red), to_device(held, offset))
    return 100.0 * length(idle) / red["span_s"]


def alignment(rec: dict, decode_starts: list):
    """Checks of the clock chain: the anchors' disagreement, the device's
    lag, and how many of ``decode_starts`` (the window's decode programs'
    device starts, in seconds) lie between their chunk's
    ``engine.dispatch`` start and ``engine.sync`` end."""
    got = aligned(rec)
    if got is None:
        return None
    evs, offset = got
    windows = []
    for c in decode_chunks(evs):
        t = {n: (t0, t) for n, t0, t in c["spans"]}
        if "engine.dispatch" in t and "engine.sync" in t:
            windows.append([t["engine.dispatch"][0] + offset,
                            t["engine.sync"][1] + offset])
    inside, j = 0, 0
    for s in sorted(decode_starts):
        while j < len(windows) and windows[j][1] < s:
            j += 1
        inside += j < len(windows) and windows[j][0] <= s
    clock = rec["trace"]["reduced"]["clock"]
    return {"anchor_disagreement_us": clock["disagreement_s"] * 1e6,
            "device_lag_ms": clock["device_lag_s"] * 1e3,
            "device_lag_bounds_ms": [x * 1e3 for x in
                                     clock["device_lag_bounds_s"]],
            "lag_pairs": clock["pairs"],
            "decode_starts": len(decode_starts),
            "inside_dispatch_to_sync": inside}


def closure(rec: dict):
    """The parts of a token gap against the gap (host_trace.py prints it): the
    mean requeue wait and the mean of each step span group, in ms, beside
    the mean gap between two tokens of one request that ends in the traced
    window (the gaps ``engine.host_ms_per_step`` reads)."""
    req = requeue_ms(rec)
    parts = {"requeue": float(np.mean(req)) if req else None,
             "dispatch": per_step_ms(rec, ("engine.prep", "engine.dispatch")),
             "sync": per_step_ms(rec, ("engine.sync",)),
             "merge": per_step_ms(rec, ("engine.merge",))}
    if any(v is None for v in parts.values()):
        return None
    tr = rec["trace"]
    gaps = [b - a for r in rec["requests"]
            for a, b in zip(r["token_times"], r["token_times"][1:])
            if tr["t0"] <= b <= tr["t1"]]
    if not gaps:
        return None
    total = sum(parts.values())
    gap = 1e3 * float(np.mean(gaps))
    return dict(parts, sum=total, gap=gap, sum_over_gap=total / gap)


# ---------------------------------------------------------------------------
# The profiler's trace: host-plane events and the clock chain
# ---------------------------------------------------------------------------

def host_events(path: str) -> dict:
    """The anchors, launches and completions on the ``/host:CPU`` plane of
    a profiler trace, each as [start, duration] in ps, sorted."""
    from jax.profiler import ProfileData
    out = {"anchors": [], "launches": [], "dones": []}
    keep = {ANCHOR: out["anchors"], LAUNCH: out["launches"],
            DONE: out["dones"]}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for ln in plane.lines:
            for e in ln.events:
                got = keep.get(e.name)
                if got is not None:
                    got.append([round(e.start_ns * 1000),
                                round(e.duration_ns * 1000)])
    for v in out.values():
        v.sort()
    return out


def busy(mods: list) -> list:
    """The device's busy intervals in ps over the programs that
    ``xplane.reduce`` keeps (all but the first and the last, which the
    profiler may cut): its ``busy_s`` and ``span_s`` are their length and
    extent."""
    return union([[s, s + d] for _, s, d in
                  sorted(mods, key=lambda m: m[1])[1:-1]])


def device_lag(mods: list, launches: list, dones: list, reach: int = 4):
    """How far the device plane's clock runs behind the host plane's, in
    ps, from the runtime's host events. A program cannot start on the
    device before its launch began on the host, nor end after the host saw
    it complete: each pair bounds the lag from below and from above.

    ``mods`` (sorted by start) are paired in order with ``launches`` and
    with ``dones``, each list at its own shift. At the profiler's start a
    program may have been launched before it and start on the device
    after it (a program without a launch: launches shift by 0 to
    ``-reach``), or be running and complete after it (a completion without
    a program: completions shift by 0 to ``reach``). A wrong shift pairs a
    program with another's launch or completion; over a trace whose
    programs do not come at a fixed pace, its bound crosses the other
    side's, or brackets the lag more loosely than the right pair. So of
    the shifts whose bounds agree, the pair with the tightest bracket
    wins. Returns {"lag", "low", "high", "pairs", "shifts"}: the lag is
    the lower bound, so that no program is put before its launch; None
    when no shifts agree."""
    def bound(host, shifts, edge, pick):
        out = {}
        for j in shifts:
            vals = [host[k + j][0] - edge(m) for k, m in enumerate(mods)
                    if 0 <= k + j < len(host)]
            if vals:
                out[j] = (pick(vals), len(vals))
        return out
    los = bound(launches, range(-reach, 1), lambda m: m[1], max)
    his = bound(dones, range(reach + 1), lambda m: m[1] + m[2], min)
    best = None
    for j, (lo, n_lo) in los.items():
        for jd, (hi, n_hi) in his.items():
            if lo <= hi and (best is None
                             or hi - lo < best["high"] - best["low"]):
                best = {"lag": lo, "low": lo, "high": hi,
                        "pairs": min(n_lo, n_hi), "shifts": [j, jd]}
    return best


def clock(mods: list, host: dict, reads: list):
    """From the scheduler tracer's clock to the device's, in seconds:
    ``offset_s`` is what to add to a tracer time to get the device time.
    It chains the anchors (each a host-plane event around one read of the
    tracer's clock, in ``reads``: tracer to host plane, the mean of the
    two, whose ``disagreement_s`` is their difference) with
    ``device_lag``. None where the trace lacks the anchors or no lag is
    consistent."""
    anchors = host.get("anchors") or []
    if len(anchors) != len(reads) or len(reads) < 2:
        return None
    offs = [(s + d / 2) * 1e-12 - r for (s, d), r in zip(anchors, reads)]
    lag = device_lag(sorted(mods, key=lambda m: m[1]),
                     host.get("launches") or [], host.get("dones") or [])
    if lag is None:
        return None
    to_host = sum(offs) / len(offs)
    return {"offset_s": to_host - lag["lag"] * 1e-12,
            "disagreement_s": max(offs) - min(offs),
            "host_offset_s": to_host,
            "device_lag_s": lag["lag"] * 1e-12,
            "device_lag_bounds_s": [lag["low"] * 1e-12, lag["high"] * 1e-12],
            "pairs": lag["pairs"]}
