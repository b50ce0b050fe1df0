"""The scheduler tracer's events against the device trace: the clock chain
(anchors, then the runtime's launches and completions), the per-layer
readers built on it and the ``idle_by_host`` breakdown, on a made-up
traced run whose answers are worked out by hand below, and on a piece of
a run recorded on a TPU v5e (``record_host_trace.py``); and the check
that the tracer's ring reached back to the traced window."""
from __future__ import annotations

import contextlib
import gzip
import json
from pathlib import Path

import pytest

import cells
import host_trace
import hostspans
import xplane
from repro.core.trace import SchedTracer

RECORDED = Path(__file__).parent / "data" / "host_trace.json.gz"
MS = 1e-3
HOST = 5.000002          # host plane less tracer clock (anchor mean)
LAG = 1 * MS             # host plane less device plane
OFFSET = HOST - LAG      # device less tracer


def ps(t: float) -> int:
    return round(t * 1e12)


def _ev(t, kind, job="decode-loop", group="serve", **args):
    e = {"t": t, "kind": kind, "slot": 0, "jid": 1 if job == "decode-loop" else 2,
         "job": job, "group": group, "jkind": "bursty"}
    if args:
        e["args"] = args
    return e


def _chunk(start, spans, stop, reason):
    """A decode chunk: its run and its spans (name, t0 ms, t ms past 10 s)."""
    out = [_ev(10 + start * MS, "start_job")]
    for name, t0, t in spans:
        out.append(_ev(10 + t * MS, "span", name=name, t0=10 + t0 * MS,
                       rids=[7]))
    out.append(_ev(10 + stop * MS, "stop_job", used=0.0, reason=reason))
    return out


# Times in ms past 10 s on the tracer's clock. Two plain decode chunks, then
# one that admits a request, then the train job's chunk.
EVENTS = (
    _chunk(100.0, [("engine.prep", 100.1, 100.2),
                   ("engine.dispatch", 100.2, 101.0),
                   ("engine.sync", 101.0, 103.0),
                   ("engine.merge", 103.0, 103.1)], 103.2, "yield")
    + _chunk(103.5, [("engine.prep", 103.6, 103.7),
                     ("engine.dispatch", 103.7, 104.5),
                     ("engine.sync", 104.5, 106.5),
                     ("engine.merge", 106.5, 106.6)], 106.7, "yield")
    + _chunk(107.0, [("engine.prep", 107.1, 107.2),
                     ("engine.admit", 107.2, 110.0),
                     ("engine.prep", 110.0, 110.1),
                     ("engine.dispatch", 110.1, 110.9),
                     ("engine.sync", 110.9, 112.9),
                     ("engine.merge", 112.9, 113.0)], 113.1, "blocked")
    + [_ev(10 + 113.3 * MS, "start_job", job="bg-train", group="train"),
       _ev(10 + 213.5 * MS, "stop_job", job="bg-train", group="train",
           used=0.1, reason="yield")])

# Device programs (name, start ms, end ms), tracer clock. The first and the
# last stand for programs the profiler's edges cut: ``reduce`` drops them.
PROGRAMS = [("jit_edge", 99.0, 99.5),
            ("jit_decode_step", 100.4, 102.0), ("jit_dynamic_slice", 102.1, 102.2),
            ("jit__argmax", 102.3, 102.5),
            ("jit_decode_step", 103.9, 105.5), ("jit_dynamic_slice", 105.6, 105.7),
            ("jit__argmax", 105.8, 106.0),
            ("jit_prefill_batch", 107.5, 109.5), ("jit_write", 109.6, 109.7),
            ("jit_decode_step", 110.3, 111.9), ("jit_dynamic_slice", 112.0, 112.1),
            ("jit__argmax", 112.2, 112.4),
            ("jit_train_step", 114.0, 210.0), ("jit_edge", 230.0, 230.1)]


def _trace():
    """The plain form ``xplane.events_from_xplane`` gives for the run."""
    mods, launches, dones = [], [], []
    for k, (name, s, e) in enumerate(PROGRAMS):
        s, e = 10 + s * MS, 10 + e * MS
        mods.append([f"{name}(1)", ps(s + OFFSET), ps(e - s)])
        # Launched on the host 0.2 ms before the device started it (one of
        # them right as it started: that one sets the lag), seen complete
        # 0.3 ms after it ended.
        early = 0.0 if k == 4 else 0.2 * MS
        launches.append([ps(s + HOST - early), 1000])
        dones.append([ps(e + HOST + 0.3 * MS), 1000])
    # Anchors: host-plane events centred 1 us either side of HOST past
    # their reads.
    anchors = [[ps(10.0 + HOST - 1e-6) - 10**6, 2 * 10**6],
               [ps(12.0 + HOST + 1e-6) - 10**6, 2 * 10**6]]
    return {"modules": mods, "ops": [], "anchors": anchors,
            "launches": launches, "dones": dones}


def _reduced(ev, reads):
    """``xplane.reduce`` with what ``host_trace.py`` adds to it."""
    red = xplane.reduce(dict(ev, ops=[]))
    red["busy"] = hostspans.busy(ev["modules"])
    red["clock"] = hostspans.clock(ev["modules"], ev, reads)
    return red


def _record():
    red = _reduced(_trace(), [10.0, 12.0])
    return {"trace": {"events": EVENTS, "anchors": [10.0, 12.0],
                      "reduced": red, "t0": 0.0, "t1": 100.0},
            # Two token gaps of 3.3 ms in the window.
            "requests": [{"token_times": [50.0, 50.0033, 50.0066]}]}


def _read(name, rec):
    return cells.metric_reader(name)(rec)


def test_clock_chain():
    c = _record()["trace"]["reduced"]["clock"]
    assert c["disagreement_s"] == pytest.approx(2e-6, abs=1e-9)
    assert c["host_offset_s"] == pytest.approx(HOST, abs=1e-9)
    assert c["device_lag_s"] == pytest.approx(LAG, abs=1e-9)
    # The other programs bound the lag from above: completion 0.3 ms late.
    assert c["device_lag_bounds_s"][1] == pytest.approx(LAG + 0.3 * MS,
                                                        abs=1e-9)
    assert c["offset_s"] == pytest.approx(OFFSET, abs=1e-9)
    assert c["pairs"] == len(PROGRAMS)


def test_clock_pairs_across_cut_edges():
    # The profiler's start: the first program was launched before it (no
    # launch), and an earlier one completes after it (an extra completion).
    ev = _trace()
    ev["launches"] = ev["launches"][1:]
    ev["dones"] = [[ev["modules"][0][1] - ps(1 * MS), 1000]] + ev["dones"]
    c = hostspans.clock(ev["modules"], ev, [10.0, 12.0])
    assert c["device_lag_s"] == pytest.approx(LAG, abs=1e-9)
    assert c["offset_s"] == pytest.approx(OFFSET, abs=1e-9)
    # Without the anchors there is no chain.
    assert hostspans.clock(ev["modules"], dict(ev, anchors=[]),
                           [10.0, 12.0]) is None


def test_engine_readers_by_hand():
    rec = _record()
    # The admitting chunk is left out: two chunks, each 0.1 + 0.8 ms before
    # the sync, 2 ms in it, 0.1 ms merging.
    assert _read("engine.dispatch_ms_per_step", rec) == pytest.approx(0.9)
    assert _read("engine.sync_ms_per_step", rec) == pytest.approx(2.0)
    assert _read("engine.merge_ms_per_step", rec) == pytest.approx(0.1)
    # Two requeue waits of 0.3 ms; the blocked stop is none.
    assert _read("sched.decode_requeue_p95_ms", rec) == pytest.approx(0.3)
    c = hostspans.closure(rec)
    assert c["sum"] == pytest.approx(3.3) and c["gap"] == pytest.approx(3.3)
    a = hostspans.alignment(rec, [10 + t * MS + OFFSET
                                  for t in (100.4, 103.9, 110.3, 100.1)])
    assert (a["decode_starts"], a["inside_dispatch_to_sync"]) == (4, 3)


def test_decode_host_idle_share_by_hand():
    # Busy from 100.4 to 210.0 ms: span 109.6 ms, 5.8 ms of it idle, of
    # which all but the 0.9 ms after the decode job blocked (113.1) lies
    # while it runs or waits out a requeue.
    rec = _record()
    assert rec["trace"]["reduced"]["span_s"] == pytest.approx(109.6 * MS)
    assert _read("device.idle_share.decode_host", rec) == pytest.approx(
        100 * 4.9 / 109.6)


def test_idle_by_host_by_hand():
    got = hostspans.idle_by_host(_record())
    by = dict(got["idle_by_host"])
    # Gaps (ms): two of 0.1 in each decode chunk's sync; 102.5-103.9,
    # 106.0-107.5 and 112.4-114.0 across the chunk edges; 109.5-109.6 and
    # 109.7-110.3 around the admission's programs.
    want = {"engine.sync": 2.1, "engine.admit": 0.7, "train.chunk": 0.7,
            "none": 0.7, "decode.requeue": 0.6, "engine.dispatch": 0.4,
            "engine.merge": 0.3, "engine.prep": 0.3, "parked": 0.0}
    assert set(by) == set(want)
    for k, v in want.items():
        assert by[k] == pytest.approx(v * MS, abs=1e-9), k
    assert [k for k, _ in got["idle_by_host"]][0] == "engine.sync"
    # The longest gap, 112.4 to 114.0 ms: 0.5 in the sync, 0.1 merging,
    # 0.3 between the blocked chunk and the train job, 0.7 in its run.
    g = got["longest_idle_gap"]
    assert g["s"] == pytest.approx(1.6 * MS)
    assert g["host"] == "train.chunk"


def test_readers_without_spans_or_tracer():
    rec = _record()
    plain = [e for e in EVENTS if e["kind"] != "span"]
    no_spans = dict(rec, trace=dict(rec["trace"], events=plain))
    for name in ("engine.dispatch_ms_per_step", "engine.sync_ms_per_step",
                 "engine.merge_ms_per_step"):
        assert _read(name, no_spans) is None
    # The program before the spans still gives the scheduler's and the
    # device's numbers, from events it already had.
    assert _read("sched.decode_requeue_p95_ms", no_spans) == pytest.approx(0.3)
    assert _read("device.idle_share.decode_host", no_spans) is not None
    untraced = dict(rec, trace={k: v for k, v in rec["trace"].items()
                                if k != "events"})
    for name in ("engine.sync_ms_per_step", "sched.decode_requeue_p95_ms",
                 "device.idle_share.decode_host"):
        assert _read(name, untraced) is None
    assert hostspans.idle_by_host(untraced) is None


class _Profiler:
    def __init__(self):
        self.calls = []

    def start_trace(self, *a, **kw):
        self.calls.append("start")

    def stop_trace(self):
        self.calls.append("stop")

    def TraceAnnotation(self, name):
        return contextlib.nullcontext()


class _Jax:
    def __init__(self):
        self.profiler = _Profiler()


@pytest.mark.parametrize("first,wrapped", [(4.0, False), (6.0, True)])
def test_attached_tracer_anchors_and_wrapped_ring(first, wrapped):
    import repro.core as core
    jax, state = _Jax(), {}
    build = core.build_kernel
    with host_trace.attached(jax, state, tracer=True):
        kernel = core.build_kernel("live", policy="ufs", n_slots=1)
        assert state["kernel"] is kernel
        assert isinstance(kernel.tracer, SchedTracer)
        # A ring of four that has dropped two: its oldest event is at
        # ``first``; the window starts at 5.0 on the tracer's clock.
        ring = SchedTracer(capacity=4)
        for i in range(6):
            ring.emit("kick", first - 2.0 + i, slot=0)
        state["kernel"] = type("K", (), {"tracer": ring, "now": 5.0})()
        jax.profiler.start_trace("dir")
        jax.profiler.stop_trace()
    assert core.build_kernel is build
    assert jax.profiler.calls == ["start", "stop"]
    assert state["anchors"] == [5.0, 5.0]
    if wrapped:
        assert "wrapped" in str(state["error"]) and "events" not in state
    else:
        assert "error" not in state
        assert [e["t"] for e in state["events"]] == [4.0, 5.0, 6.0, 7.0]


def test_recorded_trace_aligns():
    """A piece of a traced run on a TPU v5e: the clock chain holds, and the
    decode programs start on the device inside their chunk's dispatch to
    sync."""
    ev = json.loads(gzip.decompress(RECORDED.read_bytes()))
    red = _reduced(ev, ev["reads"])
    c = red["clock"]
    assert c is not None and c["pairs"] == len(ev["modules"])
    assert c["disagreement_s"] < 50e-6
    assert 0 < c["device_lag_s"] <= c["device_lag_bounds_s"][1] < 0.01
    rec = {"trace": {"events": ev["events"], "anchors": ev["reads"],
                     "reduced": red}}
    starts = [s * 1e-12 for name, s, _ in ev["modules"]
              if xplane.module_name(name) == "jit_decode_step"]
    a = hostspans.alignment(rec, starts)
    assert a["decode_starts"] > 0
    assert a["inside_dispatch_to_sync"] == a["decode_starts"]
    got = hostspans.idle_by_host(rec)
    assert sum(v for _, v in got["idle_by_host"]) == pytest.approx(
        red["span_s"] - red["busy_s"], abs=1e-9)
