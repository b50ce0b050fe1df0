"""Engine spans on the scheduler's tracer: a traced decode loop records its
phases (``engine.prep``/``admit``/``dispatch``/``sync``/``merge``) as
``span`` events nested in the decode job's runs; an untraced one records
and builds nothing.  CPU, stub model."""
import time

import numpy as np
import pytest

from repro.core.live import LiveKernel
from repro.core.policies import make_policy
from repro.core.trace import (PID_ENGINE, SPAN_NAMES, SchedTracer,
                              TraceSchemaError, TraceSummary, busy_intervals,
                              to_chrome_trace, validate_chrome_trace,
                              validate_events)
from repro.serving.engine import InferenceEngine, Request
from repro.serving.stub import TinyStubModel


def _serve(tracer, n_requests=4, n_tokens=5):
    """A one-slot engine serving a few requests to completion; returns the
    engine (stopped) and its requests."""
    model = TinyStubModel()
    kernel = LiveKernel(1, make_policy("ufs"), tracer=tracer)
    engine = InferenceEngine(model, model.init_params(0), kernel,
                             max_batch=2, max_len=64)
    kernel.start()
    engine.start()
    rng = np.random.default_rng(0)
    try:
        reqs = []
        for _ in range(n_requests):
            reqs.append(engine.submit(Request(
                prompt=rng.integers(1, model.vocab, 6).astype(np.int32),
                max_new_tokens=n_tokens)))
            time.sleep(0.01)
        for r in reqs:
            assert r.done_event.wait(60) and r.ok
    finally:
        engine.stop()
        kernel.stop()
    return engine, reqs


@pytest.fixture(scope="module")
def traced():
    tracer = SchedTracer()
    engine, reqs = _serve(tracer)
    return tracer.events, engine, reqs


def _decode_runs(events, engine):
    """The decode job's runs, each with the spans that end inside it."""
    runs = [iv for ivs in busy_intervals(events).values() for iv in ivs
            if iv["jid"] == engine._job.jid]
    spans = [e for e in events if e.kind == "span"]
    out = []
    for iv in runs:
        inside = [e for e in spans
                  if iv["start"] <= e.args["t0"] and e.t <= iv["stop"]]
        out.append((iv, sorted(inside, key=lambda e: e.args["t0"])))
    return out, spans


def test_traced_engine_emits_every_span_nested_in_decode_runs(traced):
    events, engine, reqs = traced
    validate_events(events, balanced=False)
    runs, spans = _decode_runs(events, engine)
    assert {e.args["name"] for e in spans} == SPAN_NAMES
    nested = sum(len(s) for _, s in runs)
    assert nested == len(spans), "every span lies inside one decode run"
    rids = {r.rid for r in reqs}
    for e in spans:
        assert e.jid == engine._job.jid and e.job == "decode-loop"
        assert e.slot == 0 and e.args["t0"] <= e.t
        assert set(e.args["rids"]) <= rids
    # One committed step per merge span.
    merges = sum(1 for e in spans if e.args["name"] == "engine.merge")
    assert merges == engine.stats.decode_steps


def test_spans_of_one_chunk_disjoint_and_within_run(traced):
    events, engine, _ = traced
    runs, _ = _decode_runs(events, engine)
    assert any(spans for _, spans in runs)
    for iv, spans in runs:
        for a, b in zip(spans, spans[1:]):
            assert a.t <= b.args["t0"], (a, b)
        total = sum(e.t - e.args["t0"] for e in spans)
        assert total <= iv["stop"] - iv["start"] + 1e-9
        names = [e.args["name"] for e in spans]
        if "engine.dispatch" in names:
            i = names.index("engine.dispatch")
            assert names[i - 1] == "engine.prep"
            assert names[i:] in (["engine.dispatch", "engine.sync",
                                  "engine.merge"],
                                 ["engine.dispatch", "engine.sync"])


def test_untraced_engine_emits_nothing_and_builds_no_span_args(monkeypatch):
    calls = []
    monkeypatch.setattr(InferenceEngine, "_span",
                        lambda self, *a, **kw: calls.append(a))
    engine, _ = _serve(None, n_requests=2, n_tokens=3)
    assert engine.kernel.tracer is None
    assert engine.stats.decode_steps > 0
    assert calls == []
    # No lock-hold sampling either: the bare lock is taken.
    assert len(engine.stats.lock_hold_s) == 0
    assert engine._held() is engine._lock
    assert engine.stats.summary()["lock_holds"] == 0


def test_traced_engine_samples_lock_holds(traced):
    _, engine, _ = traced
    s = engine.stats.summary()
    assert s["lock_holds"] > 0 and s["lock_hold_max_us"] >= s["lock_hold_p50_us"]


def test_validate_events_rejects_bad_spans():
    class J:
        jid, name, kind = 3, "decode-loop", "bursty"
        group = type("G", (), {"name": "serve"})

    tr = SchedTracer()
    tr.emit("span", 2.0, slot=0, job=J(), name="engine.sync", t0=1.5,
            rids=[1])
    assert validate_events(tr.events)["span"] == 1
    bad = SchedTracer()
    bad.emit("span", 2.0, slot=0, job=J(), name="engine.nap", t0=1.0)
    with pytest.raises(TraceSchemaError, match="unknown span name"):
        validate_events(bad.events)
    late = SchedTracer()
    late.emit("span", 2.0, slot=0, job=J(), name="engine.sync", t0=2.5)
    with pytest.raises(TraceSchemaError, match="after its end"):
        validate_events(late.events)


def test_chrome_export_puts_spans_on_engine_track(traced):
    events, engine, _ = traced
    doc = to_chrome_trace(events, end=events[-1].t)
    assert validate_chrome_trace(doc) > 0
    eng = [e for e in doc["traceEvents"] if e["pid"] == PID_ENGINE]
    names = {e["args"]["name"] for e in eng if e["ph"] == "M"}
    assert {"engine", "slot0"} <= names
    xs = [e for e in eng if e["ph"] == "X"]
    assert {e["name"] for e in xs} == SPAN_NAMES
    assert all(e["dur"] >= 0 and e["args"]["job"] == "decode-loop" for e in xs)
    # A stream without spans exports no engine process at all.
    plain = [e for e in events if e.kind != "span"]
    assert all(e["pid"] != PID_ENGINE
               for e in to_chrome_trace(plain)["traceEvents"])


def test_summary_diff_ignores_live_only_kinds():
    sim = TraceSummary(counts={"wake": 3, "start_job": 2})
    live = TraceSummary(counts={"wake": 5, "start_job": 4, "park": 1,
                                "unpark": 1, "span": 9})
    assert sim.diff(live) == {}
    assert sim.diff(TraceSummary(counts={"wake": 1})) == {"start_job": (2, 0)}
