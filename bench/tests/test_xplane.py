"""The trace reduction, on a small trace recorded on a TPU v5e (the
bring-up model's admission prefill and decode programs) and on a made-up
one whose answers are known."""
from __future__ import annotations

import gzip
import json
from pathlib import Path

import pytest

import cells
import flops
import xplane

SMALL = Path(__file__).parent / "data" / "small_trace.json.gz"


def test_union_self_time_and_attribution():
    # The first and last programs may be cut by the profiler: left out.
    ev = {"modules": [["jit_c(3)", -500, 20], ["jit_a(1)", 0, 100],
                      ["jit_b(2)", 150, 50], ["jit_a(1)", 300, 100],
                      ["jit_c(3)", 900, 20]],
          "ops": [["%while.1 = (...) while(...)", 10, 80],
                  ["%fusion.2 = f32[4] fusion(...)", 20, 30],
                  ['%k.3 = bf16[2,128,64]{} custom-call(bf16[2,128,64]{} %q, '
                   'bf16[2,128,64]{} %k, bf16[2,128,64]{} %v), '
                   'custom_call_target="tpu_custom_call"', 60, 20],
                  ["%fusion.9 = f32[4] fusion(...)", 160, 40]]}
    red = xplane.reduce(ev)
    assert red["busy_s"] == pytest.approx(250e-12)
    # The span covers the programs kept, not the cut ones at the edges.
    assert red["span_s"] == pytest.approx(400e-12)
    idle = cells.metric_reader("device.idle_share.mix")({"trace": {"reduced": red}})
    assert idle == pytest.approx(37.5)
    assert red["programs"]["jit_a"]["count"] == 2
    assert red["programs"]["jit_a"]["device_s"] == pytest.approx(200e-12)
    k, = red["kernels"]
    assert k["program"] == "jit_a" and k["shapes"][1] == ("bf16", (2, 128, 64))
    ops = dict(red["device_ops"])
    assert ops["jit_a/while"] == pytest.approx(30e-12)      # 80 - 30 - 20
    assert ops["jit_b/fusion"] == pytest.approx(40e-12)
    gaps = dict(red["idle_gaps"])
    assert gaps["after jit_a before jit_b"] == pytest.approx(50e-12)
    assert gaps["after jit_b before jit_a"] == pytest.approx(100e-12)


def test_recorded_trace():
    ev = json.loads(gzip.decompress(SMALL.read_bytes()))
    # The piece was cut out of a longer trace: put a program before and
    # after it, in the places the profiler's start and stop would cut.
    t0 = min(m[1] for m in ev["modules"])
    t1 = max(m[1] + m[2] for m in ev["modules"])
    ev["modules"] += [["jit_edge(0)", t0 - 10**6, 1000],
                      ["jit_edge(0)", t1 + 10**6, 1000]]
    red = xplane.reduce(ev)
    assert "jit_edge" not in red["programs"]
    progs = red["programs"]
    assert set(progs) >= {"jit_prefill_batch", "jit_decode_step"}
    # Busy time is the union of the programs: never more than their sum.
    assert red["busy_s"] <= sum(p["device_s"] for p in progs.values()) + 1e-12
    assert red["busy_s"] <= red["span_s"]
    # The flash kernel runs once per layer inside every admission prefill.
    flash = [k for k in red["kernels"] if k["program"] == "jit_prefill_batch"]
    assert flash and len(flash) % progs["jit_prefill_batch"]["count"] == 0
    f, b = flops.flash_cost(flash[0]["shapes"])
    assert f > 0 and b > 0
    assert sum(k["device_s"] for k in flash) < progs["jit_prefill_batch"]["device_s"]
