"""The check has teeth: a run at a tiny size on the CPU, past the
harness's look for a chip, comes out correct as the program stands and
not correct with the timed path broken underneath, once for each fault
the cells can have (a single chip: no exchange between chips to leave
out)."""
from __future__ import annotations

import pytest

import tiny


def _checks(out):
    return {k: v["value"] for k, v in out["checks"].items()}


def test_sound_run_is_correct():
    out = tiny.run(tiny.cell())
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


def _broken_step(kind):
    from repro.training import trainer as T
    make = T.make_train_step

    def make_broken(model, tcfg):
        step = make(model, tcfg)

        def broken(state, batch):
            if kind == "unchanged":
                _, m = step(state, batch)
                return state, m
            half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            return step(state, half)
        return broken
    return make_broken


@pytest.mark.parametrize("kind,number", [("unchanged", "train.change_gap"),
                                         ("half_batch", "train.grad_gap")])
def test_broken_train_step_is_caught(monkeypatch, kind, number):
    from repro.training import trainer as T
    monkeypatch.setattr(T, "make_train_step", _broken_step(kind))
    out = tiny.run(tiny.cell())
    assert not out["correct"]
    assert out["checks"][number]["value"] > out["checks"][number]["limit"]


def test_altered_token_is_caught(monkeypatch):
    from repro.serving import engine as E
    activate = E.InferenceEngine._activate_locked

    def altered(self, req, slot, tok, now):
        activate(self, req, slot, (tok + 1) % self.model.cfg.vocab_size, now)
    monkeypatch.setattr(E.InferenceEngine, "_activate_locked", altered)
    out = tiny.run(tiny.cell(background=None))
    assert not out["correct"]
    assert _checks(out)["serve.token_gap"] > tiny.LIMITS["serve.token_gap"]


def test_batched_rows_fail_single_row_passes():
    """The engine decodes every row of a batch at the longest row's
    position, so several rows of different lengths decode wrongly: the
    check fails with 4 cache rows and staggered arrivals and passes with
    the one row the cells hold."""
    busy = {"kind": "poisson", "rate_per_s": 40.0, "schedule_seed": 3}
    every = {"requests": 1000, "bulk_requests": 0}
    four = tiny.run(tiny.cell(background=None, max_batch=4, arrivals=busy,
                              check=every))
    one = tiny.run(tiny.cell(background=None, max_batch=1, arrivals=busy,
                             check=every))
    print("serve.token_gap with 4 rows", _checks(four)["serve.token_gap"],
          "with 1 row", _checks(one)["serve.token_gap"])
    assert not four["correct"]
    assert one["correct"]


def test_control_reads_above_the_limits():
    """The reference computed in float8 in the program's place, and the
    half-batch fault, are judged not correct by the harness's own result
    where a sound run of the same requests and rows is correct
    (``calibrate.py`` does the same on the chip at the cell's size)."""
    import run as bench_run
    import serve_cell
    import time
    c = tiny.cell()
    res = serve_cell.run(c, 987654321, 3.0, False, time.monotonic(),
                         require_tpu=False, control=True)
    assert bench_run.result(c, res, False)["correct"], res["checks"]
    assert set(res["control"]) == {"control", "half_batch"}
    for what, checks in res["control"].items():
        print(what, {k: v["value"] for k, v in checks.items()})
        judged = bench_run.result(c, dict(res, checks=checks), False)
        assert not judged["correct"], (what, checks)
