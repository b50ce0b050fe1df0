"""Decode one step ahead: the engine dispatches step n+1 from step n's
device tokens before it reads step n, and discards the step in flight on a
generation change.  Engine tokens must equal a direct greedy decode, on the
stub model and on the reduced transformer.  CPU."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.live import LiveKernel
from repro.core.policies import make_policy
from repro.models.transformer import Model
from repro.serving.engine import InferenceEngine, Request
from repro.serving.stub import TinyStubModel

MAX_LEN = 64
PROMPT_LEN = 8          # the smallest prefill bucket: no padding


@pytest.fixture(scope="module", params=["stub", "transformer"])
def model_params(request):
    if request.param == "stub":
        model = TinyStubModel()
        return model, model.init_params(0)
    model = Model(get_arch("qwen2-0.5b").reduced())
    return model, model.init_params(jax.random.PRNGKey(0))


def _engine(model, params, max_batch=2, max_len=MAX_LEN):
    kernel = LiveKernel(1, make_policy("ufs"))
    engine = InferenceEngine(model, params, kernel, max_batch=max_batch,
                             max_len=max_len)
    calls = []
    decode = engine._decode

    def counted(prms, caches, toks, pos):
        calls.append(pos)
        return decode(prms, caches, toks, pos)

    engine._decode = counted
    return kernel, engine, calls


def _serve(kernel, engine, reqs, later=(), timeout=120):
    """Queues every request before the loop starts, so that one admission
    takes them all, then serves them and those submitted ``later`` to
    completion."""
    kernel.start()
    try:
        for r in reqs:
            engine.submit(r)
        engine.start()
        for r in (*reqs, *later):
            assert r.done_event.wait(timeout) and r.ok, r.error
    finally:
        engine.stop()
        kernel.stop()


def _direct_greedy(model, params, prompt, n_tokens):
    logits, caches = model.prefill(
        params, {"tokens": jnp.asarray(prompt[None, :], jnp.int32)}, MAX_LEN)
    toks = [int(jnp.argmax(logits[0, -1]))]
    decode = jax.jit(model.decode_step)
    pos = len(prompt)
    while len(toks) < n_tokens:
        lg, caches = decode(params, caches,
                            jnp.asarray([[toks[-1]]], jnp.int32), pos)
        toks.append(int(jnp.argmax(lg[0, 0])))
        pos += 1
    return toks


def _prompt(seed, vocab):
    rng = np.random.default_rng(seed)
    return rng.integers(1, vocab, PROMPT_LEN).astype(np.int32)


def _vocab(model):
    return getattr(model, "vocab", None) or model.cfg.vocab_size


def test_one_row_decodes_ahead_exactly(model_params):
    model, params = model_params
    kernel, engine, calls = _engine(model, params)
    p = _prompt(1, _vocab(model))
    r = Request(prompt=p, max_new_tokens=32)
    _serve(kernel, engine, [r])
    assert r.tokens == _direct_greedy(model, params, p, 32)
    s = engine.stats.summary()
    assert s["decode_steps"] == 31
    assert s["decode_ahead"] == 30 and s["decode_ahead_share"] > 0.8
    assert s["decode_ahead_discarded"] == 0
    # One program call per committed step, each at its own position.
    assert len(calls) == 31
    assert calls == list(range(PROMPT_LEN, PROMPT_LEN + 31))
    assert engine._ahead is None


def test_two_rows_with_staggered_lengths(model_params):
    model, params = model_params
    kernel, engine, calls = _engine(model, params)
    prompts = [_prompt(2, _vocab(model)), _prompt(3, _vocab(model))]
    reqs = [Request(prompt=prompts[0], max_new_tokens=5),
            Request(prompt=prompts[1], max_new_tokens=12)]
    _serve(kernel, engine, reqs)
    for p, r in zip(prompts, reqs):
        assert r.tokens == _direct_greedy(model, params, p, r.max_new_tokens)
    s = engine.stats.summary()
    assert s["decode_steps"] == 11 and len(calls) == 11
    assert s["decode_ahead"] == 10 and s["decode_ahead_discarded"] == 0


def test_two_token_request_dispatches_no_ahead_step(model_params):
    model, params = model_params
    kernel, engine, calls = _engine(model, params)
    p = _prompt(4, _vocab(model))
    r = Request(prompt=p, max_new_tokens=2)
    _serve(kernel, engine, [r])
    assert r.tokens == _direct_greedy(model, params, p, 2)
    s = engine.stats.summary()
    assert len(calls) == 1 and s["decode_steps"] == 1
    assert s["decode_ahead"] == 0 and s["decode_ahead_discarded"] == 0
    assert engine._ahead is None


def test_generation_bump_discards_the_step_in_flight(model_params):
    """A publish between two chunks moves the generation while a step is
    in flight: the next chunk drops it unread and decodes again from the
    committed caches and host tokens."""
    model, params = model_params
    kernel, engine, calls = _engine(model, params)
    reserve = engine._reserve_admissions_locked
    fired = []

    def bump_once():                 # phase 1 of a chunk, lock held
        if not fired and engine.stats.decode_steps >= 3 and engine._ahead:
            fired.append(engine.stats.decode_steps)
            engine._gen += 1
        return reserve()

    engine._reserve_admissions_locked = bump_once
    p = _prompt(5, _vocab(model))
    r = Request(prompt=p, max_new_tokens=10)
    _serve(kernel, engine, [r])
    assert fired
    assert r.tokens == _direct_greedy(model, params, p, 10)
    s = engine.stats.summary()
    assert s["decode_ahead_discarded"] == 1
    assert s["decode_invalidations"] == 0
    assert s["decode_steps"] == 9 and len(calls) == 10
    assert s["decode_ahead"] == 7


def test_admission_while_a_step_is_in_flight():
    """A request admitted while the first one's next step is in flight
    (submitted from inside that step's dispatch): the step is discarded
    and both answers stay exact (the stub ignores positions)."""
    model = TinyStubModel()
    params = model.init_params(0)
    kernel, engine, calls = _engine(model, params)
    late = Request(prompt=np.arange(1, 6, dtype=np.int32), max_new_tokens=6)
    decode = engine._decode

    def submit_on_third(prms, caches, toks, pos):
        out = decode(prms, caches, toks, pos)
        if len(calls) == 3:
            engine.submit(late)
        return out

    engine._decode = submit_on_third
    first = Request(prompt=np.arange(1, 4, dtype=np.int32), max_new_tokens=9)
    _serve(kernel, engine, [first], later=[late])
    assert first.tokens == _direct_greedy(model, params, first.prompt, 9)
    assert late.tokens == _direct_greedy(model, params, late.prompt, 6)
    s = engine.stats.summary()
    assert s["decode_ahead_discarded"] == 1 and s["decode_invalidations"] == 0
    assert len(calls) == s["decode_steps"] + 1


def test_stop_drain_drops_the_step_in_flight():
    model = TinyStubModel()
    kernel, engine, _ = _engine(model, model.init_params(0), max_batch=1,
                                max_len=1 << 30)   # the stub keeps no cache
    kernel.start()
    engine.start()
    r = engine.submit(Request(prompt=np.arange(1, 4, dtype=np.int32),
                              max_new_tokens=100_000))
    deadline = time.monotonic() + 30
    while engine.stats.decode_ahead < 3 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert engine.stats.decode_ahead >= 3
    engine.stop(drain=True)
    kernel.stop()
    assert r.error == "shutdown"
    assert engine._ahead is None
    assert engine.stats.decode_ahead_discarded == 1
    assert sorted(engine.pool.free) == [0]


def test_decode_program_is_one_module_named_decode_step(model_params):
    """The device trace's readers find the decode program by its module
    name, ``jit_decode_step``; the program returns next tokens, not
    logits; both input kinds (host tokens, a previous step's tokens) share
    one compiled program."""
    model, params = model_params
    kernel = LiveKernel(1, make_policy("ufs"))
    engine = InferenceEngine(model, params, kernel, max_batch=2,
                             max_len=MAX_LEN)
    toks = jnp.asarray(np.ones((2, 1), np.int32))
    text = engine._decode.lower(params, engine.caches, toks, 3).as_text()
    assert text.startswith("module @jit_decode_step ")
    nxt, caches = engine._decode(params, engine.caches, toks, 3)
    assert nxt.shape == (2, 1) and nxt.dtype == jnp.int32
    engine._decode(params, caches, nxt, 4)
    assert engine._decode._cache_size() == 1


def test_concurrent_submits_and_expiries_keep_every_step_accounted():
    """Submitters on several threads, some requests expiring mid-answer,
    with a short switch interval: every answer (or the part served before
    its deadline) is the direct greedy decode's prefix, and every dispatched
    step ends in exactly one counter."""
    import sys
    import threading

    model = TinyStubModel()
    params = model.init_params(0)
    kernel, engine, calls = _engine(model, params, max_batch=4)
    reqs, lock = [], threading.Lock()

    def submitter(k):
        rng = np.random.default_rng(k)
        for i in range(6):
            r = Request(prompt=rng.integers(1, model.vocab, 1 + i % 5)
                        .astype(np.int32),
                        max_new_tokens=int(rng.integers(2, 24)),
                        deadline_s=0.005 if i % 3 == 0 else None)
            with lock:
                reqs.append(engine.submit(r))
            time.sleep(float(rng.uniform(0, 0.003)))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    kernel.start()
    engine.start()
    try:
        threads = [threading.Thread(target=submitter, args=(k,))
                   for k in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
            assert not t.is_alive()
        for r in reqs:
            assert r.done_event.wait(60)
    finally:
        sys.setswitchinterval(interval)
        engine.stop()
        kernel.stop()
    assert len(reqs) == 48
    for r in reqs:
        assert r.ok or r.error == "deadline"
        want = _direct_greedy(model, params, r.prompt, r.max_new_tokens)
        assert r.tokens == want[:len(r.tokens)]
    s = engine.stats.summary()
    assert len(calls) == (s["decode_steps"] + s["decode_invalidations"]
                          + s["decode_ahead_discarded"])
    assert engine._ahead is None and sorted(engine.pool.free) == [0, 1, 2, 3]
