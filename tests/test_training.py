"""Training substrate: optimizer math, grad accumulation equivalence,
gradient compression (error feedback), loss-goes-down integration,
checkpoint fault tolerance, data pipeline determinism."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.data.pipeline import SyntheticTokens, TokenFile, batches
from repro.models.transformer import Model
from repro.training import grad_compress
from repro.training.checkpoint import CheckpointManager
from repro.training.optimizer import OptimizerConfig, apply_updates, init_state, lr_at
from repro.training.trainer import TrainConfig, init_state as tstate, make_train_step

KEY = jax.random.PRNGKey(0)


def tiny_model():
    cfg = get_arch("llama3.2-1b").reduced()
    return Model(cfg), cfg


# ---------------------------------------------------------------- optimizer
def test_lr_schedule_shape():
    cfg = OptimizerConfig(lr=1.0, warmup_steps=10, total_steps=100, min_lr_frac=0.1)
    assert float(lr_at(cfg, 0)) == 0.0
    assert float(lr_at(cfg, 10)) == pytest.approx(1.0, rel=1e-3)
    assert float(lr_at(cfg, 100)) == pytest.approx(0.1, rel=1e-3)


def test_adamw_step_moves_toward_gradient():
    cfg = OptimizerConfig(lr=0.1, warmup_steps=0, weight_decay=0.0, clip_norm=0.0)
    params = {"w": jnp.ones((4, 4))}
    grads = {"w": jnp.ones((4, 4))}
    st = init_state(cfg, params)
    newp, st, m = apply_updates(cfg, params, grads, st)
    assert float(jnp.max(newp["w"])) < 1.0
    assert int(st["step"]) == 1
    assert m["grad_norm"] > 0


def test_bf16_optimizer_state_dtype():
    cfg = OptimizerConfig(state_dtype="bfloat16")
    st = init_state(cfg, {"w": jnp.ones((8,))})
    assert st["m"]["w"].dtype == jnp.bfloat16


@pytest.mark.slow
def test_grad_accum_equivalence():
    """accum=2 over a batch == accum=1 on the same batch (linear loss avg)."""
    model, cfg = tiny_model()
    params = model.init_params(KEY)
    toks = jax.random.randint(KEY, (4, 16), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    ocfg = OptimizerConfig(lr=1e-2, warmup_steps=0, clip_norm=0.0)
    s1 = {"params": params, "opt": init_state(ocfg, params)}
    s2 = {"params": params, "opt": init_state(ocfg, params)}
    step1 = make_train_step(model, TrainConfig(grad_accum=1, opt=ocfg))
    step2 = make_train_step(model, TrainConfig(grad_accum=2, opt=ocfg))
    n1, m1 = step1(s1, batch)
    n2, m2 = step2(s2, batch)
    d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                     n1["params"], n2["params"])
    assert max(jax.tree.leaves(d)) < 2e-3
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3


def test_loss_decreases_on_tiny_model():
    model, cfg = tiny_model()
    tcfg = TrainConfig(opt=OptimizerConfig(lr=3e-3, warmup_steps=2, total_steps=50))
    state = tstate(model, tcfg, KEY)
    step = jax.jit(make_train_step(model, tcfg))
    src = SyntheticTokens(cfg.vocab_size, seed=1)
    losses = []
    batch = src.batch(0, 0, 8, 32)           # overfit one batch
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    for i in range(20):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.8


# ------------------------------------------------------------- compression
def test_compression_error_feedback_unbiased():
    g = jax.random.normal(KEY, (512,))
    err = grad_compress.init_error_state(g)
    acc = jnp.zeros_like(g)
    for _ in range(50):
        out, err = grad_compress.compress_decompress(g, err)
        acc = acc + out
    # time-averaged compressed gradient converges to the true gradient
    assert float(jnp.max(jnp.abs(acc / 50 - g))) < 0.02


def test_compressed_psum_single_axis():
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("pod",))
    x = jax.random.normal(KEY, (64,))
    f = jax.shard_map(lambda a: grad_compress.compressed_psum(a, "pod"),
                      mesh=mesh, in_specs=jax.sharding.PartitionSpec(),
                      out_specs=jax.sharding.PartitionSpec())
    y = f(x)
    assert float(jnp.max(jnp.abs(y - x))) < 0.05    # quantization error only


# -------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip_and_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_n=2)
    tree = {"a": jnp.arange(6.0).reshape(2, 3), "b": {"c": jnp.ones((4,), jnp.int32)}}
    for step in (1, 2, 3):
        mgr.save(step, jax.tree.map(lambda x: x * step, tree))
    assert mgr.all_steps() == [2, 3]          # keep_n GC
    restored = mgr.restore(3, tree)
    assert np.allclose(restored["a"], np.arange(6.0).reshape(2, 3) * 3)


def test_checkpoint_atomicity_ignores_tmp(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    tree = {"a": jnp.ones((2,))}
    mgr.save(5, tree)
    os.makedirs(os.path.join(str(tmp_path), "step_00000009.tmp"))
    assert mgr.latest_step() == 5


def test_checkpoint_detects_corruption(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    tree = {"a": jnp.ones((16,))}
    mgr.save(1, tree)
    d = os.path.join(str(tmp_path), "step_00000001")
    fn = [f for f in os.listdir(d) if f.endswith(".npy")][0]
    arr = np.load(os.path.join(d, fn))
    arr[0] = 999.0
    np.save(os.path.join(d, fn), arr)
    with pytest.raises(IOError):
        mgr.restore(1, tree)


def test_checkpoint_async(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    tree = {"a": jnp.full((32,), 7.0)}
    mgr.save(4, tree)
    mgr.wait()
    assert mgr.latest_step() == 4
    got = mgr.restore(4, tree)
    assert np.allclose(got["a"], 7.0)


def test_resume_after_simulated_failure(tmp_path):
    """Train, checkpoint, 'crash', restore, continue: loss state matches."""
    model, cfg = tiny_model()
    tcfg = TrainConfig(opt=OptimizerConfig(lr=1e-3, warmup_steps=0))
    state = tstate(model, tcfg, KEY)
    step = jax.jit(make_train_step(model, tcfg))
    src = SyntheticTokens(cfg.vocab_size, seed=3)
    mgr = CheckpointManager(str(tmp_path))
    for i in range(3):
        b = {k: jnp.asarray(v) for k, v in src.batch(i, 0, 4, 16).items()}
        state, _ = step(state, b)
    mgr.save(3, state)
    ref_state = state
    for i in range(3, 5):
        b = {k: jnp.asarray(v) for k, v in src.batch(i, 0, 4, 16).items()}
        ref_state, _ = step(ref_state, b)
    # crash + restore
    like = jax.tree.map(lambda x: x, state)
    step_n, restored = mgr.restore_latest(like)
    assert step_n == 3
    for i in range(3, 5):
        b = {k: jnp.asarray(v) for k, v in src.batch(i, 0, 4, 16).items()}
        restored, _ = step(restored, b)
    diff = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                                           - b.astype(jnp.float32)))),
                        ref_state["params"], restored["params"])
    assert max(jax.tree.leaves(diff)) < 1e-5  # deterministic resume


# --------------------------------------------------------------- pipeline
def test_synthetic_determinism():
    src = SyntheticTokens(1000, seed=5)
    a = src.batch(3, 1, 4, 16)
    b = src.batch(3, 1, 4, 16)
    assert np.array_equal(a["tokens"], b["tokens"])
    c = src.batch(3, 2, 4, 16)
    assert not np.array_equal(a["tokens"], c["tokens"])   # rank-sharded


def test_token_file_and_prefetch(tmp_path):
    path = os.path.join(str(tmp_path), "toks.bin")
    np.arange(10_000, dtype=np.int32).tofile(path)
    tf = TokenFile(path, seed=1)
    it = batches(tf, steps=4, dp_rank=0, dp_size=2, batch=2, seq=32)
    got = list(it)
    assert len(got) == 4
    assert got[0]["tokens"].shape == (2, 32)
    assert np.array_equal(got[0]["labels"][:, 0], got[0]["tokens"][:, 1])
