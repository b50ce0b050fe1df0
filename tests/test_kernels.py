"""Per-kernel validation: Pallas kernel bodies (interpret mode on CPU) and
the XLA blocked implementations, swept over shapes/dtypes against the
pure-jnp oracles in kernels/ref.py."""
import jax
import jax.numpy as jnp
import pytest

pytest.importorskip("hypothesis")
import hypothesis.strategies as st
from hypothesis import given, settings

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


def rand(shape, dtype=jnp.float32, k=0, scale=1.0):
    return (jax.random.normal(jax.random.fold_in(KEY, k), shape) * scale).astype(dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 3e-2}


# ------------------------------------------------------------ flash attn
def _shape(bh, sq, sk, d, dv=None):
    """A row of the causal sweep; V takes a width of its own where ``dv``
    is given (MLA: qk 192, v 128), and the row keeps its id without it."""
    return pytest.param(bh, sq, sk, d, dv or d,
                        id="-".join(map(str, (bh, sq, sk, d) + ((dv,) if dv else ()))))


@pytest.mark.parametrize("bh,sq,sk,d,dv", [
    _shape(4, 256, 256, 64), _shape(2, 128, 256, 32), _shape(1, 512, 512, 128),
    _shape(3, 128, 128, 16), _shape(2, 256, 256, 96, 64),
    _shape(2, 128, 256, 96, 64), _shape(2, 256, 256, 192, 128),
    _shape(1, 128, 256, 192, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("backend", ["interpret", "xla"])
def test_flash_attention_causal(bh, sq, sk, d, dv, dtype, backend):
    q, k, v = rand((bh, sq, d), dtype, 1), rand((bh, sk, d), dtype, 2), rand((bh, sk, dv), dtype, 3)
    r = ref.flash_attention_ref(q, k, v, causal=True)
    o = ops.flash_attention(q, k, v, causal=True, backend=backend,
                            block_q=128, block_k=128)
    assert jnp.max(jnp.abs(o.astype(jnp.float32) - r.astype(jnp.float32))) < TOL[dtype]


@pytest.mark.parametrize("window", [64, 128])
@pytest.mark.parametrize("backend", ["interpret", "xla"])
def test_flash_attention_sliding_window(window, backend):
    q, k, v = rand((2, 256, 32), k=1), rand((2, 256, 32), k=2), rand((2, 256, 32), k=3)
    r = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    o = ops.flash_attention(q, k, v, causal=True, window=window,
                            backend=backend, block_q=64, block_k=64)
    assert jnp.max(jnp.abs(o - r)) < 2e-5


@pytest.mark.parametrize("backend", ["interpret", "xla"])
def test_flash_attention_noncausal(backend):
    q, k, v = rand((2, 128, 64), k=4), rand((2, 128, 64), k=5), rand((2, 128, 64), k=6)
    r = ref.flash_attention_ref(q, k, v, causal=False)
    o = ops.flash_attention(q, k, v, causal=False, backend=backend,
                            block_q=64, block_k=64)
    assert jnp.max(jnp.abs(o - r)) < 2e-5


@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_flash_xla_differentiable(backend):
    """The Pallas kernel differentiates through its custom VJP (backward =
    VJP of the blocked xla path); both match the reference gradient."""
    q, k, v = rand((2, 128, 32), k=1), rand((2, 128, 32), k=2), rand((2, 128, 32), k=3)

    def f(q, k, v):
        return jnp.sum(ops.flash_attention(q, k, v, backend=backend,
                                           block_q=64, block_k=64) ** 2)

    def fr(q, k, v):
        return jnp.sum(ref.flash_attention_ref(q, k, v) ** 2)
    g1 = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert jnp.max(jnp.abs(a - b)) < 1e-4


# ---------------------------------------------------------- decode attn
@pytest.mark.parametrize("bh,s,d", [(6, 512, 64), (2, 2048, 128), (8, 256, 32)])
def test_decode_attention_ragged_lengths(bh, s, d):
    q, k, v = rand((bh, 1, d), k=1), rand((bh, s, d), k=2), rand((bh, s, d), k=3)
    lengths = (jnp.arange(bh) * (s // bh) + 1).astype(jnp.int32)
    r = ref.decode_attention_ref(q, k, v, lengths)
    o = ops.decode_attention(q, k, v, lengths, backend="interpret", block_k=128)
    assert jnp.max(jnp.abs(o - r)) < 2e-5


# --------------------------------------------------------------- mlstm
@pytest.mark.parametrize("bh,s,dk,dv", [(2, 256, 32, 32), (4, 128, 16, 64),
                                        (1, 512, 64, 64)])
@pytest.mark.parametrize("backend", ["interpret", "xla"])
def test_mlstm_chunkwise_vs_recurrence(bh, s, dk, dv, backend):
    q, k = rand((bh, s, dk), k=1, scale=0.5), rand((bh, s, dk), k=2, scale=0.5)
    v = rand((bh, s, dv), k=3)
    logf = jax.nn.log_sigmoid(rand((bh, s), k=4) + 2.0)
    i = jax.nn.sigmoid(rand((bh, s), k=5))
    r = ref.mlstm_scan_ref(q, k, v, logf, i)
    o = ops.mlstm_scan(q, k, v, logf, i, backend=backend, chunk=64)
    assert jnp.max(jnp.abs(o - r)) < 1e-3


def test_mlstm_xla_differentiable():
    bh, s, d = 1, 128, 16
    q, k, v = rand((bh, s, d), k=1, scale=0.3), rand((bh, s, d), k=2, scale=0.3), rand((bh, s, d), k=3)
    logf = jax.nn.log_sigmoid(rand((bh, s), k=4) + 2.0)
    i = jax.nn.sigmoid(rand((bh, s), k=5))
    g = jax.grad(lambda v: jnp.sum(ops.mlstm_scan(q, k, v, logf, i,
                                                  backend="xla", chunk=32)))(v)
    assert jnp.all(jnp.isfinite(g))


# ------------------------------------------------------------ moe router
@pytest.mark.parametrize("t,e,k,n_valid", [(512, 64, 4, 60), (256, 256, 8, 256),
                                           (128, 16, 2, 16)])
def test_moe_topk_matches_ref(t, e, k, n_valid):
    logits = rand((t, e), k=1)
    rw, ri = ref.moe_topk_ref(logits, k, n_valid=n_valid)
    ow, oi = ops.moe_topk(logits, k, n_valid=n_valid, backend="interpret")
    assert jnp.all(ri == oi)
    assert jnp.max(jnp.abs(rw - ow)) < 1e-6


@settings(max_examples=20, deadline=None)
@given(t=st.integers(1, 64), e=st.integers(4, 64), k=st.integers(1, 4),
       pad=st.integers(0, 3), seed=st.integers(0, 100))
def test_moe_router_invariants(t, e, k, pad, seed):
    """Property: weights sum to 1, indices unique per token and always
    inside the valid (non-padding) expert range."""
    k = min(k, e)
    n_valid = max(k, e - pad)
    logits = jax.random.normal(jax.random.PRNGKey(seed), (t, e))
    w, idx = ref.moe_topk_ref(logits, k, n_valid=n_valid)
    assert jnp.allclose(jnp.sum(w, axis=-1), 1.0, atol=1e-5)
    assert int(jnp.max(idx)) < n_valid
    for row in idx:
        assert len(set(int(x) for x in row)) == k
