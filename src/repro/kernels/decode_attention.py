"""Single-token decode attention over a long KV cache -- Pallas TPU kernel.

The serving hot spot: one query token attends to a KV cache of up to 512k
positions. Memory-bound by design (every cache byte is read once), so the
kernel streams KV blocks HBM->VMEM and keeps the online-softmax running
state in VMEM scratch. Grid (batch*q_heads, kv_blocks), kv innermost.

A `length` operand masks positions beyond the live cache length (paged /
ragged caches pass their fill level).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                   *, scale: float, bk: int, nk: int):
    b = pl.program_id(0)
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]
    k_start = ik * bk

    @pl.when(k_start < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale           # (1, d)
        k = k_ref[0].astype(jnp.float32)                   # (bk, d)
        v = v_ref[0].astype(jnp.float32)                   # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))   # (1, bk)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        s = jnp.where(kpos < length, s, NEG_INF)
        m_prev = m_ref[...]                                # (1, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(kpos < length, jnp.exp(s - m_new), 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + p @ v
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention_pallas(q, k, v, lengths, *, scale: float | None = None,
                            block_k: int = 1024, interpret: bool = False):
    """q: (BH, 1, D); k, v: (BH, S, D); lengths: (BH,) int32 live lengths.

    ``lengths`` is a scalar-prefetch operand: it lands in SMEM before the
    grid runs, so each program reads its row's length as a scalar."""
    bh, one, d = q.shape
    assert one == 1
    sk = k.shape[1]
    bk = min(block_k, sk)
    assert sk % bk == 0
    nk = sk // bk
    scale = scale if scale is not None else d ** -0.5

    kernel = functools.partial(_decode_kernel, scale=scale, bk=bk, nk=nk)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, nk),
            in_specs=[
                pl.BlockSpec((1, 1, d), lambda b, j, lens: (b, 0, 0)),
                pl.BlockSpec((1, bk, d), lambda b, j, lens: (b, j, 0)),
                pl.BlockSpec((1, bk, d), lambda b, j, lens: (b, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, d), lambda b, j, lens: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((1, d), jnp.float32),
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, 1), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((bh, 1, d), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q, k, v)
