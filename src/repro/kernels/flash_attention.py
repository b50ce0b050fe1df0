"""Flash attention forward -- Pallas TPU kernel.

Online-softmax attention with O(seq) memory: grid (batch*heads, q_blocks,
kv_blocks), kv innermost so the VMEM scratch (acc, running max m, running
sum l) carries across kv steps for one q block. Causal and sliding-window
masking are predicated per block: fully-masked blocks are skipped with
``pl.when`` (no MXU work issued), the mask is built only for blocks that
cross its edge, and under causal masking the k and v index maps stay on
the last block a q block reaches, so skipped blocks are not fetched
either. V may be narrower than q and k (MLA: qk 192, v 128): the output
and the accumulator take V's width.

Both products take the operands in their stored dtype (bf16 on the model
path: one MXU pass) and accumulate in float32; the mask, the running max
and sum, exp and the accumulator are float32. BlockSpec tiling targets TPU
v5e: block sizes are multiples of 128 on both the q and kv axes. VMEM
working set per program ~= (bq + bk) * d * 2B + bk * dv * 2B + bq*bk*4B
(about 1.5 MB at bq=bk=512, d=192, dv=128), under the ~16 MB budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: int,
                  bq: int, bk: int, nk: int, offs: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # When sq != sk the q tokens are the LAST sq positions of the kv space
    # (decode-continuation convention, same as ops._flash_xla / ref).
    q_start = iq * bq + offs
    k_start = ik * bk

    def step(masked: bool):
        k = k_ref[0]                                      # (bk, d)
        v = v_ref[0]                                      # (bk, dv)
        q = (q_ref[0].astype(jnp.float32) * scale).astype(q_ref.dtype)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if masked:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = jnp.ones((bq, bk), dtype=jnp.bool_)
            if causal:
                mask = jnp.logical_and(mask, kpos <= qpos)
            if window > 0:
                mask = jnp.logical_and(mask, kpos > qpos - window)
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                               # (bq,)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        if masked:
            p = jnp.where(mask, p, 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal or window > 0:
        # Block-level reachability: skip fully-masked kv blocks; build the
        # mask only for blocks that cross its edge.
        reachable, inside = True, True
        if causal:
            reachable = k_start <= q_start + bq - 1
            inside = k_start + bk - 1 <= q_start
        if window > 0:
            # kv block must overlap [q_pos - window + 1, q_pos] for some q
            reachable = jnp.logical_and(
                reachable, k_start + bk - 1 >= q_start - window + 1)
            inside = jnp.logical_and(inside, k_start >= q_start + bq - window)
        pl.when(jnp.logical_and(reachable, inside))(lambda: step(False))
        pl.when(jnp.logical_and(reachable, jnp.logical_not(inside)))(
            lambda: step(True))
    else:
        step(False)

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           scale: float | None = None,
                           block_q: int = 512, block_k: int = 512,
                           interpret: bool = False):
    """q: (BH, Sq, D); k: (BH, Sk, D); v: (BH, Sk, Dv). Returns (BH, Sq, Dv).

    Head grouping (GQA) is resolved by the caller (ops.py) by expanding /
    reindexing KV heads into the BH axis.
    """
    bh, sq, d = q.shape
    sk, dv = k.shape[1], v.shape[2]
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    assert sq % bq == 0 and sk % bk == 0, (sq, bq, sk, bk)
    nq, nk = sq // bq, sk // bk
    scale = scale if scale is not None else d ** -0.5

    offs = sk - sq if causal else 0

    def kv_block(i, j):
        # Causal: a kv block past the q block's last position is skipped,
        # so fetch the last one it reaches instead (no new DMA).
        return jnp.minimum(j, (i * bq + offs + bq - 1) // bk) if causal else j

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, nk=nk, offs=offs)
    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, kv_block(i, j), 0)),
            pl.BlockSpec((1, bk, dv), lambda b, i, j: (b, kv_block(i, j), 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
