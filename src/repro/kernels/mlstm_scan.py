"""Chunkwise-parallel mLSTM (matrix-memory linear attention) -- Pallas TPU.

The xLSTM/hymba recurrence
    C_t = f_t * C_{t-1} + i_t * k_t v_t^T        (matrix memory, D x D)
    n_t = f_t * n_{t-1} + i_t * k_t              (normalizer)
    h_t = (q_t C_t) / max(|q_t . n_t|, 1)
is evaluated chunk-parallel: within a chunk of length L the contribution is
a masked, decay-weighted attention matrix (intra), plus the carried state
applied with cumulative decay (inter). The grid is (batch*heads, chunks)
with chunks innermost-sequential; C and n live in VMEM scratch across chunk
steps -- the TPU-native replacement for a per-timestep recurrence, giving
MXU-shaped (L x D) matmuls instead of D-wide vector ops.

Gates use log-sigmoid decay accumulated in log space for stability
(sigmoid-gated linear-attention form; see DESIGN.md section 8 for the
deviation from the exp-gate + stabilizer formulation).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mlstm_kernel(q_ref, k_ref, v_ref, logf_ref, i_ref, o_ref, c_ref, n_ref,
                  *, chunk: int, scale: float):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)
        n_ref[...] = jnp.zeros_like(n_ref)

    q = q_ref[0].astype(jnp.float32) * scale      # (L, d)
    k = k_ref[0].astype(jnp.float32)              # (L, d)
    v = v_ref[0].astype(jnp.float32)              # (L, d)
    logf = logf_ref[0].astype(jnp.float32)        # (L, 1) column
    ig = i_ref[0].astype(jnp.float32)             # (L, 1) column

    # Gates arrive as columns; the row forms come from masked reductions
    # over sublanes (no transposes, no cumsum primitive in the kernel).
    tpos = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jpos = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    la_row = jnp.sum(jnp.where(tpos <= jpos, logf, 0.0), axis=0,
                     keepdims=True)               # (1, L) cumulative log-decay
    la = jnp.sum(jnp.where(tpos == jpos, la_row, 0.0), axis=1,
                 keepdims=True)                   # (L, 1) same, as a column
    ig_row = jnp.sum(jnp.where(tpos == jpos, ig, 0.0), axis=0, keepdims=True)
    total = jnp.sum(logf, axis=0, keepdims=True)  # (1, 1)
    decay_in = jnp.exp(la)                        # state-decay seen by step t

    # inter-chunk: carried state applied with per-step decay
    qd = q * decay_in
    inter = qd @ c_ref[...]                                       # (L, d)
    n_inter = jnp.sum(qd * n_ref[...], axis=1, keepdims=True)     # (L, 1)

    # intra-chunk: pairwise decay D_tj = exp(la_t - la_j) * i_j, j <= t
    dmat = jnp.where(jpos <= tpos, jnp.exp(la - la_row) * ig_row, 0.0)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * dmat  # (L, L)
    intra = s @ v                                                 # (L, d)

    # normalizer: |q . n_t| with n_t = decayed carry + intra-chunk sum
    den = jnp.abs(n_inter + jnp.sum(s, axis=1, keepdims=True))
    o_ref[0] = ((inter + intra) / jnp.maximum(den, 1.0)).astype(o_ref.dtype)

    # carry updates
    kw = k * (ig * jnp.exp(total - la))                           # (L, d)
    c_ref[...] = jnp.exp(total) * c_ref[...] + jax.lax.dot_general(
        kw, v, (((0,), (0,)), ((), ())))
    n_ref[...] = jnp.exp(total) * n_ref[...] + jnp.sum(kw, axis=0,
                                                       keepdims=True)


def mlstm_scan_pallas(q, k, v, logf, i, *, chunk: int = 256,
                      scale: float | None = None, interpret: bool = False):
    """q, k: (BH, S, Dk); v: (BH, S, Dv); logf, i: (BH, S).

    Returns h: (BH, S, Dv). Dk == Dv for xLSTM's mLSTM; Dk = ssm_state for
    mamba-2/SSD-style heads (hymba), where k/q are the B/C projections.
    The gates enter the kernel as (BH, S, 1) columns, so their blocks
    (1, chunk, 1) meet the TPU tiling rule like the q/k/v blocks.
    """
    bh, s, dk = q.shape
    dv = v.shape[-1]
    ch = min(chunk, s)
    assert s % ch == 0
    nc = s // ch
    scale = scale if scale is not None else dk ** -0.5
    kernel = functools.partial(_mlstm_kernel, chunk=ch, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, ch, dk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, ch, dk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, ch, dv), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, ch, 1), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, ch, 1), lambda b, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, ch, dv), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((dk, dv), jnp.float32),
            pltpu.VMEM((1, dk), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, logf[..., None], i[..., None])
