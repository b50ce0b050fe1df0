"""jit'd kernel wrappers with backend selection.

Backends:
* ``pallas``    -- the TPU kernels (production target);
* ``interpret`` -- the same Pallas kernel bodies executed in Python on CPU
                   (correctness validation in this container);
* ``xla``       -- pure-jnp *blocked* implementations with the same memory
                   behaviour (online softmax over KV blocks, chunkwise mLSTM).
                   Differentiable; the CPU dry-run lowers through it, keeping
                   the roofline honest (no materialized S x S attention at 32k).

``default_backend()`` picks pallas on TPU and xla elsewhere. The Pallas
flash kernel is forward-only, so ``flash_attention`` gives it a custom VJP
whose backward is the VJP of ``_flash_xla``: training differentiates the
same call on every backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .decode_attention import decode_attention_pallas
from .flash_attention import flash_attention_pallas
from .mlstm_scan import mlstm_scan_pallas
from .moe_topk import moe_topk_pallas

NEG_INF = -1e30


def default_backend() -> str:
    return "pallas" if jax.devices()[0].platform == "tpu" else "xla"


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def _flash_xla(q, k, v, *, causal, window, scale, block_q=512, block_k=512):
    """Blocked online-softmax attention in pure jnp (flash memory behaviour,
    differentiable). q, k: (BH, S, D); v: (BH, Sk, Dv) -- Dv may differ (MLA)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    dv = v.shape[-1]
    bq, bk = min(block_q, sq), min(block_k, sk)
    nq, nk = sq // bq, sk // bk
    offs = sk - sq if causal else 0     # query positions offset into kv space
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    qblocks = qf.reshape(bh, nq, bq, d).transpose(1, 0, 2, 3)      # (nq,BH,bq,d)
    kblocks = kf.reshape(bh, nk, bk, d).transpose(1, 0, 2, 3)
    vblocks = vf.reshape(bh, nk, bk, dv).transpose(1, 0, 2, 3)

    def q_step(_, qi_blk):
        iq, qb = qi_blk                                            # qb (BH,bq,d)
        qpos = iq * bq + jnp.arange(bq) + offs

        def kv_step(carry, kv):
            m, l, acc = carry
            ik, kb, vb = kv
            kpos = ik * bk + jnp.arange(bk)
            s = jnp.einsum("bqd,bkd->bqk", qb, kb)
            mask = jnp.ones((bq, bk), bool)
            if causal:
                mask = mask & (kpos[None, :] <= qpos[:, None])
            if window > 0:
                mask = mask & (kpos[None, :] > qpos[:, None] - window)
            s = jnp.where(mask[None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            p = jnp.where(mask[None], p, 0.0)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum("bqk,bkd->bqd", p, vb)
            return (m_new, l_new, acc_new), None

        init = (jnp.full((bh, bq), NEG_INF), jnp.zeros((bh, bq)),
                jnp.zeros((bh, bq, dv)))
        (m, l, acc), _ = jax.lax.scan(
            kv_step, init, (jnp.arange(nk), kblocks, vblocks))
        return None, acc / jnp.maximum(l, 1e-30)[..., None]

    _, out = jax.lax.scan(q_step, None, (jnp.arange(nq), qblocks))
    return out.transpose(1, 0, 2, 3).reshape(bh, sq, dv).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_pallas(q, k, v, causal, window, scale, block_q, block_k, interpret):
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  scale=scale, block_q=block_q,
                                  block_k=block_k, interpret=interpret)


def _flash_pallas_fwd(q, k, v, causal, window, scale, block_q, block_k,
                      interpret):
    out = _flash_pallas(q, k, v, causal, window, scale, block_q, block_k,
                        interpret)
    return out, (q, k, v)


def _flash_pallas_bwd(causal, window, scale, block_q, block_k, interpret,
                      res, g):
    _, vjp = jax.vjp(functools.partial(
        _flash_xla, causal=causal, window=window, scale=scale,
        block_q=block_q, block_k=block_k), *res)
    return vjp(g)


_flash_pallas.defvjp(_flash_pallas_fwd, _flash_pallas_bwd)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: float | None = None, backend: str | None = None,
                    block_q: int = 512, block_k: int = 512):
    """Multi-head attention, flash-style. q: (BH, Sq, D); k, v: (BH, Sk, D)."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    backend = backend or default_backend()
    if backend == "pallas" or backend == "interpret":
        return _flash_pallas(q, k, v, causal, window, scale, block_q, block_k,
                             backend == "interpret")
    if backend == "xla":
        return _flash_xla(q, k, v, causal=causal, window=window, scale=scale,
                          block_q=block_q, block_k=block_k)
    if backend == "naive":
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                       scale=scale)
    raise ValueError(backend)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

def decode_attention(q, k, v, lengths, *, scale: float | None = None,
                     backend: str | None = None, block_k: int = 1024):
    """q: (BH, 1, D); k, v: (BH, S, D); lengths: (BH,)."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    backend = backend or default_backend()
    if backend in ("pallas", "interpret"):
        return decode_attention_pallas(q, k, v, lengths, scale=scale,
                                       block_k=block_k,
                                       interpret=(backend == "interpret"))
    return ref.decode_attention_ref(q, k, v, lengths, scale=scale)


# ---------------------------------------------------------------------------
# mLSTM chunkwise scan
# ---------------------------------------------------------------------------

def _mlstm_xla(q, k, v, logf, i, *, scale, chunk=256):
    """Chunkwise-parallel mLSTM in pure jnp (differentiable).
    q, k: (BH, S, Dk); v: (BH, S, Dv)."""
    bh, s, d = q.shape
    dv = v.shape[-1]
    ch = min(chunk, s)
    nc = s // ch
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    lf = logf.astype(jnp.float32).reshape(bh, nc, ch)
    ig = i.astype(jnp.float32).reshape(bh, nc, ch)
    qc = qf.reshape(bh, nc, ch, d).transpose(1, 0, 2, 3)
    kc = kf.reshape(bh, nc, ch, d).transpose(1, 0, 2, 3)
    vc = vf.reshape(bh, nc, ch, dv).transpose(1, 0, 2, 3)
    lc = lf.transpose(1, 0, 2)
    ic = ig.transpose(1, 0, 2)
    tpos = jnp.arange(ch)[:, None]
    jpos = jnp.arange(ch)[None, :]

    def chunk_step(carry, xs):
        c, n = carry                                   # (BH,d,d), (BH,d)
        qb, kb, vb, lb, ib = xs
        la = jnp.cumsum(lb, axis=-1)                   # (BH, ch)
        total = la[:, -1]
        decay_in = jnp.exp(la)
        inter = jnp.einsum("btd,bde->bte", qb * decay_in[..., None], c)
        n_inter = jnp.einsum("btd,bd->bt", qb * decay_in[..., None], n)
        dmat = jnp.where(jpos <= tpos,
                         jnp.exp(la[:, :, None] - la[:, None, :]) * ib[:, None, :],
                         0.0)
        smat = jnp.einsum("btd,bjd->btj", qb, kb) * dmat
        intra = jnp.einsum("btj,bjd->btd", smat, vb)
        den = jnp.maximum(jnp.abs(n_inter + jnp.sum(smat, axis=-1)), 1.0)
        h = (inter + intra) / den[..., None]
        w = ib * jnp.exp(total[:, None] - la)
        c_new = jnp.exp(total)[:, None, None] * c + jnp.einsum("btd,bte->bde", kb * w[..., None], vb)
        n_new = jnp.exp(total)[:, None] * n + jnp.einsum("bt,btd->bd", w, kb)
        return (c_new, n_new), h

    init = (jnp.zeros((bh, d, dv), jnp.float32), jnp.zeros((bh, d), jnp.float32))
    (_, _), hs = jax.lax.scan(chunk_step, init, (qc, kc, vc, lc, ic))
    return hs.transpose(1, 0, 2, 3).reshape(bh, s, dv).astype(q.dtype)


def mlstm_scan(q, k, v, logf, i, *, chunk: int = 256,
               scale: float | None = None, backend: str | None = None):
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    backend = backend or default_backend()
    if backend in ("pallas", "interpret"):
        return mlstm_scan_pallas(q, k, v, logf, i, chunk=chunk, scale=scale,
                                 interpret=(backend == "interpret"))
    if backend == "xla":
        return _mlstm_xla(q, k, v, logf, i, scale=scale, chunk=chunk)
    return ref.mlstm_scan_ref(q, k, v, logf, i, scale=scale)


# ---------------------------------------------------------------------------
# MoE router
# ---------------------------------------------------------------------------

def moe_topk(logits, top_k: int, n_valid: int | None = None,
             backend: str | None = None):
    backend = backend or default_backend()
    if backend in ("pallas", "interpret"):
        return moe_topk_pallas(logits, top_k, n_valid=n_valid,
                               interpret=(backend == "interpret"))
    return ref.moe_topk_ref(logits, top_k, n_valid=n_valid)
