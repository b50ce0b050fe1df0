"""Attention mixers: GQA (with RoPE / QKV bias / sliding window) and MLA
(DeepSeek multi-head latent attention with the absorbed-latent decode path).

Cache conventions (per layer; stacked along a leading layer axis by the
transformer's scan):

* GQA full attention : {"k": (B, S_max, KH, hd), "v": ...}
* GQA sliding window : ring buffer {"k": (B, W, KH, hd), "v": ...}
* MLA                : {"c": (B, S_max, kv_lora), "kr": (B, S_max, rope_dim)}

Decode positions are a traced scalar ``pos`` (same for the whole batch --
the serving engine aligns batches; ragged serving pads to the max length and
masks via per-request lengths).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..kernels import ops
from . import layers as L


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def gqa_init(key, cfg):
    hd = cfg.hd
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": L.linear_init(k1, cfg.d_model, cfg.n_heads * hd, cfg.dtype, bias=cfg.qkv_bias),
        "wk": L.linear_init(k2, cfg.d_model, cfg.n_kv_heads * hd, cfg.dtype, bias=cfg.qkv_bias),
        "wv": L.linear_init(k3, cfg.d_model, cfg.n_kv_heads * hd, cfg.dtype, bias=cfg.qkv_bias),
        "wo": L.linear_init(k4, cfg.n_heads * hd, cfg.d_model, cfg.dtype),
    }


def _split_heads(x, n_heads, hd):
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, hd)


def gqa_forward(cfg, p, x, positions, *, window: int = 0, causal: bool = True,
                backend: Optional[str] = None, return_cache: bool = False,
                kv_override=None, attn_constraint=None):
    """Full-sequence attention (train / prefill / encoder).

    ``kv_override``: (k, v) head tensors for cross-attention (already RoPE-free).
    """
    b, s, _ = x.shape
    hd = cfg.hd
    q = _split_heads(L.linear(p["wq"], x), cfg.n_heads, hd)
    q = L.apply_rope(q, positions, cfg.rope_theta) if kv_override is None else q
    if kv_override is None:
        k = _split_heads(L.linear(p["wk"], x), cfg.n_kv_heads, hd)
        v = _split_heads(L.linear(p["wv"], x), cfg.n_kv_heads, hd)
        k = L.apply_rope(k, positions, cfg.rope_theta)
    else:
        k, v = kv_override
    out = _grouped_flash(q, k, v, causal=causal, window=window, backend=backend,
                         attn_constraint=attn_constraint)
    y = L.linear(p["wo"], out.reshape(b, s, cfg.n_heads * hd))
    if return_cache:
        return y, {"k": k, "v": v}
    return y


def _grouped_flash(q, k, v, *, causal, window, backend, attn_constraint=None):
    """q: (B,S,H,hd); k,v: (B,Sk,KH,hd) with H = KH * G.

    ``attn_constraint``: NamedSharding for the flattened (B*KH*G, S, hd)
    layout -- pinning (batch, heads) to (data, model) on the composite
    leading dim keeps the whole flash computation shard-local (EXPERIMENTS
    section Perf, iteration B4)."""
    b, s, h, hd = q.shape
    sk, kh = k.shape[1], k.shape[2]
    g = h // kh
    # (B, KH, G, S, hd) -> flatten (B*KH*G) so each kv head serves G q heads.
    qg = q.transpose(0, 2, 1, 3).reshape(b, kh, g, s, hd)
    qf = qg.reshape(b * kh * g, s, hd)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3).reshape(b * kh, 1, sk, hd), g, axis=1) \
        .reshape(b * kh * g, sk, hd)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3).reshape(b * kh, 1, sk, hd), g, axis=1) \
        .reshape(b * kh * g, sk, hd)
    if attn_constraint is not None:
        qf = jax.lax.with_sharding_constraint(qf, attn_constraint)
        kf = jax.lax.with_sharding_constraint(kf, attn_constraint)
        vf = jax.lax.with_sharding_constraint(vf, attn_constraint)
    of = ops.flash_attention(qf, kf, vf, causal=causal, window=window,
                             backend=backend)
    if attn_constraint is not None:
        of = jax.lax.with_sharding_constraint(of, attn_constraint)
    return of.reshape(b, kh, g, s, hd).reshape(b, h, s, hd).transpose(0, 2, 1, 3)


def gqa_prefill_cache(cfg, smax: int, k, v, window: int, quant: bool = False):
    """Place prefill K/V into the (padded or ring) cache layout.

    Ring convention: position p lives at slot ``p % window`` (matches
    ``gqa_decode``); softmax attention is permutation-invariant so ring
    order never needs unwinding."""
    b, s = k.shape[0], k.shape[1]
    if window > 0:
        if s >= window:
            kk = jnp.roll(k[:, -window:], s % window, axis=1)
            vv = jnp.roll(v[:, -window:], s % window, axis=1)
        else:
            kk = jnp.pad(k, ((0, 0), (0, window - s), (0, 0), (0, 0)))
            vv = jnp.pad(v, ((0, 0), (0, window - s), (0, 0), (0, 0)))
    else:
        pad = smax - s
        kk = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vv = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    if quant:
        kq, ks = _kv_quantize(kk)
        vq, vs = _kv_quantize(vv)
        return {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    return {"k": kk, "v": vv}


def _kv_quantize(k):
    """Per-(token, head) symmetric int8 quantization of a K/V slice."""
    scale = jnp.max(jnp.abs(k.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(k.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _kv_dequant(q, scale, dtype):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def gqa_decode(cfg, p, x, cache, pos, *, window: int = 0,
               backend: Optional[str] = None, kv_constraint=None):
    """Single-token decode. x: (B, 1, d); cache per conventions above;
    ``pos`` traced scalar = number of tokens already in the cache.

    Quantized caches (int8 + per-token-head scales, see ``kv_quant``) halve
    the decode memory term; dequantization fuses into the attention matmul.
    """
    b = x.shape[0]
    hd = cfg.hd
    q = _split_heads(L.linear(p["wq"], x), cfg.n_heads, hd)
    k = _split_heads(L.linear(p["wk"], x), cfg.n_kv_heads, hd)
    v = _split_heads(L.linear(p["wv"], x), cfg.n_kv_heads, hd)
    posv = jnp.full((b, 1), pos, dtype=jnp.int32)
    q = L.apply_rope(q, posv, cfg.rope_theta)
    k = L.apply_rope(k, posv, cfg.rope_theta)
    if kv_constraint is not None:
        # A2 (EXPERIMENTS.md section Perf): align the written slice's
        # sharding with the cache so the dynamic_update_slice stays
        # shard-local instead of resharding cache tiles every layer.
        k = jax.lax.with_sharding_constraint(k, kv_constraint)
        v = jax.lax.with_sharding_constraint(v, kv_constraint)

    quant = "k_scale" in cache
    slot = jnp.mod(pos, window) if window > 0 else pos
    length = jnp.minimum(pos + 1, window) if window > 0 else pos + 1
    if quant:
        kq, ks = _kv_quantize(k)
        vq, vs = _kv_quantize(v)
        ck = jax.lax.dynamic_update_slice(cache["k"], kq, (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], vq, (0, slot, 0, 0))
        cks = jax.lax.dynamic_update_slice(cache["k_scale"], ks, (0, slot, 0))
        cvs = jax.lax.dynamic_update_slice(cache["v_scale"], vs, (0, slot, 0))
        out = _grouped_decode(q, _kv_dequant(ck, cks, x.dtype),
                              _kv_dequant(cv, cvs, x.dtype), length)
        new_cache = {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs}
    else:
        ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
        # ring order does not matter for softmax attention (permutation
        # invariant); mask by live length.
        out = _grouped_decode(q, ck, cv, length)
        new_cache = {"k": ck, "v": cv}
    y = L.linear(p["wo"], out.reshape(b, 1, cfg.n_heads * hd))
    return y, new_cache


def _grouped_decode(q, ck, cv, length):
    """Grouped-query decode attention, einsum formulation (no KV head
    expansion in HBM). q: (B,1,H,hd); ck/cv: (B,S,KH,hd)."""
    b, _, h, hd = q.shape
    s, kh = ck.shape[1], ck.shape[2]
    g = h // kh
    qg = q.reshape(b, kh, g, hd)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg.astype(jnp.float32),
                        ck.astype(jnp.float32)) * (hd ** -0.5)
    kpos = jnp.arange(s)
    mask = kpos[None, None, None, :] < length
    scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", w, cv.astype(jnp.float32))
    return out.reshape(b, 1, h, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# MLA (DeepSeek)
# ---------------------------------------------------------------------------

def mla_init(key, cfg):
    m = cfg.mla
    ks = jax.random.split(key, 6)
    h = cfg.n_heads
    return {
        "wq_a": L.linear_init(ks[0], cfg.d_model, m.q_lora_rank, cfg.dtype),
        "q_norm": L.rmsnorm_init(m.q_lora_rank, cfg.dtype),
        "wq_b": L.linear_init(ks[1], m.q_lora_rank,
                              h * (m.qk_nope_head_dim + m.qk_rope_head_dim), cfg.dtype),
        "wkv_a": L.linear_init(ks[2], cfg.d_model,
                               m.kv_lora_rank + m.qk_rope_head_dim, cfg.dtype),
        "kv_norm": L.rmsnorm_init(m.kv_lora_rank, cfg.dtype),
        "wkv_b": L.linear_init(ks[3], m.kv_lora_rank,
                               h * (m.qk_nope_head_dim + m.v_head_dim), cfg.dtype),
        "wo": L.linear_init(ks[4], h * m.v_head_dim, cfg.d_model, cfg.dtype),
    }


def _mla_rope(cfg, x, positions):
    """Rope on the MLA rope dims: adjacent pairs, YaRN frequencies where
    the config scales the rope (cos and sin unscaled; see YaRNConfig)."""
    dim = cfg.mla.qk_rope_head_dim
    y = cfg.rope_scaling
    inv = (L.yarn_inv_freq(dim, cfg.rope_theta, y) if y is not None
           else L.rope_freqs(dim, cfg.rope_theta))
    return L.apply_rope_pairs(x, positions, inv)


def mla_softmax_scale(cfg) -> float:
    """(nope + rope)^-0.5, times mscale(factor, mscale_all_dim)^2 under YaRN."""
    m = cfg.mla
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= L.yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def _mla_q(cfg, p, x, positions):
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    q = L.linear(p["wq_b"], L.rmsnorm(p["q_norm"], L.linear(p["wq_a"], x),
                                      cfg.norm_eps))
    q = q.reshape(b, s, h, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = _mla_rope(cfg, q_rope, positions)
    return q_nope, q_rope


def _mla_latent(cfg, p, x, positions):
    m = cfg.mla
    ckr = L.linear(p["wkv_a"], x)
    c, kr = jnp.split(ckr, [m.kv_lora_rank], axis=-1)
    c = L.rmsnorm(p["kv_norm"], c, cfg.norm_eps)
    kr = _mla_rope(cfg, kr[:, :, None, :], positions)[:, :, 0, :]
    return c, kr


def mla_forward(cfg, p, x, positions, *, backend=None, return_cache=False):
    """Training / prefill: reconstruct per-head K/V from the latent."""
    with jax.named_scope("mla"):
        return _mla_forward(cfg, p, x, positions, backend=backend,
                            return_cache=return_cache)


def _mla_forward(cfg, p, x, positions, *, backend, return_cache):
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    q_nope, q_rope = _mla_q(cfg, p, x, positions)
    c, kr = _mla_latent(cfg, p, x, positions)
    kv = L.linear(p["wkv_b"], c).reshape(b, s, h, m.qk_nope_head_dim + m.v_head_dim)
    k_nope, v = jnp.split(kv, [m.qk_nope_head_dim], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(kr[:, :, None, :], (b, s, h, m.qk_rope_head_dim))], axis=-1)
    scale = mla_softmax_scale(cfg)
    # flash over (B*H) rows; V is narrower than q and k (v_head_dim)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, -1)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, s, -1)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, s, -1)
    of = ops.flash_attention(qf, kf, vf, causal=True, scale=scale,
                             backend=backend)
    out = of.reshape(b, h, s, m.v_head_dim).transpose(0, 2, 1, 3)
    y = L.linear(p["wo"], out.reshape(b, s, h * m.v_head_dim))
    if return_cache:
        return y, {"c": c, "kr": kr}
    return y


def mla_prefill_cache(cfg, smax, cache):
    pad = smax - cache["c"].shape[1]
    return {"c": jnp.pad(cache["c"], ((0, 0), (0, pad), (0, 0))),
            "kr": jnp.pad(cache["kr"], ((0, 0), (0, pad), (0, 0)))}


def mla_decode(cfg, p, x, cache, pos, *, backend=None):
    """Absorbed-latent decode: attention runs over the compressed latent
    cache (kv_lora + rope dims per position), never materializing per-head
    K/V for the whole context -- the MLA memory saving, done properly."""
    with jax.named_scope("mla"):
        return _mla_decode(cfg, p, x, cache, pos)


def _mla_decode(cfg, p, x, cache, pos):
    m = cfg.mla
    b = x.shape[0]
    h = cfg.n_heads
    posv = jnp.full((b, 1), pos, dtype=jnp.int32)
    q_nope, q_rope = _mla_q(cfg, p, x, posv)           # (B,1,H,*)
    c_new, kr_new = _mla_latent(cfg, p, x, posv)
    cc = jax.lax.dynamic_update_slice(cache["c"], c_new, (0, pos, 0))
    ckr = jax.lax.dynamic_update_slice(cache["kr"], kr_new, (0, pos, 0))

    wkv_b = p["wkv_b"]["w"].reshape(m.kv_lora_rank, h, m.qk_nope_head_dim + m.v_head_dim)
    w_uk = wkv_b[..., :m.qk_nope_head_dim]             # (r, H, nope)
    w_uv = wkv_b[..., m.qk_nope_head_dim:]             # (r, H, v)
    # absorb W_uk into q: q_eff (B,1,H,r)
    q_eff = jnp.einsum("bthd,rhd->bthr", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    scale = mla_softmax_scale(cfg)
    s_lat = jnp.einsum("bthr,bsr->bhs", q_eff, cc.astype(jnp.float32))
    s_rope = jnp.einsum("bthd,bsd->bhs", q_rope.astype(jnp.float32),
                        ckr.astype(jnp.float32))
    scores = (s_lat + s_rope) * scale
    mask = jnp.arange(cc.shape[1])[None, None, :] < (pos + 1)
    scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhs,bsr->bhr", w, cc.astype(jnp.float32))   # (B,H,r)
    out = jnp.einsum("bhr,rhd->bhd", ctx, w_uv.astype(jnp.float32))
    y = L.linear(p["wo"], out.reshape(b, 1, h * m.v_head_dim).astype(x.dtype))
    return y, {"c": cc, "kr": ckr}
