"""Explicit shard_map GQA attention (EXPERIMENTS.md section Perf, B5).

The GSPMD-auto lowering reshards activations around the flash path's
(B,S,H,hd) <-> (B*H,S,hd) reshapes (iteration B1/B4 diagnosis). This module
expresses the intended schedule explicitly: each model shard

  1. projects q/k/v for *its* heads only (KV weights are pre-expanded to
     per-q-head layout, so grouped heads stay shard-local; the duplicated
     KV projection costs ~ one extra q-projection, negligible vs attention),
  2. runs flash attention locally (the Pallas kernel on TPU),
  3. applies its slice of the output projection and psums across the model
     axis -- the only collective in the mixer.

Restrictions (checked): n_heads divisible by the model-axis size, no QKV
bias. Used by the dry-run variant ``shardmap_attn`` and available to the
trainer via ``Model.shardmap_attn(mesh)``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..models import layers as L


def expand_kv_weight(w, kh: int, g: int):
    """(d, KH*hd) -> (d, KH*G*hd): repeat each kv head's columns G times so
    every q head owns a local copy of its kv projection."""
    d, _ = w.shape
    hd = w.shape[1] // kh
    w = w.reshape(d, kh, 1, hd)
    w = jnp.broadcast_to(w, (d, kh, g, hd))
    return w.reshape(d, kh * g * hd)


def make_shardmap_gqa(mesh, cfg, *, backend=None):
    """Returns fwd(p, x, positions, window) -> y for full-sequence GQA."""
    from ..kernels import ops

    tp = mesh.shape["model"]
    if cfg.n_heads % tp:
        raise ValueError(f"n_heads {cfg.n_heads} must divide model axis {tp}")
    if cfg.qkv_bias:
        raise ValueError("shard_map GQA variant does not support qkv bias")
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_spec = dp if len(dp) > 1 else (dp[0] if dp else None)
    h = cfg.n_heads
    kh = cfg.n_kv_heads
    g = h // kh
    hd = cfg.hd

    _cache: dict = {}

    def _smapped(window: int):
        if window in _cache:
            return _cache[window]

        def block(wq, wk, wv, wo, xl, pos):
            b, s, _ = xl.shape
            h_l = wq.shape[1] // hd
            q = (xl @ wq).reshape(b, s, h_l, hd)
            k = (xl @ wk).reshape(b, s, h_l, hd)
            v = (xl @ wv).reshape(b, s, h_l, hd)
            q = L.apply_rope(q, pos, cfg.rope_theta)
            k = L.apply_rope(k, pos, cfg.rope_theta)
            qf = q.transpose(0, 2, 1, 3).reshape(b * h_l, s, hd)
            kf = k.transpose(0, 2, 1, 3).reshape(b * h_l, s, hd)
            vf = v.transpose(0, 2, 1, 3).reshape(b * h_l, s, hd)
            of = ops.flash_attention(qf, kf, vf, causal=True, window=window,
                                     backend=backend)
            out = of.reshape(b, h_l, s, hd).transpose(0, 2, 1, 3) \
                .reshape(b, s, h_l * hd)
            partial = out @ wo                  # (b, s, d) partial sum
            return jax.lax.psum(partial, "model")

        _cache[window] = jax.shard_map(
            block, mesh=mesh,
            in_specs=(P(None, "model"), P(None, "model"), P(None, "model"),
                      P("model", None), P(dp_spec, None, None),
                      P(dp_spec, None)),
            out_specs=P(dp_spec, None, None), check_vma=False)
        return _cache[window]

    def fwd(p, x, positions, window: int = 0):
        wk = expand_kv_weight(p["wk"]["w"].astype(x.dtype), kh, g)
        wv = expand_kv_weight(p["wv"]["w"].astype(x.dtype), kh, g)
        positions = jnp.broadcast_to(positions, x.shape[:2]).astype(jnp.int32)
        return _smapped(window)(p["wq"]["w"].astype(x.dtype), wk, wv,
                                p["wo"]["w"].astype(x.dtype), x, positions)

    return fwd
