"""Foundational functional layers (pure JAX, params as nested dicts).

Conventions:
* every ``*_init(key, ...)`` returns a params pytree of ``jnp`` arrays;
* every forward fn is ``f(params, x, ...) -> y`` and jit/scan/shard friendly;
* compute dtype follows the input; params are stored in ``cfg.dtype``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _dtype(name: str):
    return jnp.dtype(name)


# ---------------------------------------------------------------- linear
def linear_init(key, d_in: int, d_out: int, dtype="float32", bias: bool = False,
                scale: float | None = None):
    # A Python float keeps the weights in ``dtype``; a numpy scalar would
    # promote bf16 weights to f32.
    scale = float(scale if scale is not None else 1.0 / np.sqrt(d_in))
    p = {"w": jax.random.normal(key, (d_in, d_out), _dtype(dtype)) * scale}
    if bias:
        p["b"] = jnp.zeros((d_out,), _dtype(dtype))
    return p


def linear(p, x):
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


# ---------------------------------------------------------------- norms
def rmsnorm_init(dim: int, dtype="float32"):
    return {"g": jnp.ones((dim,), _dtype(dtype))}


def rmsnorm(p, x, eps: float = 1e-5):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * p["g"].astype(jnp.float32)).astype(x.dtype)


def layernorm_init(dim: int, dtype="float32"):
    return {"g": jnp.ones((dim,), _dtype(dtype)), "b": jnp.zeros((dim,), _dtype(dtype))}


def layernorm(p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["g"].astype(jnp.float32) + p["b"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------- embedding
def embedding_init(key, vocab: int, dim: int, dtype="float32"):
    return {"table": jax.random.normal(key, (vocab, dim), _dtype(dtype)) * 0.02}


def embed(p, tokens):
    return jnp.take(p["table"], tokens, axis=0)


def unembed(p, x):
    """Tied or untied output head: logits in float32 for stable loss."""
    return x.astype(jnp.float32) @ p["table"].astype(jnp.float32).T


# ---------------------------------------------------------------- MLPs
def swiglu_init(key, d_model: int, d_ff: int, dtype="float32"):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "gate": linear_init(k1, d_model, d_ff, dtype),
        "up": linear_init(k2, d_model, d_ff, dtype),
        "down": linear_init(k3, d_ff, d_model, dtype),
    }


def swiglu(p, x):
    return linear(p["down"], jax.nn.silu(linear(p["gate"], x)) * linear(p["up"], x))


# ---------------------------------------------------------------- RoPE
def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))


def apply_rope(x, positions, theta: float = 10_000.0):
    """x: (..., seq, n_heads, head_dim); positions: (..., seq)."""
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(hd, theta))                # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]                    # (..., seq, 1, hd/2)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def cross_entropy(logits, labels, ignore_id: int = -1):
    """Mean token cross-entropy with ignore mask; logits float32."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None].clip(0), axis=-1)[..., 0]
    nll = logz - gold
    mask = (labels != ignore_id).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
