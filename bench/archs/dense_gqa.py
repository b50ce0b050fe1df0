"""Dense GQA decoder (Qwen2 / Granite-code layout): RMSNorm, rotary
positions (rotate-half), grouped-query causal softmax attention with
optional QKV bias, SwiGLU, tied or untied output head. One scanned segment
of ``L`` stacked layers.

The reference forward runs every matrix product in float32 at
``HIGHEST``; layers run one at a time under ``lax.scan``, each upcast from
the stored type as it is reached, so a model whose float32 copy would not
fit is still computed in float32.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

import flops

from . import HI, flatten_segments, mm, nest_segments, rms

# Published config.json key -> the program's ArchConfig field.
FIELDS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "qkv_bias": "qkv_bias",
    "tie_word_embeddings": "tie_embeddings",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "torch_dtype": "dtype",
}
SEGMENTS = ("layer",)


def program_config(config: dict):
    """The program's ArchConfig for ``config["arch"]``, with every model
    key of the configuration file set as the file states it: the file is
    what runs."""
    from repro.configs import get_arch
    unmapped = sorted(set(config["model"]) - set(FIELDS))
    if unmapped:
        raise SystemExit(f"dense_gqa maps no published key {unmapped}; "
                         f"it maps {sorted(FIELDS)}")
    base = get_arch(config["arch"])
    over = {FIELDS[k]: v for k, v in config["model"].items()}
    over.update(config.get("program", {}))
    return dataclasses.replace(base, **over)


def dims(config: dict) -> dict:
    """The sizes the weights, the reference and the counts need."""
    m = config["model"]
    d = m["hidden_size"]
    h = m["num_attention_heads"]
    return {
        "L": m["num_hidden_layers"], "D": d, "H": h,
        "KH": m["num_key_value_heads"], "hd": m.get("head_dim") or d // h,
        "F": m["intermediate_size"], "V": m["vocab_size"],
        "bias": bool(m.get("qkv_bias", False)),
        "tied": bool(m.get("tie_word_embeddings", False)),
        "eps": float(m["rms_norm_eps"]), "theta": float(m["rope_theta"]),
        "dtype": m["torch_dtype"],
    }


# ---------------------------------------------------------------- weights
def layout(dm: dict) -> dict:
    """name -> (shape, scale, offset): a leaf is offset + scale * N(0, 1)."""
    L, D, H, KH, hd, F, V = (dm[k] for k in ("L", "D", "H", "KH", "hd", "F", "V"))
    s = {
        "embed.table": ((V, D), 0.02, 0.0),
        "final_norm.g": ((D,), 0.1, 1.0),
        "layer.norm1.g": ((L, D), 0.1, 1.0),
        "layer.norm2.g": ((L, D), 0.1, 1.0),
        "layer.attn.wq.w": ((L, D, H * hd), D ** -0.5, 0.0),
        "layer.attn.wk.w": ((L, D, KH * hd), D ** -0.5, 0.0),
        "layer.attn.wv.w": ((L, D, KH * hd), D ** -0.5, 0.0),
        "layer.attn.wo.w": ((L, H * hd, D), (H * hd) ** -0.5, 0.0),
        "layer.ffn.gate.w": ((L, D, F), D ** -0.5, 0.0),
        "layer.ffn.up.w": ((L, D, F), D ** -0.5, 0.0),
        "layer.ffn.down.w": ((L, F, D), F ** -0.5, 0.0),
    }
    if dm["bias"]:
        for n, width in (("wq", H * hd), ("wk", KH * hd), ("wv", KH * hd)):
            s[f"layer.attn.{n}.b"] = ((L, width), 0.1, 0.0)
    if not dm["tied"]:
        s["lm_head.w"] = ((D, V), D ** -0.5, 0.0)
    return s


def nest(flat: dict) -> dict:
    return nest_segments(flat, SEGMENTS)


def flatten(tree: dict) -> dict:
    return flatten_segments(tree, SEGMENTS)


# -------------------------------------------------------------- reference
def _rope(x, pos, theta):
    """x: (B, S, N, hd); pos: (S,). Rotate-half convention."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]       # (S, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(dm, fp8, x, lp):
    """One decoder layer over the whole sequence; x: (B, S, D) float32."""
    lp = {k: v.astype(jnp.float32) for k, v in lp.items()}
    b, s, _ = x.shape
    H, KH, hd = dm["H"], dm["KH"], dm["hd"]
    pos = jnp.arange(s)
    h = rms(x, lp["norm1.g"], dm["eps"])

    def proj(n, heads):
        y = mm(h, lp[f"attn.{n}.w"], fp8)
        if dm["bias"]:
            y = y + lp[f"attn.{n}.b"]
        return y.reshape(b, s, heads, hd)

    q = _rope(proj("wq", H), pos, dm["theta"])
    k = _rope(proj("wk", KH), pos, dm["theta"])
    v = proj("wv", KH)
    q = q.reshape(b, s, KH, H // KH, hd)          # head h uses kv head h // G
    sc = jnp.einsum("bskgd,btkd->bkgst", q, k, precision=HI) * hd ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    sc = jnp.where(causal, sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bkgst,btkd->bskgd", p, v, precision=HI).reshape(b, s, H * hd)
    x = x + mm(o, lp["attn.wo.w"], fp8)
    h2 = rms(x, lp["norm2.g"], dm["eps"])
    ff = jax.nn.silu(mm(h2, lp["ffn.gate.w"], fp8)) * mm(h2, lp["ffn.up.w"], fp8)
    return x + mm(ff, lp["ffn.down.w"], fp8)


def hidden(flat, dm, tokens, fp8=False, remat=False):
    """Final normed hidden states (B, S, D) for int tokens (B, S)."""
    x = jnp.take(flat["embed.table"], tokens, axis=0).astype(jnp.float32)
    layers = {k[len("layer."):]: v for k, v in flat.items()
              if k.startswith("layer.")}
    body = functools.partial(_layer, dm, fp8)
    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(lambda c, lp: (body(c, lp), None), x, layers)
    return rms(x, flat["final_norm.g"].astype(jnp.float32), dm["eps"])


def head(flat, dm, h, fp8=False):
    """Logits (..., V) in float32 from final hidden states."""
    w = (flat["embed.table"].astype(jnp.float32).T if dm["tied"]
         else flat["lm_head.w"].astype(jnp.float32))
    return mm(h, w, fp8)


# ----------------------------------------------------------------- counts
def layer_matmul_params(dm: dict) -> int:
    D, H, KH, hd, F = dm["D"], dm["H"], dm["KH"], dm["hd"], dm["F"]
    return D * H * hd + 2 * D * KH * hd + H * hd * D + 3 * D * F


def body_params(dm: dict) -> int:
    return dm["L"] * layer_matmul_params(dm)


def prefill_flops(dm: dict, n: int) -> float:
    """A prompt of n real tokens, logits at its last position."""
    attn = 4 * dm["L"] * dm["H"] * dm["hd"] * flops.attn_pairs_causal(n)
    return 2.0 * body_params(dm) * n + attn + 2.0 * dm["V"] * dm["D"]


def decode_flops(dm: dict, pos: int) -> float:
    """One token at position ``pos`` (``pos`` tokens already cached)."""
    attn = 4 * dm["L"] * dm["H"] * dm["hd"] * (pos + 1)
    return 2.0 * body_params(dm) + attn + 2.0 * dm["V"] * dm["D"]


def decode_bytes(dm: dict, pos: int) -> float:
    """Every weight the step multiplies by (an untied embedding table is
    gathered, one row) plus the row's live keys and values."""
    b = flops.BYTES[dm["dtype"]]
    w = body_params(dm) + dm["V"] * dm["D"] + dm["D"] * (2 * dm["L"] + 1)
    kv = 2 * dm["L"] * dm["KH"] * dm["hd"] * (pos + 1)
    return float(b * (w + kv))


def train_step_flops(dm: dict, batch: int, seq: int) -> float:
    """Forward and backward (three times the forward) of batch x seq
    tokens, with logits at every position."""
    tokens = batch * seq
    attn = 4 * dm["L"] * dm["H"] * dm["hd"] * flops.attn_pairs_causal(seq) * batch
    fwd = 2.0 * body_params(dm) * tokens + 2.0 * dm["V"] * dm["D"] * tokens + attn
    return 3.0 * fwd
