"""Weights from the seed, made by the benchmark itself.

The program serves and trains these weights, and the reference makes the
same ones again from the same seed after the program's state is freed, so
the reference takes nothing that the program made. The tree follows the
program's parameter layout for a dense GQA decoder (one scanned segment of
``L`` stacked layers); the harness checks that layout against the
program's own before it hands the weights over.

Every leaf is drawn from its own key on the device, in the served type,
in one jitted call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def dims(config: dict) -> dict:
    """The sizes the reference and the weights need, from the file."""
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


def base_key(seed: int):
    """A key for any whole number up to 64 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _shapes(dm: dict) -> dict:
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


def _nest(flat: dict) -> dict:
    """Flat dotted names -> the program's nested tree."""
    tree: dict = {"segments": [{}]}
    for name, arr in flat.items():
        parts = name.split(".")
        node = tree
        if parts[0] == "layer":
            node, parts = tree["segments"][0], parts[1:]
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = arr
    return tree


def flatten(tree: dict) -> dict:
    """The program's nested tree -> flat dotted names."""
    out = {}

    def walk(node, prefix):
        if isinstance(node, dict):
            for k, v in node.items():
                walk(v, prefix + [k])
        elif isinstance(node, list):
            for v in node:                 # one scanned segment
                walk(v, ["layer"])
        else:
            out[".".join(prefix)] = node
    walk(tree, [])
    return out


@functools.lru_cache(maxsize=None)
def _maker(items: tuple, dtype: str):
    def make(key):
        out = {}
        for i, (name, (shape, scale, offset)) in enumerate(items):
            k = jax.random.fold_in(key, i)
            leaf = jax.random.normal(k, shape, jnp.dtype(dtype)) * float(scale)
            out[name] = leaf + float(offset) if offset else leaf
        return out
    return jax.jit(make)


def make_flat(dm: dict, seed: int) -> dict:
    """Flat dict of every leaf, on the default device, in ``dm["dtype"]``."""
    items = tuple(sorted(_shapes(dm).items()))
    return _maker(items, dm["dtype"])(base_key(seed))


def make_params(dm: dict, seed: int) -> dict:
    """The program's parameter tree."""
    return _nest(make_flat(dm, seed))


def check_layout(program_tree, dm: dict) -> None:
    """Raise unless the program's parameter tree (shapes from
    ``jax.eval_shape``) has exactly the leaves, shapes and types made here."""
    got = {k: (tuple(v.shape), str(v.dtype))
           for k, v in flatten(program_tree).items()}
    want = {k: (tuple(s), dm["dtype"]) for k, (s, _, _) in _shapes(dm).items()}
    if got != want:
        diff = sorted(set(got.items()) ^ set(want.items()))
        raise SystemExit(f"the program's parameter layout differs from the "
                         f"benchmark's weights: {diff[:6]}")
