"""DeepSeek-V3 layout, one rank's share under expert parallelism: leading
dense layers, then expert layers; multi-head latent attention (MLA) in
every layer, YaRN rope on adjacent dim pairs, a sigmoid group-limited
router (``noaux_tc``) over all routed experts of which this rank holds a
contiguous range, one shared expert, untied head. Two scanned segments,
``("dense", "moe")``.

The reference forward runs every matrix product in float32 at
``HIGHEST``, each weight upcast where it is used; attention is computed in
blocks of queries and the head in blocks of the vocabulary, so that after
the program is freed the weights and the reference fit on one chip. The
expert layer is written plainly: every held expert over every token, each
output weighted by the token's router weight for that expert (zero where
the expert was not selected), then the shared expert.

Departures from the published description (arXiv:2412.19437; the
configuration file's ``assumed``):

- the multi-token-prediction layer is not built: greedy decoding without
  speculation never runs it;
- weights, the router's selection bias among them, are drawn from the
  seed, the bias N(0, 0.05^2) so that it changes choices; every leaf is
  stored in the served type, bfloat16 (the published checkpoint is fp8
  with 128x128 block scales, its bias float32), so the ``fp8`` control
  sits near the published weights' own precision;
- only experts [first, first + count) of the router's ``of`` are held and
  computed; an assignment to another expert adds nothing here, as it would
  add nothing on this rank before the combine of expert parallelism.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import flops

from . import HI, flatten_segments, mm, nest_segments, rms

SEGMENTS = ("dense", "moe")
# Published keys whose value the program takes as given: any other value
# is refused, as the program has no other form of it.
FIXED = {"attention_bias": False, "hidden_act": "silu", "moe_layer_freq": 1,
         "scoring_func": "sigmoid", "topk_method": "noaux_tc",
         "tie_word_embeddings": False, "model_type": "deepseek_v3",
         "norm_topk_prob": True}
# Published keys that change nothing the program runs: the context length
# the position table was made for, the expert-parallel degree the file was
# written for (the held range is the configuration's ``experts_held``), the
# MTP layers (not built), and ``num_key_value_heads`` (MLA caches a latent).
IGNORED = ("max_position_embeddings", "ep_size", "num_nextn_predict_layers",
           "num_key_value_heads")
USED = ("num_hidden_layers", "first_k_dense_replace", "hidden_size",
        "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "intermediate_size", "moe_intermediate_size", "n_routed_experts",
        "n_shared_experts", "num_experts_per_tok", "n_group", "topk_group",
        "routed_scaling_factor", "vocab_size", "rms_norm_eps", "rope_theta",
        "rope_scaling", "torch_dtype")


# Keys of the configuration file that are the harness's own; every other
# top-level key is a published one, as the source's config.json gives it.
HARNESS = ("name", "arch", "bench_arch", "source", "reduced", "published",
           "experts_held", "assumed", "deployment", "program")


def _published(config: dict) -> dict:
    return {k: v for k, v in config.items() if k not in HARNESS}


def _check(config: dict) -> None:
    m = _published(config)
    unknown = sorted(set(m) - set(FIXED) - set(IGNORED) - set(USED))
    if unknown:
        raise SystemExit(f"mla_moe maps no published key {unknown}")
    off = {k: m[k] for k in FIXED if k in m and m[k] != FIXED[k]}
    y = m["rope_scaling"]
    if off or y.get("type") != "yarn" or y["mscale"] != y["mscale_all_dim"]:
        raise SystemExit(f"mla_moe runs only {FIXED} with yarn rope whose "
                         f"mscale equals mscale_all_dim (cos and sin "
                         f"unscaled); got {off}, {y}")
    held = config["experts_held"]
    if not 0 <= held["first"] <= held["of"] - m["n_routed_experts"]:
        raise SystemExit(f"held experts {held} do not fit the router's width")


def program_config(config: dict):
    """The program's ArchConfig: every model key of the file as it states
    it; ``n_routed_experts`` is the count held here, ``experts_held`` the
    first held and the router's width."""
    from repro.configs import get_arch
    from repro.configs.base import MLAConfig, MoEConfig, YaRNConfig
    _check(config)
    m, held = _published(config), config["experts_held"]
    y = m["rope_scaling"]
    cfg = dataclasses.replace(
        get_arch(config["arch"]),
        n_layers=m["num_hidden_layers"], first_k_dense=m["first_k_dense_replace"],
        d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_attention_heads"],
        head_dim=m["qk_nope_head_dim"] + m["qk_rope_head_dim"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        tie_embeddings=False, norm_eps=m["rms_norm_eps"],
        rope_theta=float(m["rope_theta"]), dtype=m["torch_dtype"],
        mla=MLAConfig(q_lora_rank=m["q_lora_rank"], kv_lora_rank=m["kv_lora_rank"],
                      qk_nope_head_dim=m["qk_nope_head_dim"],
                      qk_rope_head_dim=m["qk_rope_head_dim"],
                      v_head_dim=m["v_head_dim"]),
        moe=MoEConfig(n_routed=held["of"], n_shared=m["n_shared_experts"],
                      top_k=m["num_experts_per_tok"],
                      expert_ff=m["moe_intermediate_size"],
                      n_expert_groups=m["n_group"], topk_group=m["topk_group"],
                      router_scale=float(m["routed_scaling_factor"]),
                      scoring="sigmoid",
                      n_held=m["n_routed_experts"], first_held=held["first"]),
        rope_scaling=YaRNConfig(
            factor=float(y["factor"]),
            original_max_position=y["original_max_position_embeddings"],
            beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
            mscale_all_dim=float(y["mscale_all_dim"])))
    return dataclasses.replace(cfg, **config.get("program", {}))


def dims(config: dict) -> dict:
    """The sizes the weights, the reference and the counts need."""
    _check(config)
    m, held = _published(config), config["experts_held"]
    y = m["rope_scaling"]
    return {
        "L": m["num_hidden_layers"], "Ld": m["first_k_dense_replace"],
        "D": m["hidden_size"], "H": m["num_attention_heads"],
        "qr": m["q_lora_rank"], "kr": m["kv_lora_rank"],
        "nope": m["qk_nope_head_dim"], "rope": m["qk_rope_head_dim"],
        "vd": m["v_head_dim"], "F": m["intermediate_size"],
        "Fe": m["moe_intermediate_size"], "E": held["of"],
        "En": m["n_routed_experts"], "E0": held["first"],
        "K": m["num_experts_per_tok"], "G": m["n_group"],
        "TG": m["topk_group"], "Ns": m["n_shared_experts"],
        "route_scale": float(m["routed_scaling_factor"]),
        "V": m["vocab_size"], "eps": float(m["rms_norm_eps"]),
        "theta": float(m["rope_theta"]), "yarn_factor": float(y["factor"]),
        "yarn_original": int(y["original_max_position_embeddings"]),
        "beta_fast": float(y["beta_fast"]), "beta_slow": float(y["beta_slow"]),
        "mscale_all_dim": float(y["mscale_all_dim"]),
        "dtype": m["torch_dtype"],
    }


# ---------------------------------------------------------------- weights
def _attn_layout(p: str, n: int, dm: dict) -> dict:
    D, H, qr, kr = dm["D"], dm["H"], dm["qr"], dm["kr"]
    qk, vd, rope = dm["nope"] + dm["rope"], dm["vd"], dm["rope"]
    return {
        f"{p}.norm1.g": ((n, D), 0.1, 1.0),
        f"{p}.norm2.g": ((n, D), 0.1, 1.0),
        f"{p}.attn.wq_a.w": ((n, D, qr), D ** -0.5, 0.0),
        f"{p}.attn.q_norm.g": ((n, qr), 0.1, 1.0),
        f"{p}.attn.wq_b.w": ((n, qr, H * qk), qr ** -0.5, 0.0),
        f"{p}.attn.wkv_a.w": ((n, D, kr + rope), D ** -0.5, 0.0),
        f"{p}.attn.kv_norm.g": ((n, kr), 0.1, 1.0),
        f"{p}.attn.wkv_b.w": ((n, kr, H * (dm["nope"] + vd)), kr ** -0.5, 0.0),
        f"{p}.attn.wo.w": ((n, H * vd, D), (H * vd) ** -0.5, 0.0),
    }


def layout(dm: dict) -> dict:
    """name -> (shape, scale, offset): a leaf is offset + scale * N(0, 1)."""
    D, F, Fe, V = dm["D"], dm["F"], dm["Fe"], dm["V"]
    Ld, Lm, En, Fs = dm["Ld"], dm["L"] - dm["Ld"], dm["En"], dm["Ns"] * dm["Fe"]
    s = {
        "embed.table": ((V, D), 0.02, 0.0),
        "final_norm.g": ((D,), 0.1, 1.0),
        "lm_head.w": ((D, V), D ** -0.5, 0.0),
        **_attn_layout("dense", Ld, dm),
        "dense.ffn.gate.w": ((Ld, D, F), D ** -0.5, 0.0),
        "dense.ffn.up.w": ((Ld, D, F), D ** -0.5, 0.0),
        "dense.ffn.down.w": ((Ld, F, D), F ** -0.5, 0.0),
        **_attn_layout("moe", Lm, dm),
        "moe.ffn.router.w": ((Lm, D, dm["E"]), D ** -0.5, 0.0),
        "moe.ffn.router.bias": ((Lm, dm["E"]), 0.05, 0.0),
        "moe.ffn.experts.gate": ((Lm, En, D, Fe), D ** -0.5, 0.0),
        "moe.ffn.experts.up": ((Lm, En, D, Fe), D ** -0.5, 0.0),
        "moe.ffn.experts.down": ((Lm, En, Fe, D), Fe ** -0.5, 0.0),
    }
    if dm["Ns"]:
        s.update({
            "moe.ffn.shared.gate.w": ((Lm, D, Fs), D ** -0.5, 0.0),
            "moe.ffn.shared.up.w": ((Lm, D, Fs), D ** -0.5, 0.0),
            "moe.ffn.shared.down.w": ((Lm, Fs, D), Fs ** -0.5, 0.0),
        })
    return s


def nest(flat: dict) -> dict:
    return nest_segments(flat, SEGMENTS)


def flatten(tree: dict) -> dict:
    return flatten_segments(tree, SEGMENTS)


# -------------------------------------------------------------- reference
def yarn_inv_freq(dm: dict) -> np.ndarray:
    """The published YaRN frequencies of the rope dims
    (``yarn_find_correction_range``, ``yarn_linear_ramp_mask``)."""
    dim, base, orig = dm["rope"], dm["theta"], dm["yarn_original"]
    freq = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(corr(dm["beta_fast"])), 0)
    high = min(math.ceil(corr(dm["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    return (freq / dm["yarn_factor"] * (1 - mask) + freq * mask).astype(np.float32)


def softmax_scale(dm: dict) -> float:
    m = 0.1 * dm["mscale_all_dim"] * math.log(dm["yarn_factor"]) + 1.0
    return (dm["nope"] + dm["rope"]) ** -0.5 * m * m


def _rope(x, inv):
    """x: (B, S, N, dim), positions 0..S-1; rotation of adjacent pairs."""
    s = x.shape[1]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(inv)[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(x.shape)


def _w(lp, name):
    return lp[name].astype(jnp.float32)


def _block(n: int, most: int) -> int:
    """The largest divisor of n that is at most ``most``."""
    return max(b for b in range(1, min(n, most) + 1) if n % b == 0)


def _mla(dm, fp8, h, lp):
    """Causal latent attention over the whole sequence, per-head keys and
    values rebuilt from the latent; queries in blocks."""
    b, s, _ = h.shape
    H, nope, rope, vd, kr = dm["H"], dm["nope"], dm["rope"], dm["vd"], dm["kr"]
    inv = yarn_inv_freq(dm)
    cq = rms(mm(h, _w(lp, "attn.wq_a.w"), fp8), _w(lp, "attn.q_norm.g"), dm["eps"])
    q = mm(cq, _w(lp, "attn.wq_b.w"), fp8).reshape(b, s, H, nope + rope)
    qn, qp = q[..., :nope], _rope(q[..., nope:], inv)
    ckv = mm(h, _w(lp, "attn.wkv_a.w"), fp8)
    c = rms(ckv[..., :kr], _w(lp, "attn.kv_norm.g"), dm["eps"])
    kp = _rope(ckv[..., None, kr:], inv)[:, :, 0]                 # (B, S, rope)
    kv = mm(c, _w(lp, "attn.wkv_b.w"), fp8).reshape(b, s, H, nope + vd)
    kn, v = kv[..., :nope], kv[..., nope:]
    qb = _block(s, 128)
    scale = softmax_scale(dm)

    def one(i):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * qb, qb, 1)  # noqa: E731
        sc = (jnp.einsum("bqhd,bkhd->bhqk", sl(qn), kn, precision=HI)
              + jnp.einsum("bqhd,bkd->bhqk", sl(qp), kp, precision=HI)) * scale
        causal = (i * qb + jnp.arange(qb))[:, None] >= jnp.arange(s)[None, :]
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HI)
    o = jax.lax.map(one, jnp.arange(s // qb))                     # (n, B, qb, H, vd)
    o = jnp.moveaxis(o, 0, 1).reshape(b, s, H * vd)
    return mm(o, _w(lp, "attn.wo.w"), fp8)


def _swiglu(x, g, u, d, fp8):
    return mm(jax.nn.silu(mm(x, g, fp8)) * mm(x, u, fp8), d, fp8)


def route(dm, logits, bias):
    """Router weights over all experts (T, E), zero where not selected:
    sigmoid scores; biased scores choose the groups (by the sum of their
    two best) and the experts; weights are the unbiased scores of the
    chosen, renormalized and scaled."""
    t, e = logits.shape
    s = jax.nn.sigmoid(logits)
    c = (s + bias).reshape(t, dm["G"], e // dm["G"])
    gscore = jnp.sum(jax.lax.top_k(c, 2)[0], -1)
    gkeep = jax.lax.top_k(gscore, dm["TG"])[1]
    kept = jnp.zeros((t, dm["G"]), bool).at[jnp.arange(t)[:, None], gkeep].set(True)
    c = jnp.where(kept[:, :, None], c, -jnp.inf).reshape(t, e)
    idx = jax.lax.top_k(c, dm["K"])[1]
    chosen = jnp.zeros((t, e), bool).at[jnp.arange(t)[:, None], idx].set(True)
    w = jnp.where(chosen, s, 0.0)
    return w / jnp.sum(w, -1, keepdims=True) * dm["route_scale"]


def _moe(dm, fp8, x, lp):
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    w = route(dm, mm(xt, _w(lp, "ffn.router.w"), fp8), _w(lp, "ffn.router.bias"))
    y = jnp.zeros_like(xt)
    for j in range(dm["En"]):
        g, u, dn = (lp[f"ffn.experts.{n}"][j].astype(jnp.float32)
                    for n in ("gate", "up", "down"))
        ye = _swiglu(xt, g, u, dn, fp8)
        y = y + w[:, dm["E0"] + j][:, None] * ye
    if dm["Ns"]:
        y = y + _swiglu(xt, _w(lp, "ffn.shared.gate.w"), _w(lp, "ffn.shared.up.w"),
                        _w(lp, "ffn.shared.down.w"), fp8)
    return y.reshape(b, s, d)


def _layer(dm, fp8, moe, x, lp):
    x = x + _mla(dm, fp8, rms(x, _w(lp, "norm1.g"), dm["eps"]), lp)
    h = rms(x, _w(lp, "norm2.g"), dm["eps"])
    if moe:
        return x + _moe(dm, fp8, h, lp)
    return x + _swiglu(h, _w(lp, "ffn.gate.w"), _w(lp, "ffn.up.w"),
                       _w(lp, "ffn.down.w"), fp8)


def hidden(flat, dm, tokens, fp8=False, remat=False):
    """Final normed hidden states (B, S, D) for int tokens (B, S)."""
    x = jnp.take(flat["embed.table"], tokens, axis=0).astype(jnp.float32)
    for seg in SEGMENTS:
        layers = {k[len(seg) + 1:]: v for k, v in flat.items()
                  if k.startswith(seg + ".")}
        body = functools.partial(_layer, dm, fp8, seg == "moe")
        if remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(lambda c, lp: (body(c, lp), None), x, layers)
    return rms(x, _w(flat, "final_norm.g"), dm["eps"])


def head(flat, dm, h, fp8=False):
    """Logits (..., V) in float32 from final hidden states, the head's
    columns in blocks (the control's float8 scale is per block)."""
    V = dm["V"]
    vb = _block(V, 16384)
    w = flat["lm_head.w"]

    def one(i):
        wb = jax.lax.dynamic_slice_in_dim(w, i * vb, vb, 1)
        return mm(h, wb.astype(jnp.float32), fp8)
    out = jax.lax.map(one, jnp.arange(V // vb))                   # (n, ..., vb)
    return jnp.moveaxis(out, 0, -2).reshape(*h.shape[:-1], V)


# ----------------------------------------------------------------- counts
def attn_params(dm: dict) -> int:
    D, H, qr, kr = dm["D"], dm["H"], dm["qr"], dm["kr"]
    nope, rope, vd = dm["nope"], dm["rope"], dm["vd"]
    return (D * qr + qr * H * (nope + rope) + D * (kr + rope)
            + kr * H * (nope + vd) + H * vd * D)


def expert_params(dm: dict) -> int:
    return 3 * dm["D"] * dm["Fe"]


def token_params(dm: dict) -> float:
    """Weights one token multiplies by in the body: every layer's MLA, the
    dense layers' FFN, the expert layers' router and shared expert, and the
    held experts it reaches in expectation under uniform routing,
    top_k x held / routed a layer."""
    Lm = dm["L"] - dm["Ld"]
    held = dm["K"] * dm["En"] / dm["E"]
    return (dm["L"] * attn_params(dm) + dm["Ld"] * 3 * dm["D"] * dm["F"]
            + Lm * (dm["D"] * dm["E"] + dm["Ns"] * expert_params(dm)
                    + held * expert_params(dm)))


def prefill_flops(dm: dict, n: int) -> float:
    """A prompt of n real tokens, keys and values rebuilt per head, logits
    at its last position."""
    qk, vd = dm["nope"] + dm["rope"], dm["vd"]
    attn = 2 * dm["L"] * dm["H"] * (qk + vd) * flops.attn_pairs_causal(n)
    return 2.0 * token_params(dm) * n + attn + 2.0 * dm["V"] * dm["D"]


def decode_flops(dm: dict, pos: int) -> float:
    """One token at position ``pos``, absorbed: scores and context over
    the latent (and the rope keys) of the ``pos + 1`` cached positions."""
    attn = 2 * dm["L"] * dm["H"] * (2 * dm["kr"] + dm["rope"]) * (pos + 1)
    return 2.0 * token_params(dm) + attn + 2.0 * dm["V"] * dm["D"]


def decode_bytes(dm: dict, pos: int) -> float:
    """Every weight the step multiplies by (held experts at their
    expectation; the embedding gathered, one row), the norms and biases,
    and the row's latent cache at ``pos + 1`` positions."""
    b = flops.BYTES[dm["dtype"]]
    Lm = dm["L"] - dm["Ld"]
    small = (dm["D"] * (2 * dm["L"] + 2) + dm["L"] * (dm["qr"] + dm["kr"])
             + Lm * dm["E"])
    cache = dm["L"] * (dm["kr"] + dm["rope"]) * (pos + 1)
    return float(b * (token_params(dm) + dm["V"] * dm["D"] + small + cache))


def train_step_flops(dm: dict, batch: int, seq: int) -> float:
    raise NotImplementedError("no cell trains this architecture")
