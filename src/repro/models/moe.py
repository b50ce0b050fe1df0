"""Mixture-of-experts FFN: shared + routed experts, in two forms.

* ``scoring="softmax"`` (qwen2-moe): softmax top-k router (the fused
  kernel ``kernels/moe_topk`` on TPU, jnp elsewhere), renormalized; capacity
  dispatch (sort + scatter to (E, C, d), honest top-k FLOPs) that drops a
  token's assignment past its expert's capacity (factor 1.25 in prefill,
  2.0 in decode). Padding experts (EP divisibility, e.g. 60 -> 64) are
  masked out of the softmax and never receive tokens.
* ``scoring="sigmoid"`` (DeepSeek-V3's noaux_tc, as ``Gate`` in its
  published ``inference/model.py``): sigmoid scores over all ``n_routed``
  experts in float32, plus a per-expert bias used only to select; groups
  ranked by the sum of their two best biased scores, the top ``topk_group``
  kept, the top-k experts among them; weights are the selected unbiased
  scores renormalized, times ``router_scale``. The layer holds experts
  [first_held, first_held + n_held) -- one rank's share under expert
  parallelism -- and computes only the assignments that fall on them, as a
  grouped matmul (``lax.ragged_dot``) over the held assignments sorted by
  expert. It never drops one: the buffer holds every token's every held
  assignment. No auxiliary loss (aux-loss-free balancing).

Sharding strategies (distributed/sharding.py picks per mesh):
* "expert-TP": expert FF dims sharded over the model axis (default; clean
  GSPMD einsums);
* "EP": the expert axis sharded over the model axis -- the (E, C, d)
  dispatch buffer reshards token->expert, which GSPMD lowers to the
  all-to-all pair.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..kernels import ops
from . import layers as L


def moe_init(key, cfg):
    m = cfg.moe
    e = m.routed_total()
    held = m.held() if m.scoring == "sigmoid" else e      # experts stored here
    ks = jax.random.split(key, 5)
    d, f = cfg.d_model, m.expert_ff
    scale = 1.0 / jnp.sqrt(d)
    p = {
        "router": {"w": jax.random.normal(ks[0], (d, e), jnp.dtype(cfg.dtype)) * 0.02},
        "experts": {
            "gate": jax.random.normal(ks[1], (held, d, f), jnp.dtype(cfg.dtype)) * scale,
            "up": jax.random.normal(ks[2], (held, d, f), jnp.dtype(cfg.dtype)) * scale,
            "down": jax.random.normal(ks[3], (held, f, d), jnp.dtype(cfg.dtype)) * (1.0 / jnp.sqrt(f)),
        },
    }
    if m.scoring == "sigmoid":
        p["router"]["bias"] = jnp.zeros((m.n_routed,), jnp.dtype(cfg.dtype))
    if m.n_shared > 0:
        p["shared"] = L.swiglu_init(ks[4], d, m.n_shared * f, cfg.dtype)
    return p


def moe_forward(cfg, p, x, *, capacity_factor: float = 1.25,
                backend: str | None = None, stats: bool = False):
    """x: (B, S, d) -> (y, aux_loss), and with ``stats`` a third item:
    in the sigmoid form this layer's routed (token, expert) pairs, those on
    held experts and the most any held expert took (int32 scalars); None
    in the softmax form."""
    if cfg.moe.scoring == "sigmoid":
        y, st = _held_forward(cfg, p, x)
        aux = jnp.zeros((), jnp.float32)
        return (y, aux, st) if stats else (y, aux)
    y, aux = _capacity_forward(cfg, p, x, capacity_factor=capacity_factor,
                               backend=backend)
    return (y, aux, None) if stats else (y, aux)


def route_sigmoid(m, logits, bias):
    """DeepSeek-V3's noaux_tc router. logits: (T, n_routed) float32; bias:
    (n_routed,). Returns (weights (T, k) float32, expert ids (T, k))."""
    t, e = logits.shape
    s = jax.nn.sigmoid(logits)
    c = s + bias.astype(jnp.float32)[None, :]
    if m.n_expert_groups > 1:
        g = c.reshape(t, m.n_expert_groups, e // m.n_expert_groups)
        gscore = jnp.sum(jax.lax.top_k(g, 2)[0], axis=-1)            # (T, G)
        _, keep = jax.lax.top_k(gscore, m.topk_group)
        kept = jnp.any(jnp.arange(m.n_expert_groups)[None, :, None]
                       == keep[:, None, :], axis=-1)                 # (T, G)
        c = jnp.where(kept[:, :, None], g, -jnp.inf).reshape(t, e)
    _, idx = jax.lax.top_k(c, m.top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    return w / jnp.sum(w, axis=-1, keepdims=True) * m.router_scale, idx


def _held_forward(cfg, p, x):
    """The drop-free held-expert layer (sigmoid form); see the module
    docstring. Returns (y, stats)."""
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    held = m.held()
    xf = x.reshape(t, d)
    with jax.named_scope("moe.router"):
        logits = jnp.matmul(xf.astype(jnp.float32),
                            p["router"]["w"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        weights, idx = route_sigmoid(m, logits, p["router"]["bias"])
    with jax.named_scope("moe.experts"):
        # Assignments sorted by held expert, the rest last (id ``held``);
        # the first ``rows`` of the order hold every held assignment, and
        # the products' groups cover exactly those.
        local = idx.reshape(-1) - m.first_held
        local = jnp.where((local >= 0) & (local < held), local, held)
        rows = t * min(m.top_k, held)
        order = jnp.argsort(local, stable=True)
        sizes = jnp.bincount(local, length=held + 1)[:held].astype(jnp.int32)
        w_exp, groups = p["experts"], sizes
        if "layer" in p:
            # The whole (layers, held, ...) stack, this layer's groups the
            # only ones not empty: no copy of the layer's slice.
            n = w_exp["gate"].shape[0]
            groups = jax.lax.dynamic_update_slice(
                jnp.zeros((n * held,), jnp.int32), sizes, (p["layer"] * held,))
            w_exp = {k: v.reshape(n * held, *v.shape[2:]) for k, v in w_exp.items()}
        xs = jnp.take(xf, order[:rows] // m.top_k, axis=0)
        h = jax.nn.silu(jax.lax.ragged_dot(xs, w_exp["gate"].astype(xs.dtype), groups))
        h = h * jax.lax.ragged_dot(xs, w_exp["up"].astype(xs.dtype), groups)
        out = jax.lax.ragged_dot(h, w_exp["down"].astype(h.dtype), groups)
        # Back to each token's own assignments (a gather, not a scatter).
        at = jnp.minimum(jnp.argsort(order), rows - 1).reshape(t, m.top_k)
        mine = (local.reshape(t, m.top_k) < held)[..., None]
        got = jnp.where(mine, jnp.take(out, at, axis=0).astype(jnp.float32), 0.0)
        y = jnp.sum(weights[..., None] * got, axis=1)
    if "shared" in p:
        with jax.named_scope("moe.shared"):
            y = y + L.swiglu(p["shared"], xf).astype(jnp.float32)
    st = {"assignments": jnp.int32(t * m.top_k),
          "held": jnp.sum(sizes), "held_max": jnp.max(sizes)}
    return y.astype(x.dtype).reshape(b, s, d), st


def _capacity_forward(cfg, p, x, *, capacity_factor, backend):
    """The softmax router with capacity dispatch. -> (y, aux_loss)."""
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    e = m.routed_total()
    xf = x.reshape(t, d)

    logits = xf @ p["router"]["w"].astype(xf.dtype)                  # (T, E)
    weights, idx = ops.moe_topk(logits, m.top_k, n_valid=m.n_routed,
                                backend=backend)                     # (T,k)
    weights = weights * m.router_scale

    # load-balance aux loss (Switch-style) over the valid experts
    probs = jax.nn.softmax(
        jnp.where(jnp.arange(e)[None, :] < m.n_routed,
                  logits.astype(jnp.float32), -1e30), axis=-1)
    me = jnp.mean(probs, axis=0)                                     # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32), axis=1), axis=0)
    aux = m.n_routed * jnp.sum(me * ce)

    # ---- capacity dispatch: sort tokens by expert, scatter to (E, C, d)
    cap = int(max(1, round(t * m.top_k * capacity_factor / e)))
    flat_eid = idx.reshape(-1)                                       # (T*k,)
    flat_w = weights.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(t), m.top_k)
    order = jnp.argsort(flat_eid)
    eid_s = flat_eid[order]
    tok_s = flat_tok[order]
    w_s = flat_w[order]
    # position of each routed token within its expert's block
    group_sizes = jnp.bincount(eid_s, length=e)
    starts = jnp.cumsum(group_sizes) - group_sizes
    pos_s = jnp.arange(t * m.top_k) - starts[eid_s]
    keep = pos_s < cap                                               # drop overflow
    buf = jnp.zeros((e, cap, d), xf.dtype)
    buf = buf.at[eid_s, jnp.where(keep, pos_s, 0)].add(
        jnp.where(keep[:, None], xf[tok_s], 0.0))

    # ---- expert compute (E, C, d) -> (E, C, d); honest top-k FLOPs
    w_exp = p["experts"]
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_exp["gate"].astype(buf.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", buf, w_exp["up"].astype(buf.dtype))
    yexp = jnp.einsum("ecf,efd->ecd", h, w_exp["down"].astype(buf.dtype))

    # ---- combine back, weighted
    gathered = yexp[eid_s, jnp.where(keep, pos_s, 0)]                # (T*k, d)
    gathered = jnp.where(keep[:, None], gathered, 0.0) * w_s[:, None].astype(xf.dtype)
    y = jnp.zeros_like(xf).at[tok_s].add(gathered)

    if "shared" in p:
        y = y + L.swiglu(p["shared"], xf)
    return y.reshape(b, s, d), aux
