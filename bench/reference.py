"""Plain float32 reference: the check of what the timed path produced,
written against an architecture module's ``hidden`` and ``head``
(``bench/archs/<name>.py``; the primitives those modules share, ``HI``,
``mm`` and ``rms``, are in ``bench/archs/__init__.py``).

It imports nothing of the program and takes nothing the program made: its
weights come from ``weights.make_flat`` with the run's seed, made again
after the program's state is freed. Every matrix product runs in float32
at ``HIGHEST`` precision.

``fp8=True`` is the control: every weight product takes its operands
through float8 (e4m3, one scale per tensor), the step below the bfloat16
that the configurations state. The benchmark's runs never compute it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


# --------------------------------------------------------------- serving
@functools.partial(jax.jit, static_argnames=("arch", "dm_items", "fp8"))
def _gaps(flat, tokens, positions, served, mask, *, arch, dm_items, fp8):
    dm = dict(dm_items)
    h = arch.hidden(flat, dm, tokens[None])[0]                 # (S, D)
    hp = jnp.take(h, positions, axis=0)                        # (P, D)
    ref = arch.head(flat, dm, hp)                              # (P, V)
    best = jnp.max(ref, -1)
    gap = best - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
    out = {"gap": jnp.max(jnp.where(mask, gap, 0.0))}
    if fp8:
        hq = arch.hidden(flat, dm, tokens[None], fp8=True)[0]
        low = arch.head(flat, dm, jnp.take(hq, positions, axis=0), fp8=True)
        pick = jnp.argmax(low, -1)
        cgap = best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
        out["control_gap"] = jnp.max(jnp.where(mask, cgap, 0.0))
    return out


def served_gap(arch, flat, dm, prompt, served, pad_to, answer_pad, fp8=False):
    """Widest gap, over one request's served tokens, by which a served
    token's reference logit lies below the reference's best at that
    position; with ``fp8`` also the control's widest gap (the token the
    float8 reference puts first, read against the float32 reference)."""
    n = len(served)
    seq = np.zeros((pad_to,), np.int32)
    full = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    seq[: len(full)] = full
    pos = np.zeros((answer_pad,), np.int32)
    pos[:n] = len(prompt) - 1 + np.arange(n)
    srv = np.zeros((answer_pad,), np.int32)
    srv[:n] = served
    mask = np.arange(answer_pad) < n
    out = _gaps(flat, jnp.asarray(seq), jnp.asarray(pos), jnp.asarray(srv),
                jnp.asarray(mask), arch=arch, dm_items=tuple(sorted(dm.items())),
                fp8=fp8)
    return {k: float(v) for k, v in out.items()}


# --------------------------------------------------------------- training
def lr_at(o: dict, step):
    warm = jnp.minimum(step / max(o["warmup_steps"], 1), 1.0)
    prog = jnp.clip((step - o["warmup_steps"])
                    / max(o["total_steps"] - o["warmup_steps"], 1), 0.0, 1.0)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return o["lr"] * warm * (o["min_lr_frac"] + (1 - o["min_lr_frac"]) * cos)


def _decays(name: str) -> bool:
    """AdamW decays the weight matrices and the embedding, not gains or
    biases."""
    return name.endswith(".w") or name == "embed.table"


@functools.partial(jax.jit, static_argnames=("arch", "dm_items", "fp8"))
def _loss_grad(stored, rows, *, arch, dm_items, fp8):
    """Mean next-token cross-entropy over all rows and its float32
    gradient, one row at a time (rows hold equally many tokens, so the mean
    over rows of each row's mean is the mean over all tokens)."""
    dm = dict(dm_items)
    p32 = {k: v.astype(jnp.float32) for k, v in stored.items()}

    def row_loss(p, toks):
        h = arch.hidden(p, dm, toks[None], fp8=fp8, remat=True)[0]
        logits = arch.head(p, dm, h[:-1], fp8=fp8)
        lse = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, toks[1:, None], -1)[:, 0]
        return jnp.mean(lse - gold)

    def body(acc, toks):
        loss, g = jax.value_and_grad(row_loss)(p32, toks)
        return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], g)), None

    zeros = jax.tree.map(jnp.zeros_like, p32)
    (lsum, gsum), _ = jax.lax.scan(body, (jnp.zeros(()), zeros), rows)
    n = rows.shape[0]
    return lsum / n, jax.tree.map(lambda g: g / n, gsum)


@functools.partial(jax.jit, static_argnames=("o_items",), donate_argnums=(2, 3))
def _adam(stored, g, m, v, step, *, o_items):
    """One AdamW step in float32. The new parameters leave the program in
    the type the configuration states: inside one program XLA may drop a
    float32 -> bfloat16 -> float32 round trip, so the rounding is made
    real by storing them."""
    o = dict(o_items)
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, o["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    lr = lr_at(o, step)
    b1c, b2c = 1 - o["b1"] ** step, 1 - o["b2"] ** step
    newp, newm, newv = {}, {}, {}
    for k, p in stored.items():
        p = p.astype(jnp.float32)
        gk = g[k] * scale
        newm[k] = o["b1"] * m[k] + (1 - o["b1"]) * gk
        newv[k] = o["b2"] * v[k] + (1 - o["b2"]) * gk * gk
        delta = (newm[k] / b1c) / (jnp.sqrt(newv[k] / b2c) + o["eps"])
        if _decays(k):
            delta = delta + o["weight_decay"] * p
        newp[k] = (p - lr * delta).astype(stored[k].dtype)
    norms = {k: jnp.sqrt(jnp.sum(jnp.square(g[k] * scale))) for k in g}
    return newp, newm, newv, norms


@jax.jit
def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


@jax.jit
def change_norms(a, b):
    return {k: jnp.sqrt(jnp.sum(jnp.square(a[k].astype(jnp.float32)
                                           - b[k].astype(jnp.float32))))
            for k in a}


def train_steps(arch, flat, dm, batches, optimizer: dict, fp8=False):
    """Three (or ``len(batches)``) AdamW steps from ``flat``. Returns each
    step's loss, the first step's per-leaf norm of the (clipped) gradient
    the optimizer got, and each leaf's norm of its change over the steps."""
    p = flat
    m = {k: jnp.zeros(v.shape, jnp.float32) for k, v in flat.items()}
    v = {k: jnp.zeros(x.shape, jnp.float32) for k, x in flat.items()}
    dm_items = tuple(sorted(dm.items()))
    o_items = tuple(sorted(optimizer.items()))
    losses, grad1 = [], None
    for i, rows in enumerate(batches):
        loss, g = _loss_grad(p, jnp.asarray(rows), arch=arch, dm_items=dm_items,
                             fp8=fp8)
        p, m, v, gn = _adam(p, g, m, v, jnp.float32(i + 1), o_items=o_items)
        del g
        losses.append(float(loss))
        if grad1 is None:
            grad1 = {k: float(x) for k, x in gn.items()}
    change = {k: float(x) for k, x in change_norms(p, flat).items()}
    return {"loss": losses, "grad": grad1, "change": change}
