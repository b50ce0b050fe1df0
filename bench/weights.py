"""Weights from the seed, made by the benchmark itself.

The program serves and trains these weights, and the reference makes the
same ones again from the same seed after the program's state is freed, so
the reference takes nothing that the program made. The architecture
module (``bench/archs/<name>.py``) gives the leaves and the program's
tree; the harness checks that layout against the program's own before it
hands the weights over.

Every leaf is drawn from its own key on the device, in the served type,
in one jitted call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def base_key(seed: int):
    """A key for any whole number up to 64 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


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


def make_flat(arch, dm: dict, seed: int) -> dict:
    """Flat dict of every leaf of ``arch.layout(dm)``, on the default
    device, in ``dm["dtype"]``; leaf i of the sorted names is drawn from
    the seed's key folded with i."""
    items = tuple(sorted(arch.layout(dm).items()))
    return _maker(items, dm["dtype"])(base_key(seed))


def make_params(arch, dm: dict, seed: int) -> dict:
    """The program's parameter tree."""
    return arch.nest(make_flat(arch, dm, seed))


def check_layout(arch, program_tree, dm: dict) -> None:
    """Raise unless the program's parameter tree (shapes from
    ``jax.eval_shape``) has exactly the leaves, shapes and types made here."""
    got = {k: (tuple(v.shape), str(v.dtype))
           for k, v in arch.flatten(program_tree).items()}
    want = {k: (tuple(s), dm["dtype"])
            for k, (s, _, _) in arch.layout(dm).items()}
    if got != want:
        diff = sorted(set(got.items()) ^ set(want.items()))
        raise SystemExit(f"the program's parameter layout differs from the "
                         f"benchmark's weights: {diff[:6]}")
