"""One module per architecture: everything in the harness that depends on
the model's equations.

A configuration file names its module under ``"bench_arch"``; the harness
reaches it only through ``cells.arch_module(config)``, which loads
``bench/archs/<bench_arch>.py`` and fails at once, with the list of known
modules, on a missing or unknown name. A later architecture comes in as
new files only: a module here, a configuration file naming it, and its
traffic, limits and metric files. No file that exists is edited.

A module provides:

- ``program_config(config)``: the program's ``ArchConfig``, every model
  key of the file set as the file states it (nested configs, such as
  ``MoEConfig`` or ``MLAConfig``, built from their published keys), then
  the file's ``"program"`` overrides.
- ``dims(config) -> dict``: the sizes the layout, the reference and the
  counts need, hashable values only (the reference's programs take them
  as static arguments). The harness itself reads ``"V"`` (the vocabulary
  the traffic draws from) and ``"dtype"`` (the type the weights are made
  and served in).
- ``layout(dm) -> {name: (shape, scale, offset)}``: every leaf of the
  weights, drawn as ``offset + scale * N(0, 1)``. ``weights.make_flat``
  folds one key per leaf in the sorted order of the names, so renaming a
  leaf changes every weight after it. AdamW in ``reference.py`` decays
  the leaves whose names end in ``.w`` and ``embed.table``: name weight
  matrices so.
- ``nest(flat)`` and ``flatten(tree)``: flat dotted names to and from the
  program's parameter tree. The program keeps its layers in
  ``params["segments"]``, one entry per run of like layers; a module names
  each entry's prefix in order and passes the names to
  ``nest_segments`` / ``flatten_segments``. A dense decoder has one,
  ``("layer",)``; leading dense layers beside expert layers are two, say
  ``("dense", "moe")``, so that ``dense.ffn.up.w`` and
  ``moe.ffn.experts.up`` name leaves of different segments.
- ``hidden(flat, dm, tokens, fp8=False, remat=False)`` and
  ``head(flat, dm, h, fp8=False)``: the plain float32 reference forward at
  ``HIGHEST`` (final normed hidden states, then logits), importing nothing
  of the program; ``fp8`` is the control, through ``mm``.
- The yardstick's counts: ``decode_bytes(dm, pos)``,
  ``decode_flops(dm, pos)``, ``prefill_flops(dm, n)`` and
  ``train_step_flops(dm, batch, seq)`` (a multiply-add is two operations;
  no recomputation counted). The per-layer readers in ``bench/metrics/``
  reach them through the record's ``"arch"``.

The primitives the modules share, ``HI``, ``mm`` and ``rms``, are defined
here.
"""
from __future__ import annotations

import importlib
import pkgutil

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0                       # largest finite float8_e4m3fn


def _q8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)      # straight through for grads


def mm(a, b, fp8):
    """A weight product in float32 at ``HIGHEST``; with ``fp8`` both
    operands go through float8 (e4m3, one scale per tensor) first: the
    control."""
    if fp8:
        a, b = _q8(a), _q8(b)
    return jnp.matmul(a, b, precision=HI)


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def known() -> list:
    """The modules on this package's search path."""
    return sorted(m.name for m in pkgutil.iter_modules(__path__))


def load(name):
    """The module ``name``; fails on a missing or unknown name."""
    if name not in known():
        raise SystemExit(f"the configuration's bench_arch {name!r} names no "
                         f"module of bench/archs; known: {known()}")
    return importlib.import_module(f"{__name__}.{name}")


def nest_segments(flat: dict, segments: tuple) -> dict:
    """Flat dotted names -> the program's nested tree; a name that starts
    with ``segments[i]`` goes to ``params["segments"][i]``."""
    index = {p: i for i, p in enumerate(segments)}
    tree: dict = {"segments": [{} for _ in segments]}
    for name, arr in flat.items():
        parts = name.split(".")
        node = tree
        if parts[0] in index:
            node, parts = tree["segments"][index[parts[0]]], parts[1:]
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = arr
    return tree


def flatten_segments(tree: dict, segments: tuple) -> dict:
    """The program's nested tree -> flat dotted names; an entry of
    ``params["segments"]`` past those named keeps its index, so that
    ``weights.check_layout`` shows it."""
    out = {}

    def walk(node, prefix):
        if isinstance(node, dict):
            for k, v in node.items():
                walk(v, prefix + [k])
        elif isinstance(node, list):
            for i, v in enumerate(node):
                walk(v, [segments[i] if i < len(segments) else f"segments.{i}"])
        else:
            out[".".join(prefix)] = node
    walk(tree, [])
    return out
