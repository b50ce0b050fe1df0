"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches JAX device state -- the dry-run must set XLA_FLAGS before the
first device query.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    Axes: data (DP + FSDP), model (TP + EP); the pod axis defaults to an
    outer data-parallel dimension (pipeline over pods is available through
    distributed.pipeline_parallel)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    return make_mesh(shape, axes, devices=jax.devices()[:n])


def make_mesh(shape, axes, devices=None):
    """Arbitrary mesh (e.g. (4, 2) on 8 host devices).

    Axes are ``Auto``: the sharding rules in ``distributed/sharding.py``
    place arrays with ``NamedSharding`` and leave propagation to GSPMD,
    which ``jax.make_mesh``'s default ``Explicit`` axes would refuse."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def dp_axes(mesh) -> tuple:
    """Axes that carry data parallelism (pod + data when present)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def tp_axis(mesh):
    return "model" if "model" in mesh.axis_names else None
