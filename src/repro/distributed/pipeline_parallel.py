"""Pipeline parallelism over the pod axis (GPipe-style, shard_map +
collective_permute).

For multi-pod meshes the default maps the ``pod`` axis to data parallelism;
this module provides the alternative: layer stages sharded across pods,
microbatches streamed through a ppermute ring. Forward-only building block
plus a loss wrapper -- used by the dry-run's PP variant and the distributed
tests; the trainer composes it with grad accumulation.

Schedule: classic GPipe fill-drain over ``n_micro`` microbatches with
``n_stages`` stages; bubble fraction (n_stages-1)/(n_micro+n_stages-1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_forward(stage_fn, n_stages: int, axis: str):
    """Returns f(stage_params, x_micro) running the fill-drain schedule.

    stage_params: pytree with leading stage axis, sharded over ``axis``;
    x_micro: (n_micro, mb, ...) microbatched activations (replicated).
    stage_fn(params_for_stage, x) -> y, applied at every stage.
    """

    def run(stage_params, x_micro):
        n_micro = x_micro.shape[0]
        stage = jax.lax.axis_index(axis)
        my_params = jax.tree.map(lambda a: a[0], stage_params)  # shard local
        total = n_micro + n_stages - 1
        mb_shape = x_micro.shape[1:]
        carry = jnp.zeros(mb_shape, x_micro.dtype)
        outs = jnp.zeros((n_micro,) + mb_shape, x_micro.dtype)

        def step(t, state):
            carry, outs = state
            # stage 0 injects microbatch t; others use the permuted carry
            inject = jnp.where(t < n_micro, t, 0)
            x_in = jnp.where(stage == 0, x_micro[inject], carry)
            y = stage_fn(my_params, x_in)
            # last stage emits microbatch (t - n_stages + 1)
            out_idx = t - (n_stages - 1)
            emit = jnp.logical_and(stage == n_stages - 1, out_idx >= 0)
            outs = jax.lax.cond(
                emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(out_idx, 0), 0),
                lambda o: o, outs)
            # rotate activations to the next stage
            carry = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return carry, outs

        carry, outs = jax.lax.fori_loop(0, total, step, (carry, outs))
        # only the last stage holds real outputs; replicate across stages
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    return run


def make_pipelined_fn(stage_fn, mesh, n_stages: int, axis: str = "pod",
                      param_specs=None):
    """shard_map wrapper: stage_params sharded on the stage axis, data
    replicated across it (the data/model axes inside stage_fn still apply)."""
    run = pipeline_forward(stage_fn, n_stages, axis)
    in_specs = (param_specs if param_specs is not None else P(axis), P())
    return jax.shard_map(run, mesh=mesh, in_specs=in_specs, out_specs=P(),
                         check_vma=False)
