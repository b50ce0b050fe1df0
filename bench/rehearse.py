#!/usr/bin/env python3
"""Compile a cell's programs for a described TPU v5e chip, without the
chip, and print each program's ``memory_analysis`` bytes.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py --workload <name> [--batches 1,2,4,8]

Nothing runs: this finds what the chip's compiler refuses and what each
program needs in memory. For a training cell it compiles the train step
at each micro-batch in ``--batches`` and names the largest that fits
beside the serving weights, the cache and the train state with 1 GiB to
spare.
"""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import cells  # noqa: E402
import loadgen  # noqa: E402
import weights  # noqa: E402

GIB = 2 ** 30
HBM = 16 * GIB
SPARE = 1 * GIB


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batches", default="1,2,4,8")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    from repro.models.transformer import Model
    from repro.serving.engine import _next_pow2
    from repro.serving.kv_cache import cache_batch_axes, make_write_slots
    from repro.training import optimizer as opt
    from repro.training import trainer as T

    cell = cells.resolve(args.workload)
    traffic, arch = cell["traffic"], cells.arch_module(cell["config"])
    dm = arch.dims(cell["config"])
    model = Model(arch.program_config(cell["config"]), backend="pallas")
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)

    def report(name, compiled) -> dict:
        m = compiled.memory_analysis()
        row = {"arguments": m.argument_size_in_bytes,
               "outputs": m.output_size_in_bytes,
               "aliased": m.alias_size_in_bytes,
               "temporaries": m.temp_size_in_bytes}
        print(f"{name}: " + ", ".join(f"{k} {v / GIB:.3f} GiB"
                                      for k, v in row.items()), flush=True)
        return row

    params = on_chip(jax.eval_shape(lambda: weights.make_params(arch, dm, 0)))
    B, S = traffic["max_batch"], traffic["max_len"]
    caches = on_chip(jax.eval_shape(lambda: model.init_cache(B, S)))
    serve_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves((params, caches)))
    print(f"{cell['name']}: serving weights and cache {serve_bytes / GIB:.3f} GiB",
          flush=True)
    lens = sorted({_next_pow2(len(p)) for p in loadgen.warm_prompts(traffic, 0, 8)})
    for L in lens:
        batch = on_chip({"tokens": jax.ShapeDtypeStruct((B, L), jnp.int32),
                         "lengths": jax.ShapeDtypeStruct((B,), jnp.int32)})
        c = jax.jit(model.prefill_batch, static_argnums=2).lower(
            params, batch, S).compile()
        assert "tpu_custom_call" in c.as_text(), "no Mosaic kernel in prefill"
        report(f"prefill_batch B={B} L={L}", c)
    tok = on_chip(jax.ShapeDtypeStruct((B, 1), jnp.int32))
    pos = on_chip(jax.ShapeDtypeStruct((), jnp.int32))
    report(f"decode_step B={B} S={S}",
           jax.jit(model.decode_step).lower(params, caches, tok, pos).compile())
    rows = on_chip(jax.eval_shape(lambda: model.init_cache(1, S)))
    slots = on_chip(jax.ShapeDtypeStruct((1,), jnp.int32))
    report("write_slots", make_write_slots(cache_batch_axes(model, S)).lower(
        caches, rows, slots).compile())
    bg = traffic.get("background") or {}
    if bg.get("kind") == "ingest":
        toks = on_chip({"tokens": jax.ShapeDtypeStruct((1, bg["seq"]), jnp.int32)})
        report(f"bulk prefill B=1 L={bg['seq']} (jitted stand-in for the "
               f"engine's eager call)", jax.jit(model.prefill, static_argnums=2)
               .lower(params, toks, S).compile())
    if bg.get("kind") == "train":
        tcfg = T.TrainConfig(opt=opt.OptimizerConfig(**bg["optimizer"]))
        state = on_chip(jax.eval_shape(lambda p: {
            "params": p, "opt": opt.init_state(tcfg.opt, p)}, params))
        state_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(state))
        step = jax.jit(T.make_train_step(model, tcfg), donate_argnums=0)
        best = None
        for b in (int(x) for x in args.batches.split(",")):
            batch = on_chip({"tokens": jax.ShapeDtypeStruct((b, bg["seq"]), jnp.int32),
                             "labels": jax.ShapeDtypeStruct((b, bg["seq"]), jnp.int32)})
            row = report(f"train_step B={b} S={bg['seq']}",
                         step.lower(state, batch).compile())
            total = serve_bytes + state_bytes + row["temporaries"]
            fits = total + SPARE <= HBM
            print(f"  with serving and train state: {total / GIB:.3f} GiB; "
                  f"{'fits' if fits else 'does not fit'} with 1 GiB spare", flush=True)
            if fits:
                best = b
        print(f"largest micro-batch that fits: {best}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
