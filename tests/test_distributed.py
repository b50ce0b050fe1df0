"""Distribution tests: sharding rules, multi-device GSPMD compile of the
real train/serve steps, pipeline parallelism, compressed psum -- run in
subprocesses with forced host device counts (the main process must keep the
default single device)."""
import json
import os
import subprocess
import sys

import pytest

from jax.sharding import PartitionSpec as P

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-3000:]}"
    return out.stdout


# ------------------------------------------------------------ spec rules
def test_param_spec_rules_single_device():
    import jax
    import jax.numpy as jnp
    from repro.distributed.sharding import param_spec

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))

    class FakeKey:
        def __init__(self, k):
            self.key = k

    leaf = jnp.zeros((64, 128))
    spec = param_spec((FakeKey("attn"), FakeKey("wq"), FakeKey("w")), leaf, mesh)
    assert spec == P(None, None)          # size-1 axes -> replicate


def test_param_spec_rules_16x16():
    code = """
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.distributed.sharding import params_shardings
from repro.launch.mesh import make_mesh
from repro.configs import get_arch
from repro.models.transformer import Model

mesh = make_mesh((4, 2), ("data", "model"))
cfg = get_arch("llama3.2-1b").reduced()
m = Model(cfg)
shapes = jax.eval_shape(lambda: m.init_params(jax.random.PRNGKey(0)))
sh = params_shardings(shapes, mesh)
import jax.tree_util as jtu
flat = jtu.tree_flatten_with_path(sh)[0]
specs = {jtu.keystr(p): s.spec for p, s in flat}
# scanned leaves carry a leading (unsharded) layer dim;
# column-parallel wq: out dim on model, in dim on data (FSDP)
wq = [v for k, v in specs.items() if "wq" in k and "'w'" in k][0]
assert wq == P(None, "data", "model"), wq
wo = [v for k, v in specs.items() if "'wo'" in k and "'w'" in k][0]
assert wo == P(None, "model", "data"), wo
emb = [v for k, v in specs.items() if "table" in k][0]
assert emb == P(None, "model"), emb
print("SPECS-OK")
"""
    assert "SPECS-OK" in run_sub(code, devices=8)


# ----------------------------------------------------- multi-device compile
@pytest.mark.slow
def test_train_step_compiles_and_runs_on_4x2_mesh():
    """The real train_step (FSDP+TP shardings) compiles AND executes on 8
    host devices; loss finite; params stay sharded."""
    code = """
import jax, jax.numpy as jnp
from repro.configs import get_arch
from repro.distributed import sharding
from repro.launch.mesh import make_mesh
from repro.models.transformer import Model
from repro.training import optimizer as opt, trainer as T

mesh = make_mesh((4, 2), ("data", "model"))
cfg = get_arch("qwen2-0.5b").reduced()
model = Model(cfg)
tcfg = T.TrainConfig(grad_accum=2, opt=opt.OptimizerConfig(lr=1e-3))
state = T.init_state(model, tcfg, jax.random.PRNGKey(0))
state_shard = {
    "params": sharding.params_shardings(state["params"], mesh),
    "opt": sharding.params_shardings(state["opt"], mesh),
}
state = jax.device_put(state, state_shard)
batch = {"tokens": jnp.zeros((8, 32), jnp.int32),
         "labels": jnp.zeros((8, 32), jnp.int32)}
batch = jax.device_put(batch, sharding.batch_shardings(batch, mesh))
step = jax.jit(T.make_train_step(model, tcfg),
               in_shardings=(state_shard, sharding.batch_shardings(batch, mesh)),
               out_shardings=(state_shard, None))
state, m = step(state, batch)
assert jnp.isfinite(m["loss"])
wq = state["params"]["segments"][0]["attn"]["wq"]["w"]
assert len(wq.sharding.device_set) == 8
print("TRAIN-8DEV-OK", float(m["loss"]))
"""
    assert "TRAIN-8DEV-OK" in run_sub(code, devices=8)


@pytest.mark.slow
def test_decode_step_compiles_on_multi_pod_mini_mesh():
    """serve_step lowers+compiles on a (2,2,2) pod/data/model mesh -- the
    multi-pod path in miniature."""
    code = """
import jax, jax.numpy as jnp
from repro.configs import get_arch
from repro.distributed import sharding
from repro.launch.mesh import make_mesh
from repro.models.transformer import Model

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = get_arch("llama3.2-1b").reduced()
model = Model(cfg)
params = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0)))
p_shard = sharding.params_shardings(params, mesh)
caches = jax.eval_shape(lambda: model.init_cache(8, 64))
cspec = model.cache_pspecs(mesh, 8, 64)
cshard = jax.tree.map(lambda ps: jax.sharding.NamedSharding(mesh, ps), cspec,
                      is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
tok = jax.ShapeDtypeStruct((8, 1), jnp.int32)
tshard = sharding.batch_shardings({"t": tok}, mesh)["t"]
lowered = jax.jit(model.decode_step,
                  in_shardings=(p_shard, cshard, tshard, None),
                  out_shardings=(None, cshard)).lower(
    params, caches, tok, jax.ShapeDtypeStruct((), jnp.int32))
compiled = lowered.compile()
assert compiled.cost_analysis() is not None
print("DECODE-MULTIPOD-OK")
"""
    assert "DECODE-MULTIPOD-OK" in run_sub(code, devices=8)


@pytest.mark.slow
def test_pipeline_parallel_matches_sequential():
    """GPipe over a 4-stage 'pod' axis == sequential layer application."""
    code = """
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.distributed.pipeline_parallel import make_pipelined_fn
from repro.launch.mesh import make_mesh

mesh = make_mesh((4,), ("pod",))
n_stages, n_micro, mb, d = 4, 8, 2, 16
key = jax.random.PRNGKey(0)
ws = jax.random.normal(key, (n_stages, d, d)) * 0.3

def stage_fn(w, x):
    return jnp.tanh(x @ w)

x = jax.random.normal(jax.random.fold_in(key, 1), (n_micro, mb, d))
pipe = make_pipelined_fn(stage_fn, mesh, n_stages, "pod",
                         param_specs=P("pod"))
y_pipe = pipe(ws, x)
y_seq = x
for s in range(n_stages):
    y_seq = jax.vmap(lambda xb: stage_fn(ws[s], xb))(y_seq)
err = float(jnp.max(jnp.abs(y_pipe - y_seq)))
assert err < 1e-5, err
print("PIPE-OK", err)
"""
    assert "PIPE-OK" in run_sub(code, devices=8)


@pytest.mark.slow
def test_compressed_psum_multi_device():
    """int8 error-feedback all-reduce across a real 4-way axis."""
    code = """
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.training.grad_compress import compressed_psum

mesh = make_mesh((4,), ("pod",))
x = jax.random.normal(jax.random.PRNGKey(0), (4, 128))
f = jax.shard_map(lambda a: compressed_psum(a[0], "pod"), mesh=mesh,
                  in_specs=P("pod"), out_specs=P())
y = f(x)
ref = jnp.sum(x, axis=0)
rel = float(jnp.max(jnp.abs(y - ref)) / (jnp.max(jnp.abs(ref)) + 1e-9))
assert rel < 0.03, rel
print("CPSUM-OK", rel)
"""
    assert "CPSUM-OK" in run_sub(code, devices=8)
