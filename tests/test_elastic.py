"""Elasticity + fault tolerance at the system level: checkpoint on one
topology, resume on another; bulk (background-tier) serving admission."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.mark.slow
def test_checkpoint_resumes_on_different_mesh(tmp_path):
    """Save sharded train state on a (4,2) mesh, restore onto (2,2):
    checkpoints are topology-independent (unsharded leaves + re-shard on
    load), the elastic-rescale contract of DESIGN.md section 6."""
    script = f"""
import jax, jax.numpy as jnp
from repro.configs import get_arch
from repro.distributed import sharding
from repro.launch.mesh import make_mesh
from repro.models.transformer import Model
from repro.training import optimizer as opt, trainer as T
from repro.training.checkpoint import CheckpointManager

def build(mesh):
    cfg = get_arch("llama3.2-1b").reduced()
    model = Model(cfg)
    tcfg = T.TrainConfig(opt=opt.OptimizerConfig(lr=1e-3))
    state = T.init_state(model, tcfg, jax.random.PRNGKey(0))
    shard = {{"params": sharding.params_shardings(state["params"], mesh),
             "opt": sharding.params_shardings(state["opt"], mesh)}}
    return cfg, model, tcfg, state, shard

mesh_a = make_mesh((4, 2), ("data", "model"))
cfg, model, tcfg, state, shard_a = build(mesh_a)
state = jax.device_put(state, shard_a)
step = jax.jit(T.make_train_step(model, tcfg))
batch = {{"tokens": jnp.ones((8, 16), jnp.int32),
         "labels": jnp.ones((8, 16), jnp.int32)}}
state, _ = step(state, batch)
mgr = CheckpointManager({str(tmp_path)!r})
mgr.save(1, state)

# 'scale down': restore the same checkpoint onto a 2x2 mesh
mesh_b = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
shard_b = {{"params": sharding.params_shardings(state["params"], mesh_b),
           "opt": sharding.params_shardings(state["opt"], mesh_b)}}
stepn, restored = mgr.restore_latest(jax.tree.map(lambda x: x, state))
restored = jax.device_put(restored, shard_b)
state2, m = jax.jit(T.make_train_step(model, tcfg),
                    in_shardings=(shard_b, None),
                    out_shardings=(shard_b, None))(restored, batch)
assert jnp.isfinite(m["loss"])
wq = state2["params"]["segments"][0]["attn"]["wq"]["w"]
assert len(wq.sharding.device_set) == 4
print("ELASTIC-OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "ELASTIC-OK" in out.stdout


@pytest.mark.slow
def test_bulk_prefill_is_background_tier():
    """A bulk request's prefill runs as a background job: with a
    time-sensitive decode stream active, the bulk job is only dispatched
    in slack, and the decode stream's latency stays flat."""
    import time
    from repro.configs import get_arch
    from repro.core import Tier
    from repro.core.live import LiveKernel
    from repro.core.policies import make_policy
    from repro.models.transformer import Model
    from repro.serving.engine import InferenceEngine, Request

    cfg = get_arch("qwen2-0.5b").reduced()
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    kernel = LiveKernel(1, make_policy("ufs"))
    engine = InferenceEngine(model, params, kernel, max_batch=4, max_len=64)
    kernel.start()
    engine.start()
    rng = np.random.default_rng(0)
    interactive = engine.submit(Request(
        prompt=rng.integers(0, cfg.vocab_size, 6).astype(np.int32),
        max_new_tokens=6))
    bulk = engine.submit(Request(
        prompt=rng.integers(0, cfg.vocab_size, 32).astype(np.int32),
        max_new_tokens=2, tier="background"))
    assert interactive.done_event.wait(timeout=180)
    assert bulk.done_event.wait(timeout=180)
    engine.stop()
    kernel.stop()
    assert len(interactive.tokens) >= 6
    assert len(bulk.tokens) >= 2
    assert kernel.metrics.cpu_by_group.get("serve-bulk", 0.0) > 0.0
