"""B5 regression: the explicit shard_map GQA mixer is numerically identical
to the reference attention path (full and sliding-window), on a real
multi-device mesh (subprocess with 8 host devices)."""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.mark.slow
def test_shardmap_gqa_matches_reference():
    code = """
import dataclasses
import jax, jax.numpy as jnp
from repro.configs import get_arch
from repro.models import attention as A
from repro.distributed.shardmap_attention import make_shardmap_gqa
from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 2), ("data", "model"))
cfg = dataclasses.replace(get_arch("llama3.2-1b").reduced(), qkv_bias=False)
key = jax.random.PRNGKey(0)
p = A.gqa_init(key, cfg)
x = jax.random.normal(key, (8, 32, cfg.d_model)) * 0.5
pos = jnp.arange(32)[None, :]
fwd = make_shardmap_gqa(mesh, cfg)
for window in (0, 8):
    y_ref = A.gqa_forward(cfg, p, x, pos, window=window)
    y_sm = fwd(p, x, pos, window)
    err = float(jnp.max(jnp.abs(y_sm - y_ref)))
    assert err < 1e-4, (window, err)
print("SHARDMAP-GQA-OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SHARDMAP-GQA-OK" in out.stdout


def test_expand_kv_weight_layout():
    import jax.numpy as jnp
    import numpy as np
    from repro.distributed.shardmap_attention import expand_kv_weight
    d, kh, hd, g = 4, 2, 3, 2
    w = jnp.arange(d * kh * hd, dtype=jnp.float32).reshape(d, kh * hd)
    e = expand_kv_weight(w, kh, g)
    assert e.shape == (d, kh * g * hd)
    # head i's q-group copies both equal the original kv head i
    w3 = np.asarray(w).reshape(d, kh, hd)
    e4 = np.asarray(e).reshape(d, kh, g, hd)
    for i in range(kh):
        for j in range(g):
            assert np.array_equal(e4[:, i, j], w3[:, i])
