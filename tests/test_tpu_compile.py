"""The main path's Pallas kernels compile for a TPU v5e at qwen2-0.5b widths
(and DeepSeek-V3's, where its path differs).

Nothing here runs: each test lowers a kernel for one chip of a described
``v5e:2x2`` topology (the TPU compiler ships with libtpu) and checks that
the Mosaic kernel is in the compiled program. This catches what interpret
mode cannot -- block shapes off the (8, 128) tiling, rank-1 blocks, kernels
without a VJP -- at no chip time. The topology is described inside a
fixture, never at import, so only the worker that runs this file loads
libtpu.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch
from repro.kernels import ops

QWEN = get_arch("qwen2-0.5b")
BH = QWEN.n_heads                  # one sequence's query heads
HD = QWEN.hd                       # 64


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # Executables for a described chip can be written to the persistent
    # cache but never read back without one; keep it off for these tests.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("seq", [8, 512])
def test_flash_forward_compiles_at_prefill_bucket(one_chip, seq):
    x = _spec(one_chip, (BH, seq, HD))
    text = _compiled_text(
        lambda q, k, v: ops.flash_attention(q, k, v, backend="pallas"),
        x, x, x)
    assert "tpu_custom_call" in text


def test_flash_forward_compiles_at_mla_widths(one_chip):
    """DeepSeek-V3's prefill attention: q and k 192 wide (nope + rope), v
    128, over a 4096-token bulk chunk, for a few of its 128 heads."""
    m = get_arch("deepseek-v3-671b").mla
    d, dv = m.qk_nope_head_dim + m.qk_rope_head_dim, m.v_head_dim
    qk = _spec(one_chip, (4, 4096, d))
    text = _compiled_text(
        lambda q, k, v: ops.flash_attention(q, k, v, backend="pallas"),
        qk, qk, _spec(one_chip, (4, 4096, dv)))
    assert (d, dv) == (192, 128) and "tpu_custom_call" in text


def test_flash_gradient_compiles(one_chip):
    """Training differentiates the Pallas forward through its custom VJP."""
    x = _spec(one_chip, (BH, 512, HD))

    def loss(q, k, v):
        o = ops.flash_attention(q, k, v, backend="pallas")
        return jnp.sum(o.astype(jnp.float32) ** 2)

    text = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                          x, x, x)
    assert "tpu_custom_call" in text


def test_decode_attention_compiles_at_2048(one_chip):
    kv = _spec(one_chip, (BH, 2048, HD))
    text = _compiled_text(
        lambda q, k, v, n: ops.decode_attention(q, k, v, n, backend="pallas"),
        _spec(one_chip, (BH, 1, HD)), kv, kv,
        _spec(one_chip, (BH,), jnp.int32))
    assert "tpu_custom_call" in text


def test_mlstm_scan_compiles(one_chip):
    x = _spec(one_chip, (BH, 512, HD))
    gate = _spec(one_chip, (BH, 512), jnp.float32)
    text = _compiled_text(
        lambda q, k, v, f, i: ops.mlstm_scan(q, k, v, f, i, backend="pallas"),
        x, x, x, gate, gate)
    assert "tpu_custom_call" in text


def test_moe_topk_compiles(one_chip):
    """Router over one 512-token prefill bucket at qwen2-moe-a2.7b's
    routing width (the qwen2 family's MoE member): 60 experts padded to
    64, top-4."""
    moe = get_arch("qwen2-moe-a2.7b").moe
    logits = _spec(one_chip, (512, moe.routed_total()), jnp.float32)
    text = _compiled_text(
        lambda l: ops.moe_topk(l, moe.top_k, n_valid=moe.n_routed,
                               backend="pallas"), logits)
    assert "tpu_custom_call" in text


def test_held_expert_layer_compiles_without_copying_the_stack(one_chip):
    """DeepSeek-V3's held-expert layer at its published widths, one decode
    token, as the layer scan calls it: the whole (layers, 8, 7168, 2048)
    expert stack and the layer's index. The grouped products read the
    stack in place; a copy of one layer's (8, 7168, 2048) slice would read
    and write every held expert each step."""
    import dataclasses
    from repro.models import moe as M
    cfg = get_arch("deepseek-v3-671b")
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, n_held=8))
    d, f, e = cfg.d_model, cfg.moe.expert_ff, cfg.moe.n_routed
    p = {"router": {"w": _spec(one_chip, (d, e)), "bias": _spec(one_chip, (e,))},
         "experts": {"gate": _spec(one_chip, (4, 8, d, f)),
                     "up": _spec(one_chip, (4, 8, d, f)),
                     "down": _spec(one_chip, (4, 8, f, d))},
         "shared": {k: {"w": _spec(one_chip, s)} for k, s in
                    (("gate", (d, f)), ("up", (d, f)), ("down", (f, d)))},
         "layer": _spec(one_chip, (), jnp.int32)}
    text = _compiled_text(lambda p, x: M.moe_forward(cfg, p, x)[0],
                          p, _spec(one_chip, (1, 1, d)))
    assert "tpu_custom_call" in text
    assert f"bf16[8,{d},{f}]" not in text and f"bf16[8,{f},{d}]" not in text
