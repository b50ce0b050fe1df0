"""DeepSeek-V3 on one rank's share of its experts, at a reduced width on
the CPU, against the benchmark's plain float32 reference
(``bench/archs/mla_moe.py``, which imports nothing of the program).

The reduced configuration keeps group-limited routing: 16 routed experts
in 4 groups, the top 2 groups, top 4 experts, and holds 4 of them starting
at expert 4; one shared expert, one leading dense layer and two expert
layers. Weights come from the seed (``bench/weights.py``), the router's
selection bias among them.
"""
import dataclasses
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.configs.base import YaRNConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models.transformer import Model

sys.path.append(str(Path(__file__).resolve().parents[1] / "bench"))
import archs.mla_moe as ref  # noqa: E402
import weights  # noqa: E402

SEED = 20260917
SMAX = 48

CONFIG = {
    "arch": "deepseek-v3-671b", "bench_arch": "mla_moe",
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "kv_lora_rank": 16, "max_position_embeddings": 163840,
    "model_type": "deepseek_v3", "moe_intermediate_size": 32,
    "moe_layer_freq": 1, "n_group": 4, "n_routed_experts": 4,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts_per_tok": 4,
    "num_hidden_layers": 3, "num_key_value_heads": 4,
    "num_nextn_predict_layers": 1, "q_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "rms_norm_eps": 1e-6,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 2, "topk_method": "noaux_tc", "v_head_dim": 16,
    "vocab_size": 256, "torch_dtype": "float32",
    "experts_held": {"first": 4, "of": 16},
    "program": {"remat": False},
}


@pytest.fixture(scope="module")
def setup():
    cfg = ref.program_config(CONFIG)
    dm = ref.dims(CONFIG)
    model = Model(cfg)
    weights.check_layout(ref, jax.eval_shape(model.init_params,
                                             jax.random.PRNGKey(0)), dm)
    flat = weights.make_flat(ref, dm, SEED)
    return cfg, dm, model, flat


def _moe_params(flat, layer):
    return {"router": {"w": flat["moe.ffn.router.w"][layer],
                       "bias": flat["moe.ffn.router.bias"][layer]},
            "experts": {k: flat[f"moe.ffn.experts.{k}"][layer]
                        for k in ("gate", "up", "down")},
            "shared": {k: {"w": flat[f"moe.ffn.shared.{k}.w"][layer]}
                       for k in ("gate", "up", "down")}}


def test_reduced_deepseek_keeps_group_limited_routing():
    moe = get_arch("deepseek-v3-671b").reduced().moe
    assert (moe.n_routed, moe.n_expert_groups, moe.topk_group, moe.top_k,
            moe.scoring) == (16, 4, 2, 4, "sigmoid")
    qwen = get_arch("qwen2-moe-a2.7b").reduced().moe
    assert (qwen.n_routed, qwen.n_expert_groups, qwen.top_k,
            qwen.scoring) == (4, 1, 2, "softmax")


def test_prefill_then_decode_through_pooled_cache_matches_reference(setup):
    """(a) The engine's admission prefill, its row published into the
    pooled cache by ``write_slots``, then decode steps from that cache,
    against the reference's full forward over the same tokens. Both sides
    compute in float32 from the same weights; they differ in summation
    order (the absorbed decode, the grouped products), so the logits agree
    to 1e-4 of their largest magnitude, far below what a wrong equation
    (a missing YaRN scale, a dropped expert) moves them."""
    from repro.core.live import LiveKernel
    from repro.core.policies import make_policy
    from repro.serving.engine import InferenceEngine
    cfg, dm, model, flat = setup
    params = ref.nest(flat)
    engine = InferenceEngine(model, params, LiveKernel(1, make_policy("ufs")),
                             max_batch=2, max_len=SMAX)
    rng = np.random.default_rng(SEED)
    plen, steps = 13, 4
    toks = rng.integers(0, dm["V"], plen + steps).astype(np.int32)
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32).at[0, :plen].set(toks[:plen]),
             "lengths": jnp.asarray([plen, 1], jnp.int32)}
    logits, rows = engine._prefill_batch_fn(params, batch, SMAX)
    caches = engine._write_slots(engine.caches, rows, jnp.asarray([1, 2]))
    got = [logits[0, 0]]
    decode = jax.jit(model.decode_step)
    for j in range(steps):
        step = jnp.zeros((2, 1), jnp.int32).at[1, 0].set(toks[plen + j])
        lg, caches = decode(params, caches, step, plen + j)
        got.append(lg[1, 0])
    want = ref.head(flat, dm, ref.hidden(flat, dm, jnp.asarray(toks)[None])[0])
    want = want[plen - 1: plen + steps]
    got = jnp.stack(got)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * scale
    assert np.array_equal(np.argmax(got, -1), np.argmax(want, -1))


def test_expert_shares_add_up_to_the_uncut_layer(setup):
    """(b) Four ranks holding 4 experts each: their routed parts plus the
    shared expert once equal the layer that holds all 16."""
    cfg, dm, model, flat = setup
    p = _moe_params(flat, 0)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((2, 24, dm["D"])), jnp.float32)
    whole = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, n_held=16, first_held=0))
    # all 16 experts drawn; rank r holds experts 4r .. 4r + 3
    all_experts = {k: jnp.asarray(rng.standard_normal((16,) + v.shape[1:]) * 0.1,
                                  jnp.float32)
                   for k, v in p["experts"].items()}
    uncut, _ = M.moe_forward(whole, dict(p, experts=all_experts), x)
    total = jnp.zeros_like(x)
    for r in range(4):
        share = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, n_held=4, first_held=4 * r))
        pr = dict(p, experts={k: v[4 * r: 4 * r + 4]
                              for k, v in all_experts.items()})
        if r:
            pr.pop("shared")
        y, _ = M.moe_forward(share, pr, x)
        total = total + y
    assert float(jnp.max(jnp.abs(total - uncut))) < 1e-5 * float(
        jnp.max(jnp.abs(uncut)))


def _dense_route(m, logits, bias):
    w, idx = M.route_sigmoid(m, logits, bias)
    t = logits.shape[0]
    return jnp.zeros((t, m.n_routed)).at[jnp.arange(t)[:, None], idx].set(w)


def test_router_matches_reference_where_group_limit_and_bias_decide(setup):
    """(c) The program's router and the reference's choose and weigh the
    same experts, on scores where the group limit changes the choice and
    where the selection bias does."""
    cfg, dm, _, _ = setup
    m = cfg.moe
    rng = np.random.default_rng(7)
    logits = jnp.asarray(rng.standard_normal((256, 16)) * 2, jnp.float32)
    bias = jnp.asarray(rng.standard_normal(16) * 0.3, jnp.float32)
    got = _dense_route(m, logits, bias)
    want = ref.route(dm, logits, bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6,
                               atol=1e-7)
    chosen = np.asarray(got) > 0
    flat_m = dataclasses.replace(m, n_expert_groups=1)
    no_group = np.asarray(_dense_route(flat_m, logits, bias)) > 0
    no_bias = np.asarray(_dense_route(m, logits, jnp.zeros(16))) > 0
    assert (chosen != no_group).any(axis=1).mean() > 0.1
    assert (chosen != no_bias).any(axis=1).mean() > 0.1
    # Weights are the unbiased scores, renormalized and scaled by 2.5.
    np.testing.assert_allclose(np.asarray(got).sum(1), 2.5, rtol=1e-6)
    s = jax.nn.sigmoid(logits)
    ratio = np.asarray(got)[chosen] / np.asarray(s)[chosen]
    per_tok = np.asarray(got).sum(1, keepdims=True) / np.where(
        chosen, np.asarray(s), 0).sum(1, keepdims=True)
    np.testing.assert_allclose(ratio, np.broadcast_to(per_tok, chosen.shape)[chosen],
                               rtol=1e-5)


def test_yarn_frequencies_and_scale_follow_the_published_formulas():
    """(d) The published modeling code's YaRN, written out here, at
    DeepSeek-V3's widths: 64 rope dims, base 10000, factor 40 over 4096,
    beta 32 / 1, mscale and mscale_all_dim 1."""
    dim, base, factor, orig = 64, 10000.0, 40.0, 4096

    def find_correction_dim(num_rotations):
        return (dim * math.log(orig / (num_rotations * 2 * math.pi))) / (
            2 * math.log(base))

    low = max(math.floor(find_correction_dim(32)), 0)
    high = min(math.ceil(find_correction_dim(1)), dim - 1)
    assert (low, high) == (10, 23)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / (high - low),
                   0, 1)
    inv_freq_mask = 1.0 - ramp
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    freq_inter = freq_extra / factor
    want = freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask
    y = get_arch("deepseek-v3-671b").rope_scaling
    assert y == YaRNConfig(40.0, 4096, 32.0, 1.0, 1.0)
    np.testing.assert_allclose(L.yarn_inv_freq(dim, base, y), want, rtol=1e-6)
    dm = {"rope": dim, "theta": base, "yarn_original": orig,
          "beta_fast": 32.0, "beta_slow": 1.0, "yarn_factor": factor,
          "nope": 128, "mscale_all_dim": 1.0}
    np.testing.assert_allclose(ref.yarn_inv_freq(dm), want, rtol=1e-6)
    mscale = 0.1 * 1.0 * math.log(factor) + 1.0
    scale = 192 ** -0.5 * mscale * mscale
    assert A.mla_softmax_scale(get_arch("deepseek-v3-671b")) == pytest.approx(
        scale, rel=1e-12)
    assert ref.softmax_scale(dm) == pytest.approx(scale, rel=1e-12)
    # Rope on adjacent pairs is the complex product of the published code.
    rng = np.random.default_rng(3)
    x = rng.standard_normal((1, 5, 2, dim)).astype(np.float32)
    pos = np.arange(5)
    rot = np.exp(1j * pos[:, None] * want[None, :])[None, :, None, :]
    cplx = (x[..., 0::2] + 1j * x[..., 1::2]) * rot
    expect = np.stack([cplx.real, cplx.imag], -1).reshape(x.shape)
    got = L.apply_rope_pairs(jnp.asarray(x), jnp.asarray(pos)[None], want)
    np.testing.assert_allclose(np.asarray(got), expect, rtol=1e-5, atol=1e-5)


def test_one_expert_taking_every_token_drops_nothing(setup):
    """(e) A selection bias that sends every token to held expert 5: the
    layer still equals the reference's, which computes every held expert
    over every token with no capacity, and one held expert took them all."""
    cfg, dm, model, flat = setup
    p = _moe_params(flat, 1)
    p["router"]["bias"] = p["router"]["bias"].at[5].set(100.0)
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((1, 40, dm["D"])), jnp.float32)
    y, _, st = M.moe_forward(cfg, p, x, stats=True)
    assert int(st["held_max"]) == 40 and int(st["assignments"]) == 160
    lp = {"ffn.router.w": p["router"]["w"], "ffn.router.bias": p["router"]["bias"],
          **{f"ffn.experts.{k}": v for k, v in p["experts"].items()},
          **{f"ffn.shared.{k}.w": v["w"] for k, v in p["shared"].items()}}
    want = ref._moe(dm, False, x, lp)
    assert float(jnp.max(jnp.abs(y - want))) < 1e-5 * float(jnp.max(jnp.abs(want)))
    # The softmax path's capacity at 1.25 would hold 40 * 4 * 1.25 / 16 =
    # 12 of expert 5's 40 tokens.
    assert round(40 * 4 * 1.25 / 16) < int(st["held_max"])


def test_two_bulk_ingestions_of_one_length_compile_once(setup):
    """(f) The bulk prefill is one jitted program: a second bulk request
    of the same length reuses it; its routing counters add up."""
    from repro.core.live import LiveKernel
    from repro.core.policies import make_policy
    from repro.serving.engine import InferenceEngine, Request
    cfg, dm, model, flat = setup
    kernel = LiveKernel(1, make_policy("ufs"))
    engine = InferenceEngine(model, ref.nest(flat), kernel, max_batch=1,
                             max_len=SMAX)
    kernel.start()
    engine.start()
    rng = np.random.default_rng(5)
    try:
        for _ in range(2):
            r = engine.submit(Request(
                prompt=rng.integers(0, dm["V"], 32).astype(np.int32),
                max_new_tokens=1, tier="background"))
            assert r.done_event.wait(300) and r.ok
    finally:
        engine.stop()
        kernel.stop()
    assert engine._prefill_one._cache_size() == 1
    s = engine.stats.summary()
    assert s["bulk_tokens"] == 64
    assert s["moe_assignments"] == 2 * 32 * 4 * 2       # tokens x top-4 x layers
    assert 0 < s["moe_held_assignments"] <= s["moe_assignments"]
    assert 0 < s["moe_held_max"] <= 32


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)])
def test_mla_prefill_through_the_flash_kernel_matches_the_blocked_path(
        setup, dtype, tol):
    """(g) On the TPU, MLA's prefill attention runs the Pallas flash kernel
    with V narrower than q and k (here qk 24, v 16). Its body in interpret
    mode gives the blocked XLA path's output, over 1024 positions in 512
    blocks (a skipped block, an unmasked one and two that cross the
    diagonal), and the same latent cache, to the kernel tests' tolerance
    relative to the output's largest magnitude."""
    cfg, dm, model, flat = setup
    p = jax.tree_util.tree_map(lambda a: a[0].astype(dtype),
                               ref.nest(flat)["segments"][0]["attn"])
    s = 1024
    x = jax.random.normal(jax.random.PRNGKey(SEED), (1, s, dm["D"])).astype(dtype)
    positions = jnp.arange(s, dtype=jnp.int32)[None]
    (y, cache), (want, want_cache) = (
        A.mla_forward(cfg, p, x, positions, backend=b, return_cache=True)
        for b in ("interpret", "xla"))
    assert y.shape == want.shape == (1, s, dm["D"]) and y.dtype == dtype
    gap = jnp.max(jnp.abs(y.astype(jnp.float32) - want.astype(jnp.float32)))
    assert float(gap) < tol * float(jnp.max(jnp.abs(want.astype(jnp.float32))))
    for name in ("c", "kr"):
        np.testing.assert_array_equal(np.asarray(cache[name], np.float32),
                                      np.asarray(want_cache[name], np.float32))
