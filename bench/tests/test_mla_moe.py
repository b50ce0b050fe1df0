"""The DeepSeek-V3 architecture module (``bench/archs/mla_moe.py``): found
through the configuration's ``bench_arch`` with no edit to the harness, its
weights checked against the program's layout at the published widths, its
counts against a hand count, and a tiny cell of it run end to end."""
from __future__ import annotations

import copy
import math

import jax
import pytest

import cells
import tiny
import weights

CONFIG = "deepseek-v3-ep32"
WORKLOAD = "deepseek-v3-ep32.doc-ingest"

# Widths as published (hidden 7168, 128 heads, q_lora 1536, kv_lora 512,
# nope 128, rope 64, v 128, dense FFN 18432, experts of 2048, vocab 129280);
# 8 of 256 experts held, top 8, so 0.25 held experts a token a layer.
MLA = 7168 * 1536 + 1536 * 128 * 192 + 7168 * 576 + 512 * 128 * 256 \
    + 128 * 128 * 7168                                  # 187,105,280
DENSE_FFN = 3 * 7168 * 18432                            # 396,361,728
EXPERT = 3 * 7168 * 2048                                # 44,040,192
ROUTER = 7168 * 256
HEAD = 129280 * 7168
BODY = 5 * MLA + DENSE_FFN + 4 * (ROUTER + EXPERT + 0.25 * EXPERT)
NORMS = 7168 * (2 * 5 + 2) + 5 * (1536 + 512) + 4 * 256


def _config():
    return cells.load_json(cells.BENCH / "configs" / f"{CONFIG}.json")


def test_module_found_and_layout_matches_the_program():
    cell = cells.resolve(WORKLOAD)
    cfg = cell["config"]
    assert cfg == _config()
    arch = cells.arch_module(cfg)
    assert arch.__name__ == "archs.mla_moe"
    from repro.models.transformer import Model
    dm = arch.dims(cfg)
    model = Model(arch.program_config(cfg))
    weights.check_layout(arch, jax.eval_shape(model.init_params,
                                              jax.random.PRNGKey(0)), dm)
    assert model.cfg.moe.n_routed == 256 and model.cfg.moe.held() == 8
    n = sum(math.prod(s) for s, _, _ in arch.layout(dm).values())
    assert abs(n - 4.778e9) < 0.001e9                   # 9.56 GB in bf16


def test_counts_at_pos_0_match_a_hand_count():
    cfg = _config()
    arch = cells.arch_module(cfg)
    dm = arch.dims(cfg)
    assert MLA == 187_105_280
    assert arch.decode_bytes(dm, 0) == pytest.approx(
        2 * (BODY + HEAD + NORMS + 5 * 576), rel=1e-12)
    assert arch.decode_flops(dm, 0) == pytest.approx(
        2 * BODY + 2 * 5 * 128 * (2 * 512 + 64) + 2 * HEAD, rel=1e-12)
    pairs = 4096 * 4097 // 2
    assert arch.prefill_flops(dm, 4096) == pytest.approx(
        2 * BODY * 4096 + 2 * 5 * 128 * (192 + 128) * pairs + 2 * HEAD,
        rel=1e-12)
    assert arch.prefill_flops(dm, 4096) == pytest.approx(16.2e12, rel=0.01)
    assert arch.decode_bytes(dm, 4351) == pytest.approx(5.00e9, rel=0.01)
    with pytest.raises(NotImplementedError):
        arch.train_step_flops(dm, 1, 1)


def test_module_refuses_a_published_value_it_cannot_run():
    cfg = _config()
    cfg["scoring_func"] = "softmax"
    with pytest.raises(SystemExit, match="mla_moe runs only"):
        cells.arch_module(cfg).dims(cfg)


TINY = {
    "name": "tiny-deepseek", "arch": "deepseek-v3-671b", "bench_arch": "mla_moe",
    "source": "test", "reduced": [],
    "experts_held": {"first": 4, "of": 16},
    "program": {"remat": False},
}


def tiny_cell():
    cfg = dict(copy.deepcopy(_config()), **copy.deepcopy(TINY))
    cfg.update(num_hidden_layers=3, hidden_size=64, intermediate_size=128,
               kv_lora_rank=16, moe_intermediate_size=32, n_group=4,
               n_routed_experts=4, num_attention_heads=4,
               num_experts_per_tok=4, num_key_value_heads=4, q_lora_rank=32,
               qk_nope_head_dim=16, qk_rope_head_dim=8, topk_group=2,
               v_head_dim=16, vocab_size=256, torch_dtype="float32")
    c = tiny.cell(background={"kind": "ingest", "seq": 32, "outstanding": 2})
    c["config"] = cfg
    return c


def test_tiny_cell_runs_correct_with_ingestion():
    c = tiny_cell()
    line = tiny.run(c)
    assert line["correct"], line
    assert line["failed"] == 0
    assert line["metrics"]["background_tokens_per_s"]["value"] > 0
    assert line["checks"]["serve.token_gap"]["value"] < 1e-3
