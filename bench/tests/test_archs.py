"""Architecture modules: the dense module gives the same counts and the
same weights as the harness gave before it was split out, and an
architecture that only a new file provides is found, made and checked
against the program's layout with no edit to the harness."""
from __future__ import annotations

import copy
import hashlib
import json
import textwrap

import jax
import numpy as np
import pytest

import archs
import cells
import tiny
import weights


def _config(name: str) -> dict:
    return json.loads((cells.BENCH / "configs" / f"{name}.json").read_text())


# The counts and the weights' checksums as the harness gave them before the
# architecture modules (``flops.py`` and ``weights.make_flat`` as they
# were, on the CPU). qwen2-0.5b has QKV biases and a tied head;
# granite-8b-pp2 neither.
GOLDEN_COUNTS = [
    ("qwen2-0.5b", "decode_bytes", (0,), 988022528.0),
    ("qwen2-0.5b", "decode_bytes", (2175,), 1014748928.0),
    ("qwen2-0.5b", "decode_flops", (0,), 988008448.0),
    ("qwen2-0.5b", "decode_flops", (2175,), 1175093248.0),
    ("qwen2-0.5b", "prefill_flops", (512,), 377982976000.0),
    ("qwen2-0.5b", "train_step_flops", (8, 1024), 25362570412032.0),
    ("granite-8b-pp2", "decode_bytes", (0,), 8254767104.0),
    ("granite-8b-pp2", "decode_bytes", (2175,), 8415125504.0),
    ("granite-8b-pp2", "decode_flops", (2175,), 8896118784.0),
    ("granite-8b-pp2", "prefill_flops", (512,), 4059222245376.0),
    ("granite-8b-pp2", "train_step_flops", (8, 1024), 206574370947072.0),
]
TINY_SEED = 123456789012
GOLDEN_WEIGHTS = [
    ({}, "543608fa7e86cac2bd03987d2bb4a96d1025baff3d18e3b4568a7130174a24f6"),
    ({"qkv_bias": False, "tie_word_embeddings": False,
      "torch_dtype": "bfloat16"},
     "4a98b57eefaff8083edae4c73ecd0bf486d35d8885a0efd7a0f21ece6a4e643b"),
]


@pytest.mark.parametrize("config,count,args,want", GOLDEN_COUNTS)
def test_dense_counts_equal_the_parents(config, count, args, want):
    cfg = _config(config)
    arch = cells.arch_module(cfg)
    assert arch.__name__ == "archs.dense_gqa"
    assert getattr(arch, count)(arch.dims(cfg), *args) == want


@pytest.mark.parametrize("model,sha256", GOLDEN_WEIGHTS)
def test_dense_weights_equal_the_parents(model, sha256):
    cfg = copy.deepcopy(tiny.CONFIG)
    cfg["model"].update(model)
    arch = cells.arch_module(cfg)
    flat = weights.make_flat(arch, arch.dims(cfg), TINY_SEED)
    h = hashlib.sha256()
    for k in sorted(flat):
        h.update(k.encode())
        h.update(np.asarray(flat[k]).tobytes())
    assert h.hexdigest() == sha256


@pytest.mark.parametrize("config", [{"bench_arch": "no_such_arch"}, {}])
def test_unknown_module_names_the_known(config):
    with pytest.raises(SystemExit, match="known: .*'dense_gqa'"):
        cells.arch_module(config)


def test_dense_module_refuses_a_key_it_does_not_map():
    cfg = copy.deepcopy(tiny.CONFIG)
    cfg["model"]["q_lora_rank"] = 16
    with pytest.raises(SystemExit, match="q_lora_rank"):
        cells.arch_module(cfg).program_config(cfg)


# A decoder whose first layers are dense and the rest experts: the
# program keeps them as two segments, named here "dense" and "moe".
LEAD_MOE = '''
    """Leading dense layers, then expert layers (a test's own module)."""
    import dataclasses

    from . import flatten_segments, nest_segments

    SEGMENTS = ("dense", "moe")


    def program_config(config):
        from repro.configs import get_arch
        from repro.configs.base import MoEConfig
        m = config["model"]
        return dataclasses.replace(
            get_arch(config["arch"]), n_layers=m["num_hidden_layers"],
            d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
            n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
            d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
            qkv_bias=False, tie_embeddings=False, dtype=m["torch_dtype"],
            first_k_dense=m["first_k_dense_replace"],
            moe=MoEConfig(n_routed=m["n_routed_experts"],
                          n_shared=m["n_shared_experts"],
                          top_k=m["num_experts_per_tok"],
                          expert_ff=m["moe_intermediate_size"]))


    def dims(config):
        m = config["model"]
        return {"L": m["num_hidden_layers"], "K": m["first_k_dense_replace"],
                "D": m["hidden_size"], "H": m["num_attention_heads"],
                "KH": m["num_key_value_heads"], "hd": m["head_dim"],
                "F": m["intermediate_size"], "E": m["n_routed_experts"],
                "S": m["n_shared_experts"], "f": m["moe_intermediate_size"],
                "V": m["vocab_size"], "dtype": m["torch_dtype"]}


    def layout(dm):
        D, H, KH, hd, E, f = (dm[k] for k in ("D", "H", "KH", "hd", "E", "f"))
        s = {"embed.table": ((dm["V"], D), 0.02, 0.0),
             "final_norm.g": ((D,), 0.1, 1.0),
             "lm_head.w": ((D, dm["V"]), D ** -0.5, 0.0)}
        for seg, n in (("dense", dm["K"]), ("moe", dm["L"] - dm["K"])):
            s.update({f"{seg}.norm1.g": ((n, D), 0.1, 1.0),
                      f"{seg}.norm2.g": ((n, D), 0.1, 1.0),
                      f"{seg}.attn.wq.w": ((n, D, H * hd), D ** -0.5, 0.0),
                      f"{seg}.attn.wk.w": ((n, D, KH * hd), D ** -0.5, 0.0),
                      f"{seg}.attn.wv.w": ((n, D, KH * hd), D ** -0.5, 0.0),
                      f"{seg}.attn.wo.w": ((n, H * hd, D), (H * hd) ** -0.5,
                                           0.0)})
        n = dm["K"]
        for w, shape in (("gate", (D, dm["F"])), ("up", (D, dm["F"])),
                         ("down", (dm["F"], D))):
            s[f"dense.ffn.{w}.w"] = ((n,) + shape, shape[0] ** -0.5, 0.0)
        n = dm["L"] - dm["K"]
        s["moe.ffn.router.w"] = ((n, D, E), 0.02, 0.0)
        for w, shape in (("gate", (D, f)), ("up", (D, f)), ("down", (f, D))):
            s[f"moe.ffn.experts.{w}"] = ((n, E) + shape, shape[0] ** -0.5, 0.0)
            wide = tuple(dm["S"] * x if x == f else x for x in shape)
            s[f"moe.ffn.shared.{w}.w"] = ((n,) + wide, wide[0] ** -0.5, 0.0)
        return s


    def nest(flat):
        return nest_segments(flat, SEGMENTS)


    def flatten(tree):
        return flatten_segments(tree, SEGMENTS)
'''

LEAD_MOE_CONFIG = {
    "name": "lead-moe", "arch": "qwen2-moe-a2.7b", "bench_arch": "lead_moe",
    "source": "test", "reduced": [],
    "model": {"num_hidden_layers": 3, "first_k_dense_replace": 1,
              "hidden_size": 64, "num_attention_heads": 4,
              "num_key_value_heads": 2, "head_dim": 16,
              "intermediate_size": 96, "n_routed_experts": 8,
              "n_shared_experts": 1, "num_experts_per_tok": 2,
              "moe_intermediate_size": 32, "vocab_size": 256,
              "torch_dtype": "float32"},
}


@pytest.fixture
def bench_with_lead_moe(tmp_path, monkeypatch):
    """A benchmark tree with one cell whose configuration names a module
    that only this test provides, on the modules' search path."""
    mods = tmp_path / "more_archs"
    mods.mkdir()
    (mods / "lead_moe.py").write_text(textwrap.dedent(LEAD_MOE))
    monkeypatch.setattr(archs, "__path__", list(archs.__path__) + [str(mods)])
    root = tmp_path / "checkout"
    for d in ("configs", "traffic", "limits"):
        (root / "bench" / d).mkdir(parents=True)
    files = {
        "BENCHMARK.json": {
            "run_seconds": 3, "end_to_end": [], "per_layer": [],
            "configs": [{"name": "lead-moe",
                         "file": "bench/configs/lead-moe.json"}],
            "workloads": [{"name": "lead-moe.mix", "config": "lead-moe",
                           "traffic": "mix", "chips": 1}]},
        "bench/configs/lead-moe.json": LEAD_MOE_CONFIG,
        "bench/traffic/mix.json": tiny.TRAFFIC,
        "bench/limits/lead-moe.mix.json": tiny.LIMITS,
    }
    for path, data in files.items():
        (root / path).write_text(json.dumps(data))
    monkeypatch.setattr(cells, "ROOT", root)
    monkeypatch.setattr(cells, "BENCH", root / "bench")
    yield "lead-moe.mix"


def test_new_module_is_resolved_made_and_checked(bench_with_lead_moe,
                                                 monkeypatch):
    from repro.models.transformer import Model
    assert "lead_moe" in archs.known()
    cell = cells.resolve(bench_with_lead_moe)
    arch = cells.arch_module(cell["config"])
    dm = arch.dims(cell["config"])
    tree = weights.make_params(arch, dm, TINY_SEED)
    assert [sorted(s) for s in tree["segments"]] == [
        ["attn", "ffn", "norm1", "norm2"]] * 2
    assert "experts" in tree["segments"][1]["ffn"]
    model = Model(arch.program_config(cell["config"]))
    program = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    weights.check_layout(arch, program, dm)
    # Naming one segment where the program has two is caught.
    monkeypatch.setattr(arch, "SEGMENTS", ("dense",))
    with pytest.raises(SystemExit, match="parameter layout differs"):
        weights.check_layout(arch, program, dm)
