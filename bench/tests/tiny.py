"""A cell at a size a CPU test run holds: the qwen2 layout at reduced
widths, float32, short prompts, a small training job."""
from __future__ import annotations

import copy
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

OPT = {"lr": 1e-3, "warmup_steps": 1, "total_steps": 10000, "min_lr_frac": 0.1,
       "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1, "clip_norm": 1.0}

CONFIG = {
    "name": "tiny", "arch": "qwen2-0.5b", "bench_arch": "dense_gqa",
    "source": "test", "reduced": [],
    "model": {"num_hidden_layers": 2, "hidden_size": 64,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "intermediate_size": 128, "vocab_size": 256,
              "qkv_bias": True, "tie_word_embeddings": True,
              "rms_norm_eps": 1e-6, "rope_theta": 1e6,
              "torch_dtype": "float32"},
    "program": {"remat": False},
}

TRAFFIC = {
    "arrivals": {"kind": "poisson", "rate_per_s": 4.0, "schedule_seed": 1},
    "prompt": {"median": 16, "sigma": 0.6, "min": 8, "max": 32},
    "answer": {"median": 4, "sigma": 0.5, "min": 2, "max": 8},
    "max_batch": 1, "max_len": 48,
    "check": {"requests": 6, "bulk_requests": 2},
    "trace_seconds": 1,
    "background": {"kind": "train", "batch": 2, "seq": 16, "optimizer": OPT},
}

LIMITS = {"serve.token_gap": 1e-3, "train.grad_gap": 1e-3,
          "train.change_gap": 1e-2}


def cell(**traffic_over) -> dict:
    traffic = copy.deepcopy(TRAFFIC)
    for k, v in traffic_over.items():
        if v is None:
            traffic.pop(k, None)
        else:
            traffic[k] = v
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    return {"name": "tiny", "chips": 1, "config": copy.deepcopy(CONFIG),
            "traffic": traffic, "limits": dict(LIMITS),
            "end_to_end": bench["end_to_end"], "per_layer": [],
            "run_seconds": 3}


def run(c: dict, seed: int = 123456789012, seconds: float = 3.0) -> dict:
    """One run of ``c`` past the harness's look for a chip; returns the
    result line."""
    import run as bench_run
    import serve_cell
    import time
    res = serve_cell.run(c, seed, seconds, False, time.monotonic(),
                         require_tpu=False)
    return bench_run.result(c, res, False)
