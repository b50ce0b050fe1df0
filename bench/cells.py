"""Find a cell's files by name: ``BENCHMARK.json`` at the root names each
workload's configuration and traffic mix; the configuration lives in
``bench/configs/<config>.json``, the traffic mix in
``bench/traffic/<traffic>.json``, the correctness limits in
``bench/limits/<workload>.json`` and each per-layer metric's reader in
``bench/metrics/<metric>.py``. A later cell or metric is added by adding
files and entries, never by editing this module.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

# Published config.json key -> the program's ArchConfig field.
ARCH_FIELDS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "qkv_bias": "qkv_bias",
    "tie_word_embeddings": "tie_embeddings",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "torch_dtype": "dtype",
}


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def resolve(workload: str) -> dict:
    """The cell named ``workload``: its entry, configuration, traffic mix
    and limits, each loaded from its own file."""
    bench = benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    entry = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[entry["config"]]
    limits_path = BENCH / "limits" / f"{workload}.json"
    return {
        "name": workload,
        "entry": entry,
        "chips": entry["chips"],
        "config": load_json(ROOT / cfg_entry["file"]),
        "traffic": load_json(BENCH / "traffic" / f"{entry['traffic']}.json"),
        "limits": load_json(limits_path),
        "end_to_end": [m for m in bench["end_to_end"]
                       if workload in m.get("workloads", [workload])],
        "per_layer": [m for m in bench["per_layer"]
                      if workload in m.get("workloads", [workload])],
        "run_seconds": bench["run_seconds"],
    }


def arch_config(config: dict):
    """The program's ArchConfig for ``config["arch"]``, with every model
    key of the configuration file set as the file states it: the file is
    what runs."""
    from repro.configs import get_arch
    base = get_arch(config["arch"])
    over = {ARCH_FIELDS[k]: v for k, v in config["model"].items()}
    over.update(config.get("program", {}))
    return dataclasses.replace(base, **over)


def metric_reader(name: str):
    """``read(record) -> float | None`` of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
