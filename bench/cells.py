"""Find a cell's files by name: ``BENCHMARK.json`` at the root names each
workload's configuration and traffic mix; the configuration lives in
``bench/configs/<config>.json``, the traffic mix in
``bench/traffic/<traffic>.json``, the correctness limits in
``bench/limits/<workload>.json``, each per-layer metric's reader in
``bench/metrics/<metric>.py``, and the architecture that the
configuration names under ``"bench_arch"`` in
``bench/archs/<bench_arch>.py``: its mapping onto the program's config,
its weight layout, its plain reference and its counts (see
``bench/archs/__init__.py``). A later cell, metric or architecture is
added by adding files and entries, never by editing this module or any
other file of the harness: a new architecture is one module under
``bench/archs/`` and a configuration file that names it.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

import archs

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


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
    config = load_json(ROOT / configs[entry["config"]]["file"])
    arch_module(config)         # a missing or unknown name fails before set-up
    limits_path = BENCH / "limits" / f"{workload}.json"
    return {
        "name": workload,
        "entry": entry,
        "chips": entry["chips"],
        "config": config,
        "traffic": load_json(BENCH / "traffic" / f"{entry['traffic']}.json"),
        "limits": load_json(limits_path),
        "end_to_end": [m for m in bench["end_to_end"]
                       if workload in m.get("workloads", [workload])],
        "per_layer": [m for m in bench["per_layer"]
                      if workload in m.get("workloads", [workload])],
        "run_seconds": bench["run_seconds"],
    }


def arch_module(config: dict):
    """The architecture module that ``config["bench_arch"]`` names."""
    return archs.load(config.get("bench_arch"))


def metric_reader(name: str):
    """``read(record) -> float | None`` of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
