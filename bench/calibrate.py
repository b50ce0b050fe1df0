#!/usr/bin/env python3
"""Readings that the correctness limits are set from, on the chip, in one
process (the programs compile once).

    python3 bench/calibrate.py --workload <name> --seeds 1,2,... \\
        [--control-seeds 1,2,3] [--seconds 6]

For each seed, a short window of the cell's own traffic at its own load
(the mix file's rate), then the numbers the benchmark compares (the
program against the float32 reference). For each control seed also the
control (the reference in float8 put in the program's place) and, in
training cells, the planted fault of half the batch left out: each is put
through ``run.result`` in the program's place, which has to judge it not
correct. One JSON line per seed on standard output and in
``chiprun_out/calibrate/<workload>.jsonl``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import cells  # noqa: E402
import run as bench_run  # noqa: E402
import serve_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=6.0)
    args = ap.parse_args(argv)
    cell = cells.resolve(args.workload)
    ctrl = {int(s) for s in args.control_seeds.split(",") if s}
    out = cells.ROOT / "chiprun_out" / "calibrate"
    out.mkdir(parents=True, exist_ok=True)
    with open(out / f"{args.workload}.jsonl", "a") as f:
        for seed in (int(s) for s in args.seeds.split(",")):
            res = serve_cell.run(cell, seed, args.seconds, False,
                                 time.monotonic(), control=seed in ctrl)
            line = bench_run.result(cell, res, False)
            row = {"seed": seed, "correct": line["correct"],
                   "failed": line["failed"],
                   "checks": {k: v["value"] for k, v in line["checks"].items()},
                   "metrics": {k: v["value"] for k, v in line["metrics"].items()}}
            for what, checks in res["control"].items():
                judged = bench_run.result(cell, dict(res, checks=checks), False)
                row[what] = {"correct": judged["correct"],
                             **{k: v["value"] for k, v in checks.items()}}
            print(json.dumps(row), flush=True)
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
