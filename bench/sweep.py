#!/usr/bin/env python3
"""Find the knee of a cell's traffic once, on the chip: the highest
Poisson rate at which every request completes and time to first token
stays near what it is with the system all but unloaded.

    python3 bench/sweep.py --workload <name> --rates 1,2,3,4 \\
        [--seeds 7,8] [--seconds 30]

Runs the cell's mix at each rate and seed in one process (no reference
check) and prints, per run, the requests due and finished, the TTFT
median and 95th percentile from the due time, and the TTFT median of the
window's first and last quarter. The first rate is the probe: the median
TTFT of its runs stands for the unloaded time to first token. A rate is
sustained when, in every seed's run, every request finished and the
median of the whole window and of its last quarter both stay within 1.5
times the unloaded median plus 50 ms: a queue that builds up, at the
start or over the window, fails it. The last line names the knee: the
highest rate, going up, before the first that is not sustained.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np  # noqa: E402

import cells  # noqa: E402
import serve_cell  # noqa: E402


def ttft_row(rec: dict, seconds: float) -> dict:
    reqs, t0, q = rec["requests"], rec["t0"], seconds / 4
    ttft = [(r["first_token"] - r["due"]) * 1e3 for r in reqs if r["ok"]]
    last = [(r["first_token"] - r["due"]) * 1e3 for r in reqs
            if r["ok"] and r["due"] - t0 >= 3 * q]
    first = [(r["first_token"] - r["due"]) * 1e3 for r in reqs
             if r["ok"] and r["due"] - t0 < q]
    med = (lambda v: float(np.median(v)) if v else None)
    return {"due": len(reqs), "finished": len(ttft),
            "ttft_p50_ms": med(ttft),
            "ttft_p95_ms": float(np.percentile(ttft, 95)) if ttft else None,
            "ttft_p50_first_quarter_ms": med(first),
            "ttft_p50_last_quarter_ms": med(last),
            "background_tokens_per_s": (rec["background_tokens"] / seconds
                                        if rec["background_tokens"] is not None
                                        else None)}


def sustained(row: dict, unloaded_ms: float) -> bool:
    ceiling = 1.5 * unloaded_ms + 50.0
    return (row["finished"] == row["due"] > 0
            and row["ttft_p50_ms"] <= ceiling
            and row["ttft_p50_last_quarter_ms"] is not None
            and row["ttft_p50_last_quarter_ms"] <= ceiling)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seeds", default="7,8")
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    base = cells.resolve(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    knee, unloaded = None, None
    for rate in (float(r) for r in args.rates.split(",")):
        cell = copy.deepcopy(base)
        cell["traffic"]["arrivals"]["rate_per_s"] = rate
        rows = []
        for seed in seeds:
            rec = serve_cell.run(cell, seed, args.seconds, False,
                                 time.monotonic(), check=False)["rec"]
            rows.append(dict(rate_per_s=rate, seed=seed,
                             **ttft_row(rec, args.seconds)))
            print(json.dumps(rows[-1]), flush=True)
        if unloaded is None:
            unloaded = float(np.median([r["ttft_p50_ms"] for r in rows
                                        if r["ttft_p50_ms"] is not None]))
            print(json.dumps({"unloaded_ttft_p50_ms": unloaded}), flush=True)
        if all(sustained(r, unloaded) for r in rows):
            knee = rate
        else:
            break
    print(json.dumps({"knee_per_s": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
