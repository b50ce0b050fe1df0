#!/usr/bin/env python3
"""Cut a small piece out of a TPU profiler trace for the reduction's test.

    python3 bench/tests/record_small_trace.py <trace.xplane.pb> <out.json.gz> [ms]

Keeps the programs that start within ``ms`` milliseconds (default 40)
after the first admission prefill on the device's "XLA Modules" line,
with every operation inside them, in the plain form
``xplane.events_from_xplane`` returns.
"""
from __future__ import annotations

import gzip
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import xplane  # noqa: E402


def main(argv) -> int:
    src, dst = argv[0], argv[1]
    span_ps = int(float(argv[2]) * 1e9) if len(argv) > 2 else 40 * 10**9
    ev = xplane.events_from_xplane(src)
    t0 = min(m[1] for m in ev["modules"]
             if xplane.module_name(m[0]) == "jit_prefill_batch")
    mods = [m for m in ev["modules"] if t0 <= m[1] < t0 + span_ps]
    end = max(m[1] + m[2] for m in mods)
    ops = [o for o in ev["ops"] if t0 <= o[1] < end]
    with gzip.open(dst, "wt") as f:
        json.dump({"device": ev["device"], "modules": mods, "ops": ops}, f)
    print(f"{len(mods)} programs, {len(ops)} operations -> {dst}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
