#!/usr/bin/env python3
"""Cut a small piece out of a run of ``bench/host_trace.py --profile 1``
for the clock chain's test.

    python3 bench/tests/record_host_trace.py <trace.xplane.pb> \\
        <host_trace.json> <out.json.gz> [ms]

Keeps the device programs that start within ``ms`` milliseconds (default
40) after the first admission prefill (``xplane.events_from_xplane``'s
``modules``, without their operations); the two clock anchors and their
reads; the launch and completion of each program kept, paired as
``hostspans.device_lag`` pairs them over the whole trace; and the
scheduler tracer's events within 10 ms of the cut, on the tracer's clock.
"""
from __future__ import annotations

import gzip
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import hostspans  # noqa: E402
import xplane  # noqa: E402

MS = 10**9                   # ps in a millisecond


def main(argv) -> int:
    src, host_json, dst = argv[0], argv[1], argv[2]
    span_ps = int(float(argv[3]) * MS) if len(argv) > 3 else 40 * MS
    every = sorted(xplane.events_from_xplane(src)["modules"],
                   key=lambda m: m[1])
    with open(host_json) as f:
        st = json.load(f)
    lag = hostspans.device_lag(every, st["launches"], st["dones"])
    t0 = min(m[1] for m in every
             if xplane.module_name(m[0]) == "jit_prefill_batch")
    kept = [k for k, m in enumerate(every) if t0 <= m[1] < t0 + span_ps]
    mods = [every[k] for k in kept]
    end = max(m[1] + m[2] for m in mods)
    host = {name: [st[name][k + j] for k in kept]
            for name, j in zip(("launches", "dones"), lag["shifts"])}
    # Tracer clock = host-plane clock less the anchors' mean offset.
    off = sum((s + d / 2) * 1e-12 - r for (s, d), r
              in zip(st["anchors"], st["reads"])) / len(st["reads"])
    lo, hi = (t0 - 10 * MS) * 1e-12 - off, (end + 10 * MS) * 1e-12 - off
    events = [e for e in st["events"] if lo <= e["t"] <= hi]
    with gzip.open(dst, "wt") as f:
        json.dump({"modules": mods, "anchors": st["anchors"],
                   "reads": st["reads"], **host, "events": events}, f)
    print(f"{len(mods)} programs, {len(events)} tracer events -> {dst}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
