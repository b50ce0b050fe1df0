"""From a TPU profiler trace to the numbers the per-layer metrics read.

``events_from_xplane`` takes the ``.xplane.pb`` that ``jax.profiler``
writes and keeps, for the first TPU device, the top-level programs (line
"XLA Modules") and the operations (line "XLA Ops", nested: a ``while``
holds its body's operations). ``reduce`` works on that plain form, so a
small recorded trace checks it (``bench/tests/test_xplane.py``).

All times here are device times in picoseconds.
"""
from __future__ import annotations

import re
from collections import defaultdict

_HASH = re.compile(r"\(\d+\)$")
_OPNAME = re.compile(r"^%([A-Za-z0-9_.\-]+?)(\.\d+)? = ")
_SHAPE = re.compile(r"(bf16|f32|f16|s32|s8)\[([0-9,]*)\]")
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'


def events_from_xplane(path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {ln.name: ln for ln in plane.lines}

        def grab(name, full):
            # Start and duration in ns as floats: exact to the ns, and far
            # cheaper than the per-event stats in picoseconds.
            return [[e.name if full or KERNEL_TARGET in e.name else e.name[:160],
                     round(e.start_ns * 1000), round(e.duration_ns * 1000)]
                    for e in (lines[name].events if name in lines else ())]
        return {"device": plane.name, "modules": grab("XLA Modules", True),
                "ops": grab("XLA Ops", False)}
    raise RuntimeError(f"no TPU device plane in {path}")


def module_name(name: str) -> str:
    """``jit_decode_step(1234)`` -> ``jit_decode_step``."""
    return _HASH.sub("", name)


def op_name(name: str) -> str:
    m = _OPNAME.match(name)
    return m.group(1) if m else name.split(" ")[0]


def kernel_shapes(name: str) -> list:
    """Shapes in a custom call's text: the result's first, then each
    operand's, as (dtype, dims)."""
    return [(t, tuple(int(x) for x in d.split(",") if x))
            for t, d in _SHAPE.findall(name.split("custom_call_target")[0])]


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _self_times(ops: list) -> list:
    """(name, self ps) of each op: its duration less that of the ops
    nested directly inside it on the same line."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    self_ps = [op[2] for op in ops]
    stack: list = []
    for i in order:
        s, d = ops[i][1], ops[i][2]
        while stack and s >= ops[stack[-1]][1] + ops[stack[-1]][2]:
            stack.pop()
        if stack:
            self_ps[stack[-1]] -= d
        stack.append(i)
    return [(ops[i][0], max(self_ps[i], 0)) for i in range(len(ops))]


def reduce(events: dict, top: int = 10) -> dict:
    """Busy time, per-program device time, kernel events attributed to the
    program that encloses them, the heaviest ops and the longest idle
    gaps. The first and last program of the trace are left out: the
    profiler's start and stop may cut them. ``span_s`` runs from the start
    of the first program kept to the end of the last, so busy time and
    span cover the same programs."""
    mods = sorted(events["modules"], key=lambda m: m[1])[1:-1]
    busy = _union([[s, s + d] for _, s, d in mods])
    programs = defaultdict(lambda: {"count": 0, "device_s": 0.0, "each_s": []})
    for name, _, dur in mods:
        p = programs[module_name(name)]
        p["count"] += 1
        p["device_s"] += dur * 1e-12
        p["each_s"].append(dur * 1e-12)

    starts = [m[1] for m in mods]

    def enclosing(t):
        import bisect
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t < mods[i][1] + mods[i][2]:
            return module_name(mods[i][0])
        return None

    kernels = []
    for name, s, d in events["ops"]:
        if KERNEL_TARGET in name:
            kernels.append({"program": enclosing(s), "op": op_name(name),
                            "shapes": kernel_shapes(name), "device_s": d * 1e-12})

    by_op = defaultdict(float)
    for (name, ps), (_, s, _) in zip(_self_times(events["ops"]), events["ops"]):
        by_op[f"{enclosing(s) or '-'}/{op_name(name)}"] += ps * 1e-12
    device_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]

    gaps = defaultdict(float)
    for (a, b) in zip(busy, busy[1:]):
        prev = enclosing(a[1] - 1) or "-"
        nxt = enclosing(b[0]) or "-"
        gaps[f"after {prev} before {nxt}"] = max(gaps[f"after {prev} before {nxt}"],
                                                 (b[0] - a[1]) * 1e-12)
    idle_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    span = (busy[-1][1] - busy[0][0]) * 1e-12 if busy else 0.0
    return {
        "busy_s": sum(e - s for s, e in busy) * 1e-12,
        "span_s": span,
        "programs": {k: dict(v) for k, v in programs.items()},
        "kernels": kernels,
        "device_ops": [[k, v] for k, v in device_ops],
        "idle_gaps": [[k, v] for k, v in idle_gaps],
    }
