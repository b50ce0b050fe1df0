"""What the yardstick needs that no architecture decides: the chip's
peaks, the bytes of a stored type, the query-key pairs of a causal prompt
and the cost of one flash-attention call from its shapes. The counts of a
whole decode, prefill or train step are the architecture module's
(``bench/archs/<name>.py``). Operations count a multiply-add as two; no
recomputation is counted.
"""
from __future__ import annotations

import json
from pathlib import Path

BYTES = {"bfloat16": 2, "float32": 4, "bf16": 2, "f32": 4}


def peaks(device_kind: str) -> dict:
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise SystemExit(f"no peaks for device {device_kind!r} in "
                         f"bench/peaks.json; known: {sorted(table['devices'])}")
    return table["devices"][device_kind]


def attn_pairs_causal(n: int) -> int:
    """Query-key pairs of a causal prompt of n tokens."""
    return n * (n + 1) // 2


def flash_cost(shapes: list, causal: bool = True) -> tuple:
    """(flops, bytes) of one flash-attention call from its shapes: result
    (BH, Sq, hd), operands q (BH, Sq, hd), k and v (BH, Sk, hd)."""
    (_, out), (qt, q), (_, k), (_, v) = shapes[:4]
    bh, sq, hd = q
    sk = k[1]
    pairs = (attn_pairs_causal(sq) + sq * (sk - sq)) if causal else sq * sk
    flops = 4.0 * bh * hd * pairs
    nbytes = BYTES[qt] * (2 * bh * sq * hd + 2 * bh * sk * hd)
    return flops, float(nbytes)
