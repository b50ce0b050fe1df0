"""Operations and bytes the algorithms need, from shapes alone (the
yardstick of the roofline and utilization metrics). ``dm`` is
``weights.dims(config)``. Operations count a multiply-add as two; no
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


def layer_matmul_params(dm: dict) -> int:
    D, H, KH, hd, F = dm["D"], dm["H"], dm["KH"], dm["hd"], dm["F"]
    return D * H * hd + 2 * D * KH * hd + H * hd * D + 3 * D * F


def body_params(dm: dict) -> int:
    return dm["L"] * layer_matmul_params(dm)


def attn_pairs_causal(n: int) -> int:
    """Query-key pairs of a causal prompt of n tokens."""
    return n * (n + 1) // 2


def prefill_flops(dm: dict, n: int) -> float:
    """A prompt of n real tokens, logits at its last position."""
    attn = 4 * dm["L"] * dm["H"] * dm["hd"] * attn_pairs_causal(n)
    return 2.0 * body_params(dm) * n + attn + 2.0 * dm["V"] * dm["D"]


def decode_flops(dm: dict, pos: int) -> float:
    """One token at position ``pos`` (``pos`` tokens already cached)."""
    attn = 4 * dm["L"] * dm["H"] * dm["hd"] * (pos + 1)
    return 2.0 * body_params(dm) + attn + 2.0 * dm["V"] * dm["D"]


def decode_bytes(dm: dict, pos: int) -> float:
    """Every weight the step multiplies by (an untied embedding table is
    gathered, one row) plus the row's live keys and values."""
    b = BYTES[dm["dtype"]]
    w = body_params(dm) + dm["V"] * dm["D"] + dm["D"] * (2 * dm["L"] + 1)
    kv = 2 * dm["L"] * dm["KH"] * dm["hd"] * (pos + 1)
    return float(b * (w + kv))


def train_step_flops(dm: dict, batch: int, seq: int) -> float:
    """Forward and backward (three times the forward) of batch x seq
    tokens, with logits at every position."""
    tokens = batch * seq
    attn = 4 * dm["L"] * dm["H"] * dm["hd"] * attn_pairs_causal(seq) * batch
    fwd = 2.0 * body_params(dm) * tokens + 2.0 * dm["V"] * dm["D"] * tokens + attn
    return 3.0 * fwd


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
