"""Kernels: the Pallas flash-attention calls inside the admission-prefill
program, as a share of their roofline: for each call, the larger of its
causal operations over the peak rate and its bytes over the bandwidth,
from the call's own shapes, summed and set against the calls' summed
device time. Moves ``ttft_p95_ms``."""
import flops


def read(rec):
    pk = rec["peaks"]
    calls = [k for k in rec["trace"]["reduced"]["kernels"]
             if k["program"] == "jit_prefill_batch" and len(k["shapes"]) >= 4]
    if not calls:
        return None
    least = 0.0
    for k in calls:
        f, b = flops.flash_cost(k["shapes"])
        least += max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
    return 100.0 * least / sum(k["device_s"] for k in calls)
