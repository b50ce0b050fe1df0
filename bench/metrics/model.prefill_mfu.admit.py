"""Model: the admission prefill's share of the chip's peak bf16 rate. The
operations of the admitted prompts' real tokens (pads not counted), per
request admitted in the traced window, over the admission-prefill
program's mean device time (one call admits one request when the cell
holds one cache row). Moves ``ttft_p95_ms``."""


def read(rec):
    tr = rec["trace"]
    prog = tr["reduced"]["programs"].get("jit_prefill_batch")
    admitted = [r["plen"] for r in rec["requests"]
                if r["first_token"] is not None
                and tr["t0"] <= r["first_token"] <= tr["t1"]]
    if not prog or not admitted:
        return None
    count = rec["arch"].prefill_flops
    work = sum(count(rec["dm"], n) for n in admitted) / len(admitted)
    t = prog["device_s"] / prog["count"]
    return 100.0 * work / rec["peaks"]["bf16_flops_per_s"] / t
