"""Model: the whole decode step's share of the chip's peak bf16 rate: the
operations of one token at each step's position over the decode
program's mean device time. Bounds ``model.decode_roofline`` from the
compute side. Moves ``itl_p50_ms``."""


def read(rec):
    tr = rec["trace"]
    prog = tr["reduced"]["programs"].get("jit_decode_step")
    pos = [r["plen"] + j for r in rec["requests"]
           for j, t in enumerate(r["token_times"][1:])
           if tr["t0"] <= t <= tr["t1"]]
    if not prog or not pos:
        return None
    work = sum(rec["arch"].decode_flops(rec["dm"], p) for p in pos) / len(pos)
    t = prog["device_s"] / prog["count"]
    return 100.0 * work / rec["peaks"]["bf16_flops_per_s"] / t
