"""Model: the decode step's share of its memory roofline. The bytes it
must read (every weight it multiplies by, and the row's live keys and
values at each step's position) over the chip's HBM bandwidth, against
the decode program's mean device time in the trace. Moves
``itl_p50_ms``."""


def read(rec):
    tr = rec["trace"]
    prog = tr["reduced"]["programs"].get("jit_decode_step")
    pos = [r["plen"] + j for r in rec["requests"]
           for j, t in enumerate(r["token_times"][1:])
           if tr["t0"] <= t <= tr["t1"]]
    if not prog or not pos:
        return None
    need = sum(rec["arch"].decode_bytes(rec["dm"], p) for p in pos) / len(pos)
    t = prog["device_s"] / prog["count"]
    return 100.0 * need / rec["peaks"]["hbm_bytes_per_s"] / t
