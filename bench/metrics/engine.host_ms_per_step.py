"""Engine: mean wall time per committed decode step in the traced window
(the interactive gaps that end in it over ``EngineStats.decode_steps``'
growth) less the mean device time of the decode program. Moves
``itl_p50_ms``."""


def read(rec):
    tr = rec["trace"]
    prog = tr["reduced"]["programs"].get("jit_decode_step")
    steps = tr["steps1"] - tr["steps0"]
    gaps = [b - a for r in rec["requests"]
            for a, b in zip(r["token_times"], r["token_times"][1:])
            if tr["t0"] <= b <= tr["t1"]]
    if not prog or steps <= 0 or not gaps:
        return None
    return (sum(gaps) / steps - prog["device_s"] / prog["count"]) * 1e3
