"""Device: the share of the traced span in which no program ran on the
chip, 1 - (union of the device's program intervals / span). The span runs
from the first whole program's start to the last one's end on the device's
clock: the programs that the profiler's start and stop may cut are left
out of both. Moves ``background_tokens_per_s`` in cells with background
work."""


def read(rec):
    red = rec["trace"]["reduced"]
    if red["span_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["span_s"])
