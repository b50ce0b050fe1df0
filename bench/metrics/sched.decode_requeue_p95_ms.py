"""Scheduler: 95th percentile of the decode job's turnaround from a chunk
that yielded (``stop_job`` with reason "yield" in the traced window, on
the scheduler's tracer) to its next ``start_job``: the dispatch wait of a
yielded time-sensitive job. Moves ``itl_p95_ms``."""
import numpy as np

import hostspans


def read(rec):
    waits = hostspans.requeue_ms(rec)
    return float(np.percentile(waits, 95)) if waits else None
