"""Scheduler: 95th percentile of the serve group's wake -> first dispatch
delay (``Metrics.wakeup_latency["serve"]``) over the window. It includes
waiting behind a background chunk in flight. Moves ``ttft_p95_ms``."""
import numpy as np


def read(rec):
    w = rec["serve_wakeups_s"]
    return float(np.percentile(w, 95)) * 1e3 if w else None
