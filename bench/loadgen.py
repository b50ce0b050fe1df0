"""Traffic from a mix's parameters and the run's seed.

A mix file (``bench/traffic/<mix>.json``) fixes the arrival times and the
multiset of request sizes through its own ``schedule_seed``: every run of
the mix sees the same Poisson arrival path and the same sizes. The run's
``--seed`` deals the sizes out to the arrivals in its own order and draws
every token, so seeds change the work's order and content, not its
amount. Streams: 1 sizes order, 2 prompt tokens, 3 background rows.
"""
from __future__ import annotations

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**64 - 1), stream])


def lognormal_sizes(rng, spec: dict, n: int) -> np.ndarray:
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)


def arrivals(traffic: dict, seconds: float) -> np.ndarray:
    """Due times (seconds after the window opens) of the interactive
    requests in a window of ``seconds``: one fixed Poisson path."""
    a = traffic["arrivals"]
    rng = np.random.default_rng(a["schedule_seed"])
    rate = a["rate_per_s"]
    gaps = rng.exponential(1.0 / rate, int(rate * seconds * 2 + 50))
    t = np.cumsum(gaps) - gaps[0]                  # the first is due at 0
    return t[t < seconds]


def interactive(traffic: dict, seed: int, seconds: float, vocab: int) -> list:
    """[{"due": s, "prompt": int32 array, "answer": n}] for the window."""
    due = arrivals(traffic, seconds)
    n = len(due)
    sizes = np.random.default_rng(traffic["arrivals"]["schedule_seed"] + 1)
    plen = lognormal_sizes(sizes, traffic["prompt"], n)
    alen = lognormal_sizes(sizes, traffic["answer"], n)
    order = _rng(seed, 1).permutation(n)
    toks = _rng(seed, 2)
    return [{"due": float(due[i]),
             "prompt": toks.integers(0, vocab, int(plen[j]), dtype=np.int32),
             "answer": int(alen[j])}
            for i, j in enumerate(order)]


def warm_prompts(traffic: dict, seed: int, vocab: int) -> list:
    """One prompt for each power-of-two prefill bucket that the mix's
    prompt range reaches (the engine's smallest bucket is 8)."""
    lo, hi = traffic["prompt"]["min"], traffic["prompt"]["max"]
    rng = _rng(seed, 4)
    out, b = [], max(8, 1 << (lo - 1).bit_length())
    while True:
        n = min(b, hi)
        out.append(rng.integers(0, vocab, n, dtype=np.int32))
        if b >= hi:
            return out
        b *= 2


class Rows:
    """Background rows: training batches or bulk documents, all distinct,
    drawn in order from the run's seed."""

    def __init__(self, seed: int, vocab: int):
        self.rng = _rng(seed, 3)
        self.vocab = vocab

    def take(self, *shape) -> np.ndarray:
        return self.rng.integers(0, self.vocab, shape, dtype=np.int32)
