"""Mixed-workload serving driver: UFS schedules a live inference engine
(time-sensitive) against a bulk prefill and background training on the
same device slots.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \
      --requests 8 --policy ufs [--background-train] [--reduced]

Without ``--reduced`` the model runs at its published widths. This is the
paper's deployment story end-to-end on real JAX work: decode steps are
CPU-bursty time-sensitive jobs; the bulk request's prefill and training
microbatches are the background; application hints guard the cache-slot
allocator. The process exits non-zero when a request does not complete or
a scheduled job panics.
"""
from __future__ import annotations

import argparse
import sys
import threading
import time
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import get_arch
from ..core import (KernelReport, Tier, build_kernel, percentile,
                    write_chrome_trace)
from ..core.live import LiveJob
from ..models.transformer import Model
from ..serving.engine import InferenceEngine, Request
from ..training import optimizer as opt
from ..training import trainer as T
from .compile_cache import enable_compile_cache

SEED = 0                 # weights, prompts and training batches
PROMPT_LEN = 8           # interactive prompts: the smallest prefill bucket
BULK_PROMPT_LEN = 32     # the one background-tier (bulk ingestion) request
WAIT_S = 600.0           # for all requests, compilation included


def parse_args(argv: Optional[list] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU smoke scale)")
    ap.add_argument("--policy", default="ufs")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=12)
    ap.add_argument("--background-train", action="store_true")
    ap.add_argument("--slots", type=int, default=1)
    ap.add_argument("--kick-latency", type=float, default=0.0,
                    help="seconds before a kick takes effect (chunk-boundary "
                         "model; supported by both executor backends)")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome trace JSON of the run (open at "
                         "https://ui.perfetto.dev)")
    ap.add_argument("--report-out", default=None,
                    help="write the KernelReport JSON to this path")
    return ap.parse_args(argv)


@dataclass
class ServeResult:
    requests: int                 # interactive requests submitted
    completed: int                # ... that finished without error
    bulk_ok: bool                 # the bulk request finished without error
    tokens: int                   # tokens generated over all requests
    ttft_s: list                  # first-token latency per finished request
    latency_s: list               # end-to-end latency per finished request
    panics: int
    quarantines: int
    train_steps: int
    train_loss: Optional[float]   # loss of the last background step
    report: KernelReport

    @property
    def ok(self) -> bool:
        return (self.completed == self.requests and self.bulk_ok
                and self.panics == 0 and self.quarantines == 0)


class _BackgroundTrainer:
    """Training microbatches as one background-tier job: a chunk is one
    train step on a seeded random batch."""

    def __init__(self, kernel, model, cfg):
        tcfg = T.TrainConfig(opt=opt.OptimizerConfig(lr=1e-3))
        self.state = T.init_state(model, tcfg, jax.random.PRNGKey(SEED + 1))
        # The state is replaced every step: donating it lets the step
        # update it in place instead of holding two copies on the device.
        self.step = jax.jit(T.make_train_step(model, tcfg), donate_argnums=0)
        self.rng = np.random.default_rng(SEED + 1)
        self.vocab = cfg.vocab_size
        self.steps = 0
        self.loss: Optional[float] = None
        self._stop = False
        self._busy = threading.Lock()
        group = kernel.create_group("train", Tier.BACKGROUND, 1.0)
        kernel.wake(LiveJob(group, self._chunk, name="bg-train", kind="bound"))

    def _chunk(self, budget: float) -> str:
        with self._busy:
            if self._stop:
                return "done"
            toks = jnp.asarray(self.rng.integers(0, self.vocab, (2, 32),
                                                 dtype=np.int32))
            self.state, m = self.step(self.state,
                                      {"tokens": toks, "labels": toks})
            self.loss = float(m["loss"])          # waits for the step
            self.steps += 1
            return "yield"

    def stop(self, timeout: float = 600.0) -> None:
        """Let the step in flight finish; no further step starts."""
        self._stop = True
        if self._busy.acquire(timeout=timeout):
            self._busy.release()


def _wait(reqs: list, kernel) -> None:
    """Until every request is done, ``WAIT_S`` passes, or a job panics (the
    run has then failed, so waiting longer shows nothing)."""
    deadline = time.monotonic() + WAIT_S
    while not all(r.done_event.is_set() for r in reqs):
        if kernel.metrics.panics or time.monotonic() > deadline:
            return
        time.sleep(0.05)


def run(args: argparse.Namespace) -> ServeResult:
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(SEED))

    kernel = build_kernel("live", policy=args.policy, n_slots=args.slots,
                          kick_latency=args.kick_latency,
                          trace=args.trace_out is not None)
    engine = InferenceEngine(model, params, kernel, max_batch=4, max_len=64)
    kernel.start()
    trainer = None
    try:
        engine.start()
        if args.background_train:
            trainer = _BackgroundTrainer(kernel, model, cfg)

        rng = np.random.default_rng(SEED)
        prompt = rng.integers(0, cfg.vocab_size, size=BULK_PROMPT_LEN)
        bulk = engine.submit(Request(
            prompt=prompt.astype(np.int32),
            max_new_tokens=args.max_new_tokens, tier="background"))
        reqs = []
        for _ in range(args.requests):
            prompt = rng.integers(0, cfg.vocab_size, size=PROMPT_LEN)
            reqs.append(engine.submit(Request(
                prompt=prompt.astype(np.int32),
                max_new_tokens=args.max_new_tokens)))
            time.sleep(0.05)
        _wait(reqs + [bulk], kernel)
    finally:
        engine.stop()
        if trainer is not None:
            trainer.stop()
        kernel.stop()

    report = KernelReport.from_kernel(kernel)
    if args.report_out:
        report.write(args.report_out)
    if args.trace_out:
        write_chrome_trace(kernel.tracer.events, args.trace_out, end=kernel.now)
    done = [r for r in reqs + [bulk] if r.ok]
    return ServeResult(
        requests=len(reqs), completed=sum(r.ok for r in reqs),
        bulk_ok=bulk.ok, tokens=sum(len(r.tokens) for r in reqs + [bulk]),
        ttft_s=[r.first_token - r.submitted for r in done],
        latency_s=[r.latency for r in done],
        panics=len(kernel.metrics.panics),
        quarantines=kernel.metrics.quarantines,
        train_steps=trainer.steps if trainer else 0,
        train_loss=trainer.loss if trainer else None,
        report=report)


def _print_result(res: ServeResult, args: argparse.Namespace) -> None:
    print(f"completed {res.completed}/{res.requests} requests, bulk "
          f"{'completed' if res.bulk_ok else 'failed'}; "
          f"{res.tokens} tokens; panics {res.panics} "
          f"quarantines {res.quarantines}")
    if res.latency_s:
        print(f"latency mean {1e3*sum(res.latency_s)/len(res.latency_s):.1f} ms"
              f"  p95 {1e3*percentile(res.latency_s, 95):.1f} ms;  "
              f"first token p50 {1e3*percentile(res.ttft_s, 50):.1f} ms")
    if args.background_train:
        print(f"background train steps: {res.train_steps} "
              f"last loss {res.train_loss}")
    print(res.report.pretty())
    if args.report_out:
        print(f"report written to {args.report_out}")
    if args.trace_out:
        print(f"trace written to {args.trace_out} "
              f"(open at https://ui.perfetto.dev)")


def main(argv: Optional[list] = None) -> int:
    enable_compile_cache()
    args = parse_args(argv)
    res = run(args)
    _print_result(res, args)
    if not res.ok:
        print("FAILED: a request did not complete or a job panicked",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
