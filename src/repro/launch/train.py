"""End-to-end training driver.

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --reduced \
      --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Without --reduced the config runs at its published widths; use --reduced
for a CPU smoke run (1 device). Fault tolerance: periodic async checkpoints
with atomic commit; --resume restores the latest valid checkpoint (also
after a simulated --fail-at crash).

``--scheduled`` runs the loop through the unified scheduling core
(DESIGN.md section 5): microbatch steps become a time-sensitive job on a
``LiveKernel`` slot and each checkpoint write a background-tier job on the
same slot machinery, so saves only use slack and never delay a step --
the same SchedCore/UFS objects the simulator and the serving driver use.
"""
from __future__ import annotations

import argparse
import threading
import time
from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import get_arch
from ..data.pipeline import SyntheticTokens, batches
from ..models.transformer import Model
from ..training import optimizer as opt
from ..training import trainer as T
from ..training.checkpoint import CheckpointManager
from .compile_cache import enable_compile_cache


def main(argv: Optional[list] = None) -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--fail-at", type=int, default=None,
                    help="simulate a crash after N steps (fault-tolerance demo)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--scheduled", action="store_true",
                    help="run the loop under a LiveKernel: steps are a "
                         "time-sensitive job, checkpoint saves background jobs")
    args = ap.parse_args(argv)

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = Model(cfg)
    tcfg = T.TrainConfig(
        grad_accum=args.grad_accum,
        compress_grads=args.compress_grads,
        opt=opt.OptimizerConfig(lr=args.lr, warmup_steps=min(20, args.steps // 5),
                                total_steps=args.steps))
    state = T.init_state(model, tcfg, jax.random.PRNGKey(args.seed))
    n_params = sum(x.size for x in jax.tree.leaves(state["params"]))
    print(f"arch={cfg.name} params={n_params:,} devices={jax.device_count()}")

    start_step = 0
    mgr = None
    if args.ckpt_dir:
        # Scheduled mode replaces the ad-hoc save thread with background
        # jobs, so the save itself is the unit of scheduled work.
        mgr = CheckpointManager(args.ckpt_dir, keep_n=3,
                                async_save=not args.scheduled)
        if args.resume:
            got = mgr.restore_latest(state)
            if got[0] is not None:
                start_step, state = got
                print(f"resumed from checkpoint step {start_step}")

    step_fn = jax.jit(T.make_train_step(model, tcfg))
    src = SyntheticTokens(cfg.vocab_size, seed=args.seed)

    def make_batch(step: int) -> dict:
        raw = src.batch(step, 0, args.batch, args.seq)
        batch = {k: jnp.asarray(v) for k, v in raw.items()}
        if cfg.encoder_layers:
            batch["frames"] = jnp.zeros((args.batch, cfg.encoder_len, cfg.d_model))
        if cfg.vision_tokens:
            batch["vision_embeds"] = jnp.zeros(
                (args.batch, cfg.vision_tokens, cfg.d_model))
        return batch

    t0 = time.time()
    if args.scheduled:
        state = _run_scheduled(args, state, start_step, step_fn, make_batch,
                               mgr, t0)
    else:
        for step in range(start_step, args.steps):
            state, metrics = step_fn(state, make_batch(step))
            if (step + 1) % args.log_every == 0 or step == start_step:
                loss = float(metrics["loss"])
                rate = (step + 1 - start_step) * args.batch * args.seq / (time.time() - t0)
                print(f"step {step+1:5d} loss {loss:7.4f} "
                      f"lr {float(metrics['lr']):.2e} tok/s {rate:,.0f}",
                      flush=True)
            if mgr and (step + 1) % args.ckpt_every == 0:
                mgr.save(step + 1, state)
            if args.fail_at is not None and step + 1 >= args.fail_at:
                if mgr:
                    mgr.wait()
                raise SystemExit(f"simulated failure at step {step+1} "
                                 f"(restart with --resume)")
    if mgr:
        mgr.save(args.steps, state)
        mgr.wait()
    print("done")


def _run_scheduled(args, state, start_step, step_fn, make_batch, mgr, t0):
    """Drive the training loop through the unified scheduling core.

    One LiveKernel slot, UFS policy: the step loop is a time-sensitive job
    (one chunk = one microbatch), each checkpoint save a background-tier
    job on the same slot.  Saves therefore run only in the slack between
    steps and are preempted at chunk granularity if steps are queued --
    the paper's mixed-workload story applied to the training driver itself.
    """
    from ..core import KernelReport, Tier, build_kernel
    from ..core.live import LiveJob

    kernel = build_kernel("live", policy="ufs", n_slots=1)
    train_g = kernel.create_group("train", Tier.TIME_SENSITIVE, 10_000.0)
    ckpt_g = kernel.create_group("ckpt", Tier.BACKGROUND, 1.0)
    box = {"state": state, "step": start_step, "failed": False,
           "error": None, "saves_queued": 0, "saves_done": 0}
    done = threading.Event()

    def save_chunk(step: int, snap) -> str:
        mgr.save(step, snap)
        box["saves_done"] += 1
        return "done"

    def train_chunk(budget: float) -> str:
        try:
            return train_step_chunk()
        except BaseException as e:
            # The panic path quarantines the job; main re-raises instead of
            # waiting forever for a loop that will not run again.
            box["error"] = e
            done.set()
            raise

    def train_step_chunk() -> str:
        step = box["step"]
        if step >= args.steps:
            done.set()
            return "done"
        box["state"], metrics = step_fn(box["state"], make_batch(step))
        box["step"] = step + 1
        if (step + 1) % args.log_every == 0 or step == start_step:
            loss = float(metrics["loss"])
            rate = (step + 1 - start_step) * args.batch * args.seq / (time.time() - t0)
            print(f"step {step+1:5d} loss {loss:7.4f} "
                  f"lr {float(metrics['lr']):.2e} tok/s {rate:,.0f}", flush=True)
        if mgr and (step + 1) % args.ckpt_every == 0:
            snap = box["state"]
            box["saves_queued"] += 1
            kernel.wake(LiveJob(ckpt_g,
                                lambda budget, s=step + 1, st=snap: save_chunk(s, st),
                                name=f"ckpt-{step+1}", kind="bound"))
        if args.fail_at is not None and step + 1 >= args.fail_at:
            box["failed"] = True
            done.set()
            return "done"
        return "yield"

    kernel.start()
    kernel.wake(LiveJob(train_g, train_chunk, name="train-loop", kind="bound"))
    done.wait()
    # Under UFS a 1-slot kernel gives background saves no slack while steps
    # are queued; drain queued saves (now pure slack) before stopping.
    deadline = time.monotonic() + 30.0
    while box["saves_done"] < box["saves_queued"] and time.monotonic() < deadline:
        time.sleep(0.01)
    kernel.stop()
    print(KernelReport.from_kernel(kernel).pretty())
    if box["error"] is not None:
        raise box["error"]
    if box["failed"]:
        if mgr:
            mgr.wait()
        raise SystemExit(f"simulated failure at step {box['step']} "
                         f"(restart with --resume)")
    return box["state"]


if __name__ == "__main__":
    main()
