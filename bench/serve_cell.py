"""One run of one cell: set-up, the measured window, the drain, and the
check against the reference.

The window drives ``InferenceEngine.submit`` on a live UFS kernel with one
slot (``build_kernel("live", policy="ufs", n_slots=1)``), as
``launch/serve.run`` assembles it, with the mix's interactive requests
open-loop at their due times and the mix's background work woken on the
same kernel: a training job that always has a step to run, or bulk
ingestion requests kept outstanding.
"""
from __future__ import annotations

import gc
import glob
import shutil
import sys
import threading
import time

import numpy as np

import flops
import loadgen
import weights
import xplane
from cells import BENCH, arch_module

DRAIN_S = 60.0            # an answer due in the window may come this late
TRACE_DIR = BENCH / "_out" / "trace"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache while
    armed (the window): there should be none."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self, jax):
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event in self.EVENTS:
            self.count += 1


class Trainer:
    """The background training job: one chunk is one step of the
    program's train step on the next distinct rows."""

    def __init__(self, jax, model, params, bg: dict, rows: loadgen.Rows):
        from repro.training import optimizer as opt
        from repro.training import trainer as T
        self.jax = jax
        o = bg["optimizer"]
        tcfg = T.TrainConfig(opt=opt.OptimizerConfig(**o))
        self.state = {"params": params, "opt": opt.init_state(tcfg.opt, params)}
        self.step_fn = jax.jit(T.make_train_step(model, tcfg), donate_argnums=0)
        self.rows, self.shape = rows, (bg["batch"], bg["seq"])
        self.batches: list = []          # the first rows, for the reference
        self.losses: list = []
        self.spans: list = []            # host (start, loss landed) of each step
        self._stop = False
        self._busy = threading.Lock()

    def step(self) -> float:
        t0 = time.monotonic()
        toks = self.rows.take(*self.shape)
        if len(self.batches) < 3:
            self.batches.append(toks)
        jt = self.jax.numpy.asarray(toks)
        self.state, m = self.step_fn(self.state, {"tokens": jt, "labels": jt})
        loss = float(m["loss"])                  # waits for the step
        self.losses.append(loss)
        self.spans.append((t0, time.monotonic()))
        return loss

    def chunk(self, budget: float) -> str:
        with self._busy:
            if self._stop:
                return "done"
            self.step()
            return "yield"

    def stop(self, timeout: float = 120.0) -> None:
        self._stop = True
        if self._busy.acquire(timeout=timeout):
            self._busy.release()


def run(cell: dict, seed: int, seconds: float, trace: bool,
        t_start: float, require_tpu: bool = True, control: bool = False,
        check: bool = True) -> dict:
    """Returns the result; raises SystemExit without a fit device. With
    ``control`` (``calibrate.py``, never the benchmark's runs) it also reads
    the control and the planted faults on the same requests and rows;
    without ``check`` (``sweep.py``) it skips the reference."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    if require_tpu and (dev.platform != "tpu" or len(devs) < cell["chips"]):
        raise SystemExit(f"this cell needs {cell['chips']} TPU chip(s); JAX "
                         f"found {len(devs)} {dev.platform} device(s)")
    peaks = flops.peaks(dev.device_kind) if require_tpu else None
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    log(f"device {dev.platform} {dev.device_kind} x{len(devs)}; "
        f"compile cache {cache_dir}")

    from repro.core import Tier, build_kernel
    from repro.core.live import LiveJob
    from repro.models.transformer import Model
    from repro.serving.engine import InferenceEngine, Request
    import reference

    config, traffic = cell["config"], cell["traffic"]
    arch = arch_module(config)
    dm = arch.dims(config)
    model = Model(arch.program_config(config))
    weights.check_layout(arch, jax.eval_shape(model.init_params,
                                              jax.random.PRNGKey(0)), dm)
    bg = traffic.get("background") or {}
    counter = CompileCounter(jax)

    # ------------------------------------------------------------ set-up
    params = weights.make_params(arch, dm, seed)
    kernel = build_kernel("live", policy="ufs", n_slots=1)
    engine = InferenceEngine(model, params, kernel,
                             max_batch=traffic["max_batch"],
                             max_len=traffic["max_len"])
    kernel.start()
    engine.start()
    trainer = None
    rows = loadgen.Rows(seed, dm["V"])
    prog = {}
    try:
        for p in loadgen.warm_prompts(traffic, seed, dm["V"]):
            r = engine.submit(Request(prompt=p, max_new_tokens=2))
            if not r.done_event.wait(900) or not r.ok:
                raise RuntimeError(f"warm-up request failed: {r.error}")
        if bg.get("kind") == "ingest":
            r = engine.submit(Request(prompt=rows.take(bg["seq"]),
                                      max_new_tokens=1, tier="background"))
            if not r.done_event.wait(900) or not r.ok:
                raise RuntimeError(f"warm-up bulk request failed: {r.error}")
        if bg.get("kind") == "train":
            # The job the window runs, driven here through its first
            # steps by the same call and feed; the reference follows them.
            trainer = Trainer(jax, model, weights.make_params(arch, dm, seed),
                              bg, rows)
            trainer.step()
            # Adam's first moment after one step is (1 - b1) times the
            # gradient the optimizer got.
            b1 = bg["optimizer"]["b1"]
            prog["grad"] = {k: float(v) / (1 - b1) for k, v in reference.leaf_norms(
                arch.flatten(trainer.state["opt"]["m"])).items()}
            for _ in range(2):
                trainer.step()
            prog["loss"] = list(trainer.losses)
            prog["change"] = {k: float(v) for k, v in reference.change_norms(
                arch.flatten(trainer.state["params"]),
                arch.flatten(params)).items()}
            group = kernel.create_group("train", Tier.BACKGROUND, 1.0)
            kernel.wake(LiveJob(group, trainer.chunk, name="bg-train",
                                kind="bound"))
        schedule = loadgen.interactive(traffic, seed, seconds, dm["V"])
        setup_s = time.monotonic() - t_start
        log(f"set-up {setup_s:.3f} s; {len(schedule)} interactive requests due "
            f"in the {seconds:g} s window")

        # --------------------------------------------------------- window
        trace_s = min(seconds, traffic["trace_seconds"]) if trace else 0.0
        rec = _window(jax, engine, kernel, Request, schedule, bg, rows,
                      seconds, trace_s, counter, trainer)
        rec["setup_s"] = setup_s
    finally:
        if trainer is not None:
            trainer.stop()
        engine.stop()
        kernel.stop()
    stats = dev.memory_stats() or {}
    memory_peak = stats.get("peak_bytes_in_use")
    if trace:
        found = glob.glob(str(TRACE_DIR / "**" / "*.xplane.pb"), recursive=True)
        if not found:
            raise RuntimeError(f"the profiler wrote no trace under {TRACE_DIR}")
        rec["trace"]["reduced"] = xplane.reduce(
            xplane.events_from_xplane(found[0]))

    # -------------------------------------------- free, then the reference
    train_batches = trainer.batches if trainer is not None else []
    reqs = rec.pop("_reqs")
    bulk = rec.pop("_bulk")
    del engine, trainer, params, kernel
    gc.collect()
    t_check = time.monotonic()
    checks, ctrl = (_check(reference, arch, dm, traffic, cell["limits"], seed,
                           reqs, bulk, prog, train_batches, control)
                    if check else ({}, {}))
    log(f"check took {time.monotonic() - t_check:.1f} s")
    return {"rec": rec, "checks": checks, "control": ctrl,
            "memory_peak_bytes": memory_peak,
            "device": dev, "n_devices": len(devs), "peaks": peaks, "dm": dm}


def _window(jax, engine, kernel, Request, schedule, bg, rows, seconds,
            trace_s, counter, trainer) -> dict:
    wake0 = len(kernel.metrics.wakeup_latency["serve"])
    steps0 = engine.stats.decode_steps
    tr = {"seconds": trace_s}
    tracer = None
    if trace_s:
        tracer = threading.Thread(target=_trace_window, daemon=True,
                                  args=(jax, engine, seconds, tr))
    reqs, late, bulk = [], [], []
    # Where a late generator lost its time: asleep past its wake-up (the
    # process or the interpreter held it), or inside ``engine.submit``.
    stall = {"oversleep_s": 0.0, "oversleep_at": 0.0, "submit_s": 0.0,
             "gc_s": 0.0}
    gc_t = []

    def on_gc(phase, info):
        if phase == "start":
            gc_t.append(time.perf_counter())
        elif gc_t:
            stall["gc_s"] = max(stall["gc_s"], time.perf_counter() - gc_t.pop())
    gc.callbacks.append(on_gc)
    counter.armed = True
    t0 = time.monotonic()
    if tracer is not None:
        tracer.start()
    t1 = t0 + seconds
    i = 0
    wake_at = t0
    while True:
        now = time.monotonic()
        if now - wake_at > stall["oversleep_s"]:
            stall["oversleep_s"], stall["oversleep_at"] = now - wake_at, now - t0
        if now >= t1:
            break
        if bg.get("kind") == "ingest":
            while sum(1 for b in bulk if not b.done_event.is_set()) < bg["outstanding"]:
                bulk.append(engine.submit(Request(
                    prompt=rows.take(bg["seq"]), max_new_tokens=1,
                    tier="background")))
        while i < len(schedule) and t0 + schedule[i]["due"] <= now:
            s = schedule[i]
            r = engine.submit(Request(prompt=s["prompt"],
                                      max_new_tokens=s["answer"]))
            stall["submit_s"] = max(stall["submit_s"],
                                    time.monotonic() - r.submitted)
            r.due = t0 + s["due"]
            late.append(r.submitted - r.due)
            reqs.append(r)
            i += 1
        nxt = t0 + schedule[i]["due"] if i < len(schedule) else t1
        before = time.monotonic()
        wake_at = max(before, min(nxt, t1, now + 0.005))
        time.sleep(wake_at - before)
    counter.armed = False
    gc.callbacks.remove(on_gc)
    steps1 = engine.stats.decode_steps
    wakes = list(kernel.metrics.wakeup_latency["serve"][wake0:])
    if tracer is not None:
        tracer.join(timeout=300)
    deadline = t1 + DRAIN_S
    for r in reqs:
        r.done_event.wait(max(0.0, deadline - time.monotonic()))
    ttft = [(r.first_token - r.due) * 1e3 for r in reqs if r.ok]
    if ttft:
        log("ttft ms: " + ", ".join(f"p{q} {np.percentile(ttft, q):.1f}"
                                    for q in (50, 75, 90, 95, 99))
            + f" over {len(ttft)} requests")
    late_ms = sorted(x * 1e3 for x in late) or [0.0]
    # The longest gap between two tokens of one request in the window: a
    # stall of the whole process shows here too, at the same time.
    itl_gap, itl_at = max(((b - a, b - t0) for r in reqs
                           for a, b in zip(r.token_times, r.token_times[1:])
                           if t0 <= b <= t1), default=(0.0, 0.0))
    log(f"generator late: p50 {late_ms[len(late_ms) // 2]:.3f} ms, max "
        f"{late_ms[-1]:.3f} ms over {len(late)} requests; compiles in the "
        f"window: {counter.count}; longest oversleep "
        f"{stall['oversleep_s'] * 1e3:.3f} ms at {stall['oversleep_at']:.3f} s, "
        f"token gap {itl_gap * 1e3:.3f} ms at {itl_at:.3f} s, submit "
        f"{stall['submit_s'] * 1e3:.3f} ms, garbage collection "
        f"{stall['gc_s'] * 1e3:.3f} ms")
    done_bulk = [b for b in bulk if b.ok and t0 <= b.finished <= t1]
    # A step's tokens count in the share of its time that lies in the
    # window, so a step cut by either edge counts in part.
    train_steps = sum(max(0.0, min(e, t1) - max(s, t0)) / (e - s)
                      for s, e in (trainer.spans if trainer is not None else []))
    if bg.get("kind") == "train":
        bg_tokens = train_steps * bg["batch"] * bg["seq"]
    elif bg.get("kind") == "ingest":
        bg_tokens = sum(len(b.prompt) for b in done_bulk)
    else:
        bg_tokens = None
    log(f"background: {train_steps:.3f} train steps, {len(done_bulk)} bulk "
        f"requests finished in the window ({len(bulk)} submitted)")
    return {
        "t0": t0, "t1": t1, "seconds": seconds,
        "requests": [{"due": r.due, "first_token": r.first_token,
                      "token_times": list(r.token_times), "plen": len(r.prompt),
                      "ok": r.ok} for r in reqs],
        "serve_wakeups_s": wakes,
        "decode_steps": steps1 - steps0,
        "background_tokens": bg_tokens,
        "compiles_in_window": counter.count,
        "trace": tr,
        "_reqs": reqs, "_bulk": done_bulk,
    }


def _trace_window(jax, engine, seconds, tr) -> None:
    """Traces the middle of the window from its own thread, so that the
    generator keeps its schedule while the profiler starts and stops."""
    span = tr["seconds"]
    time.sleep(max(0.0, (seconds - span) / 2))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    tr["t0"] = time.monotonic()
    tr["steps0"] = engine.stats.decode_steps
    time.sleep(span)
    tr["t1"] = time.monotonic()
    tr["steps1"] = engine.stats.decode_steps
    jax.profiler.stop_trace()


def _check(reference, arch, dm, traffic, limits, seed, reqs, bulk, prog,
           train_batches, control) -> tuple:
    """Each number compared, with its limit (see PERF.md for how each
    limit was set); with ``control``, also the readings of the control and
    of the planted faults."""
    flat = weights.make_flat(arch, dm, seed)
    ck = traffic["check"]
    rng = np.random.default_rng([int(seed) & (2**64 - 1), 5])
    done = [r for r in reqs if r.ok]
    sample = []
    if done:
        longest = max(range(len(done)),
                      key=lambda j: len(done[j].prompt) + len(done[j].tokens))
        rest = [j for j in range(len(done)) if j != longest]
        pick = rng.choice(len(rest), min(ck["requests"] - 1, len(rest)),
                          replace=False) if rest else []
        sample = [done[longest]] + [done[rest[j]] for j in sorted(pick)]
    if bulk and ck.get("bulk_requests"):
        pick = rng.choice(len(bulk), min(ck["bulk_requests"], len(bulk)),
                          replace=False)
        sample += [bulk[j] for j in sorted(pick)]
    answer_pad = max(traffic["answer"]["max"], 2)
    gap, cgap, served = 0.0, 0.0, 0
    for r in sample:
        g = reference.served_gap(arch, flat, dm, np.asarray(r.prompt, np.int32),
                                 list(r.tokens), traffic["max_len"], answer_pad,
                                 fp8=control)
        gap = max(gap, g["gap"])
        cgap = max(cgap, g.get("control_gap", 0.0))
        served += len(r.tokens)
    out = {"serve.token_gap": {"value": gap if sample else None,
                               "limit": limits["serve.token_gap"]}}
    # The control's and the faults' numbers, each beside the same limit as
    # the program's, in the form ``run.result`` judges.
    ctrl = ({"control": {"serve.token_gap": {
        "value": cgap if sample else None,
        "limit": limits["serve.token_gap"]}}} if control else {})
    log(f"check: {len(sample)} requests, {served} served tokens compared")
    if prog:
        o = traffic["background"]["optimizer"]
        ref = reference.train_steps(arch, flat, dm, train_batches, o)
        out.update(train_numbers(prog, ref, limits))
        if control:
            low = reference.train_steps(arch, flat, dm, train_batches, o,
                                        fp8=True)
            ctrl["control"].update(train_numbers(low, ref, limits))
            half = [b[: len(b) // 2] for b in train_batches]
            half = reference.train_steps(arch, flat, dm, half, o)
            ctrl["half_batch"] = dict(out, **train_numbers(half, ref, limits))
    return out, ctrl


def train_numbers(prog: dict, ref: dict, limits: dict) -> dict:
    """The training numbers compared: the first gradient's norm and the
    change's norm over the steps, by the worst leaf, measured against the
    larger of the leaf's own reference norm and the median leaf's. Leaves
    whose reference gradient is under a thousandth of the median leaf's
    move by round-off alone and are left out of the change. Each step's
    loss is logged and not compared: neither the control nor a fault reads
    it far enough above sound runs (PERF.md)."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"]))
    log(f"train loss: program {prog['loss']!r} reference {ref['loss']!r}; "
        f"widest relative gap {loss!r}")
    gmed = float(np.median(list(ref["grad"].values())))
    grad = max(abs(prog["grad"][k] - g) / max(g, gmed)
               for k, g in ref["grad"].items())
    moved = [k for k, g in ref["grad"].items() if g >= 1e-3 * gmed]
    cmed = float(np.median([ref["change"][k] for k in moved]))
    change = max(abs(prog["change"][k] - ref["change"][k])
                 / max(ref["change"][k], cmed) for k in moved)
    worst = {
        "grad": max(ref["grad"], key=lambda k: abs(prog["grad"][k] - ref["grad"][k])
                    / max(ref["grad"][k], gmed)),
        "change": max(moved, key=lambda k: abs(prog["change"][k] - ref["change"][k])
                      / max(ref["change"][k], cmed))}
    for what, k in worst.items():
        log(f"worst leaf of the {what}: {k}: {prog[what][k]!r} against "
            f"{ref[what][k]!r} (median leaf {gmed if what == 'grad' else cmed!r})")
    return {name: {"value": v, "limit": limits[name]}
            for name, v in (("train.grad_gap", grad),
                            ("train.change_gap", change))}
