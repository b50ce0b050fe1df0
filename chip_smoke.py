#!/usr/bin/env python3
"""Smoke test of the UFS-scheduled serving path on one TPU chip.

    python chip_smoke.py

Runs qwen2-0.5b at its published widths (24 layers, d_model 896, 14 heads /
2 KV heads, d_ff 4864, vocab 151936, bf16; weights from a seed), all phases
in this one process, on the chip only:

  (a) the Pallas flash path's prefill logits for one 1024-token prompt
      (2 x 2 attention blocks of 512) match the blocked-XLA path's for the
      same params (bf16 tolerance below);
  (b) ``repro.launch.serve.run`` -- the code behind ``python -m
      repro.launch.serve`` -- with the UFS policy and background training:
      interactive requests plus one background-tier bulk request all
      complete, no job panics, training steps run with a finite loss;
  (c) the engine's greedy tokens for one request equal a plain jitted
      prefill + decode loop of the same model and params.

Each phase prints one line. Any failure raises and the exit code is
non-zero. On success the last line is the JSON device record. Without a
TPU it exits non-zero before doing any work.
"""
from __future__ import annotations

import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen2-0.5b"
SEED = 0
# One power of two >= 8 (the engine's smallest prefill bucket), as serve.py's
# prompts are: no padding in the prefill.
PROMPT_LEN = 8
NEW_TOKENS = 8
CHECK_PROMPT_LEN = 1024         # phase (a): several flash blocks per head
# Relative L2 distance allowed between the two attention paths' logits.
# Both keep bf16 activations between layers; each layer's attention output
# may round differently (order of the f32 sums), and 24 layers compound it.
# A wrong kernel gives a distance near 1.
LOGITS_RTOL = 5e-2
# The engine's pooled cache: serve.py's batch rows and positions.
MAX_BATCH, MAX_LEN = 4, 64


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def peak_gib(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.3f} GiB"


def phase_a(jax, np, cfg, params, dev) -> None:
    from repro.models.transformer import Model

    prompt = np.random.default_rng(SEED + 1).integers(
        0, cfg.vocab_size, CHECK_PROMPT_LEN).astype(np.int32)
    tokens = {"tokens": jax.numpy.asarray(prompt[None])}
    out, secs = {}, {}
    for backend in ("pallas", "xla"):
        fn = jax.jit(Model(cfg, backend=backend).prefill, static_argnums=2)
        t0 = time.perf_counter()
        compiled = fn.lower(params, tokens, CHECK_PROMPT_LEN).compile()
        secs[backend] = time.perf_counter() - t0
        if backend == "pallas":
            check("tpu_custom_call" in compiled.as_text(),
                  "the pallas prefill holds no Mosaic kernel")
        logits, _ = compiled(params, tokens)
        out[backend] = np.asarray(logits[0, -1], np.float32)
    a, b = out["pallas"], out["xla"]
    check(np.all(np.isfinite(a)) and np.all(np.isfinite(b)),
          "non-finite prefill logits")
    rel = float(np.linalg.norm(a - b) / np.linalg.norm(b))
    print(f"phase a: {dev.device_kind}: prefill compile pallas "
          f"{secs['pallas']:.2f} s xla {secs['xla']:.2f} s; logits rel L2 "
          f"{rel:.3e} (limit {LOGITS_RTOL:g}); argmax pallas "
          f"{int(a.argmax())} xla {int(b.argmax())}; peak {peak_gib(dev)}",
          flush=True)
    check(rel <= LOGITS_RTOL, f"pallas and xla prefill logits differ: {rel}")


def phase_b(dev) -> None:
    from repro.core import percentile
    from repro.launch import serve

    args = serve.parse_args([
        "--arch", ARCH, "--policy", "ufs", "--background-train",
        "--requests", "4", "--max-new-tokens", str(NEW_TOKENS)])
    t0 = time.perf_counter()
    res = serve.run(args)
    wall = time.perf_counter() - t0
    ttft = (f"p50 {percentile(res.ttft_s, 50):.3f} s max {max(res.ttft_s):.3f} s"
            if res.ttft_s else "none")
    print(f"phase b: serve {wall:.1f} s (compiles included): requests "
          f"{res.completed}/{res.requests} bulk ok {res.bulk_ok} tokens "
          f"{res.tokens}; first token {ttft}; "
          f"panics {res.panics} quarantines {res.quarantines}; background "
          f"train steps {res.train_steps} last loss {res.train_loss}; "
          f"peak {peak_gib(dev)}", flush=True)
    check(res.ok, "a request did not complete or a job panicked")
    check(res.train_steps > 0, "no background train step ran")
    check(res.train_loss is not None and math.isfinite(res.train_loss),
          f"background train loss {res.train_loss}")


def phase_c(jax, np, cfg, params, prompt, dev) -> None:
    import jax.numpy as jnp
    from repro.core import build_kernel
    from repro.models.transformer import Model
    from repro.serving.engine import InferenceEngine, Request

    model = Model(cfg)
    kernel = build_kernel("live", policy="ufs", n_slots=1)
    engine = InferenceEngine(model, params, kernel, max_batch=MAX_BATCH,
                             max_len=MAX_LEN)
    kernel.start()
    try:
        engine.start()
        req = engine.submit(Request(prompt=prompt, max_new_tokens=NEW_TOKENS))
        finished = req.done_event.wait(timeout=600)
    finally:
        engine.stop()
        kernel.stop()
    check(finished and req.ok, f"engine request failed: {req.error}")

    # Plain loop at the engine's batch shape (the prompt in every row):
    # rows are independent, and equal shapes keep bf16 rounding equal.
    prefill = jax.jit(model.prefill, static_argnums=2)
    decode = jax.jit(model.decode_step)
    toks = jnp.asarray(np.tile(prompt, (MAX_BATCH, 1)))
    logits, caches = prefill(params, {"tokens": toks}, MAX_LEN)
    ref = [int(jnp.argmax(logits[0, -1]))]
    for pos in range(PROMPT_LEN, PROMPT_LEN + NEW_TOKENS - 1):
        step = jnp.full((MAX_BATCH, 1), ref[-1], jnp.int32)
        logits, caches = decode(params, caches, step, pos)
        ref.append(int(jnp.argmax(logits[0, 0])))
    got = req.tokens[:NEW_TOKENS]
    print(f"phase c: engine tokens {got} plain loop {ref} "
          f"ttft {req.first_token - req.submitted:.3f} s; "
          f"peak {peak_gib(dev)}", flush=True)
    check(got == ref, "engine greedy tokens differ from the plain loop")


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is {dev.platform!r}); "
              f"this check runs on the chip only", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    print(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache {cache}", flush=True)

    import numpy as np
    from repro.configs import get_arch
    from repro.models.transformer import Model

    cfg = get_arch(ARCH)
    prompt = np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, PROMPT_LEN).astype(np.int32)
    init = jax.jit(Model(cfg).init_params)
    params = init(jax.random.PRNGKey(SEED))
    phase_a(jax, np, cfg, params, dev)
    del params                     # phase b builds its own model and state
    phase_b(dev)
    phase_c(jax, np, cfg, init(jax.random.PRNGKey(SEED)), prompt, dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
