"""Entry points: serve's result and exit code, scheduled training's error
path, the compile-cache helper, and chip_smoke.py's refusal off the chip."""
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import pytest

from repro.launch import serve, train
from repro.launch.compile_cache import DEFAULT_DIR, ENV, enable_compile_cache
from repro.serving.engine import InferenceEngine

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def no_cache_change(monkeypatch, tmp_path):
    """With the variable set, the helper leaves the cache to JAX (which
    read the variable at import), so entry points under test switch no
    cache on in this process."""
    monkeypatch.setenv(ENV, str(tmp_path))


def test_serve_completes_mixed_run_on_reduced_model():
    res = serve.run(serve.parse_args([
        "--reduced", "--requests", "2", "--max-new-tokens", "4",
        "--background-train"]))
    assert res.ok
    assert res.completed == 2 and res.bulk_ok
    assert res.tokens == 3 * 4
    assert (res.panics, res.quarantines) == (0, 0)
    assert len(res.ttft_s) == 3 and all(t > 0 for t in res.ttft_s)


def test_serve_exits_nonzero_when_decode_chunk_raises(monkeypatch,
                                                      no_cache_change):
    def boom(self, budget):
        raise RuntimeError("injected decode fault")

    monkeypatch.setattr(InferenceEngine, "_decode_chunk_impl", boom)
    t0 = time.monotonic()
    rc = serve.main(["--reduced", "--requests", "2", "--max-new-tokens", "2"])
    assert rc == 1
    assert time.monotonic() - t0 < 60, "serve kept waiting after the panic"


def test_scheduled_train_reraises_chunk_error(monkeypatch, no_cache_change):
    """A raising train step ends main with that error instead of leaving
    it waiting for a loop the panic path has quarantined."""
    def make_failing_step(model, tcfg):
        def step(state, batch):
            raise RuntimeError("injected train fault")
        return step

    monkeypatch.setattr(train.T, "make_train_step", make_failing_step)
    with pytest.raises(RuntimeError, match="injected train fault"):
        train.main(["--arch", "llama3.2-1b", "--reduced", "--steps", "3",
                    "--batch", "2", "--seq", "16", "--scheduled"])


def test_compile_cache_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv(ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_ignored_checkout_dir(monkeypatch):
    monkeypatch.delenv(ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_chip_smoke_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)         # the script finds src/ itself
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         capture_output=True, text=True, env=env,
                         cwd=ROOT, timeout=120)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert '"ok"' not in out.stdout
