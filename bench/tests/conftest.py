"""The benchmark's own tests run on the CPU at tiny sizes:

    python -m pytest bench/tests -q

They keep JAX's compile cache out of the checkout's cache directory."""
import os
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      tempfile.mkdtemp(prefix="bench-tests-jax-cache-"))

import sys
from pathlib import Path

_BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(_BENCH))
sys.path.insert(0, str(_BENCH.parent / "src"))
