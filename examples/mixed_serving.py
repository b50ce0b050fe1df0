"""Mixed-workload serving: the paper's deployment story on real JAX work.

A live UFS kernel schedules one device slot between:
  * an inference engine serving interactive requests (time-sensitive tier),
  * a background trainer running microbatches (background tier),
with hint-instrumented engine locks guarding the KV-slot allocator.

Compare against --policy fifo / rr / vdf to see background work delay the
interactive class.

  PYTHONPATH=src python examples/mixed_serving.py [--policy ufs]
"""
import sys

from repro.launch import serve

if __name__ == "__main__":
    sys.exit(serve.main(["--arch", "qwen2-0.5b", "--reduced",
                         "--requests", "8", "--max-new-tokens", "8",
                         "--background-train"] + sys.argv[1:]))
