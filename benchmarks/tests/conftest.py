"""``python -m pytest benchmarks/tests`` from the repo root, on the CPU."""

import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["LHTPU_AOT_PREWARM"] = "0"
# XLA:CPU on jax 0.9.0 cannot always reload a cached program (NOT_FOUND ...
# fusion not found): the tests compile afresh, into a directory of their
# own, and leave the program store off
os.environ["LHTPU_AOT_STORE"] = "0"
os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(prefix="bench-jc-")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
