"""CPU test set-up for the benchmark's own tests
(``python -m pytest bench/tests``): the program and the benchmark
importable, JAX on the CPU, and a compilation cache of the tests' own."""
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(tempfile.gettempdir(), "bench-tests-jax-cache"))
