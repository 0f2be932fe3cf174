"""The benchmark's own tests, on the CPU at small sizes.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They sit outside the repository's ``testpaths``.  The compile cache of
a test run goes to a directory of its own, never the checkout's.
"""
import os
import sys
import tempfile

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      tempfile.mkdtemp(prefix="bench-test-cache-"))
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
