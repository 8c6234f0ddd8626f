"""The benchmark's own tests run on the CPU, at sizes a test run holds:
``pytest bench/tests``. They sit outside the repository's tier-1
``testpaths``."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]
