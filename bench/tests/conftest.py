"""The benchmark's own tests, on the CPU: ``pytest bench/tests``."""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
