"""The benchmark's own tests: `python -m pytest benchmarks/tests -q`, CPU
only.  Not part of tests/ (tier-1).  No device or topology call happens at
import time anywhere under benchmarks/."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (os.path.dirname(BENCH), BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)
