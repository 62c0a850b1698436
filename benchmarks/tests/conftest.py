"""The benchmark's self-tests run on the CPU backend, at tiny sizes.

    python -m pytest benchmarks/tests

They are not part of tier-1 (`tests/`), and nothing they print is a
device number."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
