"""benchmarks/tests run on the CPU (`JAX_PLATFORMS=cpu python -m pytest
benchmarks/tests -q`); they are not part of tier-1."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
