"""The benchmark's own tests (not tier-1): ``python -m pytest
benchmarks/tests -q`` from the root of the repo, on the CPU."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
