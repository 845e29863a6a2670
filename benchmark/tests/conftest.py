"""Tests of the benchmark's own code, on the CPU at a tiny size:

    python -m pytest benchmark/tests -q

They are not part of the repo's tier-1 suite (`tests/`)."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
