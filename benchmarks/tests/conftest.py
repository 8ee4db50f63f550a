"""The benchmark's own tests: run by hand, ``python -m pytest
benchmarks/tests -q`` from the repo root (not part of tier-1).  Everything
runs on the CPU; the rehearsals start their own processes."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
