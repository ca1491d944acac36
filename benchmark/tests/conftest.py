"""``python -m pytest benchmark/tests`` — the benchmark's own checks, on the
CPU, not part of tier-1. Nothing here measures a speed."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
