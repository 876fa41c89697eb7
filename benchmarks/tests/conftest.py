"""Run by hand: ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``.
Not part of the repo's tier-1 suite (which collects ``tests/`` only)."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]
