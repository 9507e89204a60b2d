"""Tests of the benchmark itself (not collected by the repository's tier-1
run, which collects ``tests/`` only). Run from the checkout's root:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
