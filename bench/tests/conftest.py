"""Tests of the benchmark itself (outside the repo's tier-1 suite):

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
