"""Run by hand, not part of tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, BENCH)
