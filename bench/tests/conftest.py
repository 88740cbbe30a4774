"""Puts the benchmark's directory and the program on the import path.

Run these with ``python -m pytest bench/tests`` from the checkout's
root, on the CPU (``JAX_PLATFORMS=cpu``)."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH,
          os.path.join(BENCH, "references")):
    if p not in sys.path:
        sys.path.insert(0, p)
