"""Run with ``python -m pytest benchmark/tests -q`` from the checkout. The files
of the benchmark are plain scripts that find each other by path, as run.py does."""

import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (BENCH_DIR, os.path.join(BENCH_DIR, "tests")):
    if path not in sys.path:
        sys.path.insert(0, path)
