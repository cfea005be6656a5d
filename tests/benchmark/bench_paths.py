"""Puts the benchmark's own directory on the path for its tests."""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
TOY_MANIFEST = os.path.join(REPO, "tests", "benchmark", "toy",
                            "BENCHMARK.json")
