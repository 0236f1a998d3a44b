"""Tier-1 collects the benchmark harness's own CPU tests here
(``benchmarks/tests/test_benchmark.py``: the window's arithmetic, the
trace reduction on recorded fixtures, FLOP counts, ``BENCHMARK.json``'s
contract, whole toy runs in child processes).  Nothing is defined in
this file; ``python -m pytest benchmarks/tests`` runs the same tests."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.tests.test_benchmark import *  # noqa: E402,F401,F403
