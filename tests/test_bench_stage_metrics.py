"""Tier-1 collects the stage readers' CPU tests here
(``benchmarks/tests/test_stage_metrics.py``), in a file of their own so
the workers can run them beside ``test_bench_harness.py``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.tests.test_stage_metrics import *  # noqa: E402,F401,F403
