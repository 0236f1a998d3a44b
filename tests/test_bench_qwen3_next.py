"""Tier-1 collects the routed-expert configuration's CPU tests here
(``benchmarks/tests/test_qwen3_next.py``: the configuration, cell,
reference and metric readers of ISSUE 33), in a file of their own so
the workers can run them beside the others."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.tests.test_qwen3_next import *  # noqa: E402,F401,F403
