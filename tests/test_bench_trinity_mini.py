"""Tier-1 collects the sliding-window configuration's CPU tests here
(``benchmarks/tests/test_trinity_mini.py``: the configuration, cell, mix,
reference and metric readers of ISSUE 42), in a file of their own so the
workers can run them beside the others."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.tests.test_trinity_mini import *  # noqa: E402,F401,F403
