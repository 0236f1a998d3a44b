"""Tier-1 collects the SmallThinker configuration's CPU tests here
(``benchmarks/tests/test_smallthinker.py``: the configuration, cell,
reference and metric reader of ISSUE 46), in a file of their own so the
workers can run them beside the others."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.tests.test_smallthinker import *  # noqa: E402,F401,F403
