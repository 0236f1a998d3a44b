"""Tier-1 collects the Ling-3.0-flash configuration's CPU tests here
(``benchmarks/tests/test_ling.py``: the configuration, cell, reference
and metric readers of ISSUE 49), in a file of their own so the workers
can run them beside the others."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.tests.test_ling import *  # noqa: E402,F401,F403
