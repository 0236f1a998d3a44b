"""Tier-1 collects the token-model configuration's CPU tests here
(``benchmarks/tests/test_granite.py``: the configuration, mix,
generator, reference and metric readers of ISSUE 29), in a file of
their own so the workers can run them beside the other two."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.tests.test_granite import *  # noqa: E402,F401,F403
