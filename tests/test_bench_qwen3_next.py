"""Tier-1 collects the routed-expert configuration's CPU tests here
(``benchmarks/tests/test_qwen3_next.py``: the configuration, cell,
reference and metric readers of ISSUE 33), in a file of their own so
the workers can run them beside the others."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.tests.test_qwen3_next import *  # noqa: E402,F401,F403


def test_the_cell_is_the_one_the_issue_names():  # noqa: F811
    """What this cell's PR left, and whatever later PRs appended
    (``tests/bench_shadows.py``; ``benchmarks/`` holds its day's lists)."""
    import bench_shadows

    bench_shadows.qwen3_next_cell(bench_shadows.load())
