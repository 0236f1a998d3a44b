"""Tier-1 collects the Nemotron-H configuration's CPU tests here
(``benchmarks/tests/test_nemotron_h.py``: the configuration, cell,
reference and metric readers of ISSUE 40), in a file of their own so
the workers can run them beside the others."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.tests.test_nemotron_h import *  # noqa: E402,F401,F403


def test_the_cell_is_the_one_the_issue_names():  # noqa: F811
    """What this cell's PR left, and whatever later PRs appended
    (``tests/bench_shadows.py``; ``benchmarks/`` holds its day's lists)."""
    import bench_shadows

    bench_shadows.nemotron_h_cell(bench_shadows.load())
