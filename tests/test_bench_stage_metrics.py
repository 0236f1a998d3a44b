"""Tier-1 collects the stage readers' CPU tests here
(``benchmarks/tests/test_stage_metrics.py``), in a file of their own so
the workers can run them beside ``test_bench_harness.py``."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402
from benchmarks.tests.test_stage_metrics import *  # noqa: E402,F401,F403


# ----------------------------------------------------------------------
# train_metric_device_pct (PR 30): a counter reader added beside the
# stage readers
def _counted(*rounds):
    return {"telemetry": [{"round": i, "steps": 24, "counters": c}
                          for i, c in enumerate(rounds)]}


@pytest.mark.parametrize("rounds, want", [
    ([{"metric_rows": 6144, "metric_rows_device": 6144}] * 2, 100.0),
    ([{"metric_rows": 6144, "metric_rows_device": 6144},
      {"metric_rows": 6144}], 50.0),
    # eval_train = 0 counts no row; an iterator's counters are not rows
    ([{"tokens": 196608, "docs": 120}], None),
    ([{}], None),
    ([], None),
])
def test_train_metric_device_pct_reads_the_two_row_counters(rounds, want):
    read = run.load_metric("train_metric_device_pct").read
    assert read(_counted(*rounds)) == want
    # the parent commit's record has no ``counters`` block at all
    assert read({"telemetry": [{"round": 1, "steps": 24}]}) is None


def test_benchmark_json_names_the_reader_that_exists():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = bench["per_layer"][-1]
    assert entry["name"] == "train_metric_device_pct"
    mod = run.load_metric(entry["name"])
    assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
        entry["layer"], entry["unit"], entry["source"], entry["moves"])
    assert entry["workloads"] == ["googlenet_train_synth",
                                  "resnet50_train_synth"]
    cells = {w["name"] for w in bench["workloads"]}
    assert set(entry["workloads"]) <= cells
