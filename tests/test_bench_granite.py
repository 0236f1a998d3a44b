"""Tier-1 collects the token-model configuration's CPU tests here
(``benchmarks/tests/test_granite.py``: the configuration, mix,
generator, reference and metric readers of ISSUE 29), in a file of
their own so the workers can run them beside the other two."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.tests.test_granite import *  # noqa: E402,F401,F403


def test_the_cell_is_the_one_the_issue_names():  # noqa: F811
    """As ``benchmarks/tests/test_granite.py`` has it, for what PR 29
    left: the cell, its mix and its nine metrics.  A later cell that
    reads a metric the granite cell brought is APPENDED to that metric's
    ``workloads`` (PR 33 did, for five of them; PR 36 for those but
    ``attention_ms_step``, and for ``mlp_ms_step``; PR 40 for those but
    ``mlp_ms_step`` — its net has no MLP — and, the first to do so, for
    the mixer's ``ssd_scan_ms_step`` and ``mamba_mixer_ms_step``, leaving
    ``ssd_scan_roofline_pct``, whose reader names this configuration's
    reference; PR 42 for ``attention_ms_step``, ``mlp_ms_step`` and the
    shared four — its net has no mixer), which the test under
    ``benchmarks/`` forbids and a PR that adds a cell may not edit; a
    ``benchmark`` PR folds this back."""
    from benchmarks.tests import test_granite as g

    bench = g.run.load_json(os.path.join(g.ROOT, "BENCHMARK.json"))
    cell = g.run.find_cell(bench, g.CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        g.CONFIG, "train_packed8k", 1)
    mix = g.run.load_json(os.path.join(g.BENCH, "traffic",
                                       "train_packed8k.json"))
    assert mix["chunks_per_round"] == 3 and mix["batch_scale"] == 1
    assert mix["documents"] == {"median": 1024, "sigma": 1.2, "min": 16}
    pr33 = "qwen3_next_80b_a3b_train_packed8k"
    pr36 = "joyai_llm_flash_train_packed8k"
    pr40 = "nemotron_3_super_120b_a12b_train_packed8k"
    pr42 = "trinity_mini_train_packed16k"
    want = {"mlp_ms_step": [g.CELL, pr36, pr42],  # no MLP in PR 33's, 40's
            "attention_ms_step": [g.CELL, pr33, pr40, pr42],  # not PR 36's
            "ssd_scan_ms_step": [g.CELL, pr40],
            "mamba_mixer_ms_step": [g.CELL, pr40],
            "ssd_scan_roofline_pct": [g.CELL]}
    for m in bench["per_layer"]:
        if m["name"] in g.NEW_METRICS:
            assert m["workloads"] == want.get(
                m["name"], [g.CELL, pr33, pr36, pr40, pr42])
            assert m["moves"] == "train_samples_s_chip"
