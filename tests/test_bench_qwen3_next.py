"""Tier-1 collects the routed-expert configuration's CPU tests here
(``benchmarks/tests/test_qwen3_next.py``: the configuration, cell,
reference and metric readers of ISSUE 33), in a file of their own so
the workers can run them beside the others."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.tests.test_qwen3_next import *  # noqa: E402,F401,F403


def test_the_cell_is_the_one_the_issue_names():  # noqa: F811
    """As ``benchmarks/tests/test_qwen3_next.py`` has it, for what PR 33
    left: the cell, its configuration and its ten metrics.  PR 36 added
    a fifth cell and configuration and APPENDED its cell to the
    ``workloads`` of the six expert metrics it can read and of the shared
    ones, which the test under ``benchmarks/`` forbids (four cells, four
    configurations, ``workloads == [CELL]``, ``workloads[-1] == CELL``)
    and a PR that adds a cell may not edit; a ``benchmark`` PR folds
    this back.  PR 40 added a sixth and appended its cell likewise
    (``mlp_ms_step`` apart: its net has no MLP), PR 42 a seventh."""
    from benchmarks.tests import test_qwen3_next as q

    bench = q.run.load_json(os.path.join(q.ROOT, "BENCHMARK.json"))
    cell = q.run.find_cell(bench, q.CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        q.CONFIG, "train_packed8k", 1)
    assert [w["name"] for w in bench["workloads"]].index(q.CELL) == 3
    assert len(bench["workloads"]) == len(bench["configs"]) == 7
    assert all(w["chips"] == 1 for w in bench["workloads"])
    entry = bench["configs"][3]
    assert entry["name"] == q.CONFIG and entry["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    later = "joyai_llm_flash_train_packed8k"             # PR 36's cell
    last = "nemotron_3_super_120b_a12b_train_packed8k"   # PR 40's
    pr42 = "trinity_mini_train_packed16k"
    # the delta rule's are this cell's alone; the roofline's reader names
    # this configuration's reference and cannot read another's conf
    own = ("gdn_mixer_ms_step", "gdn_scan_ms_step", "gdn_scan_roofline_pct",
           "expert_matmul_roofline_pct")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in q.NEW_METRICS:
        assert by_name[name]["workloads"] == (
            [q.CELL] if name in own else [q.CELL, later, last, pr42])
        assert by_name[name]["moves"] == "train_samples_s_chip"
        mod = q.run.load_metric(name)
        assert (mod.UNIT, mod.SOURCE, mod.LAYER) == (
            by_name[name]["unit"], by_name[name]["source"],
            by_name[name]["layer"])
    for name in q.SHARED:
        # attention_ms_step reads conf type attention: PR 36's net has none
        want = ([q.CELL, last, pr42] if name == "attention_ms_step"
                else [q.CELL, later, last, pr42])
        assert by_name[name]["workloads"][-len(want):] == want
    for name in ("ssd_scan_ms_step", "mamba_mixer_ms_step",
                 "train_metric_ms_step", "dispatch_gap_ms_step"):
        assert q.CELL not in by_name[name]["workloads"]
    assert by_name["mlp_ms_step"]["workloads"] == [
        "granite_4_0_h_micro_train_packed8k", later, pr42]
