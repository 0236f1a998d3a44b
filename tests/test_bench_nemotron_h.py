"""Tier-1 collects the Nemotron-H configuration's CPU tests here
(``benchmarks/tests/test_nemotron_h.py``: the configuration, cell,
reference and metric readers of ISSUE 40), in a file of their own so
the workers can run them beside the others."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.tests.test_nemotron_h import *  # noqa: E402,F401,F403


def test_the_cell_is_the_one_the_issue_names():  # noqa: F811
    """As ``benchmarks/tests/test_nemotron_h.py`` has it, for what PR 40
    left: the cell, its configuration, its three metrics and the shared
    ones it is listed under.  PR 41's ``ssd_scan_fused_pct`` goes behind
    PR 40's three, which the test under ``benchmarks/`` holds to be the
    LAST three and a PR that adds a metric may not edit; PR 42's four
    readers of the windowed attention's scopes and counters go behind
    that, and its cell lists ``mlp_ms_step``; PR 43's share of the flash
    kernels' blocks whose every pair may attend is the last; a ``benchmark`` PR
    folds this back."""
    from benchmarks.tests import test_nemotron_h as n

    bench = n.run.load_json(os.path.join(n.ROOT, "BENCHMARK.json"))
    cell = n.run.find_cell(bench, n.CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        n.CONFIG, "train_packed8k", 1)
    assert len(cell["why"]) <= 200 and "352 pairs" in cell["why"]
    names = [w["name"] for w in bench["workloads"]]
    assert names.index(n.CELL) == 5 and len(bench["configs"]) >= 6
    entry = bench["configs"][5]
    assert entry["name"] == n.CONFIG and entry["reduced"] == n.REDUCED
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in n.NEW_METRICS:
        assert by_name[name]["workloads"][0] == n.CELL
        assert by_name[name]["moves"] == "train_samples_s_chip"
    for name in n.SHARED + ["ssd_scan_fused_pct"]:
        assert n.CELL in by_name[name]["workloads"]
        assert by_name[name]["workloads"].index(n.CELL) == len(
            [w for w in by_name[name]["workloads"] if names.index(w) < 5])
    for name in ("ssd_scan_roofline_pct", "expert_matmul_roofline_pct",
                 "mlp_ms_step", "mla_ms_step", "gdn_scan_ms_step",
                 "gdn_scan_fused_pct", "mtp_ms_step",
                 "train_metric_ms_step", "dispatch_gap_ms_step"):
        assert n.CELL not in by_name[name]["workloads"]
    listed = [m["name"] for m in bench["per_layer"]]
    at = listed.index(n.NEW_METRICS[0])
    assert listed[at:at + 4] == n.NEW_METRICS + ["ssd_scan_fused_pct"]
    assert listed[at + 4:] == [
        "attn_window_core_ms_step", "attn_full_core_ms_step",
        "attn_window_pairs_pct", "attn_core_roofline_pct",
        "attn_unmasked_blocks_pct", "attn_fwd_runs_per_bwd"]
    assert by_name["attn_unmasked_blocks_pct"]["workloads"][3] == n.CELL
