"""What ``tests/test_bench_<family>.py`` and ``test_bench_stage_metrics.py``
hold ``BENCHMARK.json`` to, as functions of the loaded file
(``tests/test_bench_shadows.py`` hands them a grown copy).  Not collected.

Each states what ITS PR left and allows what came later: a metric's
``workloads`` starts with the cells listed here, the metric names up to
the last one listed are in their order, later cells and metrics may
follow.  A PR that adds one edits none of this (ROADMAP D11(b)); the
exact lists under ``benchmarks/tests/`` are a ``benchmark`` PR's.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402

ALL_CELLS = ["googlenet_train_synth", "resnet50_train_synth",
             "granite_4_0_h_micro_train_packed8k",
             "qwen3_next_80b_a3b_train_packed8k",
             "joyai_llm_flash_train_packed8k",
             "nemotron_3_super_120b_a12b_train_packed8k",
             "trinity_mini_train_packed16k"]
GRANITE, PR33, PR36, PR40, PR42 = ALL_CELLS[2:]

LOOP_BILL = ["loop_device_step_ms", "loop_device_idle_pct",
             "round_head_ms_step", "h2d_tail_ms_step", "chunk_starved_pct"]

#: the entries of ``per_layer`` from PR 32's on, in the order their PRs
#: appended them: PR 33's ten, PR 34's one, PR 36's four, PR 37's one,
#: PR 38's five, PR 39's one, PR 40's three, PR 41's one, PR 42's four,
#: PR 43's one, PR 44's one, PR 46's one, PR 47's one, PR 48's one, PR
#: 49's five
METRICS_FROM_30 = [
    "chunk_overlap_pct", "gdn_mixer_ms_step", "gdn_scan_ms_step",
    "gdn_scan_roofline_pct", "moe_ms_step", "moe_route_dispatch_ms_step",
    "expert_matmul_ms_step", "expert_matmul_roofline_pct",
    "expert_pairs_per_expert", "expert_load_max_over_mean",
    "expert_pairs_dropped", "gdn_scan_fused_pct", "mla_ms_step",
    "mla_core_ms_step", "mla_core_roofline_pct", "mtp_ms_step",
    "attn_flash_pct"] + LOOP_BILL + [
    "expert_dispatch_compact_pct", "moe_latent_proj_ms_step",
    "latent_expert_matmul_roofline_pct", "ssd_scan_grouped_roofline_pct",
    "ssd_scan_fused_pct", "attn_window_core_ms_step",
    "attn_full_core_ms_step", "attn_window_pairs_pct",
    "attn_core_roofline_pct", "attn_unmasked_blocks_pct",
    "attn_fwd_runs_per_bwd", "moe_route_ms_step", "gdn_fwd_runs_per_bwd",
    "attn_bwd_fused_pct", "kda_mixer_ms_step", "kda_scan_ms_step",
    "kda_scan_roofline_pct", "kda_scan_fused_pct", "kda_fwd_runs_per_bwd"]


def load():
    return run.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def starts_with(listed, cells):
    """The cells this repo's PRs listed, in their order, first; whatever
    a later PR appended behind them."""
    return listed[:len(cells)] == cells


def from_its_cell(listed, cells):
    """``cells`` in a row from the first of them on, behind the cells of
    earlier PRs."""
    at = listed.index(cells[0])
    return listed[at:at + len(cells)] == cells


def granite_cell(bench):
    """What PR 29 left: the cell, its mix and its nine metrics.  A later
    cell that reads one of them is APPENDED to its ``workloads``: PR 33's
    (no MLP), PR 36's (no ``attention`` layer), PR 40's (no MLP; the first
    other cell with a mixer; ``ssd_scan_roofline_pct``'s reader names this
    configuration's reference), PR 42's (no mixer)."""
    from benchmarks.tests import test_granite as g

    cell = run.find_cell(bench, g.CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        g.CONFIG, "train_packed8k", 1)
    mix = run.load_json(os.path.join(g.BENCH, "traffic",
                                     "train_packed8k.json"))
    assert mix["chunks_per_round"] == 3 and mix["batch_scale"] == 1
    assert mix["documents"] == {"median": 1024, "sigma": 1.2, "min": 16}
    want = {"mlp_ms_step": [g.CELL, PR36, PR42],  # no MLP in PR 33's, 40's
            "attention_ms_step": [g.CELL, PR33, PR40, PR42],  # not PR 36's
            "ssd_scan_ms_step": [g.CELL, PR40],
            "mamba_mixer_ms_step": [g.CELL, PR40],
            "ssd_scan_roofline_pct": [g.CELL]}
    seen = set()
    for m in bench["per_layer"]:
        if m["name"] in g.NEW_METRICS:
            seen.add(m["name"])
            assert starts_with(m["workloads"], want.get(
                m["name"], [g.CELL, PR33, PR36, PR40, PR42])), m
            assert m["moves"] == "train_samples_s_chip"
    assert seen == set(g.NEW_METRICS)


def qwen3_next_cell(bench):
    """What PR 33 left: the cell, its configuration and its ten metrics.
    PRs 36, 40 and 42 each added a cell and a configuration and APPENDED
    the cell to the expert metrics it can read and to the shared ones."""
    from benchmarks.tests import test_qwen3_next as q

    cell = run.find_cell(bench, q.CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        q.CONFIG, "train_packed8k", 1)
    assert [w["name"] for w in bench["workloads"]].index(q.CELL) == 3
    assert len(bench["workloads"]) >= 7 and len(bench["configs"]) >= 7
    assert all(w["chips"] == 1 for w in bench["workloads"][:7])
    entry = bench["configs"][3]
    assert entry["name"] == q.CONFIG and entry["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    # the delta rule's are this cell's alone; the roofline's reader names
    # this configuration's reference and cannot read another's conf
    own = ("gdn_mixer_ms_step", "gdn_scan_ms_step", "gdn_scan_roofline_pct",
           "expert_matmul_roofline_pct")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in q.NEW_METRICS:
        assert starts_with(by_name[name]["workloads"], (
            [q.CELL] if name in own else [q.CELL, PR36, PR40, PR42])), name
        assert by_name[name]["moves"] == "train_samples_s_chip"
        mod = run.load_metric(name)
        assert (mod.UNIT, mod.SOURCE, mod.LAYER) == (
            by_name[name]["unit"], by_name[name]["source"],
            by_name[name]["layer"])
    for name in q.SHARED:
        # attention_ms_step reads conf type attention: PR 36's net has none
        want = ([q.CELL, PR40, PR42] if name == "attention_ms_step"
                else [q.CELL, PR36, PR40, PR42])
        assert from_its_cell(by_name[name]["workloads"], want), name
    for name in ("ssd_scan_ms_step", "mamba_mixer_ms_step",
                 "train_metric_ms_step", "dispatch_gap_ms_step"):
        assert q.CELL not in by_name[name]["workloads"]
    assert starts_with(by_name["mlp_ms_step"]["workloads"],
                       [GRANITE, PR36, PR42])


def nemotron_h_cell(bench):
    """What PR 40 left: the cell, its configuration, its three metrics
    and the shared ones it is listed under; the entries of PRs 41-47 go
    behind its three, in ``METRICS_FROM_30``'s order."""
    from benchmarks.tests import test_nemotron_h as n

    cell = run.find_cell(bench, n.CELL)
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
    since = METRICS_FROM_30[METRICS_FROM_30.index(n.NEW_METRICS[0]):]
    assert listed[at:at + 3] == n.NEW_METRICS == since[:3]
    assert listed[at:at + len(since)] == since
    assert by_name["attn_unmasked_blocks_pct"]["workloads"][3] == n.CELL


def names_the_reader(bench, name, cells, better):
    """One counter or stage reader's entry: the module's own constants,
    the cells it was listed under first, and its place in the list — a
    new entry goes to the end, behind those it found."""
    entries = [m for m in bench["per_layer"] if m["name"] == name]
    assert len(entries) == 1
    entry = entries[0]
    mod = run.load_metric(name)
    assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES, better) == (
        entry["layer"], entry["unit"], entry["source"], entry["moves"],
        entry["better"])
    assert starts_with(entry["workloads"], cells), entry["workloads"]
    assert set(entry["workloads"]) <= {w["name"] for w in bench["workloads"]}
    names = [m["name"] for m in bench["per_layer"]]
    assert names[30:30 + len(METRICS_FROM_30)] == METRICS_FROM_30
