"""A PR that adds a cell or a metric edits no list under ``tests/``
(ROADMAP D11(b)): the four shadow checks of ``tests/bench_shadows.py``
pass a copy of ``BENCHMARK.json`` with a made-up eighth cell listed
behind the seven and a made-up metric behind the last, and fail one
with a cell or a metric removed or out of its order."""

import copy

import pytest

import bench_shadows as shadows

READER = ("attn_flash_pct", shadows.ALL_CELLS[2:], "higher")
CHECKS = {
    "granite": shadows.granite_cell,
    "qwen3_next": shadows.qwen3_next_cell,
    "nemotron_h": shadows.nemotron_h_cell,
    "stage_metrics": lambda bench: shadows.names_the_reader(bench, *READER),
}
NEW_CELL = "made_up_8b_train_packed32k"


def grown(bench):
    """``bench`` as the next ``model_config`` PR would leave it: an
    eighth configuration and cell, the cell appended to every metric a
    token cell is listed under, and a metric of its own, the last."""
    bench = copy.deepcopy(bench)
    bench["configs"].append(dict(bench["configs"][-1], name="made_up_8b"))
    bench["workloads"].append(dict(bench["workloads"][-1], name=NEW_CELL,
                                   config="made_up_8b"))
    for m in bench["per_layer"]:
        if shadows.PR42 in m["workloads"]:
            m["workloads"].append(NEW_CELL)
    bench["per_layer"].append(dict(bench["per_layer"][-1],
                                   name="made_up_ms_step",
                                   workloads=[NEW_CELL]))
    return bench


def metric(bench, name):
    (m,) = [m for m in bench["per_layer"] if m["name"] == name]
    return m


def swapped(items, i, j):
    items[i], items[j] = items[j], items[i]


#: metrics every check lists PR 36's or PR 40's cell under
LISTED = ("attn_flash_pct", "head_loss_ms_step", "moe_ms_step")
DAMAGE = {
    "a_cell_removed": lambda b: [
        metric(b, n)["workloads"].remove(shadows.PR40) for n in LISTED],
    "two_cells_reordered": lambda b: [
        swapped(w, w.index(shadows.PR36), w.index(shadows.PR40))
        for w in (metric(b, n)["workloads"] for n in LISTED)],
    # one of PR 40's three: every later entry moves up one place
    "a_metric_removed": lambda b: b["per_layer"].remove(
        metric(b, "moe_latent_proj_ms_step")),
    "two_metrics_reordered": lambda b: swapped(
        b["per_layer"], -3, -2),
}


@pytest.fixture(scope="module")
def bench():
    return shadows.load()


@pytest.mark.parametrize("check", sorted(CHECKS))
def test_a_made_up_cell_and_a_trailing_metric_pass_unedited(bench, check):
    CHECKS[check](bench)
    more = grown(bench)
    assert len(more["workloads"]) == len(bench["workloads"]) + 1
    assert more["per_layer"][-1]["name"] == "made_up_ms_step"
    assert NEW_CELL in metric(more, "attn_flash_pct")["workloads"]
    CHECKS[check](more)


@pytest.mark.parametrize("check, damage", [
    (check, damage) for check in sorted(CHECKS) for damage in DAMAGE
    if "metric" not in damage or check in ("nemotron_h", "stage_metrics")])
def test_a_removed_or_reordered_one_fails(bench, check, damage):
    less = grown(bench)
    DAMAGE[damage](less)
    with pytest.raises((AssertionError, KeyError, ValueError)):
        CHECKS[check](less)
