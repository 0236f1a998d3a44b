"""The per-layer metrics that read the program's own host stages
(``lib/stages.py`` and the ten readers of ISSUE 25): on a record of a
program that bills the stages, on one that does not, and through a whole
traced run of the dropped-in tiny cell on the CPU."""

from __future__ import annotations

import json
import os

import pytest

from benchmarks import run
from benchmarks.lib import stages
from benchmarks.tests import helpers

STEP_READERS = {
    "loop_next_ms_step": "next", "loop_copy_ms_step": "copy",
    "loop_stack_ms_step": "stack", "h2d_enqueue_ms_step": "h2d",
    "scan_dispatch_ms_step": "dispatch",
    "device_wait_ms_step": "device_wait", "train_metric_ms_step": "metric",
}
ALL_READERS = sorted(STEP_READERS) + [
    "loop_self_ms_step", "iter_init_s", "jax_trace_lower_s"]


def _round(seconds: dict, steps: int = 8) -> dict:
    """One telemetry record as the program writes it: every stage it
    knows is there, zeroed where it never ran."""
    st = {name: {"count": 0, "rows": 0, "total_s": 0.0}
          for name in ("decode", "augment", "batch", "h2d", "device_wait")}
    for name, s in seconds.items():
        st[name] = {"count": 1, "rows": steps, "total_s": s}
    return {"round": 1, "steps": steps, "stages": st}


def _record(rounds, setup=None, device=None) -> dict:
    tele = [dict(r, setup=setup) if setup else r for r in rounds]
    return {"telemetry": tele, "device_at_setup": device}


BILLED = {"next": 0.08, "copy": 0.8, "stack": 0.4, "h2d": 0.16,
          "dispatch": 0.04, "device_wait": 0.8, "metric": 0.08,
          "chunk": 2.4}


def test_a_stage_reads_as_ms_per_step_over_the_windows_rounds():
    rec = _record([_round(BILLED), _round(BILLED)])
    assert run.load_metric("loop_copy_ms_step").read(rec) == pytest.approx(
        1e3 * 1.6 / 16)
    assert run.load_metric("h2d_enqueue_ms_step").read(rec) == \
        pytest.approx(20.0)
    # the parent's self time: 2.4 s of chunk, 2.36 s under its children
    assert run.load_metric("loop_self_ms_step").read(rec) == pytest.approx(
        1e3 * 0.04 / 8)
    assert stages.CHILDREN == tuple(
        STEP_READERS[k] for k in (
            "loop_next_ms_step", "loop_copy_ms_step", "loop_stack_ms_step",
            "h2d_enqueue_ms_step", "scan_dispatch_ms_step",
            "device_wait_ms_step", "train_metric_ms_step"))


def test_set_up_readers_take_the_lifetime_blocks():
    rec = _record([_round(BILLED)],
                  setup={"conf_s": 0.1, "iterators_s": 39.0,
                         "model_s": 3.0, "first_fence_s": 30.0},
                  device={"compile_seconds": 3.0, "trace_seconds": 4.5,
                          "lower_seconds": 2.5})
    assert run.load_metric("iter_init_s").read(rec) == 39.0
    assert run.load_metric("jax_trace_lower_s").read(rec) == 7.0


@pytest.mark.parametrize("name", ALL_READERS)
def test_a_reader_finds_nothing_where_the_program_bills_nothing(name):
    """The parent commit's record: the five old stages, ``h2d`` and
    ``device_wait`` never run on the scanned path, no ``setup`` block,
    no trace or lowering seconds.  Also no round at all, and no device
    summary."""
    read = run.load_metric(name).read
    parent = _record([_round({})], device={"compile_seconds": 3.0})
    assert read(parent) is None
    assert read(_record([])) is None


# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A traced run of the dropped-in tiny cell on a copy whose new
    metrics list that cell too."""
    tmp = str(tmp_path_factory.mktemp("bench_stages"))
    copy = helpers.copy_with_dropins(tmp)
    path = os.path.join(tmp, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        if m["name"] in ALL_READERS:
            m["workloads"].append("tiny_cell")
    with open(path, "w") as f:
        json.dump(bench, f)
    return helpers.run_cell_in_child(
        copy, ["--workload", "tiny_cell", "--seed", "2147484001",
               "--seconds", "2", "--trace", "1", "--cpu-rehearsal"])


def test_a_traced_run_prints_the_stage_metrics(traced):
    assert traced["correct"] is True
    got = traced["metrics"]
    for name in ALL_READERS:
        assert name in got, name
        assert got[name]["unit"] == ("s" if name.endswith("_s") else
                                     "ms/step")
    assert got["h2d_enqueue_ms_step"]["value"] > 0
    assert got["iter_init_s"]["value"] > 0
    assert got["jax_trace_lower_s"]["value"] > 0


def test_what_no_span_covers_is_a_sliver_of_the_chunk(traced):
    got = {k: v["value"] for k, v in traced["metrics"].items()}
    chunk = sum(got[k] for k in STEP_READERS) + got["loop_self_ms_step"]
    assert 0 <= got["loop_self_ms_step"] <= 0.05 * chunk
