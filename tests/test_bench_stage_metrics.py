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


# chunk_overlap_pct (PR 32): the round loop counts every scanned chunk
# it fences and those whose fence found a later chunk dispatched
@pytest.mark.parametrize("rounds, want", [
    # a round of three chunks: its last has nothing behind it
    ([{"chunks_fenced": 3, "chunks_overlapped": 2}] * 2, 100.0 * 2 / 3),
    ([{"chunks_fenced": 3, "chunks_overlapped": 2,
       "metric_rows": 6144, "metric_rows_device": 6144},
      {"chunks_fenced": 1}], 50.0),
    # every chunk fenced inside its own call (the parent's eval_train = 1)
    ([{"chunks_fenced": 3}], 0.0),
    # the parent counts no fence; the per-batch path fences no chunk
    ([{"metric_rows": 6144, "metric_rows_device": 6144}], None),
    ([{"tokens": 196608, "docs": 120}], None),
    ([{}], None),
    ([], None),
])
def test_chunk_overlap_pct_reads_the_two_fence_counters(rounds, want):
    read = run.load_metric("chunk_overlap_pct").read
    assert read(_counted(*rounds)) == pytest.approx(want)
    assert read({"telemetry": [{"round": 1, "steps": 24}]}) is None


# gdn_scan_fused_pct (PR 34): the gated_deltanet layers count the tokens
# through their scan and those the fused kernels computed, inside the
# step programs
@pytest.mark.parametrize("rounds, want", [
    # three layers x 24 steps x 8192 tokens a round, every one fused
    ([{"gdn_scan_tokens": 589824, "gdn_scan_tokens_fused": 589824,
       "expert_pairs": 61440}] * 2, 100.0),
    # a round whose programs were lowered for another platform
    ([{"gdn_scan_tokens": 589824, "gdn_scan_tokens_fused": 589824},
      {"gdn_scan_tokens": 589824}], 50.0),
    # the jax.numpy form: counted, none fused
    ([{"gdn_scan_tokens": 589824, "gdn_scan_tokens_fused": 0}], 0.0),
    ([{"gdn_scan_tokens": 589824}], 0.0),
    # the parent counts neither; other layers' counters are not tokens
    ([{"expert_pairs": 61440, "tokens": 196608}], None),
    ([{"gdn_scan_tokens": 0}], None),
    ([{}], None),
    ([], None),
])
def test_gdn_scan_fused_pct_reads_the_two_scan_counters(rounds, want):
    read = run.load_metric("gdn_scan_fused_pct").read
    assert read(_counted(*rounds)) == want
    assert read({"telemetry": [{"round": 1, "steps": 24}]}) is None


# attn_flash_pct (PR 37): the masked attention layers (``attention``'s
# masked path, ``latent_attention``) count the tokens through them and
# those the flash kernels computed, inside the step programs
@pytest.mark.parametrize("rounds, want", [
    # six latent layers x 24 steps x 8192 tokens a round, every one flash
    ([{"attn_tokens": 1179648, "attn_tokens_flash": 1179648,
       "attn_pairs": 288000000, "expert_pairs": 61440}] * 2, 100.0),
    # a round whose programs were lowered for another platform
    ([{"attn_tokens": 196608, "attn_tokens_flash": 196608},
      {"attn_tokens": 196608}], 50.0),
    # a shape the chooser left to mha's row blocks: counted, none flash
    ([{"attn_tokens": 196608, "attn_tokens_flash": 0}], 0.0),
    ([{"attn_tokens": 196608}], 0.0),
    # the parent counts neither; the iterator's pairs are not tokens
    ([{"attn_pairs": 288000000, "tokens": 196608,
       "gdn_scan_tokens": 589824}], None),
    ([{"attn_tokens": 0}], None),
    ([{}], None),
    ([], None),
])
def test_attn_flash_pct_reads_the_two_attention_counters(rounds, want):
    read = run.load_metric("attn_flash_pct").read
    assert read(_counted(*rounds)) == want
    assert read({"telemetry": [{"round": 1, "steps": 24}]}) is None


ALL_CELLS = ["googlenet_train_synth", "resnet50_train_synth",
             "granite_4_0_h_micro_train_packed8k",
             "qwen3_next_80b_a3b_train_packed8k",
             "joyai_llm_flash_train_packed8k"]


@pytest.mark.parametrize("name, cells", [
    ("train_metric_device_pct", ALL_CELLS[:2]),
    ("chunk_overlap_pct", ALL_CELLS),
    ("gdn_scan_fused_pct", ALL_CELLS[3:4]),
    ("attn_flash_pct", ALL_CELLS[2:]),
])
def test_benchmark_json_names_the_reader_that_exists(name, cells):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = [m for m in bench["per_layer"] if m["name"] == name]
    assert len(entries) == 1
    entry = entries[0]
    mod = run.load_metric(name)
    assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES, "higher") == (
        entry["layer"], entry["unit"], entry["source"], entry["moves"],
        entry["better"])
    assert entry["workloads"] == cells
    assert set(cells) <= {w["name"] for w in bench["workloads"]}
    # a new entry goes to the end of the list, behind those it found:
    # the 31st when PR 32 added it, and PR 33's ten behind it
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index("chunk_overlap_pct") == 30
    # PR 34's one entry behind them, and PR 36's four behind that
    assert names.index("gdn_scan_fused_pct") == 41
    assert names[42:46] == ["mla_ms_step", "mla_core_ms_step",
                            "mla_core_roofline_pct", "mtp_ms_step"]
    # and PR 37's one behind them, the last
    assert names[46:] == ["attn_flash_pct"]
