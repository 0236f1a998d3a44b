"""Tier-1 collects the stage readers' CPU tests here
(``benchmarks/tests/test_stage_metrics.py``), in a file of their own so
the workers can run them beside ``test_bench_harness.py``."""

import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402
from benchmarks.tests.test_stage_metrics import *  # noqa: E402,F401,F403


# ----------------------------------------------------------------------
def _counted(*rounds):
    return {"telemetry": [{"round": i, "steps": 24, "counters": c}
                          for i, c in enumerate(rounds)]}


# the readers of two counters of the rounds' records: the share of one in
# the other over the rounds that counted, None where none did
COUNTER_READERS = {
    # train_metric_device_pct (PR 30): a counter reader added beside the
    # stage readers
    "train_metric_device_pct": [
        ([{"metric_rows": 6144, "metric_rows_device": 6144}] * 2, 100.0),
        ([{"metric_rows": 6144, "metric_rows_device": 6144},
          {"metric_rows": 6144}], 50.0),
        # eval_train = 0 counts no row; an iterator's counters are not rows
        ([{"tokens": 196608, "docs": 120}], None),
        ([{}], None),
        ([], None),
    ],
    # chunk_overlap_pct (PR 32): the round loop counts every scanned chunk
    # it fences and those whose fence found a later chunk dispatched
    "chunk_overlap_pct": [
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
    ],
    # gdn_scan_fused_pct (PR 34): the gated_deltanet layers count the tokens
    # through their scan and those the fused kernels computed, inside the
    # step programs
    "gdn_scan_fused_pct": [
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
    ],
    # ssd_scan_fused_pct (PR 41): the mamba2 layers count the tokens through
    # their scan and those the fused kernels computed, inside the step
    # programs — a twin of the delta rule's reader
    "ssd_scan_fused_pct": [
        # nine mixers x 24 steps x 8192 tokens a round, every one fused
        ([{"ssd_scan_tokens": 1769472, "ssd_scan_tokens_fused": 1769472,
           "attn_tokens": 196608}] * 2, 100.0),
        # a round whose programs were lowered for another platform
        ([{"ssd_scan_tokens": 1769472, "ssd_scan_tokens_fused": 1769472},
          {"ssd_scan_tokens": 1769472}], 50.0),
        # the jax.numpy form: counted, none fused
        ([{"ssd_scan_tokens": 983040, "ssd_scan_tokens_fused": 0}], 0.0),
        ([{"ssd_scan_tokens": 983040}], 0.0),
        # the parent counts neither; the delta rule's tokens are not these
        ([{"gdn_scan_tokens": 589824, "gdn_scan_tokens_fused": 589824,
           "tokens": 196608}], None),
        ([{"ssd_scan_tokens": 0}], None),
        ([{}], None),
        ([], None),
    ],
    # attn_flash_pct (PR 37): the masked attention layers (``attention``'s
    # masked path, ``latent_attention``) count the tokens through them and
    # those the flash kernels computed, inside the step programs
    "attn_flash_pct": [
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
    ],
    # attn_bwd_fused_pct (PR 48): the same layers count the tokens whose
    # backward ran as the one kernel ``flash_bwd``
    "attn_bwd_fused_pct": [
        # Trinity's five layers x 24 steps x 16384 tokens, every one fused
        ([{"attn_tokens": 1966080, "attn_tokens_flash": 1966080,
           "attn_tokens_bwd_fused": 1966080, "attn_blocks": 180000}] * 2,
         100.0),
        # a round whose programs were lowered for another platform
        ([{"attn_tokens": 196608, "attn_tokens_bwd_fused": 196608},
          {"attn_tokens": 196608}], 50.0),
        # a row past the one kernel's VMEM budget: the two kernels ran
        ([{"attn_tokens": 196608, "attn_tokens_flash": 196608,
           "attn_tokens_bwd_fused": 0}], 0.0),
        # the parent counts tokens and flash tokens, and not these
        ([{"attn_tokens": 196608, "attn_tokens_flash": 196608}], None),
        ([{"attn_tokens": 0, "attn_tokens_bwd_fused": 0}], None),
        ([{}], None),
        ([], None),
    ],
    # attn_unmasked_blocks_pct (PR 43): the same layers count the blocks the
    # flash kernels' forward visits and those of them whose every pair may
    # attend
    "attn_unmasked_blocks_pct": [
        # Trinity's five layers x 32 heads x 24 steps: a third wholly live
        ([{"attn_blocks": 180000, "attn_blocks_unmasked": 60000,
           "attn_tokens": 1966080, "attn_tokens_flash": 1966080}] * 2,
         100.0 / 3),
        # a round of one long document a row, and one of short documents
        ([{"attn_blocks": 1000, "attn_blocks_unmasked": 1000},
          {"attn_blocks": 3000, "attn_blocks_unmasked": 0}], 25.0),
        ([{"attn_blocks": 1000}], 0.0),
        # the parent counts tokens and no blocks; mha's rows visit none
        ([{"attn_tokens": 196608, "attn_tokens_flash": 196608}], None),
        ([{"attn_tokens": 196608, "attn_blocks": 0,
           "attn_blocks_unmasked": 0}], None),
        ([{}], None),
        ([], None),
    ],
    # expert_dispatch_compact_pct (PR 39): the routed expert layers count the
    # pairs they computed in slabs after the first, inside the step programs
    "expert_dispatch_compact_pct": [
        # four layers x 24 steps x ~5 100 held pairs, every one in the first
        # slab: the loop never ran
        ([{"expert_pairs": 491520, "expert_pairs_overflow": 0,
           "expert_pairs_dropped": 0}] * 2, 100.0),
        # a round whose router sent a tenth of the pairs past the slab
        ([{"expert_pairs": 491520, "expert_pairs_overflow": 0},
          {"expert_pairs": 491520, "expert_pairs_overflow": 98304}], 90.0),
        # every held pair beyond a slab of none: nothing compact
        ([{"expert_pairs": 1000, "expert_pairs_overflow": 1000}], 0.0),
        # the parent counts pairs and no overflow; no pairs, no share
        ([{"expert_pairs": 491520, "expert_pairs_dropped": 0}], None),
        ([{"expert_pairs": 0, "expert_pairs_overflow": 0}], None),
        ([{"tokens": 196608, "attn_tokens": 196608}], None),
        ([{}], None),
        ([], None),
    ],
}


@pytest.mark.parametrize("name, rounds, want", [
    (name, rounds, want) for name, cases in COUNTER_READERS.items()
    for rounds, want in cases])
def test_a_counter_reader_reads_its_two_counters(name, rounds, want):
    read = run.load_metric(name).read
    assert read(_counted(*rounds)) == (
        want if want is None else pytest.approx(want, rel=1e-12))
    # the parent commit's record has no ``counters`` block at all
    assert read({"telemetry": [{"round": 1, "steps": 24}]}) is None


# attn_fwd_runs_per_bwd (PR 44): the forward kernel's operations over the
# backward's on the first chip's trace — events built as
# ``benchmarks/tests/test_trinity_mini.py`` builds them
def _kernel_events(fwd, bwd=1, layers=(("l1_attn0", "core_window"),
                                       ("l9_mla4", "core"))):
    """(HLO instruction, ns, ``tf_op``) of ONE step with ``fwd`` forward
    and ``bwd`` backward runs a layer, and the events a step has around
    them; a ``tf_op`` is ``<scope path>:<type>`` and a v5e's trace leaves
    the type empty (``benchmarks/fixtures/scopes_v5e.xplane.pb``; the
    kernels' lines are a chip's own, PR 44)."""
    body = "jit(step)/while/body/closed_call/"
    events = [("%fusion.1", 4000, body + "jvp(l1_attn0)/dot_general:"),
              ("%while.1", 99999, "jit(step)/while:"),
              ("%ragged-dot-none", 9000, "ragged-dot-none:"),
              ("%copy.1", 100, None)]
    for lay, core in layers:
        back = (f"{body}transpose(jvp({lay}))/jvp({lay})/checkpoint/"
                f"rematted_computation/{core}/")
        events += [(f"%flash_fwd.{lay}{i} = (bf16[4,2048,128]{{2,1,0}}, f32[4,"
                    "2048,1]{2,1,0}) custom-call(s32[2]{0} %p)", 6000, (
            f"{body}jvp({lay})/{core}/" if i == 0 else back)
            + "cond/branch_0_fun/jit(_forward)/flash_fwd/pallas_call:")
            for i in range(fwd)]
        for i in range(bwd):
            events += [(f"%flash_dq.{lay}{i} = bf16[4,2048,128]{{2,1,0}} "
                        "custom-call(s32[2]{0} %p)", 7000, back + "cond/"
                        "branch_0_fun/jit(_backward)/flash_dq/pallas_call:"),
                       (f"%flash_dkv.{lay}{i}", 9000, back + "cond/"
                        "branch_0_fun/jit(_backward)/flash_dkv/pallas_call:")]
    return events


@pytest.mark.parametrize("events, want", [
    # forward and the remat recompute for one backward: the parent
    (_kernel_events(2), 2.0),
    # the net's policy keeps o and lse: one forward a layer
    (_kernel_events(1), 1.0),
    (_kernel_events(1, layers=(("l3_attn1", "core_full"),)), 1.0),
    # the operations of the program are counted, not their events: a
    # traced round of three steps that begins inside one (its backward
    # alone) and ends inside another (its forward alone)
    ([e for e in _kernel_events(2) if "flash_fwd" not in e[0]]
     + 3 * _kernel_events(2)
     + [e for e in _kernel_events(2) if "flash_dq" not in e[0]], 2.0),
    (_kernel_events(1)[4:] + 3 * _kernel_events(1), 1.0),
    # a forward that never ran on the device is not counted
    (_kernel_events(1) + [("%flash_fwd.9", 0, "jit(step)/flash_fwd/"
                           "pallas_call:")], 1.0),
    # a trace that states the type, and one that has no colon at all
    ([("%a", 5, "jit(f)/flash_fwd/pallas_call:custom-call"),
      ("%b", 5, "jit(f)/flash_fwd/pallas_call"),
      ("%c", 5, "jit(f)/flash_dq/pallas_call")], 2.0),
    # mha's row blocks, a conv net: neither kernel in the trace
    (_kernel_events(0, 0), None),
    # a forward alone (nothing differentiated): no backward to count by
    (_kernel_events(1, 0), None),
    ([], None),
])
def test_attn_fwd_runs_per_bwd_counts_the_two_kernels_events(events, want):
    mod = run.load_metric("attn_fwd_runs_per_bwd")
    assert mod.runs_per_bwd(events) == want
    # the same kernels inside another op's name are not the kernels
    assert mod.runs_per_bwd([("%f", 5, "x/flash_fwd/pallas_call/copy:"),
                             ("%g", 5, "x/flash_dq/pallas_call:")]) is None


@pytest.mark.parametrize("name", ["attn_fwd_runs_per_bwd",
                                  "gdn_fwd_runs_per_bwd"])
def test_a_runs_per_bwd_reader_reads_nothing_without_a_trace(tmp_path, name):
    read = run.load_metric(name).read
    out = str(tmp_path)
    assert read({"out": out, "trace": None}) is None
    assert read({"out": out, "trace": {"steps": 0}}) is None
    # traced, and the round's directory is not there or holds no file
    assert read({"out": out, "trace": {"steps": 16}}) is None
    where = os.path.join(out, "trace_round1", "plugins", "profile", "x")
    os.makedirs(where)
    assert read({"out": out, "trace": {"steps": 16}}) is None
    # a v5e's own trace of a conv net: events, scopes with their colons,
    # and none of the reader's kernels
    from benchmarks.lib import scopes
    fixture = os.path.join(ROOT, "benchmarks", "fixtures",
                           "scopes_v5e.xplane.pb")
    shutil.copy(fixture, os.path.join(where, "host.xplane.pb"))
    assert any(s and s.endswith(":") for _, _, s in
               scopes.device_events(fixture))
    assert read({"out": out, "trace": {"steps": 16}}) is None


# gdn_fwd_runs_per_bwd (PR 47): the delta rule's two forward kernels'
# operations over twice the backward's, counted the same way
def _gdn_events(solve, scan, bwd=1, layers=("l1_gdn0", "l3_gdn1", "l5_gdn2")):
    """(HLO instruction, ns, ``tf_op``) of ONE step of a net whose
    ``gated_deltanet`` ``layers`` each run ``gdn_solve`` ``solve`` times,
    ``gdn_scan`` ``scan`` times and ``gdn_scan_bwd`` ``bwd`` times — the
    first run of a forward kernel in the forward pass, the second in the
    layer's ``remat`` recompute — and the events a step has around them
    (the kernels' scope paths are a compile's for a described v5e)."""
    body = "jit(step)/while/body/closed_call/"
    events = [("%fusion.1", 4000, body + "jvp(l1_gdn0)/in_proj/dot_general:"),
              ("%while.1", 99999, "jit(step)/while:"),
              ("%ragged-dot-none", 9000, "ragged-dot-none:"),
              ("%copy.1", 100, None)]
    kern = "scan/cond/branch_0_fun/{}/pallas_call:"
    for lay in layers:
        back = f"{body}transpose(jvp({lay}))/jvp({lay})/checkpoint/"
        where = (f"{body}jvp({lay})/", back + f"rematted_computation/{lay}/")
        for name, n in (("gdn_solve", solve), ("gdn_scan", scan)):
            events += [(f"%{name}.{lay}{i} = (f32[1,32,8192,64]{{3,2,1,0}}) "
                        "custom-call(bf16[1,8192,2048]{2,1,0} %p)", 3900,
                        where[i] + kern.format(name)) for i in range(n)]
        events += [(f"%gdn_scan_bwd.{lay}{i}", 4100,
                    back + f"{lay}/" + kern.format("gdn_scan_bwd"))
                   for i in range(bwd)]
    return events


@pytest.mark.parametrize("events, want", [
    # forward and the remat recompute run both kernels: the parent
    (_gdn_events(2, 2), 2.0),
    # the net's policy keeps T, W and U0: the recompute runs the scan alone
    (_gdn_events(1, 2), 1.5),
    # it keeps o and the entering states too: neither kernel runs again
    (_gdn_events(1, 1), 1.0),
    (_gdn_events(1, 2, layers=("l1_gdn0",)), 1.5),
    # the operations of the program are counted, not their events: a
    # traced round of three steps that begins inside one (its backward
    # alone) and ends inside another (its forward alone)
    ([e for e in _gdn_events(2, 2) if "gdn_scan_bwd" in e[0]]
     + 3 * _gdn_events(2, 2)
     + [e for e in _gdn_events(2, 2) if "gdn_scan_bwd" not in e[0]], 2.0),
    (_gdn_events(1, 2)[4:] + 3 * _gdn_events(1, 2), 1.5),
    # a kernel that never ran on the device is not counted
    (_gdn_events(1, 1) + [("%gdn_solve.9", 0, "jit(step)/gdn_solve/"
                           "pallas_call:")], 1.0),
    # a trace that states the type, and one that has no colon at all
    ([("%a", 5, "jit(f)/gdn_solve/pallas_call:custom-call"),
      ("%b", 5, "jit(f)/gdn_scan/pallas_call"),
      ("%c", 5, "jit(f)/gdn_scan_bwd/pallas_call")], 1.0),
    # the jax.numpy form of the rule, another family's net: no kernel
    (_gdn_events(0, 0, 0), None),
    (_kernel_events(2), None),
    # a forward alone (nothing differentiated): no backward to count by
    (_gdn_events(1, 1, 0), None),
    ([], None),
])
def test_gdn_fwd_runs_per_bwd_counts_the_three_kernels_events(events, want):
    mod = run.load_metric("gdn_fwd_runs_per_bwd")
    assert mod.runs_per_bwd(events) == want
    # the backward's name does not end as the forward scan's does, and the
    # same kernels inside another op's name are not the kernels
    assert mod.runs_per_bwd([("%f", 5, "x/gdn_scan_bwd/pallas_call:")]) is None
    assert mod.runs_per_bwd([("%f", 5, "x/gdn_scan/pallas_call/copy:"),
                             ("%g", 5, "x/gdn_scan_bwd/pallas_call:")]) is None
    # and the flash kernels' reader sees none of these
    assert run.load_metric("attn_fwd_runs_per_bwd").runs_per_bwd(
        _gdn_events(2, 2)) is None


# the round loop's bill of the device's time from its own fences (PR 38):
# ``run`` / ``run_exposed`` / ``head`` stages and three counters
def _billed(*rounds, batch=256):
    """Records of 24 steps; a stage is (count, seconds, steps)."""
    return {"batch": batch, "telemetry": [
        {"round": i, "steps": 24,
         "stages": {k: {"count": c, "total_s": s, "rows": n * batch}
                    for k, (c, s, n) in st.items()},
         "counters": dict(counters)}
        for i, (st, counters) in enumerate(rounds)]}


# a round of three chunks of 8 steps at 85 ms a step: a head of 80 ms,
# the first chunk's upload exposed for 220 ms
ROUND = ({"head": (1, 0.08, 0), "run_exposed": (1, 0.90, 8),
          "run": (2, 1.36, 16), "chunk": (3, 2.34, 24)},
         {"chunks_dispatched": 3, "chunks_fenced": 3})
# one whose second chunk had landed before its fence: one run less, and
# its third chunk's none either
LATE = ({"head": (1, 0.08, 0), "run_exposed": (1, 0.90, 8),
         "chunk": (3, 2.40, 24)},
        {"chunks_dispatched": 3, "chunks_late": 1})
# a starved round: every chunk onto an empty device, two of them late
STARVED = ({"head": (1, 0.50, 0), "run_exposed": (1, 0.70, 8),
            "chunk": (3, 4.00, 24)},
           {"chunks_dispatched": 3, "chunks_starved": 2, "chunks_late": 2})
# an upload that hid whole: the exposed run is shorter than 8 steps by
# the clock's grain
HIDDEN = ({"head": (1, 0.08, 0), "run_exposed": (1, 0.679, 8),
           "run": (2, 1.36, 16), "chunk": (3, 2.119, 24)},
          {"chunks_dispatched": 3})
# the parent's record: the eleven stages it knew, its two counters
PARENT = ({"chunk": (3, 2.34, 24), "device_wait": (3, 2.0, 24)},
          {"chunks_fenced": 3, "chunks_overlapped": 2})


@pytest.mark.parametrize("name, rounds, want", [
    ("loop_device_step_ms", [ROUND] * 2, 85.0),
    # sums over the rounds, not a mean of rounds: 1.36 s over 16 steps
    ("loop_device_step_ms", [ROUND, LATE], 85.0),
    ("loop_device_step_ms", [LATE, STARVED], None),
    ("loop_device_step_ms", [PARENT], None),
    ("loop_device_step_ms", [], None),
    # 1 - 0.085 x 24 / 2.34
    ("loop_device_idle_pct", [ROUND] * 2, 100.0 * (1 - 2.04 / 2.34)),
    ("loop_device_idle_pct", [ROUND, LATE], 100.0 * (1 - 4.08 / 4.74)),
    ("loop_device_idle_pct", [STARVED], None),
    ("loop_device_idle_pct", [PARENT], None),
    ("round_head_ms_step", [ROUND] * 2, 80.0 / 24),
    ("round_head_ms_step", [ROUND, STARVED], 580.0 / 48),
    ("round_head_ms_step", [PARENT], None),
    ("round_head_ms_step", [], None),
    # (0.90 - 8 x 0.085) s once in 24 steps
    ("h2d_tail_ms_step", [ROUND] * 2, 220.0 / 24),
    ("h2d_tail_ms_step", [ROUND, LATE], 440.0 / 48),
    # the floor: never under 0
    ("h2d_tail_ms_step", [HIDDEN], 0.0),
    # no run to take off the exposed one
    ("h2d_tail_ms_step", [STARVED], None),
    ("h2d_tail_ms_step", [PARENT], None),
    ("chunk_starved_pct", [ROUND] * 2, 0.0),
    ("chunk_starved_pct", [ROUND, STARVED], 100.0 * 2 / 6),
    ("chunk_starved_pct", [STARVED], 100.0 * 2 / 3),
    # the parent counts no dispatch; the per-batch path dispatches none
    ("chunk_starved_pct", [PARENT], None),
    ("chunk_starved_pct", [({}, {"tokens": 196608})], None),
    ("chunk_starved_pct", [], None),
])
def test_the_loops_bill_of_the_device_is_read_over_the_rounds(
        name, rounds, want):
    read = run.load_metric(name).read
    assert read(_billed(*rounds)) == (
        want if want is None else pytest.approx(want))
    # a record with no ``stages`` and no ``counters`` at all
    assert read({"batch": 256,
                 "telemetry": [{"round": 1, "steps": 24}]}) is None


def test_head_and_tail_close_on_the_loops_idle_time():
    """What the two explain is the whole of what the loop's reading of
    the device leaves of a chunk period, where no chunk was late or
    starved."""
    rec = _billed(ROUND, ROUND)
    read = {n: run.load_metric(n).read(rec) for n in (
        "loop_device_step_ms", "loop_device_idle_pct",
        "round_head_ms_step", "h2d_tail_ms_step")}
    period_ms = 1e3 * 2.34 / 24
    assert read["round_head_ms_step"] + read["h2d_tail_ms_step"] == (
        pytest.approx(read["loop_device_idle_pct"] / 100 * period_ms))
    assert period_ms - read["loop_device_step_ms"] == pytest.approx(
        read["round_head_ms_step"] + read["h2d_tail_ms_step"])


from bench_shadows import ALL_CELLS, LOOP_BILL  # noqa: E402


@pytest.mark.parametrize("name, cells, better", [
    ("train_metric_device_pct", ALL_CELLS[:2], "higher"),
    ("chunk_overlap_pct", ALL_CELLS, "higher"),
    ("gdn_scan_fused_pct", ALL_CELLS[3:4], "higher"),
    ("ssd_scan_fused_pct", [ALL_CELLS[2], ALL_CELLS[5]], "higher"),
    ("moe_latent_proj_ms_step", ALL_CELLS[5:6], "lower"),
    ("latent_expert_matmul_roofline_pct", ALL_CELLS[5:6], "higher"),
    ("ssd_scan_grouped_roofline_pct", ALL_CELLS[5:6], "higher"),
    ("attn_window_core_ms_step", ALL_CELLS[6:], "lower"),
    ("attn_full_core_ms_step", ALL_CELLS[6:], "lower"),
    ("attn_window_pairs_pct", ALL_CELLS[6:], "lower"),
    ("attn_core_roofline_pct", ALL_CELLS[6:], "higher"),
    ("attn_flash_pct", ALL_CELLS[2:], "higher"),
    ("attn_bwd_fused_pct", ALL_CELLS[2:], "higher"),
    ("attn_unmasked_blocks_pct", ALL_CELLS[2:], "higher"),
    ("attn_fwd_runs_per_bwd", ALL_CELLS[2:], "lower"),
    ("gdn_fwd_runs_per_bwd", ALL_CELLS[3:4], "lower"),
    ("expert_dispatch_compact_pct", ALL_CELLS[3:], "higher"),
] + [(name, ALL_CELLS, "lower") for name in LOOP_BILL])
def test_benchmark_json_names_the_reader_that_exists(name, cells, better):
    """``cells`` are those the entry was listed under when its PR (or a
    later one of PRs 30-44) left it; a later cell may follow them and a
    later entry the last of ``bench_shadows.METRICS_FROM_30``."""
    import bench_shadows

    bench_shadows.names_the_reader(bench_shadows.load(), name, cells, better)
