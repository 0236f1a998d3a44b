"""``tools/idle_by_span.py``'s arithmetic on a table written by hand:
the device's idle gaps laid over the round loop's spans, the gap at a
round's head split by them, and the loop's ``run`` held against the
programs' own durations.  (The tool itself needs the chip.)"""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))
sys.path.insert(0, REPO)

import idle_by_span  # noqa: E402

MS = 1_000_000
DEV, HOST = "/device:TPU:0", "/host:CPU"


def op(start_ms, dur_ms):
    return [DEV, "XLA Ops", "%fusion = f32[8]{0} fusion()", start_ms * MS,
            dur_ms * MS]


def module(start_ms, dur_ms):
    return [DEV, "XLA Modules", "jit_scan(1)", start_ms * MS, dur_ms * MS]


def span(start_ms, end_ms, **stats):
    return (int(start_ms * MS), int(end_ms * MS), stats)


def record(rnd, run_s, exposed_s, head_s, boundary_s, batch=4):
    st = {"run": {"total_s": run_s, "rows": 16 * batch},
          "run_exposed": {"total_s": exposed_s, "rows": 8 * batch},
          "head": {"total_s": head_s, "rows": 0},
          "boundary": {"total_s": boundary_s, "rows": 0},
          "chunk": {"total_s": head_s + exposed_s + run_s,
                    "rows": 24 * batch}}
    return {"round": rnd, "steps": 24, "stages": st,
            "counters": {"chunks_dispatched": 3, "tokens": 7}}


# Round 1 of three chunks of 8 steps, 80 ms each on the device, back to
# back from 100 ms; round 0's last chunk ends at 50.  The loop notices
# that fence at 52, the boundary runs to 60, the head to 70 and the
# upload's tail to 100.  Each program is two operations 1.5 ms apart
# (a gap over 1 ms under ``train.device_wait``); the dispatch's rng
# split runs for half a millisecond inside the head.
ROWS = [op(0, 50), module(0, 50), op(65, 0.5), module(65, 0.5)]
for k in range(3):
    t = 100 + 80 * k
    ROWS += [op(t, 39), op(t + 40.5, 39.5), module(t, 80)]
SPANS = {
    "train.round": [span(60, 342, round=2)],
    "train.boundary": [span(52, 59.99, step=24), span(342.01, 400, step=48)],
    "train.head": [span(60.01, 70, step=24)],
    "train.chunk": [span(60.005, 181, step=24), span(181.01, 261, step=32),
                    span(261.01, 341, step=40)],
    "train.device_wait": [span(10, 51.9, step=16), span(90, 180, step=24),
                          span(182, 260, step=32), span(262, 340, step=40)],
}
RECORDS = [record(0, 1.0, 1.0, 0.1, 0.0),
           record(1, 0.160, 0.110, 0.010, 0.008)]


def test_gaps_are_laid_over_the_loops_spans_and_the_head_is_split():
    out = idle_by_span.analyse(ROWS, SPANS, RECORDS, batch=4, steps_round=24,
                               scan=8)
    assert out["device"] == DEV
    # the head's gap, in two by the rng split, and the three inside the
    # programs
    assert out["gaps_over_1ms"] == 5
    assert [g["gap_ms"] for g in out["gaps"]] == [34.5, 15.0, 1.5, 1.5, 1.5]
    # no span of this table lies over 51.9 -> 52 (the last fence's
    # return to the boundary's start) nor over the sliver between the
    # boundary's end and the round's start
    assert out["gaps_uncovered_max_us"] == pytest.approx(100 + 10)
    assert [g["uncovered_us"] for g in out["gaps"]] == [
        0.0, pytest.approx(110.0), 0.0, 0.0, 0.0]
    assert out["step_programs"] == 4
    head, second, third = out["between_programs"]
    assert head["head_of_round"] == 1 and head["idle_ms"] == 49.5
    assert head["before_boundary_ms"] == pytest.approx(2.0)
    assert head["under_boundary_ms"] == pytest.approx(7.99)
    assert head["under_head_ms"] == pytest.approx(9.49)
    assert head["after_head_ms"] == pytest.approx(30.0)
    # the same round from the host clock: 110 ms exposed - 8 x 10
    host = head["host_clock"]
    assert host["round"] == 1
    assert host["boundary_ms"] == pytest.approx(8.0)
    assert host["head_ms"] == pytest.approx(10.0)
    assert host["run_ms_step"] == pytest.approx(10.0)
    assert host["h2d_tail_ms"] == pytest.approx(30.0)
    assert host["counters"] == {"chunks_dispatched": 3}
    # 110 ms exposed - 8 steps at the four step programs' (50 + 3 x 80) / 32
    assert head["host_tail_at_module_rate_ms"] == pytest.approx(
        110.0 - 8 * 290.0 / 32)
    # chunks 2 and 3 started where the one before ended
    assert second["idle_ms"] == third["idle_ms"] == 0.0
    assert "head_of_round" not in second


def test_the_loops_run_is_held_against_the_programs_durations():
    out = idle_by_span.analyse(ROWS, SPANS, RECORDS, batch=4, steps_round=24,
                               scan=8)
    (rnd,) = out["run_against_modules"]
    assert rnd["round"] == 1 and rnd["modules"] == 3
    assert rnd["module_ms_step"] == pytest.approx(10.0)
    assert rnd["run_ms_step"] == pytest.approx(10.0)
    assert rnd["gap_pct"] == pytest.approx(0.0)


def test_a_trace_without_a_device_plane_says_so():
    rows = [[HOST, "python3", "train.chunk", 0, 5 * MS]]
    out = idle_by_span.analyse(rows, SPANS, RECORDS, batch=4, steps_round=24,
                               scan=8)
    assert out["device"] is None
    assert out["span_counts"]["train.chunk"] == 3


@pytest.mark.parametrize("rec, want", [
    # no run billed: no step, so no tail either
    ({"round": 3, "steps": 24, "stages": {
        "chunk": {"total_s": 2.4, "rows": 96},
        "run_exposed": {"total_s": 0.9, "rows": 32}}}, (None, None, 100.0)),
    (record(2, 0.160, 0.110, 0.010, 0.008), (10.0, 30.0, 280.0 / 24)),
])
def test_a_rounds_host_clock_readings(rec, want):
    got = idle_by_span.host_clock(rec, batch=4)
    assert (got["run_ms_step"], got["h2d_tail_ms"],
            got["chunk_ms_step"]) == pytest.approx(want)
