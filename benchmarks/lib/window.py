"""The measured window: stamps taken at chunk fences and round edges,
and the arithmetic from stamps to throughput.

A *chunk* is one ``scan_steps``-step dispatch of ``update_scan``; a
*fence* is the moment the program's round loop records a lap for it
(``StepTimer.add``, which ``stop`` reaches too).  The recorder below is
put in ``StepTimer``'s place for the run, and a wrapper on the
``LearnTask`` instance stamps the entry to and the return from every
``_train_one_round``.  No file of the program is edited.

* Warm-up ends at the first fence after one whole round has run.
* The window runs from that fence to the last fence at or before
  ``seconds`` later, so it holds whole chunks only.
* A chunk's period runs from the previous fence of its round, or for a
  round's first chunk from the entry to ``_train_one_round``, to its
  own fence.  Round boundaries (last fence to the next entry: the
  train-metric print, telemetry, the checkpoint gate) are in no period.
* throughput = steps in the window's chunks x global batch / the sum
  of their periods / chips.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional, Tuple


@dataclasses.dataclass
class Stamps:
    fences: List[Tuple[float, int, int]] = dataclasses.field(
        default_factory=list)  # (t, round, n_steps)
    rounds: List[List[float]] = dataclasses.field(
        default_factory=list)  # [enter, exit or None]

    def to_json(self) -> dict:
        return {"fences": [list(f) for f in self.fences],
                "rounds": [list(r) for r in self.rounds]}

    @staticmethod
    def from_json(d: dict) -> "Stamps":
        return Stamps([tuple(f) for f in d["fences"]],
                      [list(r) for r in d["rounds"]])


def window_start_index(st: Stamps) -> Optional[int]:
    """Index of the fence that ends warm-up: the first fence of the
    first round that started after a whole round had returned."""
    for i, (_, rnd, _) in enumerate(st.fences):
        if rnd >= 1 and len(st.rounds) > rnd - 1 and \
                st.rounds[rnd - 1][1] is not None:
            return i
    return None


def chunk_periods(st: Stamps) -> List[Tuple[int, float, float, int]]:
    """(round, start, end, n_steps) of every chunk."""
    out = []
    prev_round, prev_t = -1, 0.0
    for t, rnd, n in st.fences:
        start = st.rounds[rnd][0] if rnd != prev_round else prev_t
        out.append((rnd, start, t, n))
        prev_round, prev_t = rnd, t
    return out


def reduce_window(st: Stamps, seconds: float, batch: int, chips: int
                  ) -> dict:
    """The window's numbers from the stamps alone."""
    i0 = window_start_index(st)
    if i0 is None:
        raise ValueError("no fence after a whole round: the run ended "
                         "inside warm-up")
    t0 = st.fences[i0][0]
    periods = [p for p in chunk_periods(st)[i0 + 1:] if p[2] <= t0 + seconds]
    if not periods:
        raise ValueError("the window holds no whole chunk")
    t1 = periods[-1][2]
    busy = sum(e - s for _, s, e, _ in periods)
    steps = sum(n for *_, n in periods)
    boundary = (t1 - t0) - busy
    return {
        "t0": t0, "t1": t1, "chunks": len(periods), "steps": steps,
        "first_fence": i0 + 1, "last_fence": i0 + len(periods),
        "sum_periods_s": busy, "wall_s": t1 - t0,
        "round_boundary_s": boundary,
        "round_boundary_pct": 100.0 * boundary / (t1 - t0),
        "samples_s_chip": steps * batch / busy / chips,
        "periods_s": [e - s for _, s, e, _ in periods],
    }


class Recorder:
    """Takes the stamps and calls back at each fence.  ``on_fence(i,
    now, round, fence_in_round)`` may start a trace or ask the program
    to stop; the recorder itself only keeps time."""

    def __init__(self, on_fence: Optional[Callable] = None) -> None:
        self.stamps = Stamps()
        self.on_fence = on_fence
        self._in_round = 0

    def round_enter(self) -> None:
        self.stamps.rounds.append([time.perf_counter(), None])
        self._in_round = 0

    def round_exit(self) -> None:
        self.stamps.rounds[-1][1] = time.perf_counter()

    def fence(self, n_steps: int) -> None:
        now = time.perf_counter()
        rnd = len(self.stamps.rounds) - 1
        self.stamps.fences.append((now, rnd, int(n_steps)))
        self._in_round += 1
        if self.on_fence is not None:
            self.on_fence(len(self.stamps.fences) - 1, now, rnd,
                          self._in_round)


def install(task, recorder: Recorder) -> None:
    """Put the recorder under the program's round loop: a recording
    subclass in ``StepTimer``'s place (``task_train`` imports the name
    when it is called) and a wrapper on this task's
    ``_train_one_round``."""
    from cxxnet_tpu.utils import profiler

    class RecordingStepTimer(profiler.StepTimer):
        def add(self, dt, n_steps=1):
            super().add(dt, n_steps)
            if n_steps > 0:  # a 0-step lap is the async round-end drain
                recorder.fence(n_steps)

    profiler.StepTimer = RecordingStepTimer
    inner = task._train_one_round

    def train_one_round(timer, tracer):
        import jax

        recorder.round_enter()
        try:
            with jax.profiler.TraceAnnotation("bench.train_one_round"):
                return inner(timer, tracer)
        finally:
            recorder.round_exit()

    task._train_one_round = train_one_round
