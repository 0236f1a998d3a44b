"""Device time of one training step as the program's round loop reads it
from its own fences, untraced: the ``run`` stage of the telemetry
records — for every scanned chunk that was dispatched while its
predecessor ran and found still running at its fence, the time between
the two returns of ``block_until_ready`` (``train_loop.RoundLoop``) —
over the steps of those chunks (the stage's ``rows`` / the batch), over
the window's whole rounds.  Unlike ``device_step_ms`` it does not depend
on where a trace session opens; it holds what the device waited for a
chunk's rows where an upload outlasts the run before it.  A program that
bills no ``run`` (an older commit, a round of late or starved chunks)
gives ``None``."""

from benchmarks.lib import stages

LAYER = "step programs"
UNIT = "ms/step"
SOURCE = "program_span"
MOVES = "train_samples_s_chip"


def steps_in(run, stage):
    """The steps of the chunks billed to ``stage``: its rows / batch."""
    rows = sum(r.get("stages", {}).get(stage, {}).get("rows", 0)
               for r in run["telemetry"])
    return rows / run["batch"]


def step_s(run):
    """Seconds of device time a step, or ``None`` without a ``run``."""
    s = stages.seconds(run, "run")
    n = steps_in(run, "run") if s is not None else 0
    return s / n if n else None


def read(run):
    s = step_s(run)
    return None if s is None else 1e3 * s
