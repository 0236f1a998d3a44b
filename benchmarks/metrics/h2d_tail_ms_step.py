"""The exposed tail of the upload per training step: a chunk dispatched
onto an empty device (a round's first, one behind a drain, a starved
one) is billed ``run_exposed`` from its dispatch's return to its fence —
the tail of its upload plus its run — and the run is known from the
chunks that hid their upload (``loop_device_step_ms``).  The difference,
over the window's steps, not under 0.  ``None`` without a ``run`` or
without a ``run_exposed`` (an older commit; rounds whose first chunk had
landed before the loop asked)."""

from benchmarks.lib import stages
from benchmarks.metrics.loop_device_step_ms import step_s, steps_in

LAYER = "input pipeline"
UNIT = "ms/step"
SOURCE = "program_span"
MOVES = "train_samples_s_chip"


def read(run):
    s, exposed, n = (step_s(run), stages.seconds(run, "run_exposed"),
                     stages.steps(run))
    if s is None or exposed is None or not n:
        return None
    return 1e3 * max(0.0, exposed - steps_in(run, "run_exposed") * s) / n
