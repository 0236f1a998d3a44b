"""Share of the time inside rounds in which no step program ran, from
the program's own stamps alone: 1 - ``loop_device_step_ms`` x the
window's steps / the ``chunk`` stage's seconds (fence to fence, the
window's own periods).  ``device_idle_pct`` takes the busy time from a
traced session that opens after a round's head; this one needs no
trace.  ``None`` where no ``run`` was billed."""

from benchmarks.lib import stages
from benchmarks.metrics.loop_device_step_ms import step_s

LAYER = "device"
UNIT = "%"
SOURCE = "program_span"
MOVES = "train_samples_s_chip"


def read(run):
    s, period, n = step_s(run), stages.seconds(run, "chunk"), stages.steps(run)
    if s is None or not period or not n:
        return None
    return 100.0 * (1.0 - s * n / period)
