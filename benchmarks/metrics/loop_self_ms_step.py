"""What no span covers, per training step: the ``chunk`` stage (fence to
fence, the window's own edges) minus the seven stages that tile it
(``next``, ``copy``, ``stack``, ``h2d``, ``dispatch``, ``device_wait``,
``metric``) — the round loop's self time.  Expected under 2% of a chunk
period; more means a stage's edges are wrong or a cost has no span."""

from benchmarks.lib import stages

LAYER = "round loop"
UNIT = "ms/step"
SOURCE = "program_span"
MOVES = "train_samples_s_chip"


def read(run):
    return stages.self_ms_per_step(run)
