"""Host time to hand a chunk's stack to the runtime per training step
(both ``_stage_scan`` calls in ``update_scan``): the program's ``h2d``
stage (span ``train.h2d``).  The enqueue only: the transfer's tail ends
inside ``device_wait`` and only the trace can split it."""

from benchmarks.lib import stages

LAYER = "input pipeline"
UNIT = "ms/step"
SOURCE = "program_span"
MOVES = "train_samples_s_chip"


def read(run):
    return stages.ms_per_step(run, "h2d")
