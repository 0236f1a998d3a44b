"""Host time blocked on the device per training step: the fetch of the
scan's outputs and losses in ``update_scan`` (and ``_guard_loss``'s
fetch under a divergence policy); the program's ``device_wait`` stage
(span ``train.device_wait``).  Holds the transfer's tail and the
program's run."""

from benchmarks.lib import stages

LAYER = "step programs"
UNIT = "ms/step"
SOURCE = "program_span"
MOVES = "train_samples_s_chip"


def read(run):
    return stages.ms_per_step(run, "device_wait")
