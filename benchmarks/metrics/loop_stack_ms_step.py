"""The two ``np.stack`` calls that make one chunk of ``scan_steps``
batches, per training step: the program's ``stack`` stage (span
``train.stack``)."""

from benchmarks.lib import stages

LAYER = "round loop"
UNIT = "ms/step"
SOURCE = "program_span"
MOVES = "train_samples_s_chip"


def read(run):
    return stages.ms_per_step(run, "stack")
