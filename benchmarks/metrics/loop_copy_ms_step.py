"""The round loop's copy of every batch out of the iterator's buffers
(``np.array(batch.data)``, ``np.array(batch.label)``) per training
step: the program's ``copy`` stage (span ``train.copy``)."""

from benchmarks.lib import stages

LAYER = "round loop"
UNIT = "ms/step"
SOURCE = "program_span"
MOVES = "train_samples_s_chip"


def read(run):
    return stages.ms_per_step(run, "copy")
