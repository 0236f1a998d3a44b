"""Time the round loop waited for the iterator per training step:
``itr_train.next()`` + ``value()`` (and the round's ``before_first``),
the program's ``next`` stage (span ``train.next``).  Near zero while the
threadbuffer keeps ahead of the loop; it grows when decoding cannot."""

from benchmarks.lib import stages

LAYER = "input pipeline"
UNIT = "ms/step"
SOURCE = "program_span"
MOVES = "train_samples_s_chip"


def read(run):
    return stages.ms_per_step(run, "next")
