"""Closing one chunk of ``scan_steps`` batches, per training step:
``ChunkAssembler.take()``, which hands the block's filled slices on and
moves no byte (the two ``np.stack`` calls it replaced did, until PR 26):
the program's ``stack`` stage (span ``train.stack``)."""

from benchmarks.lib import stages

LAYER = "round loop"
UNIT = "ms/step"
SOURCE = "program_span"
MOVES = "train_samples_s_chip"


def read(run):
    return stages.ms_per_step(run, "stack")
