"""The scanned step's dispatch per training step: program lookup,
``_next_rng()`` and the jitted call until it returns; the program's
``dispatch`` stage (span ``train.dispatch``)."""

from benchmarks.lib import stages

LAYER = "step programs"
UNIT = "ms/step"
SOURCE = "program_span"
MOVES = "train_samples_s_chip"


def read(run):
    return stages.ms_per_step(run, "dispatch")
