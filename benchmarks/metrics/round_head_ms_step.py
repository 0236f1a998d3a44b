"""The host's feed of a round's first chunk per training step: the
program's ``head`` stage (span ``train.head``, inside the round's first
``train.chunk``) — from the round's ``begin`` to the return of its first
dispatch: the rewind, ``next``, ``copy``, ``stack``, ``h2d`` and
``dispatch`` of one chunk, with nothing on the chip.  Once a round, so
it shrinks with the chunks a round holds.  ``None`` on a program that
bills no head."""

from benchmarks.lib import stages

LAYER = "round loop"
UNIT = "ms/step"
SOURCE = "program_span"
MOVES = "train_samples_s_chip"


def read(run):
    return stages.ms_per_step(run, "head")
