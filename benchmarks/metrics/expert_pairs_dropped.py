"""Pairs routed to a held expert and not computed, a training step: the
``expert_pairs_dropped`` counter / the steps, over the window's whole
rounds.  Expected 0: the layer has no capacity limit.  ``None`` where
the program counts none."""

from benchmarks.lib import stage_scopes

LAYER = "layers and kernels"
UNIT = "pairs/step"
SOURCE = "program_counter"
MOVES = "train_samples_s_chip"


def read(run):
    got = stage_scopes.counter(run, 'expert_pairs_dropped')
    return None if got is None else got[0] / got[1]
