"""Pairs (token, held expert) a held expert computes in a training step:
the ``expert_pairs`` counter (summed inside the step programs by the
routed expert layers, read by the program once a round) / the steps /
the expert layers / the experts held in each, over the window's whole
rounds.  160 where 8192 tokens pick 10 of 512 evenly.  ``None`` where
the program counts no pairs."""

from benchmarks.lib import stage_scopes

LAYER = "layers and kernels"
UNIT = "pairs/step"
SOURCE = "program_counter"
MOVES = "train_samples_s_chip"


def read(run):
    pairs = stage_scopes.counter(run, 'expert_pairs')
    shape = stage_scopes.expert_layers(run)
    if pairs is None or shape is None:
        return None
    return pairs[0] / pairs[1] / shape[0] / shape[1]
