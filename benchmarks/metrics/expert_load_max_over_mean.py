"""How uneven the router's load is over the experts held: the fullest held
expert's pairs, a step and layer (``expert_pairs_max``, summed over
steps and layers), over the mean held expert's (``expert_pairs`` / the
experts held).  1 is even; the grouped products' tiles follow the
fullest.  ``None`` where the program counts no pairs."""

from benchmarks.lib import stage_scopes

LAYER = "layers and kernels"
UNIT = "ratio"
SOURCE = "program_counter"
MOVES = "train_samples_s_chip"


def read(run):
    pairs = stage_scopes.counter(run, 'expert_pairs')
    most = stage_scopes.counter(run, 'expert_pairs_max')
    shape = stage_scopes.expert_layers(run)
    if pairs is None or most is None or shape is None or not pairs[0]:
        return None
    return most[0] * shape[1] / pairs[0]
