"""Share of the pairs routed to held experts that the routed expert
layers computed in their first slab (``layers/moe.py``: a slab's rows
are sized from the layer's shapes, what the router sends beyond it is
computed by further slabs in a loop): 100 x (1 - the
``expert_pairs_overflow`` counter / ``expert_pairs``), over the window's
whole rounds.  100 where no step's loop ran.  ``None`` where the program
counts no pairs or no overflow (an older commit)."""

from benchmarks.lib import stage_scopes

LAYER = "layers and kernels"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "train_samples_s_chip"


def read(run):
    pairs = stage_scopes.counter(run, 'expert_pairs')
    over = stage_scopes.counter(run, 'expert_pairs_overflow')
    if pairs is None or over is None or not pairs[0]:
        return None
    return 100.0 * (1.0 - over[0] / pairs[0])
