"""Share of the blocks the flash kernels' forward visits whose EVERY
pair may attend (``ops/flash.py``: both blocks inside one document, the
key block wholly under the diagonal and inside the window): the
``attn_blocks_unmasked`` counter over ``attn_blocks`` (live (query
block, key block) steps x heads x layers, summed inside the step
programs from the tables the kernels read — ``flash.count_blocks``, by
``attention``'s masked path and ``latent_attention``), over the window's
whole rounds.  The rest hold a document boundary, the diagonal or the
window's edge; what the traffic is, not what ran: the kernels compare
the key position with two bounds a query in every block.  ``None`` where
the program counts no blocks (an older commit, or a program ``mha``'s
row blocks computed)."""

from benchmarks.lib import stage_scopes

LAYER = "layers and kernels"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "train_samples_s_chip"


def read(run):
    blocks = stage_scopes.counter(run, 'attn_blocks')
    if blocks is None or not blocks[0]:
        return None
    free = stage_scopes.counter(run, 'attn_blocks_unmasked')
    return 100.0 * (free[0] if free else 0) / blocks[0]
