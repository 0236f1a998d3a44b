"""What the window leaves of masked attention's work: the (query, key)
pairs a causal query of its own document may see less than ``window``
positions back (``attn_window_pairs``) over all those it may see under
the diagonal (``attn_pairs``), both counted by the feed on the rows it
fed (``io/tokens.py``), over the window's whole rounds.  100 where every
document is shorter than the window, ``window / mean length`` x 2 or so
where they are long.  ``None`` where the program counts neither (the
parent commit, a mix that names no window)."""

from benchmarks.lib import stage_scopes

LAYER = "input pipeline"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "train_samples_s_chip"


def read(run):
    near = stage_scopes.counter(run, "attn_window_pairs")
    pairs = stage_scopes.counter(run, "attn_pairs")
    if near is None or pairs is None or not pairs[0]:
        return None
    return 100.0 * near[0] / pairs[0]
