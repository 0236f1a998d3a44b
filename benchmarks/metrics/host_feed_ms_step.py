"""Host time of a chunk period spent OUTSIDE ``update_scan`` per
training step: the iterator's ``next``, the round loop's copy of every
batch and the ``np.stack`` into one chunk.  Period minus the span the
benchmark's wrapper takes around ``update_scan``."""

LAYER = "round loop"
UNIT = "ms/step"
SOURCE = "host_clock"
MOVES = "train_samples_s_chip"


def read(run):
    w, s = run["window"], run["spans"]
    if not s.get("update_scan"):
        return None
    return 1e3 * (w["sum_periods_s"] - s["update_scan"]) / w["steps"]
