"""Device time a training step inside the updater's scope
(``update_<type>``, ``nnet/trainer.py`` ``_apply_updates``): adam's
pass over every weight, gradient and both moments.  ``None`` without a
trace or where the program names no such scope."""

from benchmarks.lib import scopes

LAYER = "step programs"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "train_samples_s_chip"


def read(run):
    got = scopes.by_scope(run)
    if got is None or not got["update_ns"]:
        return None
    return got["update_ns"] / 1e6 / got["steps"]
