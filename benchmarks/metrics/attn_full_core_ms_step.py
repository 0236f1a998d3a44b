"""Device time a training step under the ``core_full`` scope of the
attention layers (conf type attention): the score and value products,
the mask and the softmax of the layers WITHOUT a window, which see the
whole document — forward, recomputed forward and backward.  The sum of
the ``XLA Ops`` events of the traced chunks under that scope
(``lib/stage_scopes.py``) / the steps traced.  ``None`` without a trace
or where the program names no such scope (the parent commit)."""

from benchmarks.lib import stage_scopes

LAYER = "layers and kernels"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "train_samples_s_chip"

KIND = "attention"


def read(run):
    return stage_scopes.ms_per_step(run, KIND, ("core_full",))
