"""Device time a training step under the ``core_window`` scope of the
attention layers (conf type attention): the score and value products,
the mask and the softmax of the WINDOWED layers — the flash kernels
with the window in their masks and step tables (``ops/flash.py``), or
``mha``'s row blocks — forward, recomputed forward and backward.  The
sum of the ``XLA Ops`` events of the traced chunks under that scope
(``lib/stage_scopes.py``) / the steps traced.  ``None`` without a trace
or where the program names no such scope (the parent commit, a net
without a windowed layer)."""

from benchmarks.lib import stage_scopes

LAYER = "layers and kernels"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "train_samples_s_chip"

KIND = "attention"


def read(run):
    return stage_scopes.ms_per_step(run, KIND, ("core_window",))
