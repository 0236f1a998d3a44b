"""Device time a training step under the gated MLP layers (conf type gated_mlp: norm, the two products, residual): the sum of the
``XLA Ops`` events of the traced chunks whose scope names such a layer
(``lib/scopes.py``) / the steps traced.  ``None`` without a trace or
where the program names no such scope."""

from benchmarks.lib import scopes

LAYER = "layers and kernels"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "train_samples_s_chip"


def read(run):
    return scopes.ms_per_step(run, ('gated_mlp',), "total")
