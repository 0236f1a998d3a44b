"""Device time a training step under the Kimi Delta Attention layers (conf
type kimi_delta: norm, in_proj, conv, scan, gate_norm, out_proj,
residual), forward, recomputed forward and backward: the sum of the
``XLA Ops`` events of the traced chunks under that scope
(``lib/scopes.py``, ``lib/stage_scopes.py``) / the steps traced.  ``None``
without a trace or where the program names no such scope."""

from benchmarks.lib import scopes

LAYER = "layers and kernels"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "train_samples_s_chip"


def read(run):
    return scopes.ms_per_step(run, ('kimi_delta',), 'total')
