"""Device time a training step under the scan scope inside the Kimi Delta
Attention layers (the gate, the chunk's running sums of the decay, the
chunked delta rule of ops/kda.py — on a TPU the kernels kda_solve,
kda_scan and kda_scan_bwd of ops/kda_fused.py — and the layout changes
around them), forward, recomputed forward and backward: the sum of the
``XLA Ops`` events of the traced chunks under that scope
(``lib/scopes.py``, ``lib/stage_scopes.py``) / the steps traced.  ``None``
without a trace or where the program names no such scope."""

from benchmarks.lib import scopes

LAYER = "layers and kernels"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "train_samples_s_chip"


def read(run):
    return scopes.ms_per_step(run, ('kimi_delta',), 'scan')
