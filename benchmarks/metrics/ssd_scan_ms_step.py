"""Device time a training step under the scan scope inside the Mamba-2 layers (softplus of dt, the chunked scan of ops/ssd.py, the D skip), forward, recomputed forward and backward: the sum of the
``XLA Ops`` events of the traced chunks whose scope names such a layer
(``lib/scopes.py``) / the steps traced.  ``None`` without a trace or
where the program names no such scope."""

from benchmarks.lib import scopes

LAYER = "layers and kernels"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "train_samples_s_chip"


def read(run):
    return scopes.ms_per_step(run, ('mamba2',), "scan")
