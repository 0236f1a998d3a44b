"""Device time a training step under the scan scope inside the Gated
DeltaNet layers (the unit-length q and k, the decay and write strength,
the chunked gated delta rule of ops/gdn.py: the triangular solve, the
scan over the chunks, the outputs), forward, recomputed forward and backward: the sum of the
``XLA Ops`` events of the traced chunks under that scope
(``lib/scopes.py``, ``lib/stage_scopes.py``) / the steps traced.  ``None``
without a trace or where the program names no such scope."""

from benchmarks.lib import scopes

LAYER = "layers and kernels"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "train_samples_s_chip"


def read(run):
    return scopes.ms_per_step(run, ('gated_deltanet',), 'scan')
