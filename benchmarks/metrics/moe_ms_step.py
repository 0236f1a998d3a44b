"""Device time a training step of the routed expert layers (conf type
routed_experts: norm, route, dispatch, experts, combine, shared,
residual) and of the grouped-product kernels the compiler names
``ragged-dot-*`` with their layer's scope dropped, forward, recomputed forward and backward: the sum of the
``XLA Ops`` events of the traced chunks under that scope
(``lib/scopes.py``, ``lib/stage_scopes.py``) / the steps traced.  ``None``
without a trace or where the program names no such scope."""

from benchmarks.lib import stage_scopes

LAYER = "layers and kernels"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "train_samples_s_chip"


def read(run):
    return stage_scopes.ms_per_step(run, stage_scopes.EXPERTS, None, True)
