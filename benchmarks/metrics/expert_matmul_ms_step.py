"""Device time a training step of the held experts' grouped products: the
experts scope of the routed expert layers (the casts and transposes of
the matrices, silu and the product of gate and up) and the kernels the
compiler makes of ``jax.lax.ragged_dot`` and names ``ragged-dot-*``
with the scope dropped, forward, recomputed forward and backward: the sum of the
``XLA Ops`` events of the traced chunks under that scope
(``lib/scopes.py``, ``lib/stage_scopes.py``) / the steps traced.  ``None``
without a trace or where the program names no such scope."""

from benchmarks.lib import stage_scopes

LAYER = "layers and kernels"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "train_samples_s_chip"


def read(run):
    return stage_scopes.ms_per_step(
        run, stage_scopes.EXPERTS, ('experts',), True)
