"""Device time a training step under the route (router product, softmax,
top-k), dispatch (the two sorts, the gather into expert order) and
combine (the way back, the router's weights, the sum over a token's
picks) scopes of the routed expert layers: what routing costs beside
the products, forward, recomputed forward and backward: the sum of the
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
        run, stage_scopes.EXPERTS, ('route', 'dispatch', 'combine'))
