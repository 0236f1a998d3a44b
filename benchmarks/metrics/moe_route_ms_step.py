"""Device time a training step under the ``route`` scope of the routed
expert layers alone: the router's product, the softmax and the top-k —
and, where the router reads another node than the experts (the
SmallThinker family routes on the attention's INPUT), the norm of that
second input.  It is the part of ``moe_route_dispatch_ms_step`` that a
router placed before the attention takes off the experts' critical
path: nothing of it waits for the attention's output.  Forward,
recomputed forward and backward: the sum of the ``XLA Ops`` events of
the traced chunks under that scope (``lib/scopes.py``,
``lib/stage_scopes.py``) / the steps traced.  ``None`` without a trace
or where the program names no such scope."""

from benchmarks.lib import stage_scopes

LAYER = "layers and kernels"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "train_samples_s_chip"


def read(run):
    return stage_scopes.ms_per_step(run, stage_scopes.EXPERTS, ("route",))
