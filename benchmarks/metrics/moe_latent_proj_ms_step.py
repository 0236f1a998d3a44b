"""Device time a training step under the latent_in and latent_out scopes
of the routed expert layers (LatentMoE: the projection of every token
into the latent the held experts live in, and of a token's summed
expert output back to the stream), forward, recomputed forward and
backward: the sum of the ``XLA Ops`` events of the traced chunks under
those scopes (``lib/scopes.py``, ``lib/stage_scopes.py``) / the steps
traced.  ``None`` without a trace or where the program names no such
scope (an expert layer without a latent, a commit before it)."""

from benchmarks.lib import stage_scopes

LAYER = "layers and kernels"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "train_samples_s_chip"


def read(run):
    return stage_scopes.ms_per_step(
        run, stage_scopes.EXPERTS, ('latent_in', 'latent_out'))
