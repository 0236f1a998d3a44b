"""Device time a training step under the ``core`` scope of the
latent-attention layers (conf type latent_attention): the score and
value products, the mask and the softmax, forward, recomputed forward
and backward — what a masked attention kernel would replace.  The sum of
the ``XLA Ops`` events of the traced chunks under that scope
(``lib/stage_scopes.py``) / the steps traced.  ``None`` without a trace
or where the program names no such scope."""

from benchmarks.lib import stage_scopes

LAYER = "layers and kernels"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "train_samples_s_chip"

KIND = "latent_attention"


def read(run):
    return stage_scopes.ms_per_step(run, KIND, ("core",))
