"""Device time a training step under the latent-attention layers (conf
type latent_attention: norm, the latents' projections and norms, rotary,
scores, mask, softmax, values, output projection, residual; a
multi-token-prediction module's among them), forward, recomputed forward
and backward: the sum of the ``XLA Ops`` events of the traced chunks
under that scope (``lib/scopes.py``, ``lib/stage_scopes.py``) / the
steps traced.  ``None`` without a trace or where the program names no
such scope."""

from benchmarks.lib import stage_scopes

LAYER = "layers and kernels"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "train_samples_s_chip"

KIND = "latent_attention"


def read(run):
    return stage_scopes.ms_per_step(run, KIND)
