"""Loss layers: softmax, l2_loss, multi_logistic.

These are self-loop layers in reference configs (``layer[+0] = softmax``).
Each defines ``transform`` (the prediction-time output) and ``loss`` (a
summed scalar) such that ``d loss / d input`` equals the gradient the
reference injects in ``SetGradCPU``:

* softmax — probs; grad ``p - onehot(y)``
  (``loss/softmax_layer-inl.hpp:23-31``)  → loss = Σ cross-entropy.
  ``target_shift = s`` (new scope, sequence losses only): position ``t``
  is scored against ``label[t + s]`` and a row's last ``s`` positions
  have weight 0 — the loss of a head that predicts ``s`` tokens further
  on than the labels the iterator feeds (a multi-token-prediction
  module's, beside the main loss over the same ``label`` field)
* l2_loss — identity; grad ``x - y``
  (``loss/l2_loss_layer-inl.hpp:22-32``)  → loss = ½ Σ (x-y)²
* multi_logistic — sigmoid; grad ``σ(x) - y``
  (``loss/multi_logistic_layer-inl.hpp``) → loss = Σ BCE-with-logits

The trainer multiplies each loss by ``grad_scale / (batch_size *
update_period)`` (``loss/loss_layer_base-inl.hpp:60-63``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .base import LossLayer, register


@register
class SoftmaxLayer(LossLayer):
    type_name = "softmax"

    def __init__(self) -> None:
        super().__init__()
        self.target_shift = 0

    def set_param(self, name, val):
        if name == "target_shift":
            self.target_shift = int(val)
            if self.target_shift < 0:
                raise ValueError("softmax: target_shift counts positions "
                                 "ahead, 0 or more")
        else:
            super().set_param(name, val)

    def transform(self, x):
        return jax.nn.softmax(x, axis=-1)

    def loss(self, x, labels):
        # labels: integer class ids over x's leading dims — (N,)/(N,1)
        # for classifiers, (N, T) for per-position sequence losses
        # (language models), or (T,) for a single row under the
        # loss_masked vmap
        lab = labels.reshape(x.shape[:-1]).astype(jnp.int32)
        if self.target_shift:
            if x.ndim < 3 or x.shape[-2] <= self.target_shift:
                raise ValueError(
                    f"softmax: target_shift={self.target_shift} needs a "
                    f"sequence node longer than it, got {x.shape}")
            s = self.target_shift
            x, lab = x[..., :-s, :], lab[..., s:]
        logp = jax.nn.log_softmax(x, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, lab[..., None], axis=-1))


@register
class L2LossLayer(LossLayer):
    type_name = "l2_loss"

    def loss(self, x, labels):
        lab = labels.reshape(x.shape).astype(x.dtype)
        return 0.5 * jnp.sum((x - lab) ** 2)


@register
class MultiLogisticLayer(LossLayer):
    type_name = "multi_logistic"

    def transform(self, x):
        return jax.nn.sigmoid(x)

    def loss(self, x, labels):
        lab = labels.reshape(x.shape).astype(x.dtype)
        # BCE with logits; gradient wrt x is sigmoid(x) - lab
        return jnp.sum(
            jnp.maximum(x, 0) - x * lab + jnp.log1p(jnp.exp(-jnp.abs(x)))
        )
