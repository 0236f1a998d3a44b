"""Token embedding layer for sequence models.

New TPU-first scope — the reference is a CNN framework with no discrete
inputs (SURVEY §5); this follows the framework's own conventions
(config-driven params, per-tag hyperparameter scoping).

``embedding`` config keys:

* ``nvocab`` — vocabulary size (required)
* ``nhidden`` — embedding dimension (required)
* ``pos = none|learned|sin`` — positional encoding added to the token
  embedding: a trained ``(T, D)`` table (tag ``pos``, so ``pos:lr``
  scoping works) or fixed sinusoidal (Vaswani et al. 2017)
* ``multiplier`` — the looked-up rows are scaled by it (default 1)

``lm_head`` is the head over the vocabulary, ``logits = x W^T /
divisor`` with ``nhidden`` the vocabulary and ``divisor`` (default 1).
With ``tied = <the embedding's name>`` ``W`` is that embedding's matrix
and the head owns no parameter: the net hands it the named layer's, so
the matrix is one leaf with one gradient, the sum of both uses
(``shared[...]`` aliases a layer of the same type, which a head is
not).  Without ``tied`` the head is untied: ``wmat (nhidden, D)`` is its
own, started at ``init_sigma``.

``token_shift`` moves a row of ids on by one: ``out[t] = ids[t + 1]``,
id 0 — the separator — at a row's last position.  It is how a
multi-token-prediction module reads "the next token" from the batch the
iterator feeds; no keys, no parameters.

Input is a flat ``(N, T)`` node of token ids (the text iterator emits
ids as float32 — exact for any realistic vocab); output is the
``(N, T, D)`` sequence node the attention stack consumes.  The layer
sets ``integer_input`` so the net skips the bf16 compute-dtype cast on
the raw ids (bf16 would corrupt ids above 256).
"""

from __future__ import annotations

import math
from typing import List, Sequence

import jax
import jax.numpy as jnp

from .base import Layer, Params, Shape, register


def sin_pos_table(t: int, d: int) -> jnp.ndarray:
    """Sinusoidal positional encodings, (T, D) f32."""
    pos = jnp.arange(t, dtype=jnp.float32)[:, None]
    half = (d + 1) // 2
    freq = jnp.exp(
        -math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half
    )
    ang = pos * freq[None, :]
    table = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
    return table[:, :d]


@register
class EmbeddingLayer(Layer):
    type_name = "embedding"

    #: the net must NOT cast this layer's input to the compute dtype —
    #: token ids above 256 are not exactly representable in bf16
    integer_input = True

    def __init__(self) -> None:
        super().__init__()
        self.nvocab = 0
        self.pos = "none"
        self.multiplier = 1.0
        self.decode = 0
        self.decode_window = 0

    def set_param(self, name, val):
        if name == "nvocab":
            self.nvocab = int(val)
        elif name == "multiplier":
            self.multiplier = float(val)
        elif name == "pos":
            if val not in ("none", "learned", "sin"):
                raise ValueError(
                    f"embedding: pos must be none|learned|sin, got {val!r}"
                )
            self.pos = val
        elif name == "decode":
            # incremental decoding: positions are absolute (the loop's
            # ``step``), and the learned table spans decode_window so
            # its shape matches the training checkpoint's (T, D)
            self.decode = int(val)
        elif name == "decode_window":
            self.decode_window = int(val)
        else:
            super().set_param(name, val)

    def infer_shape(self, in_shapes: Sequence[Shape]) -> List[Shape]:
        self._check_arity(in_shapes, 1)
        (shape,) = in_shapes
        if len(shape) != 2:
            raise ValueError(
                "embedding: input must be a flat (N, T) id node "
                f"(input_shape = 1,1,T), got {shape}"
            )
        if self.nvocab <= 0 or self.param.num_hidden <= 0:
            raise ValueError("embedding: set nvocab and nhidden")
        n, t = shape
        return [(n, t, self.param.num_hidden)]

    def _table_len(self, t: int) -> int:
        if self.decode:
            if self.decode_window <= 0:
                raise ValueError(
                    "embedding: decode=1 needs decode_window (the "
                    "training T, so the pos table matches the checkpoint)"
                )
            return self.decode_window
        return t

    def init_params(self, key, in_shapes) -> Params:
        d = self.param.num_hidden
        t = self._table_len(in_shapes[0][1])
        k1, k2 = jax.random.split(key)
        sigma = self.param.init_sigma
        p = {
            "wmat": jax.random.normal(k1, (self.nvocab, d), jnp.float32)
            * sigma
        }
        if self.pos == "learned":
            p["pos"] = jax.random.normal(k2, (t, d), jnp.float32) * sigma
        return p

    def apply(self, params, inputs, *, train=False, rng=None, step=None):
        from jax import lax

        x = inputs[0]
        ids = jnp.clip(
            jnp.round(x).astype(jnp.int32), 0, self.nvocab - 1
        )
        table = params["wmat"]
        out = jnp.take(table, ids, axis=0)
        if self.multiplier != 1.0:
            out = out * jnp.asarray(self.multiplier, out.dtype)
        t, d = out.shape[1], out.shape[2]
        if self.decode:
            # absolute positions step..step+t-1 (the decode loop's clock)
            start = jnp.asarray(0 if step is None else step, jnp.int32)
            if self.pos == "learned":
                sl = lax.dynamic_slice(
                    params["pos"].astype(out.dtype), (start, 0), (t, d)
                )
                out = out + sl[None]
            elif self.pos == "sin":
                full = sin_pos_table(self._table_len(t), d)
                sl = lax.dynamic_slice(full, (start, 0), (t, d))
                out = out + sl.astype(out.dtype)[None]
            return [out]
        if self.pos == "learned":
            out = out + params["pos"].astype(out.dtype)[None, :t]
        elif self.pos == "sin":
            out = out + sin_pos_table(t, d).astype(out.dtype)
        return [out]


@register
class LMHeadLayer(Layer):
    type_name = "lm_head"

    def __init__(self) -> None:
        super().__init__()
        self.tied = ""  # FunctionalNet reads it: the layer whose params
        self.divisor = 1.0

    def set_param(self, name, val):
        if name == "tied":
            self.tied = val
        elif name == "divisor":
            self.divisor = float(val)
        else:
            super().set_param(name, val)

    def infer_shape(self, in_shapes: Sequence[Shape]) -> List[Shape]:
        self._check_arity(in_shapes, 1)
        if self.param.num_hidden <= 0:
            raise ValueError(
                "lm_head: set nhidden (the vocabulary) and, for a head "
                "tied to the embedding, tied (the embedding's name)")
        return [tuple(in_shapes[0][:-1]) + (self.param.num_hidden,)]

    def init_params(self, key, in_shapes) -> Params:
        if self.tied:
            return {}  # the embedding's, by FunctionalNet's key
        return {"wmat": jax.random.normal(
            key, (self.param.num_hidden, in_shapes[0][-1]), jnp.float32)
            * self.param.init_sigma}

    def apply(self, params, inputs, *, train=False, rng=None, step=None):
        x = inputs[0]
        table = params["wmat"]
        if table.shape != (self.param.num_hidden, x.shape[-1]):
            raise ValueError(
                f"lm_head: tied matrix is {tuple(table.shape)}, the head "
                f"needs ({self.param.num_hidden}, {x.shape[-1]})")
        y = x @ table.astype(x.dtype).T
        return [y / jnp.asarray(self.divisor, y.dtype)]


@register
class TokenShiftLayer(Layer):
    type_name = "token_shift"

    #: reads raw ids, like ``embedding``
    integer_input = True

    def infer_shape(self, in_shapes: Sequence[Shape]) -> List[Shape]:
        self._check_arity(in_shapes, 1)
        (shape,) = in_shapes
        if len(shape) != 2 or shape[1] < 2:
            raise ValueError(
                f"token_shift: input must be a flat (N, T) id node with "
                f"T > 1, got {shape}")
        return [tuple(shape)]

    def apply(self, params, inputs, *, train=False, rng=None, step=None):
        return [jnp.pad(inputs[0][:, 1:], ((0, 0), (0, 1)))]
