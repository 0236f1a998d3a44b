"""Dense layers: fullc, gated_mlp, fixconn, flatten.

Parity sources:
* fullc — ``/root/reference/src/layer/fullc_layer-inl.hpp`` (``out =
  dot(in, W^T) + bias``; W stored ``(nhidden, nin)``; init fan_in =
  W.shape[1], fan_out = W.shape[0])
* gated_mlp — no parity source (new scope): the gated feed-forward of
  Shazeer 2020 ("GLU Variants Improve Transformer") with silu,
  ``(silu(x W_g) * (x W_v)) W_2``.  ``wmat`` is ``[W_g; W_v]`` fused,
  ``(2 * nhidden, nin)``, ``wproj`` is ``(nin, nhidden)``; no biases.
  ``prenorm`` / ``postnorm`` / ``residual_scale`` / ``eps`` keep the
  residual branch in the one layer (``sequence.Branch``), so that under
  ``remat`` the ``2 * nhidden``-wide activation is recomputed and never
  kept
* fixconn — ``/root/reference/src/layer/fixconn_layer-inl.hpp`` (frozen
  sparse weight loaded from a ``nrow ncol nnz`` + ``row col val`` text
  file; never updated)
* flatten — ``/root/reference/src/layer/flatten_layer-inl.hpp``
  (image → flat matrix node; here NHWC-ravel instead of NCHW-ravel)
"""

from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .base import Layer, Params, Shape, register
from .sequence import Branch


@register
class FullConnectLayer(Layer):
    type_name = "fullc"

    def infer_shape(self, in_shapes: Sequence[Shape]) -> List[Shape]:
        self._check_arity(in_shapes, 1)
        (shape,) = in_shapes
        if len(shape) not in (2, 3):
            raise ValueError(
                "FullcLayer: input needs to be a matrix or sequence node"
            )
        if self.param.num_hidden <= 0:
            raise ValueError("FullcLayer: must set nhidden correctly")
        nin = shape[-1]
        if self.param.num_input_node == 0:
            self.param.num_input_node = nin
        elif self.param.num_input_node != nin:
            raise ValueError("FullcLayer: input hidden nodes inconsistent")
        # sequence nodes (N, T, D) project per position
        return [tuple(shape[:-1]) + (self.param.num_hidden,)]

    def init_params(self, key, in_shapes) -> Params:
        p = self.param
        nin, nout = in_shapes[0][-1], p.num_hidden
        out: Params = {"wmat": p.rand_init_weight(key, (nout, nin), nin, nout)}
        if p.no_bias == 0:
            out["bias"] = jnp.full((nout,), p.init_bias, jnp.float32)
        return out

    def apply(self, params, inputs, *, train=False, rng=None, step=None):
        x = inputs[0]
        y = x @ params["wmat"].astype(x.dtype).T
        if "bias" in params:
            y = y + params["bias"].astype(x.dtype)
        return [y]


@register
class GatedMLPLayer(Layer, Branch):
    type_name = "gated_mlp"
    # cast where they are used, inside the layer's checkpoint
    f32_tags = frozenset({"wmat", "wproj", "norm", "postnorm"})

    def set_param(self, name, val):
        if not self.set_branch_param(name, val):
            super().set_param(name, val)

    def infer_shape(self, in_shapes: Sequence[Shape]) -> List[Shape]:
        self._check_arity(in_shapes, 1)
        if len(in_shapes[0]) not in (2, 3):
            raise ValueError(
                "gated_mlp: input needs to be a matrix or sequence node")
        if self.param.num_hidden <= 0:
            raise ValueError("gated_mlp: must set nhidden correctly")
        return [tuple(in_shapes[0])]

    def init_params(self, key, in_shapes) -> Params:
        p = self.param
        d, nh = in_shapes[0][-1], p.num_hidden
        k1, k2 = jax.random.split(key)
        out = {"wmat": p.rand_init_weight(k1, (2 * nh, d), d, 2 * nh),
               "wproj": p.rand_init_weight(k2, (d, nh), nh, d)}
        out.update(self.branch_params(d))
        return out

    def apply(self, params, inputs, *, train=False, rng=None, step=None):
        x = inputs[0]
        nh = self.param.num_hidden
        gv = self.branch_in(params, x) @ params["wmat"].astype(x.dtype).T
        y = (jax.nn.silu(gv[..., :nh]) * gv[..., nh:]) @ params[
            "wproj"].astype(x.dtype).T
        return [self.branch_out(params, x, y)]


@register
class FixConnectLayer(Layer):
    """fullc with a frozen sparse weight matrix read from a text file."""

    type_name = "fixconn"

    def __init__(self) -> None:
        super().__init__()
        self.fname_weight = "NULL"
        self._wmat: np.ndarray | None = None

    def set_param(self, name, val):
        if name == "fixconn_weight":
            self.fname_weight = val
        super().set_param(name, val)

    def infer_shape(self, in_shapes: Sequence[Shape]) -> List[Shape]:
        self._check_arity(in_shapes, 1)
        (shape,) = in_shapes
        if len(shape) != 2:
            raise ValueError("FixConnLayer: input needs to be a matrix node")
        if self.param.num_hidden <= 0:
            raise ValueError("FixConnLayer: must set nhidden correctly")
        if self.fname_weight == "NULL":
            raise ValueError("FixConnLayer: must specify fixconn_weight")
        self._wmat = self._load_sparse(self.fname_weight, self.param.num_hidden, shape[1])
        return [(shape[0], self.param.num_hidden)]

    @staticmethod
    def _load_sparse(fname: str, nrow_want: int, ncol_want: int) -> np.ndarray:
        # format parity: fixconn_layer-inl.hpp:40-55
        with open(fname, "r", encoding="utf-8") as f:
            toks = f.read().split()
        nrow, ncol, nnz = int(toks[0]), int(toks[1]), int(toks[2])
        if nrow != nrow_want or ncol != ncol_want:
            raise ValueError("FixConnLayer: fixconn_weight shape does not match architecture")
        w = np.zeros((nrow, ncol), np.float32)
        vals = toks[3:]
        if len(vals) != 3 * nnz:
            raise ValueError("FixConnLayer: fixconn_weight invalid sparse matrix format")
        for k in range(nnz):
            x, y, v = int(vals[3 * k]), int(vals[3 * k + 1]), float(vals[3 * k + 2])
            if not (0 <= x < nrow and 0 <= y < ncol):
                raise ValueError("FixConnLayer: fixconn_weight index exceeds matrix shape")
            w[x, y] = v
        return w

    def apply(self, params, inputs, *, train=False, rng=None, step=None):
        assert self._wmat is not None, "infer_shape must run before apply"
        x = inputs[0]
        w = jnp.asarray(self._wmat, x.dtype)
        return [x @ w.T]


@register
class FlattenLayer(Layer):
    type_name = "flatten"

    def infer_shape(self, in_shapes: Sequence[Shape]) -> List[Shape]:
        self._check_arity(in_shapes, 1)
        (shape,) = in_shapes
        n = 1
        for d in shape[1:]:
            n *= d
        return [(shape[0], n)]

    def apply(self, params, inputs, *, train=False, rng=None, step=None):
        x = inputs[0]
        return [x.reshape(x.shape[0], -1)]
