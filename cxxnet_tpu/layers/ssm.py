"""State-space layers: ``mamba2``, the Mamba-2 mixer (Dao & Gu 2024).

New TPU-first scope, like ``sequence.py``: the reference framework has
no sequence axis.  The layer follows the published block

    [z | xBC | dt] = u W_in                  widths E | E + 2GS | H
    xBC = silu(conv(xBC) + b)                depthwise, causal, width K
    [x | B | C] = xBC                        x as H heads of P, E = H P;
                                             B, C as G groups of S: head
                                             h reads group h G // H
    dt = softplus(dt + dt_bias);  a = -exp(a_log)      one scalar a head
    S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t;  y_t = S_t C_t + d x_t
    y = rms_norm(y * silu(z)) * gate_norm    over each group's E / G
                                             columns alone
    out = y W_out

with the scan in its chunked form (``ops/ssd.py``): lowered for a TPU,
at heads of 64 or 128 columns on a state a multiple of 128 wide, whole
chunks of 128 or 256 tokens and one group, it is two Pallas kernels with
their own backward that keep a chunk's decay and score matrices and the
state on the chip (``ops/ssd_fused.py``); everywhere else, and with
``ngroup > 1``, plain ``jax.numpy`` differentiated by ``jax.grad``.  No
key chooses: the platform the program is lowered for and the shapes do.

``mamba2`` config keys:

* ``nhead`` (H), ``head_dim`` (P), ``nstate`` (S) — required
* ``conv_width`` (K, default 4), ``ngroup`` (G, default 1: ``B`` and
  ``C`` shared by all heads; G must divide H), ``chunk`` (default 256),
  ``eps`` (1e-5).  The groups are what Megatron's tensor parallelism
  divides a mixer by: rank ``r`` of ``G`` holds group ``r``, its ``H /
  G`` heads' columns of ``z``, ``x`` and ``dt``, their rows of ``W_out``
  and a gated norm of its own, so ONE RANK'S SHARE of a ``G``-group
  mixer is this layer at ``nhead = H / G``, ``ngroup = 1``, and the
  shares' branches add up to the whole layer's
* ``prenorm`` / ``residual_scale`` — the residual branch in one layer
  (``sequence.Branch``): ``y = x + residual_scale * f(rms_norm(x))``
* ``init_sigma`` for the two matrices; ``a_log``, ``dt_bias``, ``d``,
  the conv and the norms start as the published code starts them
* a second input, the net's token ids (``layer[x,0->y] = mamba2``):
  at a document's first token (one begins after every separator id 0)
  the scan starts from ``S = 0`` and the convolution sees zeros before
  it.  With one input a row is one document.

Parameters (tags): ``wmat`` (E + E + 2GS + H, D), ``conv`` (E + 2GS, K),
``conv_bias``, ``dt_bias`` (H), ``a_log`` (H), ``d`` (H), ``gate_norm``
(E), ``wproj`` (D, E), and ``norm`` (D) with ``prenorm``.  All stay
float32 at rest under mixed precision and are cast where they are
used, inside the layer's ``jax.checkpoint`` under ``remat``, so no
bfloat16 copy of a matrix outlives its layer.

State (``aux``, carried through the step programs and read once a
round by ``NetTrainer.count_layer_state``, as ``gated_deltanet``'s):
``scan_tokens`` — tokens through the scan; ``scan_tokens_fused`` —
those of them the fused kernels computed, which the branch that ran
says for itself (``ops/ssd.ssd_scan_counted``).  uint32, wrapping: the
reader takes differences.  The round's counters ``ssd_scan_tokens`` and
``ssd_scan_tokens_fused`` sum them over the layers.

Every stage runs under a ``jax.named_scope`` of its own (``in_proj``,
``conv``, ``scan``, ``gate_norm``, ``out_proj``) inside the layer's, so
a profiler trace splits the mixer; the kernels, forward and backward,
run under ``scan``.
"""

from __future__ import annotations

import math
from typing import List, Sequence

import jax
import jax.numpy as jnp

from ..ops.ssd import doc_index, ssd_scan_counted
from .base import Layer, Params, Shape, register
from .sequence import Branch, _check_ids_input, rms_norm


#: the layer's ``aux`` state: what the scan counts
COUNTERS = ("scan_tokens", "scan_tokens_fused")


def causal_conv(x, w, bias, doc=None):
    """Depthwise causal convolution over time: ``y_t = bias + sum_j
    w[:, K-1-j] x_{t-j}`` for ``j < K``, ``x (N,T,C)``, ``w (C,K)``; a
    tap that reaches before the row's or the token's document's start
    reads zero."""
    n, t, _ = x.shape
    k = w.shape[1]
    y = x * w[:, k - 1] + bias
    for j in range(1, min(k, t)):
        past = jnp.pad(x[:, :t - j], ((0, 0), (j, 0), (0, 0)))
        if doc is not None:
            same = jnp.pad(doc[:, j:] == doc[:, :t - j], ((0, 0), (j, 0)))
            past = jnp.where(same[..., None], past, 0)
        y = y + past * w[:, k - 1 - j]
    return y


@register
class Mamba2Layer(Layer, Branch):
    type_name = "mamba2"
    #: state leaf -> the round's counter it is added to
    #: (``NetTrainer.count_layer_state``)
    aux_counters = {name: "ssd_" + name for name in COUNTERS}
    f32_tags = frozenset({"wmat", "conv", "conv_bias", "dt_bias", "a_log",
                          "d", "gate_norm", "wproj", "norm", "postnorm"})

    def __init__(self) -> None:
        super().__init__()
        self.nhead = 0
        self.head_dim = 0
        self.nstate = 0
        self.conv_width = 4
        self.ngroup = 1
        self.chunk = 256

    def set_param(self, name, val):
        if name == "nhead":
            self.nhead = int(val)
        elif name == "head_dim":
            self.head_dim = int(val)
        elif name == "nstate":
            self.nstate = int(val)
        elif name == "conv_width":
            self.conv_width = int(val)
        elif name == "chunk":
            self.chunk = int(val)
        elif name == "ngroup":
            self.ngroup = int(val)
        elif not self.set_branch_param(name, val):
            super().set_param(name, val)

    def infer_shape(self, in_shapes: Sequence[Shape]) -> List[Shape]:
        _check_ids_input("mamba2", in_shapes)
        if len(in_shapes[0]) != 3:
            raise ValueError("mamba2: input must be a sequence node "
                             "(N, T, D)")
        if min(self.nhead, self.head_dim, self.nstate, self.conv_width,
               self.chunk) <= 0:
            raise ValueError("mamba2: set nhead, head_dim and nstate")
        if self.ngroup < 1 or self.nhead % self.ngroup:
            raise ValueError(
                f"mamba2: ngroup={self.ngroup} must divide "
                f"nhead={self.nhead}")
        return [tuple(in_shapes[0])]

    def init_params(self, key, in_shapes) -> Params:
        d = in_shapes[0][2]
        h, e, s, k = (self.nhead, self.nhead * self.head_dim,
                      self.ngroup * self.nstate, self.conv_width)
        k1, k2, k3, k4, k5 = jax.random.split(key, 5)
        sigma = self.param.init_sigma
        # a step drawn log-uniform in [1e-3, 1e-1], through the inverse
        # of softplus; a decay rate uniform in [1, 16]
        step = jnp.exp(jax.random.uniform(
            k4, (h,), jnp.float32, math.log(1e-3), math.log(1e-1)))
        bound = 1.0 / math.sqrt(k)
        out = {
            "wmat": jax.random.normal(
                k1, (2 * e + 2 * s + h, d), jnp.float32) * sigma,
            "conv": jax.random.uniform(
                k2, (e + 2 * s, k), jnp.float32, -bound, bound),
            "conv_bias": jnp.zeros((e + 2 * s,), jnp.float32),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "a_log": jnp.log(jax.random.uniform(
                k5, (h,), jnp.float32, 1.0, 16.0)),
            "d": jnp.ones((h,), jnp.float32),
            "gate_norm": jnp.ones((e,), jnp.float32),
            "wproj": jax.random.normal(k3, (d, e), jnp.float32) * sigma,
        }
        out.update(self.branch_params(d))
        return out

    def init_aux(self, in_shapes):
        return {name: jnp.zeros((), jnp.uint32) for name in COUNTERS}

    def apply(self, params, inputs, *, train=False, rng=None, step=None):
        return self._run(params, inputs)[0]

    def apply_stateful(self, params, aux, inputs, *, train=False, rng=None,
                       step=None):
        outs, fused = self._run(params, inputs)
        tokens = jnp.uint32(inputs[0].shape[0] * inputs[0].shape[1])
        return outs, {
            "scan_tokens": aux["scan_tokens"] + tokens,
            "scan_tokens_fused": aux["scan_tokens_fused"] + tokens * fused,
        }

    def _run(self, params, inputs):
        """``([out], 1 if the fused kernels computed the scan else 0)``."""
        x0 = inputs[0]
        n, t, _ = x0.shape
        h, p, g = self.nhead, self.head_dim, self.ngroup
        e, s = h * p, g * self.nstate
        cdt = x0.dtype
        f32 = jnp.float32
        doc = doc_index(inputs[1]) if len(inputs) > 1 else None
        u = self.branch_in(params, x0)
        with jax.named_scope("in_proj"):
            zxd = u @ params["wmat"].astype(cdt).T
            z, xbc, dt = (zxd[..., :e], zxd[..., e:2 * e + 2 * s],
                          zxd[..., 2 * e + 2 * s:])
        with jax.named_scope("conv"):
            xbc = jax.nn.silu(causal_conv(
                xbc, params["conv"].astype(cdt),
                params["conv_bias"].astype(cdt), doc))
            x = xbc[..., :e].reshape(n, t, h, p)
            b, c = xbc[..., e:e + s], xbc[..., e + s:]
            if g > 1:
                b, c = (v.reshape(n, t, g, self.nstate) for v in (b, c))
        with jax.named_scope("scan"):
            dt = jax.nn.softplus(dt.astype(f32) + params["dt_bias"])
            # the D skip goes with the scan: each form adds it on the
            # view of ``x`` it works on
            y, fused = ssd_scan_counted(
                x, dt, -jnp.exp(params["a_log"].astype(f32)), b, c, doc,
                self.chunk, skip=params["d"])
        with jax.named_scope("gate_norm"):
            y = y.reshape(n, t, e) * jax.nn.silu(z)
            if g > 1:  # each group's columns under a norm of their own
                y = rms_norm(y.reshape(n, t, g, e // g),
                             params["gate_norm"].reshape(g, e // g),
                             self.eps).reshape(n, t, e)
            else:
                y = rms_norm(y, params["gate_norm"], self.eps)
        with jax.named_scope("out_proj"):
            out = y @ params["wproj"].astype(cdt).T
        return [self.branch_out(params, x0, out)], fused
