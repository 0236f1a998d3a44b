"""Linear-attention layers whose decay is a matrix: ``gated_deltanet``,
the Gated DeltaNet mixer (Yang, Kautz & Hatamizadeh 2024) as the
Qwen3-Next family uses it, and ``kimi_delta``, Kimi Delta Attention
(the same rule with one decay a key channel; at the end of this file).

New TPU-first scope, beside ``ssm.py``.  The layer follows the
published block, per value head ``j`` (its key head is ``j // (Hv /
Hk)``)

    [q | k | v | z] = u W_in             widths HkDk | HkDk | HvDv | HvDv
    [b | a] = u W_ba                     widths Hv | Hv
    [q | k | v] = silu(conv([q | k | v]))    depthwise, causal, K wide, no bias
    q = q / |q| / sqrt(Dk);  k = k / |k|       (eps 1e-6 under the root)
    beta = sigmoid(b);  g = -exp(a_log) * softplus(a + dt_bias)
    S_t = e^{g_t} S_{t-1} + beta_t k_t (v_t - (e^{g_t} S_{t-1})^T k_t)^T
    o_t = S_t^T q_t
    y = rms_norm_Dv(o) * gate_norm * silu(z)       one norm a head
    out = y W_out

with the scan in its chunked form (``ops/gdn.gated_delta_scan``: on a
TPU, at the widths they are written for, fused Pallas kernels with
their own backward; everywhere else the same algorithm in plain
``jax.numpy`` differentiated by ``jax.grad``.  The program reads which
from the platform it is lowered for and from the shapes; no conf key
chooses, and the layer counts what ran: below).  ``q`` and ``k`` go to
the scan with their own ``Hk`` heads; a value head reads its key head.
The rows of ``W_in`` are ``q | k | v | z``, each head-major: a
permutation of the family's checkpoints, which interleave the four by
key-head group.

``gated_deltanet`` config keys:

* ``nkhead`` (Hk), ``nvhead`` (Hv, a multiple of Hk), ``key_dim`` (Dk),
  ``value_dim`` (Dv) — required
* ``conv_width`` (K, default 4), ``chunk`` (default 64, a power of
  two up to ``SEGMENT``), ``eps`` (1e-5: the pre-norm's and the gated
  norm's)
* ``prenorm`` / ``residual_scale`` — the residual branch in one layer
  (``sequence.Branch``)
* ``init_sigma`` for the three matrices; ``a_log``, ``dt_bias`` and the
  conv start as ``mamba2``'s do (a decay rate uniform in [1, 16], a
  step log-uniform in [1e-3, 1e-1] through the inverse of softplus,
  the conv uniform at 1/sqrt(K)); the norms start at 1
* a second input, the net's token ids (``layer[x,0->y] =
  gated_deltanet``): at a document's first token (one begins after
  every separator id 0) the scan starts from ``S = 0`` and the
  convolution sees zeros before it.  With one input a row is one
  document.

Parameters (tags): ``wmat`` (2 HkDk + 2 HvDv, D), ``wba`` (2 Hv, D),
``conv`` (2 HkDk + HvDv, K), ``a_log`` (Hv), ``dt_bias`` (Hv),
``gate_norm`` (Dv), ``wproj`` (D, HvDv), and ``norm`` (D) with
``prenorm``.  All stay float32 at rest under mixed precision and are
cast where they are used, as ``mamba2``'s.

State (``aux``, carried through the step programs and read once a
round by ``NetTrainer.count_layer_state``, as ``routed_experts``'):
``scan_tokens`` — tokens through the scan; ``scan_tokens_fused`` —
those of them the fused kernels computed, which the branch that ran
says for itself (``ops/gdn.gated_delta_scan_counted``).  uint32,
wrapping: the reader takes differences.  The round's counters
``gdn_scan_tokens`` and ``gdn_scan_tokens_fused`` sum them over the
layers.

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

from ..ops.gdn import gated_delta_scan_counted
from ..ops.kda import BLOCK, kimi_delta_scan_counted
from ..ops.ssd import doc_index
from .base import Layer, Params, Shape, register
from .sequence import Branch, _check_ids_input, rms_norm
from .ssm import causal_conv


#: the ``jax.numpy`` scan walks a longer row in segments of this many
#: tokens, each under ``jax.checkpoint``, so its backward holds one
#: segment's chunk matrices and not the row's (``ops/gdn.gated_delta_xla``;
#: the kernels keep no chunk matrices and walk the row whole)
SEGMENT = 2048

#: the layer's ``aux`` state: what the scan counts
COUNTERS = ("scan_tokens", "scan_tokens_fused")


def head_norm(o, w, eps: float):
    """``rms_norm`` over each head's ``Dv`` of ``o (N, T, H, Dv)``, taken
    on the view ``(N, T/8, H, 8, Dv)``: its row-major order is the order
    of the ``(N, T, H Dv)`` rows the scan's kernels write and read (eight
    tokens of one head are one tile), so on the TPU the norm and its
    gradient run in place, where the ``(N, T, H, Dv)`` view costs a
    float32 copy of the whole tensor each way — three of 134 MB a layer
    and step at 8192 tokens and 32 heads, outside every scope (read from
    a compile for a described v5e, PR 34).  The same numbers either way."""
    n, t, h, d = o.shape
    if t % 8:
        return rms_norm(o, w, eps)
    rows = o.reshape(n, t // 8, 8, h, d).transpose(0, 1, 3, 2, 4)
    return rms_norm(rows, w, eps).transpose(0, 1, 3, 2, 4).reshape(o.shape)


@register
class GatedDeltaNetLayer(Layer, Branch):
    type_name = "gated_deltanet"
    #: state leaf -> the round's counter it is added to
    #: (``NetTrainer.count_layer_state``)
    aux_counters = {name: "gdn_" + name for name in COUNTERS}
    f32_tags = frozenset({"wmat", "wba", "conv", "dt_bias", "a_log",
                          "gate_norm", "wproj", "norm", "postnorm"})

    def __init__(self) -> None:
        super().__init__()
        self.nkhead = 0
        self.nvhead = 0
        self.key_dim = 0
        self.value_dim = 0
        self.conv_width = 4
        self.chunk = 64

    #: every one must be set positive
    _INT_KEYS = ("nkhead", "nvhead", "key_dim", "value_dim", "conv_width",
                 "chunk")

    def set_param(self, name, val):
        if name in self._INT_KEYS:
            setattr(self, name, int(val))
        elif not self.set_branch_param(name, val):
            super().set_param(name, val)

    #: the keys a conf has to set, as an error names them
    _REQUIRED = "nkhead, nvhead, key_dim and value_dim"

    def infer_shape(self, in_shapes: Sequence[Shape]) -> List[Shape]:
        kind = self.type_name
        _check_ids_input(kind, in_shapes)
        if self.chunk & (self.chunk - 1) or self.chunk > SEGMENT:
            raise ValueError(
                f"{kind}: chunk={self.chunk} must be a power of "
                f"two, at most {SEGMENT}")
        if len(in_shapes[0]) != 3:
            raise ValueError(f"{kind}: input must be a sequence "
                             "node (N, T, D)")
        if min(getattr(self, k) for k in self._INT_KEYS) <= 0:
            raise ValueError(f"{kind}: set {self._REQUIRED}")
        if self.nvhead % self.nkhead:
            raise ValueError(
                f"{kind}: nkhead={self.nkhead} must divide "
                f"nvhead={self.nvhead}")
        return [tuple(in_shapes[0])]

    def _widths(self):
        return self.nkhead * self.key_dim, self.nvhead * self.value_dim

    def init_params(self, key, in_shapes) -> Params:
        d = in_shapes[0][2]
        ek, ev = self._widths()
        hv, k = self.nvhead, self.conv_width
        k1, k2, k3, k4, k5, k6 = jax.random.split(key, 6)
        sigma = self.param.init_sigma
        step = jnp.exp(jax.random.uniform(
            k4, (hv,), jnp.float32, math.log(1e-3), math.log(1e-1)))
        bound = 1.0 / math.sqrt(k)
        out = {
            "wmat": jax.random.normal(
                k1, (2 * ek + 2 * ev, d), jnp.float32) * sigma,
            "wba": jax.random.normal(k6, (2 * hv, d), jnp.float32) * sigma,
            "conv": jax.random.uniform(
                k2, (2 * ek + ev, k), jnp.float32, -bound, bound),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "a_log": jnp.log(jax.random.uniform(
                k5, (hv,), jnp.float32, 1.0, 16.0)),
            "gate_norm": jnp.ones((self.value_dim,), jnp.float32),
            "wproj": jax.random.normal(k3, (d, ev), jnp.float32) * sigma,
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
        hk, hv, dk, dv = self.nkhead, self.nvhead, self.key_dim, self.value_dim
        ek, ev = self._widths()
        cdt = x0.dtype
        f32 = jnp.float32
        doc = doc_index(inputs[1]) if len(inputs) > 1 else None
        u = self.branch_in(params, x0)
        with jax.named_scope("in_proj"):
            qkvz = u @ params["wmat"].astype(cdt).T
            qkv, z = qkvz[..., :2 * ek + ev], qkvz[..., 2 * ek + ev:]
            ba = (u @ params["wba"].astype(cdt).T).astype(f32)
        with jax.named_scope("conv"):
            qkv = jax.nn.silu(causal_conv(
                qkv, params["conv"].astype(cdt), jnp.zeros((), cdt), doc))
        with jax.named_scope("scan"):
            q = qkv[..., :ek].reshape(n, t, hk, dk)
            k = qkv[..., ek:2 * ek].reshape(n, t, hk, dk)
            v = qkv[..., 2 * ek:].reshape(n, t, hv, dv)
            beta = jax.nn.sigmoid(ba[..., :hv])
            g = -jnp.exp(params["a_log"].astype(f32)) * jax.nn.softplus(
                ba[..., hv:] + params["dt_bias"].astype(f32))
            # q and k go as the convolution left them: the scan brings
            # them to unit length, and q to 1 / sqrt(Dk)
            o, fused = gated_delta_scan_counted(
                q, k, v, g, beta, doc, self.chunk, SEGMENT, unit=1e-6,
                q_scale=1.0 / math.sqrt(dk))
        with jax.named_scope("gate_norm"):
            y = head_norm(o, params["gate_norm"], self.eps).reshape(
                n, t, ev) * jax.nn.silu(z)
        with jax.named_scope("out_proj"):
            out = y @ params["wproj"].astype(cdt).T
        return [self.branch_out(params, x0, out)], fused


@register
class KimiDeltaLayer(GatedDeltaNetLayer):
    """``kimi_delta``: Kimi Delta Attention (Kimi Linear, arXiv:2510.26692,
    section 3) with the bounded gate of the ``bailing_hybrid`` family —
    ``gated_deltanet``'s skeleton (projections, convolution, scan, gated
    norm, out), scopes and counters, per head of ``H``::

        [q | k | v | f | z] = u W_in         widths HDk | HDk | HDv | HDk | HDv
        b = u W_beta                         width H
        [q | k | v] = silu(conv([q | k | v]))    depthwise, causal, K wide
        q = q / |q| / sqrt(Dk);  k = k / |k|     (eps 1e-6 under the root)
        beta = sigmoid(b)
        g = lower_bound * sigmoid(exp(a_log) * (f + dt_bias))   float32: one
                                             decay a head AND key channel
        S_t = (I - beta_t k_t k_t^T) Diag(e^{g_t}) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t
        y = rms_norm_Dv(o) * gate_norm * sigmoid(z)
        out = y W_out

    with the scan ``ops/kda.kimi_delta_scan`` (on a TPU, at the widths
    they are written for, the kernels of ``ops/kda_fused.py``; the plain
    ``jax.numpy`` form everywhere else; no conf key chooses).

    Config keys: ``nhead`` (H), ``key_dim``, ``value_dim`` — required;
    ``lower_bound`` (default -5, in ``[-80 / 16, 0)``: the scan's blocks of
    16 tokens keep every factor inside float32 only down to there);
    ``conv_width``, ``chunk``, ``eps``, ``prenorm`` / ``residual_scale``,
    ``init_sigma`` and the second input (the net's token ids) as
    ``gated_deltanet``'s.  ``a_log`` (H) and the conv start as
    ``gated_deltanet``'s (a rate uniform in [1, 16]); ``dt_bias`` (H Dk)
    starts uniform in [-0.5, 0): under ``gated_deltanet``'s draw (the
    inverse softplus of a small step, -6.9 to -2.3) times a rate of up to
    16 this gate's sigmoid starts saturated at 0 for most heads, whose
    ``a_log``, ``dt_bias`` and gate projection then get gradients of 1e-13
    and never move; from [-0.5, 0) every head's argument starts inside
    (-8, 0) plus the projection's own part and the gate is live.

    Parameters (tags): ``wmat`` (3 HDk + 2 HDv, D),
    ``wbeta`` (H, D), ``conv`` (2 HDk + HDv, K), ``a_log`` (H), ``dt_bias``
    (H Dk), ``gate_norm`` (Dv), ``wproj`` (D, HDv), ``norm`` (D) with
    ``prenorm``.

    State (``aux``): ``scan_tokens``, ``scan_tokens_fused``, summed into the
    round's counters ``kda_scan_tokens`` and ``kda_scan_tokens_fused``.
    """
    type_name = "kimi_delta"
    aux_counters = {name: "kda_" + name for name in COUNTERS}
    f32_tags = frozenset({"wmat", "wbeta", "conv", "dt_bias", "a_log",
                          "gate_norm", "wproj", "norm", "postnorm"})

    _REQUIRED = "nhead, key_dim and value_dim"

    def __init__(self) -> None:
        super().__init__()
        self.lower_bound = -5.0

    def set_param(self, name, val):
        if name == "nhead":
            self.nkhead = self.nvhead = int(val)
        elif name == "lower_bound":
            self.lower_bound = float(val)
        elif name in ("nkhead", "nvhead"):
            raise ValueError(f"kimi_delta: set nhead, not {name} (q, k and "
                             "v have as many heads)")
        else:
            super().set_param(name, val)

    def infer_shape(self, in_shapes: Sequence[Shape]) -> List[Shape]:
        if not -80.0 / BLOCK <= self.lower_bound < 0:
            raise ValueError(
                f"kimi_delta: lower_bound={self.lower_bound} must lie in "
                f"[{-80.0 / BLOCK}, 0): over the scan's blocks of {BLOCK} "
                "tokens a stronger decay leaves float32")
        if self.chunk < BLOCK:
            raise ValueError(f"kimi_delta: chunk={self.chunk} must be at "
                             f"least {BLOCK}")
        return super().infer_shape(in_shapes)

    def init_params(self, key, in_shapes) -> Params:
        d = in_shapes[0][2]
        ek, ev = self._widths()
        h, k = self.nvhead, self.conv_width
        k1, k2, k3, k4, k5, k6 = jax.random.split(key, 6)
        sigma = self.param.init_sigma
        bound = 1.0 / math.sqrt(k)
        out = {
            "wmat": jax.random.normal(
                k1, (3 * ek + 2 * ev, d), jnp.float32) * sigma,
            "wbeta": jax.random.normal(k6, (h, d), jnp.float32) * sigma,
            "conv": jax.random.uniform(
                k2, (2 * ek + ev, k), jnp.float32, -bound, bound),
            "dt_bias": jax.random.uniform(k4, (ek,), jnp.float32, -0.5, 0.0),
            "a_log": jnp.log(jax.random.uniform(
                k5, (h,), jnp.float32, 1.0, 16.0)),
            "gate_norm": jnp.ones((self.value_dim,), jnp.float32),
            "wproj": jax.random.normal(k3, (d, ev), jnp.float32) * sigma,
        }
        out.update(self.branch_params(d))
        return out

    def _run(self, params, inputs):
        x0 = inputs[0]
        n, t, _ = x0.shape
        h, dk, dv = self.nvhead, self.key_dim, self.value_dim
        ek, ev = self._widths()
        cdt = x0.dtype
        f32 = jnp.float32
        doc = doc_index(inputs[1]) if len(inputs) > 1 else None
        u = self.branch_in(params, x0)
        with jax.named_scope("in_proj"):
            mixed = u @ params["wmat"].astype(cdt).T
            qkv = mixed[..., :2 * ek + ev]
            f = mixed[..., 2 * ek + ev:3 * ek + ev].astype(f32)
            z = mixed[..., 3 * ek + ev:]
            b = (u @ params["wbeta"].astype(cdt).T).astype(f32)
        with jax.named_scope("conv"):
            qkv = jax.nn.silu(causal_conv(
                qkv, params["conv"].astype(cdt), jnp.zeros((), cdt), doc))
        with jax.named_scope("scan"):
            q = qkv[..., :ek].reshape(n, t, h, dk)
            k = qkv[..., ek:2 * ek].reshape(n, t, h, dk)
            v = qkv[..., 2 * ek:].reshape(n, t, h, dv)
            rate = jnp.repeat(jnp.exp(params["a_log"].astype(f32)), dk)
            g = jnp.float32(self.lower_bound) * jax.nn.sigmoid(
                rate * (f + params["dt_bias"].astype(f32)))
            o, fused = kimi_delta_scan_counted(
                q, k, v, g.reshape(n, t, h, dk), jax.nn.sigmoid(b), doc,
                self.chunk, SEGMENT, unit=1e-6, q_scale=1.0 / math.sqrt(dk))
        with jax.named_scope("gate_norm"):
            y = head_norm(o, params["gate_norm"], self.eps).reshape(
                n, t, ev) * jax.nn.sigmoid(z)
        with jax.named_scope("out_proj"):
            out = y @ params["wproj"].astype(cdt).T
        return [self.branch_out(params, x0, out)], fused
