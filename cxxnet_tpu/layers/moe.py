"""Routed experts: ``routed_experts``, a feed-forward layer of gated
(SwiGLU) experts behind a top-k router, computing the share of the
experts THIS device holds.

New TPU-first scope.  It is the layer expert parallelism asks for: the
router keeps its full width, its top-k and its weights over all
``nexpert`` experts; the device holds ``nheld`` of them, from
``first_expert`` on, and adds only the terms of the experts it holds.
With ``nheld = nexpert`` that is the whole layer; with fewer, the sum
over the ranks of an expert-parallel group (the shared expert counted
once) is the whole layer, and on one device the partial sum is what
goes on — nothing stands in for the absent ranks or their exchange.

**In a share the routing weights are constants of the backward pass**
(``stop_gradient``; a whole layer differentiates them like everything
else).  The cotangent of a token's ``topk`` weights is a sum over the
ranks that hold its experts, and the router's gradient the sum of all
ranks' parts; one rank alone has the terms of its own experts, and
that part is no estimate of the whole: it says "the held experts
answer, the others do not".  Applied to the router it drove the held
experts' share of all picks from 1/16 to 60% in a hundred steps of
adam, and left to flow into the router's input with the router frozen
it still raised their load by half (PERF.md, PR 33).  So a share's
router gets a gradient of zero, stays where it started under any
updater, and nothing upstream learns to steer tokens to the held
experts; the exchange that would complete the sum is ``parallel/``'s
to bring (ROADMAP R2).

    p = softmax(u W_r^T)  in float32, over all nexpert
    (w, e) = the topk largest of p;  w = w / sum(w)  with norm_topk = 1
    y = sum over the pairs (token, e) with e held of
            w * W_d^e (silu(W_g^e u) * W_u^e u)
      + sigmoid(u . w_s) * W_sd (silu(W_sg u) * W_su u)     with shared_hidden

With ``score_func = sigmoid`` (the DeepSeek-V3 family's router) ``p =
sigmoid(u W_r^T)``; with ``select_bias = 1`` the ``topk`` are the
largest of ``p + b`` for a leaf ``score_bias`` ``b (nexpert,)`` while
the weights stay the unbiased ``p`` of the chosen, then ``w = w /
(sum(w) + 1e-20)`` and, last, ``w = routed_scale * w``.  ``b`` enters
the selection only: its gradient is exactly zero and no updater moves
it (the family moves it by a balance rule between steps, from all
ranks' loads; that rule is not here).  ``shared_gate = 0`` adds the
shared expert ungated.

**No pair is dropped and no expert has a capacity.**  The (token,
expert) pairs are sorted by expert, pairs of experts held elsewhere
last; the tokens' rows are gathered in that order, the two grouped
products (``jax.lax.ragged_dot``, which the TPU compiler turns into a
kernel that visits the tiles of rows the groups really hold) run over
the held groups, and the rows go back to their tokens by the inverse
permutation.  Every buffer has room for all ``tokens * topk`` pairs, so
whatever the router does, every pair routed to a held expert is
computed.  Both reorderings are permutations and are differentiated as
such (a gather each way, never a scatter).

``routed_experts`` config keys:

* ``nexpert`` (the router's width), ``topk``, ``nhidden`` (an expert's
  width) — required
* ``first_expert`` (default 0), ``nheld`` (default: all from
  ``first_expert`` on) — this device's share
* ``shared_hidden`` — width of the always-on shared expert (default 0:
  none), under its sigmoid gate unless ``shared_gate = 0`` (default 1);
  ``norm_topk`` (default 1)
* ``score_func`` — ``softmax`` (default) or ``sigmoid``; ``select_bias``
  (default 0) — 1 chooses by score + ``score_bias``; ``routed_scale``
  (default 1) multiplies the chosen weights
* ``prenorm`` / ``residual_scale`` / ``eps`` — the residual branch in
  one layer (``sequence.Branch``); ``init_sigma`` for every matrix

Parameters (tags): ``wgate`` (nexpert, D); the held experts' matrices
as the grouped product reads them, ``(expert, in, out)`` — ``wmat``
(nheld, D, 2 nhidden) gate | up and ``wproj`` (nheld, nhidden, D): kept
``(out, in)`` like the framework's other matrices they would be
transposed for every product, and the compiler then carried them
through the scanned step in the transposed layout, with a copy of every
expert's weight and of both its adam moments at the loop's edges
(4.5 GB at 4 x 32 experts; read from compiles for a described v5e, PR
33); and with a shared expert
``shared_wmat`` (2 shared_hidden, D), ``shared_wproj`` (D,
shared_hidden), ``shared_gate`` (1, D) unless ``shared_gate = 0``;
``score_bias`` (nexpert,) with ``select_bias``, started at 0; ``norm``
(D) with ``prenorm``.
All float32 at rest, cast where used; the router's product is float32
at the highest precision.

State (``aux``, carried through the step programs like batch-norm's
running statistics and read once a round by
``NetTrainer.count_layer_state``): ``pairs`` — pairs routed to held
experts; ``pairs_max`` — each step's fullest held expert's pairs,
summed; ``pairs_dropped`` — 0, by the construction above.  uint32,
wrapping: the reader takes differences.

Scopes inside the layer's: ``route`` (router, softmax, top-k),
``dispatch`` (sort and gather), ``experts`` (the grouped products),
``combine`` (the way back and the weights), ``shared``.

The older ``moe`` type (``sequence.MoELayer``) stays beside this one:
it is a different function (one linear projection an expert to another
width, every expert on every token, a soft mixture by default) that
GSPMD shards over the mesh's ``model`` axis; this one routes, and is
what a published mixture-of-experts block is.
"""

from __future__ import annotations

import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from .base import Layer, Params, Shape, register
from .sequence import Branch

COUNTERS = ("pairs", "pairs_max", "pairs_dropped")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(x, order, inv, k):
    """Row ``r`` of the result is token ``order[r] // k``'s: the pairs
    in sorted order, a token's row once for each of its ``k`` picks.
    ``inv`` is the inverse of the permutation ``order``."""
    del inv
    return x[order // k]


def _dispatch_fwd(x, order, inv, k):
    return x[order // k], (inv, x.shape[0])


def _dispatch_bwd(k, res, g):
    inv, m = res
    return g[inv].reshape(m, k, g.shape[-1]).sum(axis=1), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _unsort(y, order, inv):
    """``y`` back in the pairs' own order: row ``p`` is ``y[inv[p]]``."""
    del order
    return y[inv]


def _unsort_fwd(y, order, inv):
    return y[inv], order


def _unsort_bwd(order, g):
    return g[order], None, None


_unsort.defvjp(_unsort_fwd, _unsort_bwd)


def route(logits, topk: int, norm_topk: bool = True, *,
          score_func: str = "softmax", bias=None, scale: float = 1.0):
    """``logits (M, E)`` float32 -> (weights ``(M, k)`` float32, expert
    ids ``(M, k)`` int32): the ``topk`` largest of the softmax over all
    ``E``, by index so a tie admits no extra expert, divided by their
    sum with ``norm_topk``.  ``score_func = "sigmoid"`` scores each
    expert alone; ``bias (E,)`` is added for the choice only — the
    weights are the unbiased scores of the chosen; ``scale`` multiplies
    them last."""
    logits = logits.astype(jnp.float32)
    if score_func == "softmax" and bias is None:
        w, idx = lax.top_k(jax.nn.softmax(logits, axis=-1), topk)
        if norm_topk:
            w = w / w.sum(axis=-1, keepdims=True)
    else:
        p = (jax.nn.sigmoid(logits) if score_func == "sigmoid"
             else jax.nn.softmax(logits, axis=-1))
        _, idx = lax.top_k(
            p if bias is None
            else p + lax.stop_gradient(bias.astype(jnp.float32)), topk)
        w = jnp.take_along_axis(p, idx, axis=-1)
        if norm_topk:
            w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    if scale != 1.0:
        w = w * jnp.float32(scale)
    return w, idx.astype(jnp.int32)


def held_experts(x, w, idx, wmat, wproj, first: int):
    """The held experts' part of the layer: ``x (M, D)``, the router's
    ``w`` / ``idx (M, k)``, ``wmat (G, D, 2F)`` and ``wproj (G, F, D)``
    of the ``G`` experts ``first .. first + G - 1`` -> (``y (M, D)``,
    pairs a held expert ``(G,)`` int32)."""
    m, k = idx.shape
    g, _, f2 = wmat.shape
    f = f2 // 2
    with jax.named_scope("dispatch"):
        local = idx.reshape(-1) - first
        key = jnp.where((local >= 0) & (local < g), local, g)
        pair = lax.iota(jnp.int32, m * k)
        skey, order = lax.sort((key, pair), num_keys=1)
        _, inv = lax.sort((order, pair), num_keys=1)
        counts = (key[:, None] == lax.iota(jnp.int32, g)[None]).sum(
            axis=0, dtype=jnp.int32)
        valid = (skey < g)[:, None]
        xs = jnp.where(valid, _dispatch(x, order, inv, k), 0)
    with jax.named_scope("experts"):
        # the kernels accumulate in float32 and put out the rows in the
        # activations' dtype: (tokens * topk, 2F) and (tokens * topk, D)
        # in float32 would be a gigabyte a layer
        gu = lax.ragged_dot(xs, wmat, counts,
                            preferred_element_type=x.dtype)
        h = (jax.nn.silu(gu[:, :f].astype(jnp.float32))
             * gu[:, f:].astype(jnp.float32)).astype(x.dtype)
        ys = lax.ragged_dot(jnp.where(valid, h, 0), wproj, counts,
                            preferred_element_type=x.dtype)
    with jax.named_scope("combine"):
        # rows past the held groups are no group's: whatever the
        # grouped product left there is put out
        ys = jnp.where(valid, ys, 0)
        y = (_unsort(ys, order, inv).reshape(m, k, -1)
             * w.astype(x.dtype)[..., None]).sum(axis=1)
    return y, counts


@register
class RoutedExpertsLayer(Layer, Branch):
    type_name = "routed_experts"
    #: state leaf -> the round's counter it is added to
    #: (``NetTrainer.count_layer_state``)
    aux_counters = {name: "expert_" + name for name in COUNTERS}
    f32_tags = frozenset({"wgate", "wmat", "wproj", "shared_wmat",
                          "shared_wproj", "shared_gate", "score_bias",
                          "norm"})

    def __init__(self) -> None:
        super().__init__()
        self.nexpert = 0
        self.topk = 0
        self.first_expert = 0
        self.nheld = 0  # 0: all from first_expert on
        self.shared_hidden = 0
        self.shared_gate = 1
        self.norm_topk = 1
        self.score_func = "softmax"
        self.select_bias = 0
        self.routed_scale = 1.0

    _INT_KEYS = ("nexpert", "topk", "first_expert", "nheld",
                 "shared_hidden", "shared_gate", "norm_topk", "select_bias")

    def set_param(self, name, val):
        if name in self._INT_KEYS:
            setattr(self, name, int(val))
        elif name == "score_func":
            if val not in ("softmax", "sigmoid"):
                raise ValueError(
                    f"routed_experts: score_func is softmax or sigmoid, "
                    f"got {val!r}")
            self.score_func = val
        elif name == "routed_scale":
            self.routed_scale = float(val)
        elif not self.set_branch_param(name, val):
            super().set_param(name, val)

    def _held(self) -> int:
        return self.nheld or self.nexpert - self.first_expert

    def infer_shape(self, in_shapes: Sequence[Shape]) -> List[Shape]:
        self._check_arity(in_shapes, 1)
        if len(in_shapes[0]) not in (2, 3):
            raise ValueError("routed_experts: input must be a matrix or a "
                             "sequence node")
        if self.param.num_hidden <= 0 or self.nexpert < 1 or not (
                1 <= self.topk <= self.nexpert):
            raise ValueError("routed_experts: set nexpert, nhidden and "
                             "1 <= topk <= nexpert")
        if self.first_expert < 0 or self._held() < 1 or (
                self.first_expert + self._held() > self.nexpert):
            raise ValueError(
                f"routed_experts: experts {self.first_expert}.."
                f"{self.first_expert + self._held() - 1} are not among "
                f"the {self.nexpert} routed")
        return [tuple(in_shapes[0])]

    def init_params(self, key, in_shapes) -> Params:
        d = in_shapes[0][-1]
        f, g, sh = self.param.num_hidden, self._held(), self.shared_hidden
        ks = jax.random.split(key, 6)
        sigma = self.param.init_sigma

        def normal(k, shape):
            return jax.random.normal(k, shape, jnp.float32) * sigma

        out = {"wgate": normal(ks[0], (self.nexpert, d)),
               "wmat": normal(ks[1], (g, d, 2 * f)),
               "wproj": normal(ks[2], (g, f, d))}
        if sh:
            out.update({"shared_wmat": normal(ks[3], (2 * sh, d)),
                        "shared_wproj": normal(ks[4], (d, sh))})
            if self.shared_gate:
                out["shared_gate"] = normal(ks[5], (1, d))
        if self.select_bias:
            out["score_bias"] = jnp.zeros((self.nexpert,), jnp.float32)
        out.update(self.branch_params(d))
        return out

    def init_aux(self, in_shapes):
        return {name: jnp.zeros((), jnp.uint32) for name in COUNTERS}

    def apply(self, params, inputs, *, train=False, rng=None, step=None):
        return self._run(params, inputs[0])[0]

    def apply_stateful(self, params, aux, inputs, *, train=False, rng=None,
                       step=None):
        outs, counts = self._run(params, inputs[0])
        counts = counts.astype(jnp.uint32)
        return outs, {
            "pairs": aux["pairs"] + counts.sum(),
            "pairs_max": aux["pairs_max"] + counts.max(),
            "pairs_dropped": aux["pairs_dropped"],
        }

    def _run(self, params, x0):
        cdt = x0.dtype
        u = self.branch_in(params, x0)
        x = u.reshape(-1, u.shape[-1])
        with jax.named_scope("route"):
            logits = jnp.dot(x.astype(jnp.float32), params["wgate"].T,
                             precision=lax.Precision.HIGHEST)
            w, idx = route(logits, self.topk, bool(self.norm_topk),
                           score_func=self.score_func,
                           bias=params.get("score_bias"),
                           scale=self.routed_scale)
            if self._held() < self.nexpert:
                # a share: the weights' cotangent needs the other ranks'
                w = lax.stop_gradient(w)
        y, counts = held_experts(
            x, w, idx, params["wmat"].astype(cdt),
            params["wproj"].astype(cdt), self.first_expert)
        if self.shared_hidden:
            with jax.named_scope("shared"):
                sh = self.shared_hidden
                gu = x @ params["shared_wmat"].astype(cdt).T
                s = (jax.nn.silu(gu[:, :sh]) * gu[:, sh:]) @ params[
                    "shared_wproj"].astype(cdt).T
                if self.shared_gate:
                    s = jax.nn.sigmoid(
                        x @ params["shared_gate"].astype(cdt).T) * s
                y = y + s
        return [self.branch_out(x0, y.reshape(x0.shape))], counts
