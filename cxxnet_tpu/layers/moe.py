"""Routed experts: ``routed_experts``, a feed-forward layer of experts —
gated (SwiGLU) or ungated (``relu(.)^2``), in the stream's own width or
in a narrower latent — behind a top-k router, computing the share of
the experts THIS device holds.

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
(sum(w) + 1e-20)`` and, last, ``w = routed_scale * w``.  With ``n_group
= G > 1`` the choice is group-limited first (DeepSeek-V3's ``noaux_tc``):
the router's width is ``G`` groups of consecutive experts, a group's
score is the sum of its 2 largest ``p + b``, a token keeps its
``topk_group`` best groups and its ``topk`` are the largest ``p + b``
among THEIR experts.  ``b`` enters
the selection only: its gradient is exactly zero and no updater moves
it (the family moves it by a balance rule between steps, from all
ranks' loads; that rule is not here).  ``shared_gate = 0`` adds the
shared expert ungated.

With ``expert_act = relu2`` (the Nemotron-H family's ``mlp_hidden_act``)
an expert, and the shared expert, is two matrices and no gate: ``W_d
relu(W_u x)^2``.  With ``latent_hidden = L`` (LatentMoE) the held experts
live in an ``L``-wide latent behind two projections of the layer's own::

    l = u W_in^T                                  (D -> L), once a token
    r = sum over the pairs (token, e) with e held of  w * expert_e(l)
    y = r W_out^T + shared(u)                     (L -> D), once a token

— the router and the shared expert keep reading ``u``; route, dispatch,
the grouped products and the sum onto the tokens all run on ``L``-wide
rows, and the projection back comes after that sum.  In a share the two
projections are computed by every rank alike (replicated, like the
router): ``r`` is linear in the experts' terms, so the ranks' ``r
W_out^T`` add up to the whole layer's.

With ``expert_act = reglu`` (the SmallThinker family's sparse ReGLU) an
expert is the gated three matrices with ``relu`` in the gate's place of
``silu``: ``W_d (relu(W_g x) * W_u x)``, on the same fused ``gate | up``.

**A router that reads another node** (the SmallThinker family routes
BEFORE it attends, so that a device can fetch the chosen experts while
attention runs).  Given a SECOND input the router reads that node and
the experts the first::

    layer[x0,h0->h1] = routed_experts:moe0      x0 = h0 + attention(...)
      route_norm = attn0

    p = softmax(rms_norm(h0; attn0's norm) W_r^T),   y = ... expert_e(u)

``route_norm = <layer>`` puts the second input through an ``rms_norm``
under THAT layer's ``norm`` weight (its ``prenorm``; this layer's
``eps``) first — the attention's own normed input, without a node or a
weight of its own: ``FunctionalNet`` hands the leaf over beside this
layer's parameters (``Layer.borrows``), one leaf and one gradient, the
sum of both uses.  Without the key the second input is read as it is.
Under ``remat = 1`` the layer's checkpoint keeps its two inputs, and the
second is the array the attention layer's checkpoint keeps already: no
``(N, T, D)`` array more than a layer with one input; the norm is
computed once more here, forward and in the recompute.  In a whole layer
the router's gradient flows into that norm weight and into the stream
before the attention; in a share it is zero like everything of the
router's.

**No pair is dropped and no expert has a capacity.**  The (token,
expert) pairs are sorted by expert, pairs of experts held elsewhere
last.  Every array between that sort and a token's sum has the ``C``
rows of a SLAB, ``C`` fixed by the layer's shapes (``slab_rows``):
``SLAB_FACTOR`` times the pairs an even router sends the held experts,
in whole row tiles of the grouped product, and never more than ``tokens
* topk``.  The first ``C`` sorted pairs' rows are gathered, the two
grouped products (``jax.lax.ragged_dot``, which the TPU compiler turns
into a kernel that visits the tiles of rows the groups really hold) run
over the held groups' rows in the slab, and the rows, times their
weights, are summed onto their tokens in float32.  Whatever the router
sends beyond ``C`` goes through the same code on further slabs, in ONE
loop whose trip count is read from the run's own counts (0 in a step
whose held pairs fit the first slab), so whatever the router does,
every pair routed to a held expert is computed.  A loop with a run-time
trip count has no reverse mode: ``_slabs`` is one ``custom_vjp`` whose
residuals are its inputs (nothing with ``tokens * topk`` rows but the
int32 order) and whose backward walks the same slabs, gathering a
slab's rows and putting them through the first product once more.
With ``nheld = nexpert`` (or ``SLAB_FACTOR`` x the share >= 1) ``C`` is
``tokens * topk``: one slab and no loop.

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
  (default 1) multiplies the chosen weights; ``n_group`` / ``topk_group``
  (default 1 / 1: no limit) — the group-limited choice; ``n_group``
  divides ``nexpert`` and ``topk_group`` groups hold at least ``topk``
  experts
* ``expert_act`` — ``swiglu`` (default), ``reglu`` or ``relu2``, for
  the held experts and the shared one alike; ``latent_hidden`` (default
  0: none) — the width ``L`` the held experts read and write
* ``route_norm`` — with a second input (the node the router reads): the
  layer whose ``norm`` weight that input is normed under (default: none,
  the router reads the second input as it is)
* ``prenorm`` / ``postnorm`` / ``residual_scale`` / ``eps`` — the
  residual branch in one layer (``sequence.Branch``); with ``postnorm``
  the layer's output — in a share the PARTIAL sum of the held experts
  and the shared one — goes through an ``rms_norm`` of its own before
  the residual add, as it is: that norm is not linear, so the ranks'
  parts add up to the whole layer's before it, not after;
  ``init_sigma`` for every matrix

Parameters (tags), with ``W`` the experts' width — ``latent_hidden``, or
D without — and ``c`` = 2 (``swiglu``, ``reglu``: gate | up fused) or 1
(``relu2``): ``wgate`` (nexpert, D); the held experts' matrices
as the grouped product reads them, ``(expert, in, out)`` — ``wmat``
(nheld, W, c nhidden) and ``wproj`` (nheld, nhidden, W): kept
``(out, in)`` like the framework's other matrices they would be
transposed for every product, and the compiler then carried them
through the scanned step in the transposed layout, with a copy of every
expert's weight and of both its adam moments at the loop's edges
(4.5 GB at 4 x 32 experts; read from compiles for a described v5e, PR
33; with the loop over further slabs in the step the compiler turned
them again, so the layer now states the layout, ``_as_kept``); and with
a shared expert
``shared_wmat`` (c shared_hidden, D), ``shared_wproj`` (D,
shared_hidden), ``shared_gate`` (1, D) unless ``shared_gate = 0``;
``score_bias`` (nexpert,) with ``select_bias``, started at 0;
``latent_in`` (L, D) and ``latent_out`` (D, L) with ``latent_hidden``;
``norm`` (D) with ``prenorm``, ``postnorm`` (D) with ``postnorm``.
All float32 at rest, cast where used; the router's product is float32
at the highest precision.

State (``aux``, carried through the step programs like batch-norm's
running statistics and read once a round by
``NetTrainer.count_layer_state``): ``pairs`` — pairs routed to held
experts; ``pairs_max`` — each step's fullest held expert's pairs,
summed; ``pairs_dropped`` — 0, by the construction above;
``pairs_overflow`` — pairs beyond the first slab, computed by the loop.
uint32, wrapping: the reader takes differences.

Scopes inside the layer's: ``route`` (router, softmax, top-k; with a
second input its norm too; inside it ``group_limit`` with ``n_group >
1``),
``dispatch`` (the sort, a slab's plan and gather; backward: ``dx``),
``experts`` (the grouped products), ``combine`` (the weights and the sum
onto the tokens), ``shared``, and with a latent ``latent_in`` and
``latent_out`` (the two projections).  The slabs after the first name
``dispatch`` / ``experts`` / ``combine`` under the loop's ``while/body``.

The older ``moe`` type (``sequence.MoELayer``) stays beside this one:
it is a different function (one linear projection an expert to another
width, every expert on every token, a soft mixture by default) that
GSPMD shards over the mesh's ``model`` axis; this one routes, and is
what a published mixture-of-experts block is.
"""

from __future__ import annotations

import functools
import math
from typing import List, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint

from .base import Layer, Params, Shape, register
from .sequence import Branch, rms_norm

COUNTERS = ("pairs", "pairs_max", "pairs_dropped", "pairs_overflow")

#: a slab has room for this many times the pairs an even router sends
#: the held experts.  Chosen on the chip (tools/moe_ab.py; PERF.md section
#: 6, PR 39): the layer forward + backward at qwen3_next's shapes reads
#: 5.66 / 6.19 / 6.96 ms at 1.5 / 2 / 3 with one slab and 10.95 with two;
#: the cells' loads read 1.5-1.9 times their mean and drifted +37%
SLAB_FACTOR = 2
#: a slab is whole row tiles of the grouped product's kernel
ROW_TILE = 512
#: ``expert_act`` of a gated expert -> what its gate goes through
GATES = {"swiglu": jax.nn.silu, "reglu": jax.nn.relu}


def slab_rows(pairs: int, nheld: int, nexpert: int) -> int:
    """Rows of a slab, from the layer's shapes alone: ``SLAB_FACTOR``
    times the share of the ``pairs`` (tokens x topk) that ``nheld`` of
    ``nexpert`` experts get from an even router, in whole row tiles, and
    never more than all pairs — a whole layer's one slab."""
    tiles = math.ceil(SLAB_FACTOR * pairs * nheld / (nexpert * ROW_TILE))
    return min(pairs, tiles * ROW_TILE)


def _onto_tokens(rows, tok, m: int):
    """``rows (C, D)`` float32, row ``r`` token ``tok[r]``'s: the sum of
    each token's rows, ``(m, D)`` float32.  A scatter-add: on a v5e 1.8
    ms for 10 240 rows of 2048, where a gather of all ``tokens x topk``
    pairs through the inverse order took 6.3 and sorting the rows
    token-major first changed nothing (tools/moe_ab.py; PERF.md, PR 39)."""
    return jax.ops.segment_sum(rows, tok, num_segments=m)


def _group_limited(choice, n_group: int, topk_group: int):
    """``choice (M, E)`` with every expert outside a token's ``topk_group``
    best of ``n_group`` groups of consecutive experts at ``-inf``; a
    group's score is the sum of its 2 largest entries (DeepSeek-V3's
    ``noaux_tc``)."""
    m, e = choice.shape
    grp = choice.reshape(m, n_group, e // n_group)
    score = lax.top_k(grp, min(2, e // n_group))[0].sum(axis=-1)
    _, best = lax.top_k(score, topk_group)
    kept = (best[:, :, None] == lax.iota(jnp.int32, n_group)).any(axis=1)
    return jnp.where(kept[:, :, None], grp, -jnp.inf).reshape(m, e)


def route(logits, topk: int, norm_topk: bool = True, *,
          score_func: str = "softmax", bias=None, scale: float = 1.0,
          n_group: int = 1, topk_group: int = 1):
    """``logits (M, E)`` float32 -> (weights ``(M, k)`` float32, expert
    ids ``(M, k)`` int32): the ``topk`` largest of the softmax over all
    ``E``, by index so a tie admits no extra expert, divided by their
    sum with ``norm_topk``.  ``score_func = "sigmoid"`` scores each
    expert alone; ``bias (E,)`` is added for the choice only — the
    weights are the unbiased scores of the chosen; ``scale`` multiplies
    them last.  With ``n_group > 1`` the choice is group-limited: the
    ``topk`` are taken among the experts of each token's ``topk_group``
    best groups (scope ``group_limit``)."""
    logits = logits.astype(jnp.float32)
    if score_func == "softmax" and bias is None and n_group == 1:
        w, idx = lax.top_k(jax.nn.softmax(logits, axis=-1), topk)
        if norm_topk:
            w = w / w.sum(axis=-1, keepdims=True)
    else:
        p = (jax.nn.sigmoid(logits) if score_func == "sigmoid"
             else jax.nn.softmax(logits, axis=-1))
        choice = (p if bias is None
                  else p + lax.stop_gradient(bias.astype(jnp.float32)))
        if n_group > 1:
            with jax.named_scope("group_limit"):
                choice = _group_limited(choice, n_group, topk_group)
        _, idx = lax.top_k(choice, topk)
        w = jnp.take_along_axis(p, idx, axis=-1)
        if norm_topk:
            w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    if scale != 1.0:
        w = w * jnp.float32(scale)
    return w, idx.astype(jnp.int32)


def _experts(xs, wmat, wproj, sizes, valid, act: str = "swiglu"):
    """A slab's rows ``xs (c, D)`` through their experts, ``(c, D)`` in
    the activations' dtype and zero past the held pairs (whatever the
    grouped product left there is put out): ``act = "swiglu"`` on a
    fused ``wmat (G, D, 2F)`` gate | up, ``"reglu"`` on the same with
    ``relu`` for ``silu``, ``"relu2"`` (``relu(.)^2``, no gate) on
    ``wmat (G, D, F)``.  The kernels accumulate in float32.  It
    names no scope: the backward calls it under ``jax.vjp``, which
    would wrap one (``jvp(experts)``) where the trace's readers look
    for the plain name."""
    gu = lax.ragged_dot(xs, wmat, sizes, preferred_element_type=xs.dtype)
    if act == "relu2":
        h = jnp.square(jax.nn.relu(gu.astype(jnp.float32))).astype(xs.dtype)
    else:
        f = wmat.shape[-1] // 2
        h = (GATES[act](gu[:, :f].astype(jnp.float32))
             * gu[:, f:].astype(jnp.float32)).astype(xs.dtype)
    ys = lax.ragged_dot(jnp.where(valid, h, 0), wproj, sizes,
                        preferred_element_type=xs.dtype)
    return jnp.where(valid, ys, 0)


def _slab_inputs(x, w, order, counts, s, c: int):
    """Slab ``s`` of the sorted pairs, rows ``s c .. s c + c - 1``: its
    pairs ``(c,)``, the rows each held expert has in it ``(G,)``, which
    of its rows are a held pair's ``(c, 1)``, their tokens' rows of ``x``
    ``(c, D)`` and their weights ``(c,)``."""
    with jax.named_scope("dispatch"):
        lo = s * c
        pair = lax.dynamic_slice(order, (lo,), (c,))
        ends = jnp.cumsum(counts)
        sizes = jnp.maximum(
            jnp.minimum(ends, lo + c) - jnp.maximum(ends - counts, lo), 0)
        valid = (lo + lax.iota(jnp.int32, c) < ends[-1])[:, None]
        xs = jnp.where(valid, x[pair // w.shape[1]], 0)
        return pair, sizes, valid, xs, w.reshape(-1)[pair]


def _slab(x, w, wmat, wproj, order, counts, s, c: int, act: str):
    """Slab ``s``'s terms of every token's sum: ``(M, D)`` float32."""
    pair, sizes, valid, xs, wrow = _slab_inputs(x, w, order, counts, s, c)
    with jax.named_scope("experts"):
        ys = _experts(xs, wmat, wproj, sizes, valid, act)
    with jax.named_scope("combine"):
        return _onto_tokens(ys.astype(jnp.float32) * wrow[:, None],
                            pair // w.shape[1], w.shape[0])


def _slab_grads(x, w, wmat, wproj, order, counts, s, c: int, act: str, g):
    """Slab ``s``'s terms of the cotangents of ``x`` (float32), ``w``
    (flat), ``wmat`` and ``wproj`` for ``g (M, D)``, the output's: the
    slab's rows are gathered and put through their experts once more."""
    m, k = w.shape
    pair, sizes, valid, xs, wrow = _slab_inputs(x, w, order, counts, s, c)
    with jax.named_scope("experts"):
        ys, vjp = jax.vjp(
            lambda *a: _experts(*a, sizes, valid, act), xs, wmat, wproj)
    with jax.named_scope("combine"):
        grow = g[pair // k].astype(jnp.float32)
        dys = (grow * wrow[:, None]).astype(ys.dtype)
        dw = jnp.zeros((m * k,), jnp.float32).at[pair].add(
            (grow * ys.astype(jnp.float32)).sum(axis=-1))
    with jax.named_scope("experts"):
        dxs, dwmat, dwproj = vjp(dys)
    with jax.named_scope("dispatch"):
        dx = _onto_tokens(jnp.where(valid, dxs, 0).astype(jnp.float32),
                          pair // k, m)
    return dx, dw, dwmat, dwproj


def _slabs_held(counts, c: int):
    """Slabs that hold a pair, read from the run's own routing."""
    return (counts.sum() + (c - 1)) // c


def _slabs_impl(x, w, wmat, wproj, order, counts, c, act):
    y = _slab(x, w, wmat, wproj, order, counts, 0, c, act)
    if c < w.size:
        y = lax.fori_loop(
            1, _slabs_held(counts, c),
            lambda s, y: y + _slab(x, w, wmat, wproj, order, counts, s, c,
                                   act),
            y)
    return y.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _slabs(x, w, wmat, wproj, order, counts, c: int, act: str):
    """The sum over the slabs of ``c`` sorted pairs that hold one: the
    first always, the others in a loop whose trip count the routing
    gives.  A loop has no reverse mode, so the backward is written
    out: it keeps the inputs and walks the same slabs."""
    return _slabs_impl(x, w, wmat, wproj, order, counts, c, act)


def _slabs_fwd(x, w, wmat, wproj, order, counts, c, act):
    return (_slabs_impl(x, w, wmat, wproj, order, counts, c, act),
            (x, w, wmat, wproj, order, counts))


def _slabs_bwd(c, act, res, g):
    x, w = res[:2]
    grads = _slab_grads(*res, 0, c, act, g)
    if c < w.size:
        grads = lax.fori_loop(
            1, _slabs_held(res[5], c),
            lambda s, acc: jax.tree_util.tree_map(
                jnp.add, acc, _slab_grads(*res, s, c, act, g)),
            grads)
    dx, dw, dwmat, dwproj = grads
    return (dx.astype(x.dtype), dw.reshape(w.shape).astype(w.dtype), dwmat,
            dwproj, None, None)


_slabs.defvjp(_slabs_fwd, _slabs_bwd)


def _as_kept(w):
    """The held experts' matrix ``w (G, in, out)`` in the layout it is
    kept in, row-major.  A row's way back through a matrix wants it
    turned, and with those products inside a loop the TPU compiler chose
    to KEEP every expert's float32 weight and both its adam moments
    turned through the scanned step, with a copy of each at the scan's
    edges (+5.0 GB of temporaries in the qwen3_next step, +3.3 in
    JoyAI's, which then does not fit a chip; read from compiles for a
    described v5e, PR 39).  This says where the layout's choice ends."""
    return with_layout_constraint(w, Layout(major_to_minor=(0, 1, 2)))


def held_experts(x, w, idx, wmat, wproj, first: int, nexpert: int,
                 act: str = "swiglu"):
    """The held experts' part of the layer: ``x (M, D)``, the router's
    ``w`` / ``idx (M, k)`` over ``nexpert`` experts, ``wmat (G, D, 2F)``
    (gate | up; ``(G, D, F)`` with ``act = "relu2"``) and ``wproj (G, F, D)`` of
    the ``G`` experts ``first .. first + G - 1`` -> (``y (M, D)``, pairs
    a held expert ``(G,)`` int32).  ``D`` is whatever width the experts
    live in: the stream's, or a latent's."""
    m, k = idx.shape
    g = wmat.shape[0]
    c = slab_rows(m * k, g, nexpert)
    with jax.named_scope("dispatch"):
        local = idx.reshape(-1) - first
        key = jnp.where((local >= 0) & (local < g), local, g)
        _, order = lax.sort((key, lax.iota(jnp.int32, m * k)), num_keys=1)
        counts = (key[:, None] == lax.iota(jnp.int32, g)[None]).sum(
            axis=0, dtype=jnp.int32)
        # whole slabs: a slice that starts in the last one stays inside
        order = jnp.pad(order, (0, -(m * k) % c))
    return _slabs(x, w, wmat, wproj, order, counts, c, act), counts


@register
class RoutedExpertsLayer(Layer, Branch):
    type_name = "routed_experts"
    #: state leaf -> the round's counter it is added to
    #: (``NetTrainer.count_layer_state``)
    aux_counters = {name: "expert_" + name for name in COUNTERS}
    f32_tags = frozenset({"wgate", "wmat", "wproj", "shared_wmat",
                          "shared_wproj", "shared_gate", "score_bias",
                          "latent_in", "latent_out", "norm", "postnorm"})

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
        self.latent_hidden = 0  # 0: the experts read the stream itself
        self.expert_act = "swiglu"
        self.route_norm = ""  # the layer whose norm the router's input takes
        self.n_group = 1
        self.topk_group = 1

    _INT_KEYS = ("nexpert", "topk", "first_expert", "nheld",
                 "shared_hidden", "shared_gate", "norm_topk", "select_bias",
                 "latent_hidden", "n_group", "topk_group")

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
        elif name == "expert_act":
            if val not in (*GATES, "relu2"):
                raise ValueError(
                    f"routed_experts: expert_act is swiglu, reglu or relu2, "
                    f"got {val!r}")
            self.expert_act = val
        elif name == "route_norm":
            self.route_norm = val
        elif not self.set_branch_param(name, val):
            super().set_param(name, val)

    def _held(self) -> int:
        return self.nheld or self.nexpert - self.first_expert

    def borrows(self):
        """``FunctionalNet`` reads it: the router's input is normed under
        the named layer's ``norm`` weight, handed over as ``route_norm``."""
        return ({"route_norm": (self.route_norm, "norm")}
                if self.route_norm else {})

    def infer_shape(self, in_shapes: Sequence[Shape]) -> List[Shape]:
        if len(in_shapes) not in (1, 2):
            raise ValueError(
                f"routed_experts: expected 1 input, or 2 (the experts' and "
                f"the router's), got {len(in_shapes)}")
        if len(in_shapes[0]) not in (2, 3):
            raise ValueError("routed_experts: input must be a matrix or a "
                             "sequence node")
        if len(in_shapes) == 2 and tuple(in_shapes[1]) != tuple(in_shapes[0]):
            raise ValueError(
                f"routed_experts: the router's input {tuple(in_shapes[1])} "
                f"is not shaped like the experts' {tuple(in_shapes[0])}")
        if self.route_norm and len(in_shapes) != 2:
            raise ValueError(
                "routed_experts: route_norm norms the router's own input, "
                "the layer's second")
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
        if self.latent_hidden < 0:
            raise ValueError("routed_experts: latent_hidden >= 0")
        if self.n_group < 1 or self.nexpert % self.n_group or not (
                1 <= self.topk_group <= self.n_group) or (
                self.n_group > 1 and self.topk_group
                * (self.nexpert // self.n_group) < self.topk):
            raise ValueError(
                f"routed_experts: n_group={self.n_group} must divide "
                f"nexpert={self.nexpert}, and topk_group={self.topk_group} "
                f"(at most n_group) groups must hold at least topk="
                f"{self.topk} experts")
        return [tuple(in_shapes[0])]

    def init_params(self, key, in_shapes) -> Params:
        d = in_shapes[0][-1]
        f, g, sh = self.param.num_hidden, self._held(), self.shared_hidden
        lat = self.latent_hidden or d  # the width the held experts live in
        fused = 1 if self.expert_act == "relu2" else 2  # up, or gate | up
        ks = jax.random.split(key, 6)
        sigma = self.param.init_sigma

        def normal(k, shape):
            return jax.random.normal(k, shape, jnp.float32) * sigma

        out = {"wgate": normal(ks[0], (self.nexpert, d)),
               "wmat": normal(ks[1], (g, lat, fused * f)),
               "wproj": normal(ks[2], (g, f, lat))}
        if sh:
            out.update({"shared_wmat": normal(ks[3], (fused * sh, d)),
                        "shared_wproj": normal(ks[4], (d, sh))})
            if self.shared_gate:
                out["shared_gate"] = normal(ks[5], (1, d))
        if self.latent_hidden:
            kin, kout = jax.random.split(jax.random.fold_in(key, 6))
            out.update({"latent_in": normal(kin, (lat, d)),
                        "latent_out": normal(kout, (d, lat))})
        if self.select_bias:
            out["score_bias"] = jnp.zeros((self.nexpert,), jnp.float32)
        out.update(self.branch_params(d))
        return out

    def init_aux(self, in_shapes):
        return {name: jnp.zeros((), jnp.uint32) for name in COUNTERS}

    def apply(self, params, inputs, *, train=False, rng=None, step=None):
        return self._run(params, *inputs)[0]

    def apply_stateful(self, params, aux, inputs, *, train=False, rng=None,
                       step=None):
        outs, counts = self._run(params, *inputs)
        pairs = counts.sum()
        tokens = inputs[0].size // inputs[0].shape[-1]
        slab = slab_rows(tokens * self.topk, self._held(), self.nexpert)
        return outs, {
            "pairs": aux["pairs"] + pairs.astype(jnp.uint32),
            "pairs_max": aux["pairs_max"] + counts.max().astype(jnp.uint32),
            "pairs_dropped": aux["pairs_dropped"],
            "pairs_overflow": aux["pairs_overflow"] + jnp.maximum(
                pairs - slab, 0).astype(jnp.uint32),
        }

    def _run(self, params, x0, r0=None):
        cdt = x0.dtype
        u = self.branch_in(params, x0)
        x = u.reshape(-1, u.shape[-1])
        with jax.named_scope("route"):
            xr = x
            if r0 is not None:
                # the router reads another node than the experts
                if self.route_norm:
                    r0 = rms_norm(r0, params["route_norm"], self.eps)
                xr = r0.reshape(x.shape)
            logits = jnp.dot(xr.astype(jnp.float32), params["wgate"].T,
                             precision=lax.Precision.HIGHEST)
            w, idx = route(logits, self.topk, bool(self.norm_topk),
                           score_func=self.score_func,
                           bias=params.get("score_bias"),
                           scale=self.routed_scale, n_group=self.n_group,
                           topk_group=self.topk_group)
            if self._held() < self.nexpert:
                # a share: the weights' cotangent needs the other ranks'
                w = lax.stop_gradient(w)
        with jax.named_scope("experts"):
            # the turned copies the backward's products read are made
            # from these: billed where the parent's were
            wmat, wproj = _as_kept(params["wmat"]), _as_kept(params["wproj"])
        xe = x
        if self.latent_hidden:
            with jax.named_scope("latent_in"):
                xe = x @ params["latent_in"].astype(cdt).T
        y, counts = held_experts(xe, w, idx, wmat.astype(cdt),
                                 wproj.astype(cdt), self.first_expert,
                                 self.nexpert, self.expert_act)
        if self.latent_hidden:
            # once a token, after the sum over its picks
            with jax.named_scope("latent_out"):
                y = y @ params["latent_out"].astype(cdt).T
        if self.shared_hidden:
            with jax.named_scope("shared"):
                sh = self.shared_hidden
                gu = x @ params["shared_wmat"].astype(cdt).T
                hid = (jnp.square(jax.nn.relu(gu))
                       if self.expert_act == "relu2"
                       else GATES[self.expert_act](gu[:, :sh]) * gu[:, sh:])
                s = hid @ params["shared_wproj"].astype(cdt).T
                if self.shared_gate:
                    s = jax.nn.sigmoid(
                        x @ params["shared_gate"].astype(cdt).T) * s
                y = y + s
        return [self.branch_out(params, x0, y.reshape(x0.shape))], counts
