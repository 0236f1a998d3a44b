"""The delta rule of Kimi Delta Attention, chunked (Kimi Linear,
arXiv:2510.26692, section 3): ``ops/gdn.py``'s rule with the scalar
decay a head replaced by a vector, one rate a key channel.

The recurrence, one ``(Dk, Dv)`` state a head::

    S_t = (I - b_t k_t k_t^T) Diag(e^{g_t}) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

with ``g_t`` in ``(lower_bound, 0]^Dk``, ``b_t`` in (0, 1) and ``S = 0``
before a document's first token.  With ``G_l`` the sum of ``g`` from
the chunk's start to ``l`` (a vector), what token ``l`` adds solves the
same unit lower triangular system as in ``ops/gdn.py``::

    (I + A) U = b V - b (K e^G) S_0
    A_ls = b_l sum_d k_ld k_sd e^{G_ld - G_sd},   s < l

but the decay now sits INSIDE the contraction over ``d``: there is no
``(C, C)`` mask to lay over one ``k k^T`` product, and the factor
``e^{-G_s}`` alone leaves float32 over a chunk of 64 (``e^{320}`` at a
gate floor of -5).  So a chunk's pair matrices are computed in ``BLOCK``
rows at a time: row block ``I`` takes its reference at its first token
``r``, its rows are ``k_l e^{G_l - G_r}`` (at most 1) and its columns
``k_s e^{G_r - G_s}`` — at most 1 left of the diagonal block, and at
most ``e^{-BLOCK lower_bound}`` (``e^{80}``, inside float32 and
bfloat16's range alike) inside it, where the mask ``s <= l`` keeps only
pairs whose product of factors is at most 1.  Every term of a sum is
bounded by ``|k_ld k_sd|``: nothing cancels.  The caller's gate has to
keep ``BLOCK * |g| <= 80``.

The rest is ``ops/gdn._chunked`` with vectors where it has scalars
(``from_start``, ``to_end`` and ``carry`` are ``e^G``, ``e^{G_end - G}``
and ``e^{G_end}`` a key channel), in the same precisions: decays float32
and in log space until an ``exp``, the solve and the carried state
float32, every other product on the activations' dtype with float32
accumulation.

One algorithm and one call site, ``kimi_delta_scan``, in two forms —
plain ``jax.numpy`` (``kimi_delta_xla``; what runs off the TPU and what
the kernels are tested against, with the token-by-token
``kimi_delta_recurrence``) and fused Pallas kernels with their own
backward (``ops/kda_fused.py``) — chosen as ``ops/gdn.py`` chooses:
``jax.lax.platform_dependent`` and ``kda_fused.supported``, no conf key.
With ``g`` equal across a head's channels both are
``ops/gdn.gated_delta_scan`` (a test).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .gdn import unit_lower_inverse, unit_rows

#: rows of a chunk that share one reference of the decay
BLOCK = 16


def kimi_delta_scan(q, k, v, g, beta, doc=None, chunk: int = 64,
                    segment: int = 0) -> jnp.ndarray:
    """``q``/``k (N,T,H,Dk)`` (``k`` of unit length), ``v (N,T,H,Dv)``,
    ``g (N,T,H,Dk)`` float32, never positive and at least ``-80 /
    BLOCK``, ``beta (N,T,H)`` float32, ``doc (N,T)`` int32 or ``None``
    (one document a row) -> ``o (N,T,H,Dv)`` in ``v``'s dtype."""
    return kimi_delta_scan_counted(q, k, v, g, beta, doc, chunk, segment)[0]


def kimi_delta_scan_counted(q, k, v, g, beta, doc=None, chunk: int = 64,
                            segment: int = 0, unit=None,
                            q_scale: float = 1.0):
    """``(kimi_delta_scan's o, 1 if the fused kernels computed it else
    0)``, with ``unit`` and ``q_scale`` as
    ``ops/gdn.gated_delta_scan_counted`` takes them."""
    from . import kda_fused

    def xla(q, k, v, g, beta, doc):
        if unit is not None:
            q = (unit_rows(q, unit) * jnp.float32(q_scale)).astype(q.dtype)
            k = unit_rows(k, unit).astype(k.dtype)
        return (kimi_delta_xla(q, k, v, g, beta, doc, chunk, segment),
                jnp.uint32(0))

    def fused(q, k, v, g, beta, doc):
        return (kda_fused.kimi_delta_fused(q, k, v, g, beta, doc, unit,
                                           q_scale), jnp.uint32(1))

    if doc is None:
        doc = jnp.zeros(q.shape[:2], jnp.int32)
    if not kda_fused.supported(q, k, v, chunk):
        return xla(q, k, v, g, beta, doc)
    return lax.platform_dependent(q, k, v, g, beta, doc, tpu=fused,
                                  default=xla)


def kimi_delta_xla(q, k, v, g, beta, doc=None, chunk: int = 64,
                   segment: int = 0) -> jnp.ndarray:
    """``kimi_delta_scan`` in plain ``jax.numpy``; ``segment`` as
    ``ops/gdn.gated_delta_xla``'s."""
    n, t, h, dk = q.shape
    dv = v.shape[-1]
    c = int(chunk)
    if c < BLOCK or c & (c - 1):
        raise ValueError(f"kimi_delta_scan: chunk={chunk} must be a power "
                         f"of two, at least {BLOCK}")
    seg = int(segment) if segment and 0 < int(segment) < t else 0
    if seg % c:
        raise ValueError(f"kimi_delta_scan: segment={segment} must be a "
                         f"multiple of chunk={chunk}")
    pad = (-t) % (seg or c)
    if doc is None:
        doc = jnp.zeros((n, t), jnp.int32)
    if pad:
        # a padded step has g = 0 and beta = 0: the state passes it
        # unchanged and it adds nothing; its outputs are cut off below
        q, k, v, g = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for a in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
        doc = jnp.pad(doc, ((0, 0), (0, pad)), mode="edge")
    prev = jnp.concatenate(
        [jnp.full((n, 1), -1, doc.dtype), doc[:, :-1]], axis=1)
    state = jnp.zeros((n, h, dk, dv), jnp.float32)
    if not seg:
        o, _ = _chunked(state, (q, k, v, g, beta, doc, prev), c)
    else:
        cut = lambda a: jnp.moveaxis(  # noqa: E731
            a.reshape((n, (t + pad) // seg, seg) + a.shape[2:]), 1, 0)
        _, o = lax.scan(
            jax.checkpoint(lambda s, xs: _chunked(s, xs, c)[::-1]), state,
            tuple(cut(a) for a in (q, k, v, g, beta, doc, prev)))
        o = jnp.moveaxis(o, 0, 1).reshape(n, t + pad, h, dv)
    return o[:, :t]


def chunk_sums(g, c: int):
    """``g (N, T, ...)`` float32 -> its running sum from each chunk's
    start, as ONE product with a ``(C, C)`` triangle of ones at full
    precision (a cumulative sum along a middle axis is a window
    reduction on the TPU)."""
    n, t = g.shape[:2]
    tri = jnp.tril(jnp.ones((c, c), jnp.float32))
    cut = g.astype(jnp.float32).reshape((n, t // c, c) + g.shape[2:])
    return jnp.einsum("ls,ncs...->ncl...", tri, cut,
                      precision=lax.Precision.HIGHEST).reshape(g.shape)


def pair_products(lefts, kc, gs, cdt):
    """``sum_d a_ld k_sd e^{G_ld - G_sd}`` for every pair of a chunk's
    tokens with ``s`` at most the end of ``l``'s block, for each ``a``
    of ``lefts``: ``(..., C, Dk)`` each, ``gs`` float32 -> ``(..., C,
    C)`` float32 each, in blocks of ``BLOCK`` rows (the module's
    docstring).  Pairs right of the diagonal block are 0; those above
    the diagonal inside it are NOT masked."""
    c, dk = kc.shape[-2:]
    nb = c // BLOCK
    lead = kc.shape[:-2]
    f32 = jnp.float32
    blk = gs.reshape(lead + (nb, BLOCK, dk))
    ref = blk[..., :1, :]                                   # (..., nb, 1, Dk)
    lfac = jnp.exp(blk - ref)
    # (..., nb, C, Dk): block I's reference against every token s
    diff = ref - gs[..., None, :, :]
    live = (jnp.arange(c) < (jnp.arange(nb)[:, None] + 1) * BLOCK)[..., None]
    rfac = jnp.where(live, jnp.exp(jnp.where(live, diff, 0.0)), 0.0)
    right = (kc.astype(f32)[..., None, :, :] * rfac).astype(cdt)
    out = []
    for a in lefts:
        left = (a.astype(f32).reshape(blk.shape) * lfac).astype(cdt)
        out.append(jnp.einsum("...ild,...isd->...ils", left, right,
                              preferred_element_type=f32
                              ).reshape(lead + (c, c)))
    return out


def _chunked(state, xs, c: int):
    """One stretch of whole chunks from the state that enters it:
    ``(o, the state that leaves it)``."""
    q, k, v, g, beta, doc, prev = xs
    n, t, h, dk = q.shape
    dv = v.shape[-1]
    nc = t // c
    f32 = jnp.float32
    cdt = v.dtype
    # (N, NC, H, C, D): a chunk's tokens are the rows of its matrices
    cut = lambda a: jnp.moveaxis(  # noqa: E731
        a.reshape(n, nc, c, h, a.shape[-1]), 3, 2)
    qc, kc, vc = cut(q), cut(k), cut(v)
    gs = cut(chunk_sums(g, c))                              # (N,NC,H,C,Dk)
    bc = jnp.moveaxis(beta.astype(f32).reshape(n, nc, c, h), 3, 2)
    dq = doc.reshape(n, nc, c)
    end_doc = dq[:, :, -1]                                    # (N, NC)
    prev_doc = prev.reshape(n, nc, c)[:, :, 0]   # before the chunk's first

    # -- inside a chunk: token l against tokens s <= l of its document
    row = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    same = (dq[:, :, :, None] == dq[:, :, None, :])[:, :, None]
    qk, kk = pair_products((qc, kc), kc, gs, cdt)
    qk = jnp.where(same & (row >= col), qk, 0.0)
    a = jnp.where(same & (row > col), kk * bc[..., None], 0.0)
    # what reaches a token from the state that entered the chunk, and
    # what of a token reaches the state that leaves it: a key channel
    from_start = jnp.where((dq == prev_doc[:, :, None])[:, :, None, :, None],
                           jnp.exp(gs), 0.0)                # (N,NC,H,C,Dk)
    to_end = jnp.where((dq == end_doc[:, :, None])[:, :, None, :, None],
                       jnp.exp(gs[..., -1:, :] - gs), 0.0)
    kb = kc.astype(f32) * bc[..., None]
    rhs = jnp.concatenate(
        [vc.astype(f32) * bc[..., None], kb * from_start], axis=-1)
    sol = jnp.einsum("nchls,nchsd->nchld", unit_lower_inverse(a), rhs,
                     precision=lax.Precision.HIGHEST)
    u0, w = sol[..., :dv].astype(cdt), sol[..., dv:].astype(cdt)

    # -- a chunk moves the state by S_end = M S_0 + B
    kd = (kc.astype(f32) * to_end).astype(cdt)
    carry = jnp.where((end_doc == prev_doc)[..., None, None],
                      jnp.exp(gs[..., -1, :]), 0.0)         # (N,NC,H,Dk)
    m = carry[..., None] * jnp.eye(dk, dtype=f32) - jnp.einsum(
        "nchlk,nchlj->nchkj", kd, w, preferred_element_type=f32)
    b = jnp.einsum("nchlk,nchlv->nchkv", kd, u0, preferred_element_type=f32)

    # -- the state that enters each chunk: a scan over the chunks
    def step(s, inp):
        mi, bi = inp
        new = jnp.einsum("nhkj,nhjv->nhkv", mi, s,
                         precision=lax.Precision.HIGHEST) + bi
        return new, s

    last, enter = lax.scan(step, state,
                           (jnp.moveaxis(m, 1, 0), jnp.moveaxis(b, 1, 0)))
    enter = jnp.moveaxis(enter, 0, 1).astype(cdt)             # (N,NC,H,Dk,Dv)

    # -- and the outputs, every chunk at once
    u = u0.astype(f32) - jnp.einsum("nchlk,nchkv->nchlv", w, enter,
                                    preferred_element_type=f32)
    o = jnp.einsum("nchls,nchsv->nchlv", qk.astype(cdt), u.astype(cdt),
                   preferred_element_type=f32)
    o = o + jnp.einsum("nchlk,nchkv->nchlv",
                       (qc.astype(f32) * from_start).astype(cdt), enter,
                       preferred_element_type=f32)
    return jnp.moveaxis(o, 2, 3).reshape(n, t, h, dv).astype(cdt), last


def kimi_delta_recurrence(q, k, v, g, beta, doc=None):
    """The same function token by token, in float32: one ``lax.scan``
    over the tokens.  What ``kimi_delta_scan`` and its gradient are held
    against in the tests; nothing in the program calls it."""
    n, t, h, dk = q.shape
    f32 = jnp.float32
    if doc is None:
        doc = jnp.zeros((n, t), jnp.int32)
    start = jnp.concatenate(
        [jnp.ones((n, 1), bool), doc[:, 1:] != doc[:, :-1]], axis=1)

    def step(state, inp):
        qt, kt, vt, gt, bt, st = inp
        keep = jnp.where(st[:, None, None], 0.0, jnp.exp(gt))  # (N,H,Dk)
        state = keep[..., None] * state
        delta = bt[..., None] * (vt - jnp.einsum("nhkv,nhk->nhv", state, kt))
        state = state + kt[..., :, None] * delta[..., None, :]
        return state, jnp.einsum("nhkv,nhk->nhv", state, qt)

    seq = tuple(jnp.moveaxis(a, 1, 0) for a in (
        q.astype(f32), k.astype(f32), v.astype(f32), g.astype(f32),
        beta.astype(f32), start))
    with jax.default_matmul_precision("highest"):
        _, o = lax.scan(step, jnp.zeros((n, h, dk, v.shape[-1]), f32), seq)
    return jnp.moveaxis(o, 0, 1)
