"""The gated delta rule of Gated DeltaNet, chunked (Yang, Kautz &
Hatamizadeh 2024, "Gated Delta Networks"; the chunked form of Yang et
al. 2024, "Parallelizing Linear Transformers with the Delta Rule").

The recurrence, one ``(Dk, Dv)`` state a head, whose decay is a matrix::

    S_t = a_t S_{t-1} + b_t k_t (v_t - (a_t S_{t-1})^T k_t)^T
        = a_t (I - b_t k_t k_t^T) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

with ``a_t = exp(g_t)`` in (0, 1], ``b_t`` in (0, 1) and ``S = 0``
before a document's first token.  Write ``u_t = b_t (v_t - (a_t
S_{t-1})^T k_t)`` for what token ``t`` really adds; then ``S_t`` is a
decayed sum of ``k_s u_s^T`` exactly as in a scalar-decay scan, and
inside a chunk of ``C`` tokens the ``u`` solve one unit lower
triangular system::

    (I + A) U = b V - (b K c) S_0,    A_ls = b_l (k_l . k_s) decay(l, s), s < l

(``decay(l, s) = exp(g_{s+1} + ... + g_l)``, ``c_l`` the decay from the
chunk's start to ``l``, ``S_0`` the state that enters the chunk).  So

* one unit triangular inverse a chunk (``unit_lower_inverse``: block
  substitution as matrix products) gives ``(I + A)^{-1} [b V | b K c]``;
* a chunk moves the state by an affine map, ``S_end = M S_0 + B`` with
  ``M = c_end I - (K d)^T W`` and ``B = (K d)^T U0`` (``d`` the decay to
  the chunk's end), both matrix products of chunk size;
* the states that enter the chunks are a short scan over the chunks,
  one ``(Dk, Dk) @ (Dk, Dv)`` product a step, in float32;
* and every output is ``(q c) S_0 + (q k^T . decay) (U0 - W S_0)``.

One algorithm and one call site, ``gated_delta_scan``, in two forms.
Decays are kept in float32 and in log space until the one ``exp`` of a
difference that is never positive; the products take the activations'
dtype and accumulate in float32; the solve and the carried state are
float32 — in both:

* plain ``jax.numpy`` that XLA fuses, differentiated by ``jax.grad``
  (``gated_delta_xla``): what runs wherever the other does not, and
  what the kernels are tested against, with the token-by-token
  ``gated_delta_recurrence``;
* fused Pallas kernels with their own backward (``ops/gdn_fused.py``),
  which keep a chunk's matrices and the state on the chip where the
  first writes each to HBM (193 ms of a 508 ms step at 1% of the scan's
  roofline, ledger PR 33).

Which runs is read from what the code can observe, and no conf key
chooses: the platform the program is LOWERED for
(``jax.lax.platform_dependent``: a TPU takes the kernels, also when the
lowering host is a CPU that compiles for a described chip; everything
else the ``jax.numpy`` form) and the shapes the kernels are written for
(``gdn_fused.supported``: chunks of 64, ``Dk`` and ``Dv`` multiples of
128, bfloat16 or float32).  ``gated_delta_scan_counted`` also returns
which branch ran, from inside the branch, for the layer's counter.

Documents: ``doc`` is a non-decreasing document index a token; no
state, and nothing inside a chunk, crosses from one index to the next.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def gated_delta_scan(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     g: jnp.ndarray, beta: jnp.ndarray,
                     doc: Optional[jnp.ndarray] = None,
                     chunk: int = 64, segment: int = 0) -> jnp.ndarray:
    """``q``/``k (N,T,Hk,Dk)`` (``k`` of unit length for the rule to be
    a contraction), ``v (N,T,H,Dv)`` with ``Hk`` dividing ``H`` (value
    head ``j`` reads key head ``j // (H / Hk)``), ``g (N,T,H)`` float32
    and never positive (the log of the decay), ``beta (N,T,H)``
    float32, ``doc (N,T)`` int32 or ``None`` (one document a row) ->
    ``o (N,T,H,Dv)`` in ``v``'s dtype.

    ``segment`` is the ``jax.numpy`` form's (``gated_delta_xla``); the
    kernels hold no chunk matrices through the backward and walk the
    row whole."""
    return gated_delta_scan_counted(q, k, v, g, beta, doc, chunk, segment)[0]


def unit_rows(x, eps: float = 1e-6):
    """``x / sqrt(sum(x^2) + eps)`` over the last axis, in float32."""
    xf = x.astype(jnp.float32)
    return xf * lax.rsqrt((xf * xf).sum(axis=-1, keepdims=True)
                          + jnp.float32(eps))


def gated_delta_scan_counted(q, k, v, g, beta, doc=None, chunk: int = 64,
                             segment: int = 0, unit=None,
                             q_scale: float = 1.0):
    """``(gated_delta_scan's o, 1 if the fused kernels computed it else
    0)``: the second is a uint32 scalar each branch returns for itself,
    so it says what ran where the program was lowered for.

    ``unit`` (an eps) hands over ``q`` and ``k`` as they come: the scan
    takes ``unit_rows(k, unit)`` and ``unit_rows(q, unit) * q_scale``,
    rounded to their dtype — the kernels a tile at a time in VMEM, the
    ``jax.numpy`` form before it starts."""
    from . import gdn_fused

    def xla(q, k, v, g, beta, doc):
        if unit is not None:
            q = (unit_rows(q, unit) * jnp.float32(q_scale)).astype(q.dtype)
            k = unit_rows(k, unit).astype(k.dtype)
        return (gated_delta_xla(q, k, v, g, beta, doc, chunk, segment),
                jnp.uint32(0))

    def fused(q, k, v, g, beta, doc):
        return (gdn_fused.gated_delta_fused(q, k, v, g, beta, doc, unit,
                                            q_scale), jnp.uint32(1))

    if doc is None:
        doc = jnp.zeros(q.shape[:2], jnp.int32)
    if not gdn_fused.supported(q, k, v, chunk):
        return xla(q, k, v, g, beta, doc)
    return lax.platform_dependent(q, k, v, g, beta, doc, tpu=fused,
                                  default=xla)


def gated_delta_xla(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    g: jnp.ndarray, beta: jnp.ndarray,
                    doc: Optional[jnp.ndarray] = None,
                    chunk: int = 64, segment: int = 0) -> jnp.ndarray:
    """``gated_delta_scan`` in plain ``jax.numpy``.

    ``segment`` > 0 (a multiple of ``chunk``) walks the row in segments
    of that many tokens, one after the other, each under
    ``jax.checkpoint`` with the state carried between them: the
    backward pass then holds one segment's chunk matrices and states,
    not the row's (at 8192 tokens, 32 heads of 128 x 128 and chunks of
    64 that is 4 GB a layer against 1 GB at 2048), for one more
    forward pass of the scan."""
    h = v.shape[2]
    if q.shape[2] != h:
        q, k = (jnp.repeat(a, h // a.shape[2], axis=2) for a in (q, k))
    n, t, _, dk = q.shape
    dv = v.shape[-1]
    c = int(chunk)
    if c < 1 or c & (c - 1):
        raise ValueError(f"gated_delta_scan: chunk={chunk} must be a power "
                         "of two (the solve inside a chunk doubles its "
                         "blocks)")
    seg = int(segment) if segment and 0 < int(segment) < t else 0
    if seg % c:
        raise ValueError(f"gated_delta_scan: segment={segment} must be a "
                         f"multiple of chunk={chunk}")
    pad = (-t) % (seg or c)
    if doc is None:
        doc = jnp.zeros((n, t), jnp.int32)
    if pad:
        # a padded step has g = 0 and beta = 0: the state passes it
        # unchanged and it adds nothing; its outputs are cut off below
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
        g, beta = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (g, beta))
        doc = jnp.pad(doc, ((0, 0), (0, pad)), mode="edge")
    # the document of the token before each token; -1 before the first
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


def unit_lower_inverse(a: jnp.ndarray) -> jnp.ndarray:
    """``(I + a)^{-1}`` for ``a (..., C, C)`` strictly lower triangular,
    by block forward substitution written as matrix products: with the
    inverse ``T`` of the diagonal blocks of size ``b`` in hand, the
    blocks of size ``2b`` are ``[[T1, 0], [-T2 A21 T1, T2]]``, and since
    ``T`` is block diagonal that is ``T - T (A . mask21) T`` for the
    whole batch at once; ``log2 C`` doublings from ``T = I``.  The same
    arithmetic as substitution row by row (no power series, nothing
    that grows), in ``2 log2 C`` products of chunk size where the TPU's
    own ``triangular_solve`` walks the rows of every one of the batch's
    thousands of small matrices (11 ms a call at 4096 matrices of 64 x
    64, my chip run, PR 33).  ``C`` is a power of two."""
    c = a.shape[-1]
    row = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    t = jnp.broadcast_to(jnp.eye(c, dtype=a.dtype), a.shape)
    hi = lax.Precision.HIGHEST
    b = 1
    while b < c:
        # rows in the second half, columns in the first half, of the
        # same block of size 2b
        m21 = ((row // (2 * b) == col // (2 * b))
               & (row // b % 2 == 1) & (col // b % 2 == 0))
        t = t - jnp.matmul(jnp.matmul(t, jnp.where(m21, a, 0.0),
                                      precision=hi), t, precision=hi)
        b *= 2
    return t


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
    bc = jnp.moveaxis(beta.astype(f32).reshape(n, nc, c, h), 3, 2)
    # log decay, summed from the chunk's start: (N, NC, H, C), <= 0
    cs = jnp.cumsum(jnp.moveaxis(g.astype(f32).reshape(n, nc, c, h), 3, 2),
                    axis=3)
    dq = doc.reshape(n, nc, c)
    end_doc = dq[:, :, -1]                                    # (N, NC)
    prev_doc = prev.reshape(n, nc, c)[:, :, 0]   # before the chunk's first

    # -- inside a chunk: token l against tokens s <= l of its document
    row = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    same = (dq[:, :, :, None] == dq[:, :, None, :])[:, :, None]
    decay = jnp.exp(jnp.where(same & (row >= col),
                              cs[..., :, None] - cs[..., None, :],
                              -jnp.inf))                      # (N,NC,H,C,C)
    kb = kc * bc[..., None].astype(cdt)
    kk = jnp.einsum("nchld,nchsd->nchls", kb, kc,
                    preferred_element_type=f32)
    a = jnp.where(row > col, kk * decay, 0.0)
    # what reaches a token from the state that entered the chunk, and
    # what of a token reaches the state that leaves it
    from_start = jnp.where((dq == prev_doc[:, :, None])[:, :, None],
                           jnp.exp(cs), 0.0)                  # (N,NC,H,C)
    to_end = jnp.where((dq == end_doc[:, :, None])[:, :, None],
                       jnp.exp(cs[..., -1:] - cs), 0.0)
    rhs = jnp.concatenate(
        [vc.astype(f32) * bc[..., None],
         kb.astype(f32) * from_start[..., None]], axis=-1)
    sol = jnp.einsum("nchls,nchsd->nchld", unit_lower_inverse(a), rhs,
                     precision=lax.Precision.HIGHEST)
    u0, w = sol[..., :dv].astype(cdt), sol[..., dv:].astype(cdt)

    # -- a chunk moves the state by S_end = M S_0 + B
    kd = kc * to_end[..., None].astype(cdt)
    carry = jnp.where((end_doc == prev_doc)[..., None],
                      jnp.exp(cs[..., -1]), 0.0)              # (N,NC,H)
    m = carry[..., None, None] * jnp.eye(dk, dtype=f32) - jnp.einsum(
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
    qk = jnp.einsum("nchld,nchsd->nchls", qc, kc, preferred_element_type=f32)
    o = jnp.einsum("nchls,nchsv->nchlv", (qk * decay).astype(cdt),
                   u.astype(cdt), preferred_element_type=f32)
    o = o + from_start[..., None] * jnp.einsum(
        "nchlk,nchkv->nchlv", qc, enter, preferred_element_type=f32)
    return jnp.moveaxis(o, 2, 3).reshape(n, t, h, dv).astype(cdt), last


def gated_delta_recurrence(q, k, v, g, beta, doc=None):
    """The same function token by token, in float32: one ``lax.scan``
    over the tokens.  What ``gated_delta_scan`` and its gradient are
    held against in the tests; nothing in the program calls it."""
    h = v.shape[2]
    if q.shape[2] != h:
        q, k = (jnp.repeat(a, h // a.shape[2], axis=2) for a in (q, k))
    n, t, _, dk = q.shape
    f32 = jnp.float32
    if doc is None:
        doc = jnp.zeros((n, t), jnp.int32)
    start = jnp.concatenate(
        [jnp.ones((n, 1), bool), doc[:, 1:] != doc[:, :-1]], axis=1)

    def step(state, inp):
        qt, kt, vt, gt, bt, st = inp
        keep = jnp.where(st[:, None], 0.0, jnp.exp(gt))        # (N,H)
        state = keep[..., None, None] * state
        delta = bt[..., None] * (vt - jnp.einsum("nhkv,nhk->nhv", state, kt))
        state = state + kt[..., :, None] * delta[..., None, :]
        return state, jnp.einsum("nhkv,nhk->nhv", state, qt)

    seq = tuple(jnp.moveaxis(a, 1, 0) for a in (
        q.astype(f32), k.astype(f32), v.astype(f32), g.astype(f32),
        beta.astype(f32), start))
    with jax.default_matmul_precision("highest"):
        _, o = lax.scan(step, jnp.zeros((n, h, dk, v.shape[-1]), f32), seq)
    return jnp.moveaxis(o, 0, 1)
