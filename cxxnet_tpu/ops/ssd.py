"""The selective state-space scan of Mamba-2, chunked (Dao & Gu 2024,
"Transformers are SSMs", section 6: the state-space dual form).

The recurrence, one scalar decay a head::

    S_t = exp(dt_t * a) * S_{t-1} + dt_t * x_t (x) B_t        S: (P, N)
    y_t = S_t . C_t

with ``S = 0`` before a document's first token.  Step by step that is
T dependent updates of an ``(H, P, N)`` state; the chunked form cuts the
sequence into chunks of ``chunk`` tokens and computes

* inside a chunk, every token against every earlier token of its own
  document as one masked matrix product (the quadratic, attention-like
  half: ``(C B^T * decay) @ (dt x)``),
* each chunk's contribution to the state at its end,
* the states that enter each chunk by a short scan over the chunks,
* and what the entering state adds to each token of the chunk,

so nearly all the work is matrix products of chunk size.

One algorithm and one call site, ``ssd_scan``, in two forms.  Decays are
kept in float32 and in log space until the one ``exp`` of a difference
that is never positive; the products take the activations' dtype and
accumulate in float32; the carried state is float32 — in both:

* plain ``jax.numpy`` that XLA fuses, differentiated by ``jax.grad``
  (``ssd_xla``; the backward of every product is a product of the same
  kind): what runs wherever the other does not, and what the kernels
  are tested against, with the token-by-token ``ssd_recurrence``;
* fused Pallas kernels with their own backward (``ops/ssd_fused.py``),
  which keep a chunk's decay and score matrices and the state on the
  chip where the first writes each to HBM as a whole-row float32 tensor
  (75 ms of a 480 ms step at 5% of the scan's roofline, ledger PR 40).

Which runs is read from what the code can observe, and no conf key
chooses: the platform the program is LOWERED for
(``jax.lax.platform_dependent``: a TPU takes the kernels, also when the
lowering host is a CPU that compiles for a described chip; everything
else the ``jax.numpy`` form) and the shapes the kernels are written for
(``ssd_fused.supported``: heads of 64 or 128 columns, a state that is a
multiple of 128 wide, whole chunks of 128 or 256 tokens, one group's
``(N,T,S)`` operands, bfloat16 or float32).  ``ssd_scan_counted`` also
returns which branch ran, from inside the branch, for the layer's
counter.

``B`` and ``C`` are shared by the heads of a GROUP: with ``(N,T,S)``
operands every head is in the one group (as the dense Mamba-2 hybrids
have it, and as one tensor-parallel rank of a grouped mixer holds it);
with ``(N,T,G,S)`` head ``h`` of ``H`` reads group ``h G // H``, and the
scan is the one-group ``jax.numpy`` scan mapped over the groups — one
group's program is bit for bit what it was before groups came; the
kernels take one group.  Documents: ``doc`` is a
non-decreasing document index a token; no state, and nothing inside a
chunk, crosses from one index to the next.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def doc_index(ids: jnp.ndarray, sep: int = 0) -> jnp.ndarray:
    """``(N, T)`` token ids -> ``(N, T)`` int32 document index: a
    document begins at a row's first token and after every separator
    ``sep`` (which closes the document it follows)."""
    ids = jnp.round(ids).astype(jnp.int32)
    after_sep = jnp.concatenate(
        [jnp.zeros_like(ids[:, :1]), (ids[:, :-1] == sep).astype(jnp.int32)],
        axis=1)
    return jnp.cumsum(after_sep, axis=1)


def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
             b: jnp.ndarray, c: jnp.ndarray,
             doc: Optional[jnp.ndarray] = None,
             chunk: int = 256) -> jnp.ndarray:
    """``x (N,T,H,P)``, ``dt (N,T,H)`` float32 and positive, ``a (H,)``
    float32 and negative, ``b``/``c (N,T,S)`` or, in ``G`` groups of
    ``H / G`` heads, ``(N,T,G,S)``, ``doc (N,T)`` int32 or ``None`` (one
    document a row) -> ``y (N,T,H,P)`` in ``x``'s dtype."""
    return ssd_scan_counted(x, dt, a, b, c, doc, chunk)[0]


def ssd_scan_counted(x, dt, a, b, c, doc=None, chunk: int = 256, skip=None):
    """``(ssd_scan's y, 1 if the fused kernels computed it else 0)``:
    the second is a uint32 scalar each branch returns for itself, so it
    says what ran where the program was lowered for.

    ``skip (H,)`` adds the mixer's ``D`` term, ``y + skip x`` a head on
    ``x``'s dtype: the ``jax.numpy`` form on the ``(N,T,H,P)`` view as it
    always did, the kernels' branch on the ``(N,T,H P)`` rows they write
    (beside a row-major kernel a per-head broadcast costs a float32 copy
    of the whole tensor each way: PERF.md, PR 34)."""
    from . import ssd_fused

    if not ssd_fused.supported(x, b, c, chunk):
        return _xla_branch(x, dt, a, b, c, doc, skip, chunk)
    if doc is None:
        doc = jnp.zeros(x.shape[:2], jnp.int32)
    return _by_platform(x, dt, a, b, c, doc, skip, chunk=int(chunk))


def _xla_branch(x, dt, a, b, c, doc, skip, chunk):
    y = ssd_xla(x, dt, a, b, c, doc, chunk)
    if skip is not None:
        y = y + skip.astype(x.dtype)[:, None] * x
    return y, jnp.uint32(0)


def _fused_branch(x, dt, a, b, c, doc, skip, chunk):
    from . import ssd_fused

    y = ssd_fused.ssd_fused(x, dt, a, b, c, doc, chunk)
    if skip is not None:
        n, t, h, p = x.shape
        y = (y.reshape(n, t, h * p) + jnp.repeat(skip.astype(x.dtype), p)
             * x.reshape(n, t, h * p)).reshape(x.shape)
    return y, jnp.uint32(1)


@functools.partial(jax.jit, static_argnames=("chunk",))
def _by_platform(x, dt, a, b, c, doc, skip, chunk):
    """Both forms under ``lax.platform_dependent``, in a module-level
    ``jax.jit``: the mixers of a stack are one traced function, one
    derivative of it and one lowering — a trace a layer of both branches
    cost granite's nine mixers 3.6 s of set-up (PERF.md, PR 41)."""
    return lax.platform_dependent(
        x, dt, a, b, c, doc, skip,
        tpu=functools.partial(_fused_branch, chunk=chunk),
        default=functools.partial(_xla_branch, chunk=chunk))


def ssd_xla(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
            b: jnp.ndarray, c: jnp.ndarray,
            doc: Optional[jnp.ndarray] = None,
            chunk: int = 256) -> jnp.ndarray:
    """``ssd_scan`` in plain ``jax.numpy``."""
    n, t, h, p = x.shape
    if b.ndim == 4:
        g = b.shape[2]
        by_group = jax.vmap(
            lambda xg, dtg, ag, bg, cg: ssd_xla(xg, dtg, ag, bg, cg, doc,
                                                chunk),
            in_axes=(2, 2, 0, 2, 2), out_axes=2)
        return by_group(x.reshape(n, t, g, h // g, p),
                        dt.reshape(n, t, g, h // g), a.reshape(g, h // g),
                        b, c).reshape(n, t, h, p)
    s = b.shape[-1]
    q = int(chunk)
    pad = (-t) % q
    if doc is None:
        doc = jnp.zeros((n, t), jnp.int32)
    if pad:
        # a padded step has dt = 0: the state passes it unchanged and it
        # adds nothing; its outputs are cut off below
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
        doc = jnp.pad(doc, ((0, 0), (0, pad)), mode="edge")
    nc = (t + pad) // q
    f32 = jnp.float32
    xd = (x * dt[..., None].astype(x.dtype)).reshape(n, nc, q, h, p)
    bq = b.reshape(n, nc, q, s)
    cq = c.reshape(n, nc, q, s)
    dq = doc.reshape(n, nc, q)
    # log decay, summed from the chunk's start: (N, C, Q, H), <= 0
    cs = jnp.cumsum((dt.astype(f32) * a.astype(f32)).reshape(n, nc, q, h),
                    axis=2)
    end_doc = dq[:, :, -1]                                   # (N, C)
    # the document of the token before the chunk; -1 before the first
    prev_doc = jnp.concatenate(
        [jnp.full((n, 1), -1, dq.dtype), end_doc[:, :-1]], axis=1)

    # -- inside a chunk: token l against tokens s <= l of its document
    seen = (lax.broadcasted_iota(jnp.int32, (q, q), 0)
            >= lax.broadcasted_iota(jnp.int32, (q, q), 1))
    same = (dq[:, :, :, None] == dq[:, :, None, :]) & seen   # (N,C,Q,Q)
    csh = jnp.moveaxis(cs, 3, 2)                             # (N,C,H,Q)
    diff = jnp.where(same[:, :, None],
                     csh[..., :, None] - csh[..., None, :], -jnp.inf)
    g = jnp.einsum("nclk,ncsk->ncls", cq, bq, preferred_element_type=f32)
    m = (g[:, :, None] * jnp.exp(diff)).astype(x.dtype)      # (N,C,H,Q,Q)
    y = jnp.einsum("nchls,ncshp->nclhp", m, xd, preferred_element_type=f32)

    # -- what each chunk leaves in the state at its end: (N,C,H,P,S)
    to_end = jnp.where((dq == end_doc[:, :, None])[..., None],
                       jnp.exp(cs[:, :, -1:, :] - cs), 0.0)   # (N,C,Q,H)
    left = jnp.einsum("ncqhp,ncqk->nchpk",
                      xd * to_end[..., None].astype(x.dtype), bq,
                      preferred_element_type=f32)
    # -- the state that enters each chunk: a scan over the chunks
    carry = jnp.where((end_doc == prev_doc)[..., None],
                      jnp.exp(cs[:, :, -1, :]), 0.0)          # (N,C,H)

    def step(state, inp):
        keep, add = inp
        return keep[..., None, None] * state + add, state

    _, enter = lax.scan(step, jnp.zeros((n, h, p, s), f32),
                        (jnp.moveaxis(carry, 1, 0), jnp.moveaxis(left, 1, 0)))
    enter = jnp.moveaxis(enter, 0, 1)                        # (N,C,H,P,S)
    # -- and what that state adds to each token of the chunk
    from_start = jnp.where((dq == prev_doc[:, :, None])[..., None],
                           jnp.exp(cs), 0.0)                  # (N,C,Q,H)
    y = y + from_start[..., None] * jnp.einsum(
        "ncqk,nchpk->ncqhp", cq, enter.astype(x.dtype),
        preferred_element_type=f32)
    return y.reshape(n, nc * q, h, p)[:, :t].astype(x.dtype)


def ssd_recurrence(x, dt, a, b, c, doc=None):
    """The same function step by step, in float32: one ``lax.scan`` over
    the tokens.  What ``ssd_scan`` and its gradient are held against in
    the tests; nothing in the program calls it."""
    n, t, h, p = x.shape
    f32 = jnp.float32
    if b.ndim == 4:  # head h reads group h G // H: a row a head
        b, c = (jnp.repeat(v, h // v.shape[2], axis=2) for v in (b, c))
    else:
        b, c = (jnp.broadcast_to(v[:, :, None], (n, t, h, v.shape[-1]))
                for v in (b, c))
    if doc is None:
        doc = jnp.zeros((n, t), jnp.int32)
    start = jnp.concatenate(
        [jnp.ones((n, 1), bool), doc[:, 1:] != doc[:, :-1]], axis=1)

    def step(state, inp):
        xt, dtt, bt, ct, st = inp
        keep = jnp.where(st[:, None], 0.0, jnp.exp(dtt * a))  # (N,H)
        state = (keep[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        return state, jnp.einsum("nhpk,nhk->nhp", state, ct)

    seq = tuple(jnp.moveaxis(v, 1, 0) for v in (
        x.astype(f32), dt.astype(f32), b.astype(f32), c.astype(f32), start))
    _, y = lax.scan(step, jnp.zeros((n, h, p, b.shape[-1]), f32), seq)
    return jnp.moveaxis(y, 0, 1)
