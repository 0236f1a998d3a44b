"""Fused flash attention as Pallas TPU kernels.

``ops/attention.mha`` is the golden model: it materializes the score
matrix in HBM — ``(B, H, T, T)``, or ``(B, H, 512, <=T)`` float32 row
blocks once the sequence is long — which is both the memory ceiling for
long sequences and an HBM round-trip per step, forward, recomputed and
backward.  These kernels run the standard flash-attention recurrence —
blockwise scores with an online (log-sum-exp) softmax — entirely in
VMEM: scores never touch HBM, and memory is O(T) instead of O(T^2).

The backward pass is the flash recomputation scheme: the forward saves
``(q, k, v, o)`` and the per-row LSE (``m + log l``); the backward
re-derives the probability blocks from (q, k, lse) and accumulates

* ``dq_i  = sum_j  [p_ij * (do_i . v_j - delta_i)] k_j * scale``
* ``dk_j  = sum_i  [p_ij * (do_i . v_j - delta_i)] q_i * scale``
* ``dv_j  = sum_i  p_ij^T do_i``

with ``delta_i = sum_d dO_id O_id`` computed once in XLA.  It has two
forms, one algorithm, and ``_Geometry`` chooses between them from the
call's shapes alone (``_Geometry.one``, ``_one_fits``; no conf key, no
environment variable):

* **ONE kernel, ``flash_bwd``** (PR 48) — ``s``, ``p``, ``dp`` and
  ``ds`` of a (query block, key block) pair derived ONCE and all three
  gradients taken from them: five products and one ``exp`` a live pair.
  ``dq`` sums over key blocks and ``dk`` / ``dv`` over query blocks and
  the group's query heads, so one sweep cannot keep both in block-sized
  accumulators: the grid is ``(key-value heads, steps)``, the steps the
  forward's table once a query head of the group (query-major), ``dq``
  in a ``(block, Dqk)`` float32 accumulator written at a query block's
  last step, ``dk`` and ``dv`` for the key-value head's WHOLE row in
  float32 VMEM, indexed by the step's key block and written once a head.
  No new HBM buffer, no read-modify-write.  The tile is the transposed
  one (keys along the sublanes, the per-query scalars rows), so four of
  the five products are a plain ``a @ b`` or ``a @ b^T`` and one,
  ``ds^T k``, contracts over the tile's first axis.  It runs where the
  row's accumulators and the two buffers of each output fit
  ``_ROW_VMEM``: every token cell's shapes (16384 x (128 + 128), 8192 x
  (256 + 256), 8192 x (192 + 128) in bfloat16).
* **two kernels, ``flash_dq`` and ``flash_dkv``**, for a row past that
  budget: each re-derives the same ``s``, ``p``, ``dp`` and ``ds`` —
  three products and an ``exp`` for ``dq`` on the forward's grid, four
  and an ``exp`` for ``dk`` / ``dv`` on the transposed block, the key
  block resident — seven products and two ``exp`` a live pair, in
  block-sized accumulators whatever the row's length.

Both forms keep the same live pairs and the same float32 statistics.

What the kernels (``flash_fwd``; ``flash_bwd`` or ``flash_dq`` +
``flash_dkv``) take, behind ONE ``jax.custom_vjp`` (``_flash``):

* **a document mask**: ``doc (B, T)``, the non-decreasing document
  index of ``ops/ssd.doc_index``; a query sees the keys of its own
  document (and, causal, not the later ones).
* **the whole mask as two bounds a query** (PR 43).  Documents are runs
  along T and the diagonal and the window are intervals, so "may attend"
  is ``lo_q <= key position <= hi_q`` with two integers a QUERY that XLA
  makes once a row (``_bounds``); they reach a kernel as two small
  operands beside the query block, and a tile compares ONE row of key
  positions with them (``_may_attend``) — no ``(block, block)`` iota, no
  position arithmetic on the tile, no document compare.  A body with no
  mask at all for the blocks whose every pair may attend was measured
  beside it and paid nothing (PERF.md, PR 43: the compares hide behind
  the products), so there is none; ``count_blocks`` says how many such
  blocks a row has.
* **a block the diagonal or the window's edge crosses pays for the
  parts it needs** (PR 43).  What bounds a live block on a v5e is its
  products, not its softmax (PERF.md, PR 43), so the pairs an edge leaves
  dead are worth leaving out: a step's body is chosen from its scalars
  (``_tiles``) — each half of one block (the queries' in the forward,
  the keys' in the backward's kernels) runs against the halves of the other
  it can see, as one tile, and a half wholly above the diagonal or
  beyond the window is in no tile.  A quarter of such a block's
  products, its ``exp`` and its passes are not done; a block no edge
  crosses is its two halves against the whole other block.  Same live
  pairs, same float32 statistics.
* **whole-block skipping**: documents are runs along T, so the key
  blocks a query block may see are ONE range ``[lo, hi]`` (``_ranges``:
  the causal edge and the documents' first/last index a block, made by
  XLA, scalar-prefetched into SMEM).  A step outside it computes nothing
  and its index map is clamped into the range, so it names the block
  that is already there and issues no DMA.  A causal grid does not even
  visit the blocks above the diagonal: the grid is ``(heads, steps)``
  over a static table of the (query block, key block) pairs.
* **two widths**: q and k ``(..., Dqk)``, v and o ``(..., Dv)``.
* **a stated scale** (default ``1 / sqrt(Dqk)``).
* **grouped heads by the index map**: query head ``h`` reads key-value
  head ``h // (H / Hkv)``; nothing is repeated in HBM, and ``dk`` /
  ``dv`` are summed over the group in their float32 accumulators before
  they leave.
* **dynamic position offsets** for the causal mask (ring hops,
  ``flash_mha_lse``): they move ``hi_q`` and the parts a step computes.
* **a window** (static, 0 = none): a query at position ``i`` sees the
  keys ``j`` with ``i - j < window`` — causal, itself and the ``window
  - 1`` before it.  The edge is monotone in the block index like the
  causal edge and the documents', so the reach stays ONE range (its
  ``lo`` raised, ``qhi`` lowered) and the static step table leaves out
  the (query block, key block) pairs that lie wholly beyond the window,
  forward and backward alike: at T 16384, window 2048 and blocks
  of 1024 a query block visits at most 3 key blocks, 45 steps for 136.
  Positions are the row's own, from 0 on both sides: a window with
  position offsets (``flash_mha_lse``, ring hops) is refused, as a
  document mask with them is (ROADMAP R3).  ``window = 0`` builds the
  tables, operands and kernels this module built before it had one.
* **the forward's two outputs named for a ``remat``** (PR 44).  ``o``
  and ``lse`` are the kernel's own outputs AND the backward's residuals;
  ``_flash_fwd`` names them (``KEPT_NAMES``, ``checkpoint_name``), so a
  ``jax.checkpoint`` whose policy saves those names — the net's, one a
  conf layer under ``remat = 1`` (``nnet/net.REMAT_POLICY``) — keeps
  them across the backward pass and its recompute rebuilds ``q``, ``k``
  and ``v`` but runs no second ``flash_fwd``: one forward and one
  backward kernel a layer a step.  A layer then holds ``o`` as the kernel
  wrote it (``(B H, T, Dv)`` in the operands' dtype) and ``lse`` as its
  numbers, ``(B H, T)`` float32 — the kernel's ``(B H, T, 1)`` column
  is tiled to 128 lanes in HBM, so the column is dropped before the
  name and put back in ``_flash_bwd``.  Under a plain ``jax.checkpoint``
  (``mha``'s row blocks' own, any other caller's) nothing is kept and
  nothing changes; where nothing is differentiated a name is the
  identity.  The ring path (``flash_mha_lse``, ``ring_attention_flash``)
  shares the ``custom_vjp``: under the net's ``remat`` a hop's ``o`` and
  ``lse`` would be kept too, ``n`` hops' worth a layer (CPU tests only,
  no cell measures it).

Precision is that of ``ops/attention._attend``: the products take the
operands as they come (bf16 into the MXU, float32 stays float32) and
accumulate in float32; scores, softmax and the running statistics are
float32; probabilities (and ``ds``) are cast to the operands' dtype
before their products.

Layout contract matches ``ops/attention``: ``q, k, v`` are
``(B, T, H, Dh)``; internally heads fold into the grid's batch dim and
blocks are ``(block, Dh)`` tiles.

``interpret=True`` runs the identical kernels on CPU for golden tests
(the PairTest discipline, SURVEY §4.1).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name


NEG_INF = -1e30
#: the block ``block_for`` asks for, queries and keys alike: on a v5e a
#: row of 8192 tokens in ~5 documents read 20.4 / 13.7 / 12.3 ms forward
#: and backward at 1024 against 23.8 / 17.6 / 12.7 at 512 and 26.6 / 17.6
#: / 18.0 at 2048 (heads of 192 + 128, 64, 256; PERF.md, PR 37) — fewer
#: steps and rescalings a pair against more dead pairs inside live blocks.
#: Under a window too: a row of 16384 tokens in 4 documents, 32 query
#: heads on 4 of 128, window 2048 read 26.6 ms forward and backward at
#: 1024 (a third of the visited pairs dead) against 27.6 at 512 (a
#: fifth), 29.8 at 1024 x 512 and 33.9 at 2048, and 8.2 / 9.9 ms forward
#: alone; without the window 34.3 at 1024 against 44.7 at 512
#: (``tools/attn_ab.py --window``; PERF.md, PR 42) — so a windowed
#: layer takes the same block and ``block_for`` does not ask for it
BLOCK = 1024
_VMEM_LIMIT = 100 * 1024 * 1024
#: a block is computed in ``_PARTS`` parts a side, those the diagonal or
#: the window's edge leaves no pair in left out (``_tiles``) — where a
#: part is whole lane tiles (``_LANES``: a v5e's vector registers are 8 x
#: 128).  On the chip halves won: quarters of 256 read 30.2 ms forward and
#: backward for the halves' 22.6 on Trinity's windowed row, the whole
#: block 24.8 (``tools/attn_ab.py``; PERF.md, PR 43)
_PARTS = 2
_LANES = 128

_NN = (((1,), (0,)), ((), ()))    # a @ b
_NT = (((1,), (1,)), ((), ()))    # a @ b^T
_TN = (((0,), (0,)), ((), ()))    # a^T @ b

#: bits of a step's flag: the first / the last step of its output block
_FIRST, _LAST = 1, 2

#: a grid's form, ``(keys, kv)``.  ``keys``: the key block is the resident
#: one and the query blocks sweep past it (else the query block, and the
#: key blocks sweep).  ``kv``: the grid's first axis is the key-value
#: heads, the group's query heads part of the sweep, and the tile is
#: transposed — keys along the sublanes, the per-query scalars rows (else
#: the query heads, the scalars columns).  The forward's and ``dq``'s, the
#: ``dk``/``dv`` kernel's, the one backward kernel's
_BY_Q, _BY_K, _ONE = (False, False), (True, True), (False, True)

#: what the ONE backward kernel may hold in VMEM of a key-value head's
#: whole row: ``dk`` and ``dv`` in float32 accumulators and, as they leave
#: in the operands' dtype, the two buffers of each — ``Tk (Dqk + Dv) (4 + 2
#: itemsize)`` bytes, 32 MiB in bfloat16 at 16384 x (128 + 128) and at 8192
#: x (256 + 256), 20 at 8192 x (192 + 128).  Beside the ~20 MiB of blocks
#: and tiles every kernel here takes, under ``_VMEM_LIMIT`` with room; a
#: row of 32768 or float32 operands at 16384 are past it and run the two
#: kernels
_ROW_VMEM = 40 * 1024 * 1024


def _one_fits(tk: int, dqk: int, dv: int, dtype) -> bool:
    return (tk * (dqk + dv) * (4 + 2 * jnp.dtype(dtype).itemsize)
            <= _ROW_VMEM)


def _dot(a, b, dims):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _may_attend(k0, nk, k_axis, lo_q, hi_q):
    """A tile's mask: its ``nk`` keys, at the positions ``k0 ..`` along
    ``k_axis``, against its queries' two bounds (two columns, or two rows
    beside a transposed tile) — one iota of a row or a column, two
    compares and an ``and``."""
    kpos = k0 + lax.broadcasted_iota(
        jnp.int32, (1, nk) if k_axis else (nk, 1), k_axis)
    return (kpos >= lo_q) & (kpos <= hi_q)


def _parts(bq: int, bk: int) -> int:
    """How many parts a side a block is computed in where the diagonal
    or the window's edge crosses it: ``_PARTS`` where that cuts both
    sides into whole lane tiles, else 1."""
    whole = _PARTS * _LANES
    return 1 if bq % whole or bk % whole else _PARTS


def _part_seen(q0, k0, a, b, hq, hk, causal, window):
    """Whether part ``(a, b)`` — ``hq`` queries by ``hk`` keys — of the
    block whose first query stands at ``q0`` and first key at ``k0`` holds
    a pair the diagonal and the window let attend (documents aside)."""
    ok = True
    if causal:
        ok = k0 + b * hk <= q0 + a * hq + (hq - 1)
    if window:
        near = q0 + a * hq - (k0 + b * hk + (hk - 1)) < window
        ok = near if ok is True else ok & near
    return ok


def _tiles(tile, live, offs, iq, ik, bq, bk, causal, window, by_keys):
    """Run a live step as calls of ``tile(qs, ks)`` on slices of its query
    and key blocks, chosen from the step's scalars: each part of one
    block — the key block's with ``by_keys``, else the query block's —
    against the parts of the other it can see, as ONE tile — they are a
    run, the diagonal and the window's edge being monotone — and the
    parts wholly above the diagonal or beyond the window in no tile.  A
    block neither edge crosses is one side's parts against the whole
    other block; documents are the bounds' (``_may_attend``).  The
    forward walks the queries' parts (its recurrence is a row's); the two
    backward kernels, which have none, the keys': on the chip ``dq`` read
    3% less that way and ``dkv`` 9% less than on the whole block."""
    from jax.experimental import pallas as pl

    n = _parts(bq, bk) if causal or window else 1
    hq, hk = bq // n, bk // n
    q0, k0 = offs[0] + iq * bq, offs[1] + ik * bk
    hr, hc = (hk, hq) if by_keys else (hq, hk)
    for r in range(n):
        seen = [_part_seen(q0, k0, *((c, r) if by_keys else (r, c)),
                           hq, hk, causal, window) for c in range(n)]
        for c0, c1 in ((a, b) for a in range(n) for b in range(a, n)):
            # the other block's parts c0 .. c1 are seen, and no other
            run = live
            for c in range(n):
                run = run & (seen[c] if c0 <= c <= c1
                             else jnp.logical_not(seen[c]))
            rows, cols = pl.ds(r * hr, hr), pl.ds(c0 * hc, (c1 - c0 + 1) * hc)
            pl.when(run)(functools.partial(
                tile, *((cols, rows) if by_keys else (rows, cols))))


def _split(refs, n_in, masked):
    """A kernel's refs after the scalars: its ``n_in`` tensor inputs, the
    queries' two bounds (or ``None``s), the rest."""
    ins, rest = refs[:n_in], refs[n_in:]
    if masked:
        return ins, rest[0], rest[1], rest[2:]
    return ins, None, None, rest


def _fwd_kernel(offs, iq_t, ik_t, fl_t, lo_t, hi_t, g_t, *refs,
                bq, bk, n, tb, causal, window, masked, scale):
    from jax.experimental import pallas as pl

    (q_ref, k_ref, v_ref), lo_q, hi_q, (o_ref, lse_ref, acc, m, l) = (
        _split(refs, 3, masked))
    s_i = pl.program_id(1)
    iq, ik, fl = iq_t[s_i], ik_t[s_i], fl_t[s_i]
    row = (pl.program_id(0) // tb) * n + iq if tb else iq

    @pl.when((fl & _FIRST) != 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m[:] = jnp.full_like(m, NEG_INF)
        l[:] = jnp.zeros_like(l)

    def tile(qs, ks):
        s = _dot(q_ref[0, qs, :], k_ref[0, ks, :], _NT) * scale
        if masked:
            s = jnp.where(_may_attend(ik * bk + ks.start, ks.size, 1,
                                      lo_q[0, qs, :], hi_q[0, qs, :]),
                          s, NEG_INF)
        m_prev = m[qs, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        if masked:
            # a query row masked whole within a live tile leaves m_new at
            # NEG_INF; with 0 in its place (a column's pass, not the
            # tile's) exp(s - .) = 0 for its entries, so `out` alone is
            # valid even under the non-block-aligned offsets the public
            # flash_mha_lse allows
            p = jnp.exp(s - jnp.where(m_new > NEG_INF * 0.5, m_new, 0.0))
        else:
            p = jnp.exp(s - m_new)
        l[qs, :1] = l[qs, :1] * corr + p.sum(axis=-1, keepdims=True)
        m[qs, :1] = m_new
        acc[qs, :] = acc[qs, :] * corr + _dot(p.astype(v_ref.dtype),
                                              v_ref[0, ks, :], _NN)

    _tiles(tile, (ik >= lo_t[row]) & (ik <= hi_t[row]), offs, iq, ik, bq, bk,
           causal, window, False)

    @pl.when((fl & _LAST) != 0)
    def _done():
        lf = jnp.maximum(l[:, :1], 1e-30)
        o_ref[0] = (acc[:] / lf).astype(o_ref.dtype)
        lse_ref[0] = m[:, :1] + jnp.log(lf)


def _dq_kernel(offs, iq_t, ik_t, fl_t, lo_t, hi_t, g_t, *refs,
               bq, bk, n, tb, causal, window, masked, scale):
    from jax.experimental import pallas as pl

    ((q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref), lo_q, hi_q,
     (out_ref, acc)) = _split(refs, 6, masked)
    s_i = pl.program_id(1)
    iq, ik, fl = iq_t[s_i], ik_t[s_i], fl_t[s_i]
    row = (pl.program_id(0) // tb) * n + iq if tb else iq

    @pl.when((fl & _FIRST) != 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    def tile(qs, ks):
        kb = k_ref[0, ks, :]
        s = _dot(q_ref[0, qs, :], kb, _NT) * scale
        p = jnp.exp(s - lse_ref[0, qs, :])
        if masked:
            p = jnp.where(_may_attend(ik * bk + ks.start, ks.size, 1,
                                      lo_q[0, qs, :], hi_q[0, qs, :]), p, 0.0)
        dp = _dot(do_ref[0, qs, :], v_ref[0, ks, :], _NT)
        ds = p * (dp - dl_ref[0, qs, :])
        acc[qs, :] += _dot(ds.astype(kb.dtype), kb, _NN)

    _tiles(tile, (ik >= lo_t[row]) & (ik <= hi_t[row]), offs, iq, ik, bq, bk,
           causal, window, True)

    @pl.when((fl & _LAST) != 0)
    def _done():
        out_ref[0] = (acc[:] * scale).astype(out_ref.dtype)


def _dkv_kernel(offs, ik_t, iq_t, fl_t, lo_t, hi_t, g_t, *refs,
                bq, bk, n, tb, causal, window, masked, scale):
    """The transposed block: keys along the sublanes, queries along the
    lanes; ``lse``, ``delta`` and the queries' bounds are rows."""
    from jax.experimental import pallas as pl

    ((q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref), lo_q, hi_q,
     (dk_out, dv_out, kacc, vacc)) = _split(refs, 6, masked)
    s_i = pl.program_id(1)
    iq, ik, fl = iq_t[s_i], ik_t[s_i], fl_t[s_i]
    row = (pl.program_id(0) // tb) * n + ik if tb else ik

    @pl.when((fl & _FIRST) != 0)
    def _init():
        kacc[:] = jnp.zeros_like(kacc)
        vacc[:] = jnp.zeros_like(vacc)

    def tile(qs, ks):
        qb, dob = q_ref[0, qs, :], do_ref[0, qs, :]
        s = _dot(k_ref[0, ks, :], qb, _NT) * scale
        p = jnp.exp(s - lse_ref[0, :, qs])
        if masked:
            p = jnp.where(_may_attend(ik * bk + ks.start, ks.size, 0,
                                      lo_q[0, :, qs], hi_q[0, :, qs]), p, 0.0)
        vacc[ks, :] += _dot(p.astype(dob.dtype), dob, _NN)
        dp = _dot(v_ref[0, ks, :], dob, _NT)
        ds = p * (dp - dl_ref[0, :, qs])
        kacc[ks, :] += _dot(ds.astype(qb.dtype), qb, _NN)

    _tiles(tile, (iq >= lo_t[row]) & (iq <= hi_t[row]), offs, iq, ik, bq, bk,
           causal, window, True)

    @pl.when((fl & _LAST) != 0)
    def _done():
        dk_out[0] = (kacc[:] * scale).astype(dk_out.dtype)
        dv_out[0] = vacc[:].astype(dv_out.dtype)


def _bwd_kernel(offs, iq_t, ik_t, fl_t, lo_t, hi_t, g_t, *refs,
                bq, bk, n, tb, causal, window, masked, scale):
    """``dq``, ``dk`` and ``dv`` from ONE derivation of a tile's ``s``,
    ``p``, ``dp`` and ``ds``: ``_dkv_kernel``'s transposed block (keys
    along the sublanes) under ``_dq_kernel``'s sweep (a query block with
    its key blocks, for each query head of the key-value head's group).
    ``dq`` sums over the sweep's inner axis into a block-sized
    accumulator as there; ``dk`` and ``dv`` sum over the outer two, so
    their accumulators hold the key-value head's WHOLE row, indexed by
    the step's key block, and leave once a head.  Five products a tile
    for the two kernels' seven, one ``exp`` for two; ``ds^T k`` contracts
    over the tile's first axis."""
    from jax.experimental import pallas as pl

    ((q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref), lo_q, hi_q,
     (dq_out, dk_out, dv_out, qacc, kacc, vacc)) = _split(refs, 6, masked)
    s_i = pl.program_id(1)
    iq, ik, fl = iq_t[s_i], ik_t[s_i], fl_t[s_i]
    row = (pl.program_id(0) // tb) * n + iq if tb else iq

    @pl.when(s_i == 0)
    def _init_row():
        kacc[:] = jnp.zeros_like(kacc)
        vacc[:] = jnp.zeros_like(vacc)

    @pl.when((fl & _FIRST) != 0)
    def _init():
        qacc[:] = jnp.zeros_like(qacc)

    def tile(qs, ks):
        qb, dob, kb = q_ref[0, qs, :], do_ref[0, qs, :], k_ref[0, ks, :]
        rows = pl.ds(pl.multiple_of(ik * bk, bk) + ks.start, ks.size)
        s = _dot(kb, qb, _NT) * scale
        p = jnp.exp(s - lse_ref[0, :, qs])
        if masked:
            p = jnp.where(_may_attend(ik * bk + ks.start, ks.size, 0,
                                      lo_q[0, :, qs], hi_q[0, :, qs]), p, 0.0)
        vacc[rows, :] += _dot(p.astype(dob.dtype), dob, _NN)
        dp = _dot(v_ref[0, ks, :], dob, _NT)
        ds = (p * (dp - dl_ref[0, :, qs])).astype(qb.dtype)
        kacc[rows, :] += _dot(ds, qb, _NN)
        qacc[qs, :] += _dot(ds, kb, _TN)

    _tiles(tile, (ik >= lo_t[row]) & (ik <= hi_t[row]), offs, iq, ik, bq, bk,
           causal, window, True)

    @pl.when((fl & _LAST) != 0)
    def _done():
        dq_out[0] = (qacc[:] * scale).astype(dq_out.dtype)

    @pl.when(s_i == pl.num_programs(1) - 1)
    def _done_row():
        dk_out[0] = (kacc[:] * scale).astype(dk_out.dtype)
        dv_out[0] = vacc[:].astype(dv_out.dtype)


def _pick_block(t: int, want: int) -> int:
    b = min(want, t)
    while t % b:
        b //= 2
    return max(b, 1)


# -- which blocks a step visits, and which of them are live ---------------
@functools.lru_cache(maxsize=None)
def _steps(nq: int, nk: int, bq: int, bk: int, tri: bool, group: int,
           window: int = 0):
    """The static tables of a grid's second axis.

    ``(iq, ik, flags)`` for the forward and ``dq`` kernels — query
    blocks in order, each with its key blocks — and ``(ik, iq, flags)``
    for the ``dk``/``dv`` kernel — key blocks in order, each with the
    ``group`` query heads of its key-value head and their query blocks;
    the fourth table is the step's query head within the group.  ``tri``
    (causal, positions from 0 on both sides, one length) leaves out the
    pairs above the diagonal; ``window`` those whose nearest pair (the
    block's first query, the key block's last key) is beyond it."""
    def live(i, j):
        return ((not tri or j * bk <= i * bq + bq - 1)
                and (not window or i * bq - (j * bk + bk - 1) < window))

    def flags(n):
        f = np.zeros(n, np.int32)
        f[0] |= _FIRST
        f[-1] |= _LAST
        return f

    fwd = [(i, j) for i in range(nq) for j in range(nk) if live(i, j)]
    fl = np.concatenate([flags(sum(1 for p in fwd if p[0] == i))
                         for i in range(nq)])
    fwd_t = (np.array([p[0] for p in fwd], np.int32),
             np.array([p[1] for p in fwd], np.int32), fl,
             np.zeros(1, np.int32))
    bwd = [(j, g, i) for j in range(nk) for g in range(group)
           for i in range(nq) if live(i, j)]
    fl = np.concatenate([flags(sum(1 for p in bwd if p[0] == j))
                         for j in range(nk)])
    bwd_t = (np.array([p[0] for p in bwd], np.int32),
             np.array([p[2] for p in bwd], np.int32), fl,
             np.array([p[1] for p in bwd], np.int32))
    return fwd_t, bwd_t


def _ranges(doc, offs, nq: int, nk: int, bq: int, bk: int, causal: bool,
            window: int = 0):
    """``(lo, hi, qlo, qhi)``, flat int32: for query block ``i`` of table
    row ``b`` the key blocks ``lo[b nq + i] .. hi[b nq + i]`` hold every
    key one of its queries may see (``hi = -1``: none), and for key block
    ``j`` the query blocks ``qlo[b nk + j] .. qhi[b nk + j]``.  One table
    row a batch row with documents, one in all without.

    Causal: key block ``j`` is in reach of query block ``i`` when its
    first key is not after the block's last query.  Documents: ``doc``
    does not decrease along T, so a block's first and last entries are
    its smallest and largest index, and a key block is in reach when the
    two blocks' index ranges overlap.  A window (positions from 0 on
    both sides): the first key block in reach holds the earliest key the
    block's FIRST query sees, ``i bq - window + 1``, and the last query
    block the latest query that sees the key block's LAST key, ``j bk +
    bk - 1 + window - 1``.  All three conditions are monotone in ``j``
    (and in ``i``), so the reach is one range."""
    i = jnp.arange(nq, dtype=jnp.int32)[None]
    j = jnp.arange(nk, dtype=jnp.int32)[None]
    lo, hi = jnp.zeros_like(i), jnp.full_like(i, nk - 1)
    qlo, qhi = jnp.zeros_like(j), jnp.full_like(j, nq - 1)
    if causal:
        gap = offs[0] - offs[1]
        hi = jnp.minimum(hi, (gap + i * bq + bq - 1) // bk)
        qlo = jnp.maximum(qlo, (j * bk - gap) // bq)
    if window:
        lo = jnp.maximum(lo, (i * bq - window + 1) // bk)
        qhi = jnp.minimum(qhi, (j * bk + bk + window - 2) // bq)
    if doc is not None:
        n = doc.shape[0]
        dq, dk = doc.reshape(n, nq, bq), doc.reshape(n, nk, bk)
        qmin, qmax = dq[:, :, :1], dq[:, :, -1:]            # (n, nq, 1)
        kmin, kmax = dk[:, None, :, 0], dk[:, None, :, -1]  # (n, 1, nk)
        count = lambda c, axis: c.sum(axis, dtype=jnp.int32)
        lo = jnp.maximum(lo, count(kmax < qmin, 2))
        hi = jnp.minimum(hi, count(kmin <= qmax, 2) - 1)
        qlo = jnp.maximum(qlo, count(qmax < kmin, 1))
        qhi = jnp.minimum(qhi, count(qmin <= kmax, 1) - 1)
    return (jnp.clip(lo, 0, nk - 1).reshape(-1),
            jnp.clip(hi, -1, nk - 1).reshape(-1),
            jnp.clip(qlo, 0, nq - 1).reshape(-1),
            jnp.clip(qhi, -1, nq - 1).reshape(-1))


def _bounds(doc, offs, t: int, tk: int, causal: bool, window: int = 0):
    """``(lo_q, hi_q)``, int32 ``(B, T)`` with documents and ``(1, T)``
    without: query ``i`` may attend exactly the keys at ``lo_q[i] <= j <=
    hi_q[i]``, ``j`` counted from the keys' own first row.  Documents are
    runs along T and the causal edge and the window are intervals, so the
    whole mask is two integers a query: ``lo_q`` the later of its
    document's first position and ``i - window + 1``, ``hi_q`` the
    earlier of its document's last position and (causal) its own — under
    position offsets ``i + q_off - k_off``, below 0 for a query before
    every key."""
    pos = jnp.arange(t, dtype=jnp.int32)[None]
    lo, hi = jnp.zeros_like(pos), jnp.full_like(pos, tk - 1)
    if doc is not None:
        edge = doc[:, 1:] != doc[:, :-1]
        ends = jnp.ones_like(doc[:, :1], bool)
        lo = lax.cummax(jnp.where(jnp.concatenate([ends, edge], 1), pos, 0),
                        axis=1)
        hi = lax.cummin(jnp.where(jnp.concatenate([edge, ends], 1), pos,
                                  t - 1), axis=1, reverse=True)
    if causal:
        hi = jnp.minimum(hi, pos + (offs[0] - offs[1]))
    if window:
        lo = jnp.maximum(lo, pos - (window - 1))
    return lo, hi


def _clamp(x, lo, hi):
    """``x`` into ``[lo, hi]``, and ``lo`` where the range is empty."""
    return jnp.minimum(jnp.maximum(x, lo), jnp.maximum(hi, lo))


def _offs(q_off, k_off):
    """The ``(2,)`` int32 SMEM operand the kernels read their position
    offsets from; None -> 0 (the plain static path)."""
    return jnp.stack([jnp.asarray(0 if o is None else o, jnp.int32)
                      .reshape(()) for o in (q_off, k_off)])


class _Geometry:
    """What a call's kernels share: block counts, the head grouping, the
    step tables and the live ranges, the block specs over them by a
    grid's form, and which form the backward takes (``one``)."""

    def __init__(self, q, k, v, doc, q_off, k_off, causal, scale, bq, bk,
                 heads, window=0):
        self.bh, self.t, self.dqk = q.shape
        self.bhk, self.tk, self.dv = v.shape
        self.bq, self.bk = bq, bk
        self.nq, self.nk = self.t // bq, self.tk // bk
        self.group = self.bh // self.bhk
        self.causal, self.scale, self.window = causal, scale, window
        self.has_doc = doc is not None
        dyn = q_off is not None or k_off is not None
        if self.has_doc and (dyn or self.t != self.tk):
            raise ValueError("flash: a document mask needs queries and "
                             "keys of one length and no position offsets")
        if window < 0 or (window and (dyn or self.t != self.tk)):
            raise ValueError(
                f"flash: window = {window} needs queries and keys of one "
                "length and no position offsets (flash_mha_lse and the "
                "ring hops have no windowed path: ROADMAP R3)")
        self.offs = _offs(q_off, k_off)
        self.ranges = _ranges(doc, self.offs, self.nq, self.nk, bq, bk,
                              causal, window)
        #: heads of the grid's first axis that one row of ``ranges``
        #: serves, query heads and key-value heads (0: the one row serves
        #: all)
        self.tb = ((heads, heads // self.group) if self.has_doc else (0, 0))
        self.fwd_t, bwd_t = _steps(
            self.nq, self.nk, bq, bk,
            causal and not dyn and self.t == self.tk, self.group, window)
        #: the backward is ONE kernel where a key-value head's whole row
        #: of ``dk`` and ``dv`` fits its share of VMEM, else two
        self.one = _one_fits(self.tk, self.dqk, self.dv, v.dtype)
        # the one kernel's: the forward's sweep once a query head of the
        # group
        iq, ik, fl, _ = self.fwd_t
        self.tables = {_BY_Q: self.fwd_t, _BY_K: bwd_t, _ONE: (
            *(np.tile(x, self.group) for x in (iq, ik, fl)),
            np.repeat(np.arange(self.group, dtype=np.int32), len(iq)))}
        #: nothing masks a call that is not causal and has no window and
        #: no documents: no bounds, no operands
        self.masked = bool(causal or window or self.has_doc)
        self.doc = doc
        if self.masked:
            self.bounds = _bounds(doc, self.offs, self.t, self.tk, causal,
                                  window)

    def kernel(self, fn, form):
        keys, kv = form
        return functools.partial(
            fn, bq=self.bq, bk=self.bk, tb=self.tb[kv],
            n=self.nk if keys else self.nq, causal=self.causal,
            window=self.window, masked=self.masked, scale=self.scale)

    def classes(self):
        """``(live, full, one)``, bool ``(table rows, forward steps)``: the
        steps of the forward table that compute (the key block in the
        query block's live range); those of them whose EVERY pair may
        attend — both blocks in one and the same document, the key block
        wholly under the diagonal and inside the window; those whose two
        blocks lie in one document (the second kind, and the blocks only
        the diagonal or the window's edge crosses)."""
        iq, ik = self.fwd_t[0], self.fwd_t[1]
        lo, hi = (r.reshape(-1, self.nq)[:, iq] for r in self.ranges[:2])
        live = (ik >= lo) & (ik <= hi)
        one = full = jnp.ones_like(live)
        if self.has_doc:
            # ``doc`` does not decrease along T: a block's first and last
            # entries say whether it lies in one document, and which
            ends = lambda n, b: self.doc.reshape(-1, n, b)[:, :, (0, -1)]
            dq = ends(self.nq, self.bq)[:, iq]
            dk = ends(self.nk, self.bk)[:, ik]
            one = full = ((dq[..., 0] == dq[..., 1])
                          & (dk[..., 0] == dk[..., 1])
                          & (dq[..., 0] == dk[..., 0]))
        q0, k0 = self.offs[0] + iq * self.bq, self.offs[1] + ik * self.bk
        if self.causal:
            full = full & (k0 + (self.bk - 1) <= q0)
        if self.window:
            full = full & (q0 + (self.bq - 1) - k0 < self.window)
        return live, live & full, live & one

    def specs(self, form):
        """``(q-side spec of a width, k-side spec of a width, the spec of a
        query block's per-row scalars, [the documents' two specs])`` for
        a grid of ``form``: the forward's and ``dq``'s (heads of q, the
        query block resident; the scalars are ``offs, iq_t, ik_t, fl_t,
        lo, hi, g_t``), the ``dk``/``dv`` grid (heads of k, the key block
        resident; ``offs, ik_t, iq_t, fl_t, qlo, qhi, g_t``) or the one
        backward kernel's (heads of k, the query block resident: the
        first's scalars, the second's heads and rows).  The block that
        moves is clamped into the resident block's live range."""
        from jax.experimental import pallas as pl

        keys, kv = form
        g, tb = self.group, self.tb[kv]
        n = self.nk if keys else self.nq

        def moving(b, s, offs, own_t, other_t, fl_t, lo, hi, g_t):
            r = (b // tb) * n + own_t[s] if tb else own_t[s]
            return _clamp(other_t[s], lo[r], hi[r])

        def resident(b, s, offs, own_t, *_):
            return own_t[s]

        q_blk, k_blk = (moving, resident) if keys else (resident, moving)
        if kv:
            q_head = lambda b, s, *sc: b * g + sc[-1][s]
            k_head = lambda b, s, *sc: b
        else:
            q_head = lambda b, s, *sc: b
            k_head = lambda b, s, *sc: b // g
        row_b = lambda b, s, *sc: b // tb if tb else 0

        def spec(block, head, blk, t_axis):
            """A ``(1, ...)`` block whose T axis is ``t_axis``."""
            def index(b, s, *sc):
                idx = [head(b, s, *sc), 0, 0]
                idx[t_axis] = blk(b, s, *sc)
                return tuple(idx)
            return pl.BlockSpec(block, index)

        qs = lambda width: spec((1, self.bq, width), q_head, q_blk, 1)
        ks = lambda width: spec((1, self.bk, width), k_head, k_blk, 1)
        # a query block's per-row scalars (its two bounds: one row of
        # them a table row): a column beside the resident query block, a
        # row beside the transposed one
        if kv:
            qrow = spec((1, 1, self.bq), q_head, q_blk, 2)
            bound = spec((1, 1, self.bq), row_b, q_blk, 2)
        else:
            qrow = spec((1, self.bq, 1), q_head, q_blk, 1)
            bound = spec((1, self.bq, 1), row_b, q_blk, 1)
        return qs, ks, qrow, ([bound, bound] if self.masked else [])

    def bound_operands(self, form):
        """The queries' two bounds as ``specs`` reads them."""
        if not self.masked:
            return ()
        return tuple(x[:, None, :] if form[1] else x[:, :, None]
                     for x in self.bounds)

    def call(self, kern, form, in_specs, out_specs, out_shape, scratch,
             operands, name, interpret):
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        keys, kv = form
        tabs = self.tables[form]
        scalars = (self.offs, *tabs[:3],
                   *self.ranges[2 * keys:2 * keys + 2], tabs[3])
        return pl.pallas_call(
            self.kernel(kern, form),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(scalars),
                grid=(self.bhk if kv else self.bh, len(tabs[0])),
                in_specs=in_specs, out_specs=out_specs,
                scratch_shapes=scratch),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret, name=name,
        )(*scalars, *operands)


def _flash_fwd_raw(q, k, v, geo: _Geometry, interpret):
    """Folded layout -> ``(out (BH, T, Dv), lse (BH, T, 1) float32)`` —
    the lane-1 layout keeps T in sublanes so the kernel writes it
    without a relayout."""
    from jax.experimental.pallas import tpu as pltpu

    qs, ks, qrow, bounds = geo.specs(_BY_Q)
    bq, dv = geo.bq, geo.dv
    return geo.call(
        _fwd_kernel, _BY_Q,
        [qs(geo.dqk), ks(geo.dqk), ks(dv), *bounds],
        [qs(dv), qrow],
        [jax.ShapeDtypeStruct((geo.bh, geo.t, dv), q.dtype),
         jax.ShapeDtypeStruct((geo.bh, geo.t, 1), jnp.float32)],
        [pltpu.VMEM((bq, dv), jnp.float32),
         pltpu.VMEM((bq, 128), jnp.float32),
         pltpu.VMEM((bq, 128), jnp.float32)],
        (q, k, v, *geo.bound_operands(_BY_Q)), "flash_fwd", interpret)


def _flash_bwd_raw(q, k, v, do, lse, dl, geo: _Geometry, interpret):
    """``lse`` and ``dl`` (``delta``, less a cotangent of ``lse``) as the
    forward's ``(BH, T, 1)`` columns -> ``(dq, dk, dv)``, by the one
    kernel where ``geo.one`` says a row fits, else by the two."""
    if geo.one:
        return _bwd_one(q, k, v, do, lse, dl, geo, interpret)
    return (_bwd_dq(q, k, v, do, lse, dl, geo, interpret),
            *_bwd_dkv(q, k, v, do, lse, dl, geo, interpret))


def _bwd_dq(q, k, v, do, lse, dl, geo: _Geometry, interpret):
    from jax.experimental.pallas import tpu as pltpu

    dqk, dv = geo.dqk, geo.dv
    qs, ks, qrow, bounds = geo.specs(_BY_Q)
    return geo.call(
        _dq_kernel, _BY_Q,
        [qs(dqk), ks(dqk), ks(dv), qs(dv), qrow, qrow, *bounds],
        qs(dqk), jax.ShapeDtypeStruct(q.shape, q.dtype),
        [pltpu.VMEM((geo.bq, dqk), jnp.float32)],
        (q, k, v, do, lse, dl, *geo.bound_operands(_BY_Q)), "flash_dq",
        interpret)


def _bwd_dkv(q, k, v, do, lse, dl, geo: _Geometry, interpret):
    """The key block is the resident operand; the query heads of its
    group and their query blocks sweep innermost."""
    from jax.experimental.pallas import tpu as pltpu

    bk, dqk, dv = geo.bk, geo.dqk, geo.dv
    qs, ks, qrow, bounds = geo.specs(_BY_K)
    as_rows = lambda x: x.reshape(geo.bh, 1, geo.t)
    return geo.call(
        _dkv_kernel, _BY_K,
        [qs(dqk), ks(dqk), ks(dv), qs(dv), qrow, qrow, *bounds],
        [ks(dqk), ks(dv)],
        [jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(v.shape, v.dtype)],
        [pltpu.VMEM((bk, dqk), jnp.float32),
         pltpu.VMEM((bk, dv), jnp.float32)],
        (q, k, v, do, as_rows(lse), as_rows(dl), *geo.bound_operands(_BY_K)),
        "flash_dkv", interpret)


def _bwd_one(q, k, v, do, lse, dl, geo: _Geometry, interpret):
    """The query block is the resident operand, under the group's query
    heads; ``dk`` and ``dv`` leave as a key-value head's whole row."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    dqk, dv = geo.dqk, geo.dv
    qs, ks, qrow, bounds = geo.specs(_ONE)
    row = lambda width: pl.BlockSpec((1, geo.tk, width),
                                     lambda b, s, *sc: (b, 0, 0))
    as_rows = lambda x: x.reshape(geo.bh, 1, geo.t)
    return geo.call(
        _bwd_kernel, _ONE,
        [qs(dqk), ks(dqk), ks(dv), qs(dv), qrow, qrow, *bounds],
        [qs(dqk), row(dqk), row(dv)],
        [jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(v.shape, v.dtype)],
        [pltpu.VMEM((geo.bq, dqk), jnp.float32),
         pltpu.VMEM((geo.tk, dqk), jnp.float32),
         pltpu.VMEM((geo.tk, dv), jnp.float32)],
        (q, k, v, do, as_rows(lse), as_rows(dl), *geo.bound_operands(_ONE)),
        "flash_bwd", interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(6, 13)))
def _flash(q, k, v, doc, q_off, k_off, causal, scale, bq, bk, heads,
           interpret, window=0):
    """The kernels on the folded layout: ``q (B H, T, Dqk)``, ``k (B Hkv,
    Tk, Dqk)``, ``v (B Hkv, Tk, Dv)``, ``doc (B, T)`` or ``None``,
    traced position offsets or ``None`` -> ``(o (B H, T, Dv), lse (B H,
    T, 1))``.  Cotangents of BOTH outputs are taken: ``dL/dlse`` folds
    into the backward kernels as ``ds = p * (dp - (delta - dlse))``."""
    return _flash_fwd(q, k, v, doc, q_off, k_off, causal, scale, bq, bk,
                      heads, interpret, window)[0]


#: what a call is compiled for, beside its operands' shapes
_STATIC = ("causal", "scale", "bq", "bk", "heads", "interpret", "window")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _forward(q, k, v, doc, q_off, k_off, causal, scale, bq, bk, heads,
             interpret, window):
    """The forward call under ONE ``jax.jit``: the layers of a net that
    call it with the same shapes and settings share one trace of the
    kernel and one lowering of it in a program (a step program's set-up
    pays a Mosaic lowering a distinct call, not a layer)."""
    geo = _Geometry(q, k, v, doc, q_off, k_off, causal, scale, bq, bk, heads,
                    window)
    return _flash_fwd_raw(q, k, v, geo, interpret)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _backward(q, k, v, doc, q_off, k_off, out, lse, g, g_lse, causal, scale,
              bq, bk, heads, interpret, window):
    """The backward's call — or its two — shared the same way."""
    geo = _Geometry(q, k, v, doc, q_off, k_off, causal, scale, bq, bk, heads,
                    window)
    delta = (g.astype(jnp.float32) * out.astype(jnp.float32)).sum(
        -1, keepdims=True)
    # dL/dlse_i adds p_ij * dlse_i to ds_ij; the kernels compute
    # ds = p * (dp - dl) so dl = delta - dlse absorbs it
    return _flash_bwd_raw(q, k, v, g, lse, delta - g_lse, geo, interpret)


#: the names (``jax.ad_checkpoint.checkpoint_name``) of what the forward
#: kernel computed and the backward kernels read again: a ``jax.checkpoint``
#: whose policy saves them (``nnet/net.REMAT_POLICY``) keeps the two across
#: the backward pass and its recompute runs no second ``flash_fwd``
KEPT_NAMES = ("flash_o", "flash_lse")


def _flash_fwd(q, k, v, doc, q_off, k_off, causal, scale, bq, bk, heads,
               interpret, window=0):
    out, lse = _forward(q, k, v, doc, q_off, k_off, causal, scale, bq, bk,
                        heads, interpret, window)
    # named OUTSIDE the jitted call; the named values are the primal
    # outputs AND the residuals, so every reader reads what a policy may
    # keep (``lse`` lane-dense, without its column: the module docstring)
    out = checkpoint_name(out, KEPT_NAMES[0])
    lse = checkpoint_name(lse[:, :, 0], KEPT_NAMES[1])
    return (out, lse[:, :, None]), (q, k, v, doc, q_off, k_off, out, lse)


def _flash_bwd(causal, scale, bq, bk, heads, interpret, window, res, cts):
    *ins, out, lse = res
    dq, dk, dv = _backward(*ins, out, lse[:, :, None], *cts, causal, scale,
                           bq, bk, heads, interpret, window)
    return dq, dk, dv, None, None, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def block_for(q, k, v):
    """The block, queries and keys alike, for ``q (B, T, H, Dqk)``, ``k``,
    ``v (B, T, Hkv, Dv)`` — or ``None``: not a shape the kernels are
    written for, ``ops/attention.attend`` leaves it to ``mha``.  They
    take one long sequence on both sides (``attention.LONG_T`` tokens or
    more, divided by a block of 128 or more), head widths that are
    multiples of 64 up to 256, grouped heads, bfloat16 or float32.  The
    largest block up to ``BLOCK`` that divides T, whatever the widths:
    measured at heads of 64, 192 + 128 and 256 (``tools/attn_ab.py``,
    PERF.md PR 37), one size won at all three, and under a window of
    2048 in rows of 16384 again (PR 42: ``BLOCK`` has the reading)."""
    from .attention import LONG_T

    t, h, dqk = q.shape[1:]
    dv = v.shape[-1]
    block = _pick_block(t, BLOCK)
    ok = (t >= LONG_T and k.shape[1] == t and block >= 128
          and h % k.shape[2] == 0 and k.shape[:3] == v.shape[:3]
          and all(d % 64 == 0 and d <= 256 for d in (dqk, dv))
          and q.dtype == k.dtype == v.dtype
          and v.dtype in (jnp.bfloat16, jnp.float32))
    return block if ok else None


def one_backward(q, k, v) -> bool:
    """Whether the backward of ``flash_attention(q, k, v, ...)`` at
    ``block_for``'s blocks — as ``ops/attention.attend`` calls the
    kernels — is the ONE kernel ``flash_bwd`` (``_Geometry.one``: a
    key-value head's whole row of ``dk`` and ``dv`` fits its share of
    VMEM) and not ``flash_dq`` + ``flash_dkv``; ``False`` where
    ``block_for`` has no block (``mha`` computes such a call)."""
    return block_for(q, k, v) is not None and _one_fits(
        k.shape[1], q.shape[-1], v.shape[-1], v.dtype)


def count_blocks(q, k, v, *, causal: bool = False, doc=None, window: int = 0,
                 block_q: int | None = None, block_k: int | None = None):
    """What the forward kernel visits for ``flash_attention(q, k, v,
    ...)``, uint32 ``(3,)``: the live steps of its table (a query block
    against a key block of its live range) over all query heads and rows;
    those of them whose every pair may attend (no mask would be needed);
    those whose two blocks lie in one document (the second kind, and the
    ones only the diagonal or the window's edge crosses).  Summed in XLA
    from the tables the kernels read (``_Geometry.classes``).  The blocks
    default to ``block_for``'s, as ``ops/attention.attend`` calls the
    kernels; zeros where it has none (``mha`` computes such a call)."""
    b, t, h, dqk = q.shape
    if block_q is None:
        block_q = block_k = block_for(q, k, v)
        if block_q is None:
            return jnp.zeros(3, jnp.uint32)
    geo = _Geometry(
        jax.ShapeDtypeStruct((b * h, t, dqk), q.dtype), None,
        jax.ShapeDtypeStruct((b * k.shape[2], k.shape[1], v.shape[-1]),
                             v.dtype),
        doc, None, None, bool(causal), 1.0, _pick_block(t, block_q),
        _pick_block(k.shape[1], block_k), h, int(window))
    classes = geo.classes()
    rows = classes[0].shape[0]
    return jnp.stack([c.sum(dtype=jnp.uint32) for c in classes]) * jnp.uint32(
        b * h // rows)


def _fold(x):
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _unfold(x, b, h):
    bh, t, d = x.shape
    return x.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def flash_attention(q, k, v, *, causal: bool = False, scale=None, doc=None,
                    q_off=None, k_off=None, block_q: int = 512,
                    block_k: int = 512, interpret: bool = False,
                    window: int = 0):
    """``ops/attention.mha`` as the flash kernels: ``q (B, T, H, Dqk)``,
    ``k (B, Tk, Hkv, Dqk)``, ``v (B, Tk, Hkv, Dv)`` with ``Hkv`` dividing
    ``H``, ``scale`` in place of ``1 / sqrt(Dqk)``, ``doc (B, T)`` the
    document index a token, ``window`` the keys a query sees counted
    back from itself (0: all) -> ``(o (B, T, H, Dv), lse (B, T, H))``.
    ``_pick_block`` halves a block until it divides its length."""
    b, t, h, dqk = q.shape
    if h % k.shape[2] or k.shape[:3] != v.shape[:3]:
        raise ValueError(f"flash: q {q.shape}, k {k.shape}, v {v.shape}")
    out, lse = _flash(
        _fold(q), _fold(k), _fold(v), doc, q_off, k_off, bool(causal),
        float(1.0 / math.sqrt(dqk) if scale is None else scale),
        _pick_block(t, block_q), _pick_block(k.shape[1], block_k), h,
        bool(interpret), int(window))
    return _unfold(out, b, h), lse[:, :, 0].reshape(b, h, t).transpose(
        0, 2, 1)


def flash_mha(q, k, v, causal: bool = False, block_q: int = 512,
              block_k: int = 512, interpret: bool = False):
    """Flash attention on ``(B, T, H, Dh)`` tensors — drop-in for
    ``attention.mha``.  ``_pick_block`` halves the block until it
    divides T; callers (the layer's ``auto`` dispatch) should route T
    whose largest dividing block is tiny back to ``mha`` — a block-1
    kernel is valid but pathologically slow."""
    return flash_attention(q, k, v, causal=causal, block_q=block_q,
                           block_k=block_k, interpret=interpret)[0]


def flash_mha_lse(q, k, v, q_off, k_off, causal: bool = True,
                  block_q: int = 512, block_k: int = 512,
                  interpret: bool = False):
    """Flash attention returning ``(out, lse)`` with dynamic position
    offsets — the ring-attention building block.

    ``lse`` is the per-row log-sum-exp ``(B, T, H)`` of the (masked)
    scores; ring hops merge partial results as
    ``lse' = logaddexp(lse_a, lse_b)``, ``o' = (o_a e^{lse_a-lse'} +
    o_b e^{lse_b-lse'})``.  ``q_off``/``k_off`` are traced scalars: the
    global positions of this call's first query/key row, consumed by
    the causal mask (a hop whose keys all sit after the queries yields
    lse ~ -1e30 and washes out of the merge).  The VJP accepts
    cotangents for BOTH outputs.
    """
    return flash_attention(q, k, v, causal=causal, q_off=q_off, k_off=k_off,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret)
