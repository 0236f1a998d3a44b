"""The chunked delta rule of ``ops/kda.py`` (a decay a key channel) as
Pallas TPU kernels with their own backward: ``ops/gdn_fused.py``'s three
kernels with the decay INSIDE a chunk's contractions.

The same mathematics as ``ops/kda._chunked`` in the same precisions, in
three kernels behind one ``jax.custom_vjp``:

* ``kda_solve`` — no chunk waits for another: per chunk the two pair
  matrices ``Pq_ls = sum_d q_ld k_sd e^{G_ld - G_sd}`` and ``Pk`` (``k``
  in ``q``'s place), ``BLOCK`` rows at a time against the row block's own
  reference (``_pairs``; ``ops/kda.py`` says why no factor leaves
  float32), ``A = beta Pk`` under the strict lower mask, ``T = (I +
  A)^{-1}`` by the block doubling of ``ops/gdn.unit_lower_inverse``, two
  chunks side by side so that a product fills the MXU.  ``[T | Pq]`` (float32, a chunk's two matrices side by side on a tile's
  128 lanes) goes to HBM once; ``scan`` and the backward read it.
* ``kda_scan`` — the chunks of a row in order, the state in VMEM scratch
  and TRANSPOSED, ``(Dv, Dk)``: a decay a key channel is then a row
  along the lanes, which multiplies the state with no transpose of its
  own.  ``U = T b (V - (k e^G) S)``, ``o = Pq U + (q e^G) S``, ``S <-
  e^{G_end} S + (k e^{G_end - G})^T U``.  It keeps ``U`` and the state
  that entered each chunk (on the activations' dtype, as every product
  reads it) for the backward.
* ``kda_scan_bwd`` — the chunks in reverse, ``dS`` in VMEM scratch.  The
  decay's cotangent needs no matrix of its own: wherever ``G`` stands it
  multiplies a ``q`` or a ``k``, so ``dG = q dq + k (dk_left -
  dk_right)`` with the cotangents of ``k`` kept apart by the side of the
  pair it stood on (``+G_l`` on the left, ``-G_s`` on the right), plus,
  on the chunk's last row, what ``e^{G_end}`` carried.

NOTHING the forward kernels write is named for ``nnet/net.REMAT_POLICY``
(as ``ops/gdn_fused.KEPT_NAMES`` names the scalar rule's): a layer's
``remat`` recompute runs ``solve`` and ``scan`` a second time.  The one
configuration that runs this rule has five such mixers beside 9.2 GB of
weights and adam's moments; its step compiles for a described v5e to
14.35 GB live with nothing kept, to 14.74 with ``[T | Pq]`` alone kept
(134 MB a layer) and to 16.41 with everything the backward reads kept,
against the 14.4 GB the token cells are held to (PERF.md, PR 49).

The decay comes in as ``gs``, its running sum from each chunk's start
(``ops/kda.chunk_sums``, plain ``jax.numpy`` outside: ``jax.grad`` takes
the kernels' ``dgs`` through it and through the gate); ``beta``, the
document index and the three 0/1 masks of ``_chunked`` (``from_start``,
``to_end``, ``carry`` without their decays) as one ``(ROWS, LANES)``
float32 tile a chunk and head, in ``gdn_fused``'s rows.  Heads are one
to one: ``q``, ``k`` and ``v`` have as many (the kernels' ``rep``, which
``gdn_fused._Dims`` hands every kernel, is 1 and unread).

``interpret=True`` runs the identical kernels on the CPU for the tests.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from .gdn_fused import (CHUNK, LANES, R_BETA, R_CARRY, R_DOC, R_FS, R_TE, ROWS,
                        STRETCH, _col, _Dims, _dot, _ds, _HI, _iotas, _keep,
                        _NN, _NT, _params, _row, _specs, _TN, _unit,
                        _unit_bwd)
from .kda import BLOCK, chunk_sums

#: heads a grid step takes, at most (their chains of products do not depend
#: on each other and interleave: at 32 heads forward / forward + backward
#: read 8.96 / 14.57 ms a layer at 2, 8.23 / 13.57 at 4, 8.01 / 13.04 at 8,
#: my chip run, PR 49; two pairs of chunks a turn of ``solve``'s loop bought
#: nothing: 8.71 / 14.48 at 2 heads); read when a kernel is first traced for
#: a shape: ``tools/kda_ab.py`` sets it before its first call
HEADS = 8


def supported(q, k, v, chunk: int) -> bool:
    """The shapes the kernels are written for."""
    dk, dv = q.shape[-1], v.shape[-1]
    return (int(chunk) == CHUNK and dk % LANES == 0 and dv % LANES == 0
            and q.dtype == k.dtype == v.dtype
            and v.dtype in (jnp.bfloat16, jnp.float32)
            and v.shape[2] == q.shape[2] == k.shape[2])


# -- inside a kernel ------------------------------------------------------
def _last_row(x):
    """``x (C, D)`` -> its last row ``(1, D)``: a select and a sublane
    reduction, exact."""
    at = lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.sum(jnp.where(at == x.shape[0] - 1, x, 0.0), axis=0,
                   keepdims=True)


def _blocks(gs, kf, cdt):
    """The row blocks of one chunk: for each, ``(its rows, lfac, rfac,
    kr)`` — the rows' own factor ``e^{G_l - G_r}`` ``(BLOCK, Dk)``, the
    columns' ``e^{G_r - G_s}`` ``(C, Dk)`` (0 right of the diagonal
    block) and ``k`` times it on the activations' dtype."""
    c = gs.shape[0]
    at = lax.broadcasted_iota(jnp.int32, gs.shape, 0)
    out = []
    for r0 in range(0, c, BLOCK):
        rows = slice(r0, r0 + BLOCK)
        ref = gs[r0:r0 + 1]
        live = at < r0 + BLOCK
        rfac = jnp.where(live, jnp.exp(jnp.where(live, ref - gs, 0.0)), 0.0)
        out.append((rows, jnp.exp(gs[rows] - ref), rfac,
                    (kf * rfac).astype(cdt)))
    return out


def _pairs(lefts, blocks, act):
    """``ops/kda.pair_products`` of one chunk: ``lefts`` float32 ``(C,
    Dk)`` each -> as many ``(C, C)`` float32."""
    cdt = blocks[0][3].dtype
    n = len(lefts)
    got = []
    for rows, lfac, _, kr in blocks:
        left = jnp.concatenate([(a[rows] * lfac).astype(cdt) for a in lefts],
                               axis=0)
        got.append(_dot(left, kr, _NT, act))
    return [jnp.concatenate([p[j * BLOCK:(j + 1) * BLOCK] for p in got],
                            axis=0) for j in range(n)]


def _same_doc(aux, eye):
    c = eye.shape[0]
    doc_r = aux[R_DOC:R_DOC + 1, :c]
    return _col(doc_r, eye) == doc_r


def _tiles(i, c, hh, dk, dv):
    """Chunk ``i``'s tokens, head ``hh``'s key and value lanes, the
    chunk's tile of per-token scalars."""
    return (_ds(i * c, c), slice(hh * dk, (hh + 1) * dk),
            slice(hh * dv, (hh + 1) * dv), _ds(i * ROWS, ROWS))


def _solve_kernel(q_ref, k_ref, gs_ref, aux_ref, tp_ref, *, ncb, hb, rep,
                  c, dk, dv, unit, q_scale):
    """Two chunks at a time for the doubling, their ``(C, C)`` matrices side
    by side along the lanes as ``gdn_fused._solve_kernel`` lays them: a
    product is ``[T_a | T_b] @ diag(X_a, X_b)``, one pass of 64 rows over
    the whole MXU where two chunks apart take two over a quarter of it
    each.  The same arithmetic a chunk: the other chunk's block of ``diag``
    is zeros."""
    cdt = q_ref.dtype
    f32 = jnp.float32
    act = _HI if cdt == f32 else None
    row, col = _iotas(c)
    eye = row == col
    w2 = 2 * c
    row2 = lax.broadcasted_iota(jnp.int32, (c, w2), 0)
    lane = lax.broadcasted_iota(jnp.int32, (c, w2), 1)
    col2, left = lane & (c - 1), lane < c
    same = row2 ^ col2

    def diag(strip):
        """``[X_a | X_b] (C, 2C)`` -> ``[[X_a, 0], [0, X_b]]``."""
        return jnp.concatenate([jnp.where(left, strip, 0.0),
                                jnp.where(left, 0.0, strip)], axis=0)

    def m21(b):
        return (same < 2 * b) & ((row2 & b) != 0) & ((col2 & b) == 0)

    def chunk(i, hh):
        """``(A, Pq)`` of chunk ``i``, both masked."""
        tok, ks, _, ar = _tiles(i, c, hh, dk, dv)
        aux = aux_ref[0, hh, ar, :]
        gs = gs_ref[0, tok, ks]
        qf = _unit(q_ref[0, tok, ks], unit, q_scale, cdt)[0].astype(f32)
        kf = _unit(k_ref[0, tok, ks], unit, 1.0, cdt)[0].astype(f32)
        beta = _col(aux[R_BETA:R_BETA + 1, :c], eye)
        doc = _same_doc(aux, eye)
        pq, pk = _pairs((qf, kf), _blocks(gs, kf, cdt), act)
        # Pq as the products take it: rounded to their dtype
        return (jnp.where(doc & (row > col), pk * beta, 0.0),
                jnp.where(doc & (row >= col), pq, 0.0).astype(cdt).astype(f32))

    def one(p, hh):
        (a0, pq0), (a1, pq1) = chunk(2 * p, hh), chunk(2 * p + 1, hh)
        a = jnp.concatenate([a0, a1], axis=1)
        t = jnp.where(row2 == col2, 1.0, 0.0) - jnp.where(m21(1), a, 0.0)
        b = 2
        while b < c:
            am = diag(jnp.where(m21(b), a, 0.0))
            t = t - _dot(_dot(t, am, _NN, _HI), diag(t), _NN, _HI)
            b *= 2
        tp_ref[0, hh, _ds(2 * p * c, c), :] = jnp.concatenate(
            [t[:, :c], pq0], axis=1)
        tp_ref[0, hh, _ds((2 * p + 1) * c, c), :] = jnp.concatenate(
            [t[:, c:], pq1], axis=1)

    def body(p, loop):
        for hh in range(hb):
            one(p, hh)
        return loop

    lax.fori_loop(0, ncb // 2, body, 0)


def _scan_kernel(q_ref, k_ref, v_ref, gs_ref, tp_ref, aux_ref, o_ref, u_ref,
                 s_ref, state, *, ncb, hb, rep, c, dk, dv, unit, q_scale):
    from jax.experimental import pallas as pl

    cdt = o_ref.dtype
    f32 = jnp.float32
    act = _HI if cdt == f32 else None
    eye = jnp.equal(*_iotas(c))

    @pl.when(pl.program_id(2) == 0)
    def _start():
        state[...] = jnp.zeros_like(state)

    def body(i, loop):
        for hh in range(hb):
            tok, ks, vs, ar = _tiles(i, c, hh, dk, dv)
            aux = aux_ref[0, hh, ar, :]
            gs = gs_ref[0, tok, ks]
            end = _last_row(gs)
            qf = _unit(q_ref[0, tok, ks], unit, q_scale, cdt)[0].astype(f32)
            kf = _unit(k_ref[0, tok, ks], unit, 1.0, cdt)[0].astype(f32)
            beta = _col(aux[R_BETA:R_BETA + 1, :c], eye)
            fse = _col(aux[R_FS:R_FS + 1, :c], eye) * jnp.exp(gs)
            te = _col(aux[R_TE:R_TE + 1, :c], eye)
            tp = tp_ref[0, hh, tok, :]
            s0 = state[hh]                                   # (Dv, Dk)
            sc = s0.astype(cdt)
            s_ref[0, hh, i] = sc
            # U = T beta (V - (k e^G) S): U0 - W S with both under one T
            kp = (kf * fse).astype(cdt)
            u = _dot(tp[:, :c], beta * (
                v_ref[0, tok, vs].astype(f32) - _dot(kp, sc, _NT, act)),
                _NN, _HI).astype(cdt)
            u_ref[0, tok, vs] = u
            qp = (qf * fse).astype(cdt)
            o = (_dot(tp[:, c:].astype(cdt), u, _NN, act)
                 + _dot(qp, sc, _NT, act))
            o_ref[0, tok, vs] = o.astype(cdt)
            kd = (kf * (te * jnp.exp(end - gs))).astype(cdt)
            state[hh] = (_keep(aux, dk) * jnp.exp(end) * s0
                         + _dot(u, kd, _TN, act))
        return loop

    lax.fori_loop(0, ncb, body, 0)


def _scan_bwd_kernel(q_ref, k_ref, v_ref, gs_ref, u_ref, tp_ref, s_ref,
                     aux_ref, do_ref, dq_ref, dk_ref, dv_ref, dgs_ref,
                     daux_ref, dstate, *, ncb, hb, rep, c, dk, dv, unit,
                     q_scale):
    from jax.experimental import pallas as pl

    cdt = v_ref.dtype
    f32 = jnp.float32
    act = _HI if cdt == f32 else None
    row, col = _iotas(c)
    eye = row == col
    sub = lax.broadcasted_iota(jnp.int32, (ROWS, c), 0)
    last = lax.broadcasted_iota(jnp.int32, (c, dk), 0) == c - 1

    @pl.when(pl.program_id(2) == 0)
    def _start():
        dstate[...] = jnp.zeros_like(dstate)

    def head(i, hh):
        tok, ks, vs, ar = _tiles(i, c, hh, dk, dv)
        aux = aux_ref[0, hh, ar, :]
        gs = gs_ref[0, tok, ks]
        end = _last_row(gs)
        qc, q_back = _unit(q_ref[0, tok, ks], unit, q_scale, cdt)
        kc, k_back = _unit(k_ref[0, tok, ks], unit, 1.0, cdt)
        qf, kf = qc.astype(f32), kc.astype(f32)
        do = do_ref[0, tok, vs]
        beta = _col(aux[R_BETA:R_BETA + 1, :c], eye)
        fse = _col(aux[R_FS:R_FS + 1, :c], eye) * jnp.exp(gs)
        ted = _col(aux[R_TE:R_TE + 1, :c], eye) * jnp.exp(end - gs)
        keep = _keep(aux, dk) * jnp.exp(end)                 # (1, Dk)
        doc = _same_doc(aux, eye)
        sc = s_ref[0, hh, i]                                 # (Dv, Dk)
        tp = tp_ref[0, hh, tok, :]
        ds1 = dstate[hh]
        dsc = ds1.astype(cdt)
        u = u_ref[0, tok, vs]
        kp, qp, kd = ((a * m).astype(cdt)
                      for a, m in ((kf, fse), (qf, fse), (kf, ted)))

        # -- o = pq u + qp s0;  s1 = keep s0 + kd^T u;  u = T beta (v - kp s0)
        du = (_dot(tp[:, c:].astype(cdt), do, _TN, act)
              + _dot(kd, dsc, _NT, act))
        dr = _dot(tp[:, :c], du, _TN, _HI)
        drc = dr.astype(cdt)
        dpq = jnp.where(doc & (row >= col), _dot(do, u, _NT, act), 0.0)
        da = jnp.where(doc & (row > col), -_dot(drc, u, _NT, act), 0.0)
        ksn = _dot(kp, sc, _NT, act)
        dy = (-beta * dr).astype(cdt)                        # d(kp s0)
        dkp = _dot(dy, sc, _NN, act)
        dqp = _dot(do, sc, _NN, act)
        dkd = _dot(u, dsc, _NN, act)
        dstate[hh] = (keep * ds1 + _dot(do, qp, _TN, act)
                      + _dot(dy, kp, _TN, act))

        # -- the pair matrices, a row block at a time: what the rows'
        # side hands q and k (x: per unit of beta), and the columns' k
        dql, x, dkr = [], [], jnp.zeros((c, dk), f32)
        for rows, lfac, rfac, kr in _blocks(gs, kf, cdt):
            dp = jnp.concatenate([dpq[rows], da[rows]], axis=0).astype(cdt)
            m = _dot(dp, kr, _NN, act)
            dql.append(lfac * m[:BLOCK])
            x.append(lfac * m[BLOCK:])
            left = jnp.concatenate(
                [(qf[rows] * lfac).astype(cdt),
                 (kf[rows] * lfac * beta[rows]).astype(cdt)], axis=0)
            dkr = dkr + rfac * _dot(dp, left, _TN, act)
        x = jnp.concatenate(x, axis=0)
        dq = jnp.concatenate(dql, axis=0) + fse * dqp
        dk_left = beta * x + fse * dkp
        to_end = ted * dkd
        dk_right = dkr + to_end
        dbeta = (jnp.sum(dr * (v_ref[0, tok, vs].astype(f32) - ksn), axis=1,
                         keepdims=True)
                 + jnp.sum(kf * x, axis=1, keepdims=True))
        # what e^{G_end} carried: the state's decay and every k's to_end
        dend = (jnp.sum(kf * to_end, axis=0, keepdims=True)
                + keep * jnp.sum(ds1 * sc.astype(f32), axis=0,
                                 keepdims=True))
        dgs_ref[0, tok, ks] = (qf * dq + kf * (dk_left - dk_right)
                               + jnp.where(last, dend, 0.0))
        dv_ref[0, tok, vs] = (beta * dr).astype(cdt)
        daux_ref[0, hh, ar, :] = jnp.zeros((ROWS, LANES), f32)
        daux_ref[0, hh, ar, :c] = jnp.where(sub == R_BETA, _row(dbeta, eye),
                                            0.0)
        dq_ref[0, tok, ks] = _unit_bwd(dq, q_back, q_scale).astype(cdt)
        dk_ref[0, tok, ks] = _unit_bwd(dk_left + dk_right, k_back,
                                       1.0).astype(cdt)

    def body(j, loop):
        for hh in range(hb):
            head(ncb - 1 - j, hh)
        return loop

    lax.fori_loop(0, ncb, body, 0)


# -- the calls --------------------------------------------------------------
def _kda_specs(dims, t, reverse=False):
    """``gdn_fused._specs`` plus ``[T | Pq]``'s (a chunk's two ``(C, C)``
    matrices side by side: ``2 C = LANES`` columns, none of a tile's lanes
    left empty) and the transposed state's."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    sp = _specs(dims, t, reverse)
    tb, nt = dims.ncb * CHUNK, t // (dims.ncb * CHUNK)
    at = (lambda i: nt - 1 - i) if reverse else (lambda i: i)
    sp["tp"] = pl.BlockSpec((1, dims.hb, tb, 2 * CHUNK),
                            lambda b, h, i: (b, h, at(i), 0),
                            memory_space=pltpu.VMEM)
    sp["state_t"] = pl.BlockSpec(
        (1, dims.hb, dims.ncb, dims.dv, dims.dk),
        lambda b, h, i: (b, h, at(i), 0, 0), memory_space=pltpu.VMEM)
    return sp


def _state_scratch(dims):
    from jax.experimental.pallas import tpu as pltpu

    return [pltpu.VMEM((dims.hb, dims.dv, dims.dk), jnp.float32)]


@functools.partial(jax.jit, static_argnames=("dims", "interpret"))
def _solve(q, k, gs, aux, dims, interpret):
    from jax.experimental import pallas as pl

    n, t, _ = k.shape
    sp = _kda_specs(dims, t)
    return pl.pallas_call(
        dims.kernel(_solve_kernel, q_scale=dims.q_scale),
        grid=dims.grid(n, t),
        in_specs=[sp["key"], sp["key"], sp["key"], sp["aux"]],
        out_specs=sp["tp"],
        out_shape=jax.ShapeDtypeStruct((n, dims.hv, t, 2 * CHUNK),
                                       jnp.float32),
        compiler_params=_params(False), interpret=interpret,
        name="kda_solve",
    )(q, k, gs, aux)


@functools.partial(jax.jit, static_argnames=("dims", "interpret"))
def _scan(q, k, v, gs, tp, aux, dims, interpret):
    from jax.experimental import pallas as pl

    n, t, _ = k.shape
    sp = _kda_specs(dims, t)
    return pl.pallas_call(
        dims.kernel(_scan_kernel, q_scale=dims.q_scale), grid=dims.grid(n, t),
        in_specs=[sp["key"], sp["key"], sp["val"], sp["key"], sp["tp"],
                  sp["aux"]],
        out_specs=[sp["val"], sp["val"], sp["state_t"]],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(
                       (n, dims.hv, t // CHUNK, dims.dv, dims.dk), v.dtype)],
        scratch_shapes=_state_scratch(dims),
        compiler_params=_params(True), interpret=interpret,
        name="kda_scan",
    )(q, k, v, gs, tp, aux)


@functools.partial(jax.jit, static_argnames=("dims", "interpret"))
def _scan_bwd(q, k, v, gs, u, tp, states, aux, do, dims, interpret):
    from jax.experimental import pallas as pl

    n, t, _ = k.shape
    sp = _kda_specs(dims, t, reverse=True)
    return pl.pallas_call(
        dims.kernel(_scan_bwd_kernel, q_scale=dims.q_scale),
        grid=dims.grid(n, t),
        in_specs=[sp["key"], sp["key"], sp["val"], sp["key"], sp["val"],
                  sp["tp"], sp["state_t"], sp["aux"], sp["val"]],
        out_specs=[sp["key"], sp["key"], sp["val"], sp["key"], sp["aux"]],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(gs.shape, jnp.float32),
                   jax.ShapeDtypeStruct(aux.shape, jnp.float32)],
        scratch_shapes=_state_scratch(dims),
        compiler_params=_params(True), interpret=interpret,
        name="kda_scan_bwd",
    )(q, k, v, gs, u, tp, states, aux, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rule(q, k, v, gs, aux, dims, interpret):
    """``q``/``k (N, T, H Dk)``, ``v (N, T, H Dv)``, ``gs (N, T, H Dk)``
    float32, ``aux (N, H, T / C * ROWS, LANES)`` float32 -> ``o (N, T, H
    Dv)``; ``T`` whole stretches."""
    return _rule_fwd(q, k, v, gs, aux, dims, interpret)[0]


def _rule_fwd(q, k, v, gs, aux, dims, interpret):
    tp = _solve(q, k, gs, aux, dims, interpret)
    o, u, states = _scan(q, k, v, gs, tp, aux, dims, interpret)
    return o, (q, k, v, gs, aux, tp, u, states)


def _rule_bwd(dims, interpret, res, do):
    q, k, v, gs, aux, tp, u, states = res
    return _scan_bwd(q, k, v, gs, u, tp, states, aux, do, dims, interpret)


_rule.defvjp(_rule_fwd, _rule_bwd)


def _aux_tiles(beta, doc, prev, c):
    """``beta``, the document index and ``_chunked``'s three masks, one
    ``(ROWS, LANES)`` tile a chunk and head: ``(N, H, T / C * ROWS,
    LANES)`` float32."""
    n, t, h = beta.shape
    nc = t // c
    f32 = jnp.float32
    dq = doc.reshape(n, nc, c)
    end_doc = dq[:, :, -1:]
    prev_doc = prev.reshape(n, nc, c)[:, :, :1]
    # (N, NC, C) -> (N, H, NC, LANES): the tile's lanes past C are 0
    lanes = lambda a: jnp.pad(jnp.broadcast_to(  # noqa: E731
        a.astype(f32)[:, None], (n, h, nc, c)),
        ((0, 0),) * 3 + ((0, LANES - c),))
    rows = [jnp.zeros((n, h, nc, LANES), f32)] * ROWS
    rows[R_BETA] = jnp.pad(
        jnp.transpose(beta.astype(f32).reshape(n, nc, c, h), (0, 3, 1, 2)),
        ((0, 0),) * 3 + ((0, LANES - c),))
    rows[R_DOC] = lanes(dq)
    rows[R_FS], rows[R_TE] = lanes(dq == prev_doc), lanes(dq == end_doc)
    # one number a chunk, along every lane (``gdn_fused._keep``)
    rows[R_CARRY] = jnp.broadcast_to(
        (end_doc == prev_doc).astype(f32)[:, None], (n, h, nc, LANES))
    return jnp.stack(rows, axis=3).reshape(n, h, nc * ROWS, LANES)


def kimi_delta_fused(q, k, v, g, beta, doc=None, unit=None,
                     q_scale: float = 1.0, interpret: bool = False):
    """``ops/kda.kimi_delta_scan`` through the kernels: ``q``/``k (N, T,
    H, Dk)``, ``v (N, T, H, Dv)``, ``g (N, T, H, Dk)``, ``beta (N, T,
    H)`` -> ``o (N, T, H, Dv)``; ``unit`` and ``q_scale`` as
    ``gdn_fused.gated_delta_fused`` takes them.  The caller has checked
    ``supported``."""
    n, t, h, dk = q.shape
    dv = v.shape[3]
    c = CHUNK
    ncb = min(STRETCH, 2 * -(-t // (2 * c)))    # the solve pairs chunks
    pad = (-t) % (ncb * c)
    if doc is None:
        doc = jnp.zeros((n, t), jnp.int32)
    if pad:
        # as kimi_delta_xla pads: g = 0 and beta = 0 change nothing
        q, k, v, g = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for a in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
        doc = jnp.pad(doc, ((0, 0), (0, pad)), mode="edge")
    prev = jnp.concatenate(
        [jnp.full((n, 1), -1, doc.dtype), doc[:, :-1]], axis=1)
    aux = _aux_tiles(beta, doc, prev, c)
    hb = math.gcd(h, HEADS)
    flat = lambda a: a.reshape(n, t + pad, -1)  # noqa: E731
    dims = _Dims(h, h, dk, dv, ncb, hb,
                 None if unit is None else float(unit), float(q_scale))
    o = _rule(flat(q), flat(k), flat(v), flat(chunk_sums(g, c)), aux, dims,
              bool(interpret))
    return o.reshape(n, t + pad, h, dv)[:, :t]
