"""The chunked gated delta rule of ``ops/gdn.py`` as Pallas TPU kernels
with their own backward: a chunk's matrices and the carried state stay
in VMEM, where the ``jax.numpy`` form writes each of them (``decay``,
``A``, the doubling's operands, ``M``, ``B``, ``enter`` ...) to HBM as a
whole-row tensor and reads it back in the next fusion.

The same mathematics as ``ops/gdn._chunked`` in the same precisions
(decays float32 and in log space until the one ``exp``; the solve and
the carried state float32 with full-precision products; every other
product on the activations' dtype with float32 accumulation), in three
kernels behind one ``jax.custom_vjp``:

* ``solve`` — no chunk waits for another: per chunk the decay matrix,
  ``A``, ``T = (I + A)^{-1}`` by the block doubling of
  ``unit_lower_inverse``, ``U0 = T (b V)`` and ``W = T (b K c)``, two
  chunks side by side so that a product fills the MXU.  ``T``
  (float32), ``U0`` and ``W`` (the activations' dtype, as ``_chunked``
  rounds them) go to HBM once: 16 + 2 x 16 KB a chunk and head where
  the ``jax.numpy`` form moved megabytes, and the backward reads them
  instead of solving again.  (One kernel for solve and scan would keep
  them in VMEM; apart, the solve's products are free of the state's
  chain and the backward has its residuals, for 0.2 GB a layer-pass at
  8192 tokens and 32 heads: 0.25 ms of HBM time.)
* ``scan`` — the chunks of a row in order, the ``(Dk, Dv)`` float32
  state in VMEM scratch, in the direct form the state at hand allows:
  ``U = U0 - W S``, ``o = (q k^T . decay) U + (q c) S``, ``S <- carry S
  + (K d)^T U``, which is ``M S + B`` without ever forming ``M`` and
  ``B``.  It keeps the state that entered each chunk for the backward.
* ``scan_bwd`` — the chunks in reverse, ``dS`` in VMEM scratch: the
  cotangents of ``q``, ``k``, ``v`` and of the per-token scalars, with
  ``dA = -T^T dT T^T = -(T^T dU) U^T`` under the strict lower mask, so
  no ``dT`` is formed.

What ``solve`` and ``scan`` write is NAMED where the forward rule returns
it (``KEPT_NAMES``, ``checkpoint_name``), so a ``jax.checkpoint`` whose
policy saves the names (the net's ``remat``: ``nnet/net.REMAT_POLICY``)
keeps it across the backward pass and its recompute runs neither kernel
again: 0.6 GB a layer at 8192 tokens and 32 heads (``T`` is held as the
kernel wrote it, its 64 columns on 128 lanes: 134 of them; its numbers
alone would cost a relayout copy each way), for 5.0 ms of a layer's 14
(PERF.md, PR 47).

A grid step takes a stretch of ``STRETCH`` chunks (an inner loop) for
the value heads of ONE key head: ``q``/``k`` are read by the block's
index map at ``j // (Hv / Hk)``, never repeated in HBM, and their
cotangents are summed over those value heads before they leave.

The per-token scalars (the chunk's running log decay ``cs``, ``beta``,
the document index, and what ``_chunked`` calls ``from_start``,
``to_end``, ``carry``) are computed by plain ``jax.numpy`` outside and
handed in as one ``(ROWS, LANES)`` float32 tile a chunk and head; the backward
hands back the cotangent of that tile and ``jax.grad`` takes it through
the ``cumsum`` and the ``exp`` to ``g`` and ``beta``.

``interpret=True`` runs the identical kernels on the CPU for the tests
(the idiom of ``ops/flash.py``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

#: the chunk the kernels are written for (a quarter of the MXU's side)
CHUNK = 64
#: chunks a grid step walks
STRETCH = 8
#: pairs of chunks the solve's loop takes at a time: their chains of
#: products do not depend on each other and interleave
SOLVE_UNROLL = 2
#: rows of the per-token scalars' tile, and what each holds
ROWS = 8
LANES = 128
R_CS, R_BETA, R_DOC, R_FS, R_TE, R_CARRY = range(6)

_HI = lax.Precision.HIGHEST
_VMEM_LIMIT = 64 * 1024 * 1024


def supported(q, k, v, chunk: int) -> bool:
    """The shapes the kernels are written for."""
    dk, dv = q.shape[-1], v.shape[-1]
    return (int(chunk) == CHUNK and dk % LANES == 0 and dv % LANES == 0
            and q.dtype == k.dtype == v.dtype
            and v.dtype in (jnp.bfloat16, jnp.float32)
            and v.shape[2] % q.shape[2] == 0)


# -- inside a kernel ------------------------------------------------------
def _iotas(c):
    return (lax.broadcasted_iota(jnp.int32, (c, c), 0),
            lax.broadcasted_iota(jnp.int32, (c, c), 1))


def _col(row_vec, eye):
    """``(1, C)`` -> ``(C, 1)``: a select and a lane reduction, exact."""
    return jnp.sum(jnp.where(eye, row_vec, 0.0), axis=1, keepdims=True)


def _row(col_vec, eye):
    """``(C, 1)`` -> ``(1, C)``."""
    return jnp.sum(jnp.where(eye, col_vec, 0.0), axis=0, keepdims=True)


def _decay(aux, row, col):
    """``decay(l, s)`` of one chunk, 0 above the diagonal and across
    documents, from the tile of per-token scalars."""
    eye = row == col
    c = row.shape[0]
    cs_r, doc_r = aux[R_CS:R_CS + 1, :c], aux[R_DOC:R_DOC + 1, :c]
    live = (_col(doc_r, eye) == doc_r) & (row >= col)
    diff = jnp.where(live, _col(cs_r, eye) - cs_r, 0.0)
    return jnp.where(live, jnp.exp(diff), 0.0)


def _keep(aux, width):
    """The chunk's ``carry`` as a ``(1, width)`` row, for a product with
    the ``(Dk, width)`` state (Mosaic broadcasts along one of lanes and
    sublanes at a time, so the tile holds it along all its lanes)."""
    row = aux[R_CARRY:R_CARRY + 1]
    return row if width == LANES else jnp.concatenate(
        [row] * (width // LANES), axis=1)


def _dot(a, b, dims, prec=None):
    return lax.dot_general(a, b, (dims, ((), ())), precision=prec,
                           preferred_element_type=jnp.float32)


_NN = ((1,), (0,))    # a @ b
_NT = ((1,), (1,))    # a @ b^T
_TN = ((0,), (0,))    # a^T @ b


def _unit(x, unit, scale, cdt):
    """A tile's rows as the scan takes them: as they are (``unit`` is
    ``None``: the caller made them), or brought to unit length here as
    ``ops/gdn.unit_rows`` does — ``x / sqrt(sum(x^2) + eps)`` in float32,
    times ``scale``, rounded to the activations' dtype.  Also ``(r, u)``,
    the row's ``1 / sqrt(...)`` and its float32 unit vector, for the way
    back (``_unit_bwd``)."""
    if unit is None:
        return x, None
    xf = x.astype(jnp.float32)
    r = lax.rsqrt(jnp.sum(xf * xf, axis=1, keepdims=True)
                  + jnp.float32(unit))
    u = xf * r
    return (u * jnp.float32(scale)).astype(cdt), (r, u)


def _unit_bwd(dy, back, scale):
    """The cotangent of ``_unit``'s input from its output's."""
    if back is None:
        return dy
    r, u = back
    return jnp.float32(scale) * r * (
        dy - u * jnp.sum(u * dy, axis=1, keepdims=True))


def _scaled(x, col_vec, cdt):
    """``x * col_vec`` as ``_chunked`` multiplies them: both on the
    activations' dtype."""
    return (x.astype(jnp.float32)
            * col_vec.astype(cdt).astype(jnp.float32)).astype(cdt)


def _ds(start, size):
    """``size`` rows from ``start``, a multiple of ``size``."""
    from jax.experimental import pallas as pl

    if isinstance(start, int):
        return pl.ds(start, size)
    return pl.ds(pl.multiple_of(start, size), size)


def _tiles(i, c, kh, dk, hh, dv):
    """Chunk ``i``'s tokens, key head ``kh``'s and value head ``hh``'s
    lanes, the chunk's tile of per-token scalars."""
    return (_ds(i * c, c), slice(kh * dk, (kh + 1) * dk),
            slice(hh * dv, (hh + 1) * dv), _ds(i * ROWS, ROWS))


def _solve_kernel(k_ref, v_ref, aux_ref, t_ref, w_ref, u_ref, *,
                  ncb, hb, rep, c, dk, dv, unit, unroll):
    """Two chunks at a time, their ``(C, C)`` matrices side by side
    along the lanes (``[A_a | A_b]``: a full vector register wide where
    one chunk's fills half), so that a product of the doubling is ``[T_a
    | T_b] @ diag(X_a, X_b)``: one pass of 64 rows over the whole 128 x
    128 MXU where two chunks apart take two passes over a quarter of it
    each (7.6 -> 4.5 ms a call at 8192 tokens and 32 heads, my chip run,
    PR 34).  The same arithmetic a chunk: the other chunk's block of
    ``diag`` is zeros.  The doubling is ``ops/gdn.unit_lower_inverse``'s;
    its first level, where ``T`` is still ``I``, needs no product."""
    from jax.experimental.pallas import tpu as pltpu

    cdt = v_ref.dtype
    f32 = jnp.float32
    act = _HI if cdt == f32 else None
    w2 = 2 * c
    row = lax.broadcasted_iota(jnp.int32, (c, w2), 0)
    lane = lax.broadcasted_iota(jnp.int32, (c, w2), 1)
    col, left = lane & (c - 1), lane < c
    same = row ^ col
    r2 = lax.broadcasted_iota(jnp.int32, (w2, w2), 0)
    eye2 = r2 == lax.broadcasted_iota(jnp.int32, (w2, w2), 1)

    def diag(strip):
        """``[X_a | X_b] (C, 2C)`` -> ``[[X_a, 0], [0, X_b]]``."""
        return jnp.concatenate([jnp.where(left, strip, 0.0),
                                jnp.where(left, 0.0, strip)], axis=0)

    def halves(col2):
        """``(2C, 1)``, chunk a's rows over chunk b's -> ``(C, 2C)``:
        each chunk's column along its own half of the lanes."""
        return jnp.where(left, col2[:c], col2[c:])

    def m21(b):
        return (same < 2 * b) & ((row & b) != 0) & ((col & b) == 0)

    def one(p, hh):
        tok = _ds(p * w2, w2)
        ks = slice(hh // rep * dk, (hh // rep + 1) * dk)
        vs = slice(hh * dv, (hh + 1) * dv)
        # the two chunks' per-token rows, a's lanes then b's
        aux = (aux_ref[0, hh, _ds(2 * p * ROWS, ROWS), :]
               + pltpu.roll(aux_ref[0, hh, _ds((2 * p + 1) * ROWS, ROWS), :],
                            c, 1))
        k2 = _unit(k_ref[0, tok, ks], unit, 1.0, cdt)[0]     # (2C, Dk)
        beta2 = _col(aux[R_BETA:R_BETA + 1], eye2)           # (2C, 1)
        fs2 = _col(aux[R_FS:R_FS + 1], eye2)
        kb2 = _scaled(k2, beta2, cdt)
        kk = _dot(kb2, k2, _NT, act)         # the blocks off its diagonal
        kk = jnp.where(left, kk[:c], kk[c:])               # are not used
        cs_r, doc_r = aux[R_CS:R_CS + 1], aux[R_DOC:R_DOC + 1]
        live = (halves(_col(doc_r, eye2)) == doc_r) & (row >= col)
        decay = jnp.where(live, jnp.exp(jnp.where(
            live, halves(_col(cs_r, eye2)) - cs_r, 0.0)), 0.0)
        a = jnp.where(row > col, kk * decay, 0.0)
        t = jnp.where(row == col, 1.0, 0.0) - jnp.where(m21(1), a, 0.0)
        b = 2
        while b < c:
            am = diag(jnp.where(m21(b), a, 0.0))
            t = t - _dot(_dot(t, am, _NN, _HI), diag(t), _NN, _HI)
            b *= 2
        t_ref[0, hh, _ds(2 * p * c, c), :] = t[:, :c]
        t_ref[0, hh, _ds((2 * p + 1) * c, c), :] = pltpu.roll(t, c, 1)[:, :c]
        td = diag(t)
        u_ref[0, tok, vs] = _dot(
            td, v_ref[0, tok, vs].astype(f32) * beta2, _NN, _HI).astype(cdt)
        w_ref[0, tok, slice(hh * dk, (hh + 1) * dk)] = _dot(
            td, kb2.astype(f32) * fs2, _NN, _HI).astype(cdt)

    def body(j, loop):
        for u in range(unroll):
            for hh in range(hb):
                one(j * unroll + u, hh)
        return loop

    lax.fori_loop(0, ncb // 2 // unroll, body, 0)


def _scan_kernel(q_ref, k_ref, w_ref, u_ref, aux_ref, o_ref, s_ref, state,
                 *, ncb, hb, rep, c, dk, dv, unit, q_scale):
    from jax.experimental import pallas as pl

    cdt = o_ref.dtype
    f32 = jnp.float32
    act = _HI if cdt == f32 else None
    row, col = _iotas(c)
    eye = row == col

    @pl.when(pl.program_id(2) == 0)
    def _start():
        state[...] = jnp.zeros_like(state)

    def body(i, loop):
        for hh in range(hb):
            tok, ks, vs, ar = _tiles(i, c, hh // rep, dk, hh, dv)
            aux = aux_ref[0, hh, ar, :]
            qc = _unit(q_ref[0, tok, ks], unit, q_scale, cdt)[0]
            kc = _unit(k_ref[0, tok, ks], unit, 1.0, cdt)[0]
            s0 = state[hh]
            s_ref[0, hh, i] = s0
            sc = s0.astype(cdt)
            w = w_ref[0, tok, slice(hh * dk, (hh + 1) * dk)]
            u = (u_ref[0, tok, vs].astype(f32)
                 - _dot(w, sc, _NN, act)).astype(cdt)
            p = (_dot(qc, kc, _NT, act) * _decay(aux, row, col)).astype(cdt)
            fs = _col(aux[R_FS:R_FS + 1, :c], eye)
            o = _dot(p, u, _NN, act) + fs * _dot(qc, sc, _NN, act)
            o_ref[0, tok, vs] = o.astype(cdt)
            kd = _scaled(kc, _col(aux[R_TE:R_TE + 1, :c], eye), cdt)
            state[hh] = _keep(aux, dv) * s0 + _dot(kd, u, _TN, act)
        return loop

    lax.fori_loop(0, ncb, body, 0)


def _scan_bwd_kernel(q_ref, k_ref, v_ref, w_ref, u_ref, t_ref, s_ref,
                     aux_ref, do_ref, dq_ref, dk_ref, dv_ref, daux_ref,
                     dstate, *, ncb, hb, rep, c, dk, dv, unit, q_scale):
    from jax.experimental import pallas as pl

    cdt = v_ref.dtype
    f32 = jnp.float32
    act = _HI if cdt == f32 else None
    row, col = _iotas(c)
    eye = row == col
    sub = lax.broadcasted_iota(jnp.int32, (ROWS, c), 0)
    sub_l = lax.broadcasted_iota(jnp.int32, (ROWS, LANES), 0)

    @pl.when(pl.program_id(2) == 0)
    def _start():
        dstate[...] = jnp.zeros_like(dstate)

    def head(i, hh, qc, kc):
        """One chunk of one value head: its share of (dq, dk)."""
        tok, ks, vs, ar = _tiles(i, c, hh // rep, dk, hh, dv)
        aux = aux_ref[0, hh, ar, :]
        kf = kc.astype(f32)
        do = do_ref[0, tok, vs]
        beta = _col(aux[R_BETA:R_BETA + 1, :c], eye)
        fs = _col(aux[R_FS:R_FS + 1, :c], eye)
        te = _col(aux[R_TE:R_TE + 1, :c], eye)
        carry = _keep(aux, dv)
        d = _decay(aux, row, col)
        s0 = s_ref[0, hh, i]
        sc = s0.astype(cdt)
        ds1 = dstate[hh]
        dsc = ds1.astype(cdt)

        # -- the forward's values again, from what it kept
        kb = _scaled(kc, beta, cdt)
        kd = _scaled(kc, te, cdt)
        kk = _dot(kb, kc, _NT, act)
        qk = _dot(qc, kc, _NT, act)
        qs = _dot(qc, sc, _NN, act)
        ksn = _dot(kc, sc, _NN, act)
        w = w_ref[0, tok, slice(hh * dk, (hh + 1) * dk)]
        u = (u_ref[0, tok, vs].astype(f32) - _dot(w, sc, _NN, act)).astype(cdt)
        p = (qk * d).astype(cdt)

        # -- o = p u + fs (q s0);  s1 = carry s0 + kd^T u
        du = _dot(p, do, _TN, act) + _dot(kd, dsc, _NN, act)
        # u = T rhs, rhs = beta (v - fs (k s0)): T^T du, at full precision
        dr = _dot(t_ref[0, hh, tok, :], du, _TN, _HI)
        drc = dr.astype(cdt)
        dpd = _dot(do, u, _NT, act) * d                    # dP . decay
        dad = jnp.where(row > col, -_dot(drc, u, _NT, act) * d, 0.0)
        dpc, dac = dpd.astype(cdt), dad.astype(cdt)
        # the decay matrix's own cotangent, through cs(l) - cs(s)
        gm = dpd * qk + dad * kk
        dcs = _row(jnp.sum(gm, axis=1, keepdims=True), eye) - jnp.sum(
            gm, axis=0, keepdims=True)
        dkb = _dot(dac, kc, _NN, act)                      # d(beta k)
        dkd = _dot(u, dsc, _NT, act)                       # d(te k)
        x = v_ref[0, tok, vs].astype(f32) - fs * ksn
        dbeta = (jnp.sum(dr * x, axis=1, keepdims=True)
                 + jnp.sum(dkb * kf, axis=1, keepdims=True))
        dfs = (jnp.sum(do.astype(f32) * qs, axis=1, keepdims=True)
               - beta * jnp.sum(dr * ksn, axis=1, keepdims=True))
        dte = jnp.sum(dkd * kf, axis=1, keepdims=True)
        dy = (-(fs * beta) * dr).astype(cdt)               # d(k s0)

        dv_ref[0, tok, vs] = (beta * dr).astype(cdt)
        # d(carry), spread over the lanes the tile holds carry along
        dkeep = jnp.sum(ds1 * s0, axis=0, keepdims=True)
        dkeep = sum(dkeep[:, j:j + LANES] for j in range(0, dv, LANES))
        daux_ref[0, hh, ar, :] = jnp.where(sub_l == R_CARRY, dkeep, 0.0)
        daux_ref[0, hh, ar, :c] = (
            jnp.where(sub == R_CS, dcs, 0.0)
            + jnp.where(sub == R_BETA, _row(dbeta, eye), 0.0)
            + jnp.where(sub == R_FS, _row(dfs, eye), 0.0)
            + jnp.where(sub == R_TE, _row(dte, eye), 0.0)
            + jnp.where(sub == R_CARRY, dkeep[:, :c], 0.0))
        dstate[hh] = (carry * ds1
                      + _dot((qc.astype(f32) * fs).astype(cdt), do, _TN, act)
                      + _dot(kc, dy, _TN, act))
        dq = _dot(dpc, kc, _NN, act) + fs * _dot(do, sc, _NT, act)
        dk_ = (_dot(dpc, qc, _TN, act) + te * dkd + _dot(dy, sc, _NT, act)
               + beta * dkb + _dot(dac, kb, _TN, act))
        return dq, dk_

    def body(j, loop):
        i = ncb - 1 - j
        for kh in range(hb // rep):
            tok, ks = _tiles(i, c, kh, dk, 0, dv)[:2]
            qc, q_back = _unit(q_ref[0, tok, ks], unit, q_scale, cdt)
            kc, k_back = _unit(k_ref[0, tok, ks], unit, 1.0, cdt)
            got = [head(i, kh * rep + r, qc, kc) for r in range(rep)]
            dq_ref[0, tok, ks] = _unit_bwd(
                sum(g[0] for g in got), q_back, q_scale).astype(cdt)
            dk_ref[0, tok, ks] = _unit_bwd(
                sum(g[1] for g in got), k_back, 1.0).astype(cdt)
        return loop

    lax.fori_loop(0, ncb, body, 0)


# -- the calls --------------------------------------------------------------
class _Dims(NamedTuple):
    """What is static in a call: key and value heads and their widths,
    chunks a stretch, value heads a grid step, and how ``q`` and ``k``
    come (``gated_delta_fused``)."""
    hk: int
    hv: int
    dk: int
    dv: int
    ncb: int
    hb: int
    unit: Optional[float]
    q_scale: float

    def kernel(self, fn, **more):
        return functools.partial(
            fn, ncb=self.ncb, hb=self.hb, rep=self.hv // self.hk, c=CHUNK,
            dk=self.dk, dv=self.dv, unit=self.unit, **more)

    def grid(self, n, t):
        return (n, self.hv // self.hb, t // (self.ncb * CHUNK))


def _specs(dims, t, reverse=False):
    """Block specs of a stretch's operands, by what they are."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    hk, hv, dk, dv, ncb, hb = dims[:6]
    c = CHUNK
    tb, kb, nt = ncb * c, hb * hk // hv, t // (ncb * c)
    at = (lambda i: nt - 1 - i) if reverse else (lambda i: i)

    def spec(block, index):
        return pl.BlockSpec(block, index, memory_space=pltpu.VMEM)

    return {
        "key": spec((1, tb, kb * dk), lambda b, h, i: (b, at(i), h)),
        "val": spec((1, tb, hb * dv), lambda b, h, i: (b, at(i), h)),
        "w": spec((1, tb, hb * dk), lambda b, h, i: (b, at(i), h)),
        "aux": spec((1, hb, ncb * ROWS, LANES),
                    lambda b, h, i: (b, h, at(i), 0)),
        "t": spec((1, hb, tb, c), lambda b, h, i: (b, h, at(i), 0)),
        "state": spec((1, hb, ncb, dk, dv),
                      lambda b, h, i: (b, h, at(i), 0, 0)),
    }


def _params(sequential: bool):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel",
                             "arbitrary" if sequential else "parallel"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _solve(k, v, aux, dims, interpret):
    from jax.experimental import pallas as pl

    n, t, _ = k.shape
    hv, dk, dv = dims.hv, dims.dk, dims.dv
    sp = _specs(dims, t)
    unroll = SOLVE_UNROLL if dims.ncb % (2 * SOLVE_UNROLL) == 0 else 1
    return pl.pallas_call(
        dims.kernel(_solve_kernel, unroll=unroll), grid=dims.grid(n, t),
        in_specs=[sp["key"], sp["val"], sp["aux"]],
        out_specs=[sp["t"], sp["w"], sp["val"]],
        out_shape=[jax.ShapeDtypeStruct((n, hv, t, CHUNK), jnp.float32),
                   jax.ShapeDtypeStruct((n, t, hv * dk), v.dtype),
                   jax.ShapeDtypeStruct((n, t, hv * dv), v.dtype)],
        compiler_params=_params(False), interpret=interpret,
        name="gdn_solve",
    )(k, v, aux)


def _scan(q, k, w, u0, aux, dims, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, t, _ = k.shape
    hv, dk, dv = dims.hv, dims.dk, dims.dv
    sp = _specs(dims, t)
    return pl.pallas_call(
        dims.kernel(_scan_kernel, q_scale=dims.q_scale), grid=dims.grid(n, t),
        in_specs=[sp["key"], sp["key"], sp["w"], sp["val"], sp["aux"]],
        out_specs=[sp["val"], sp["state"]],
        out_shape=[jax.ShapeDtypeStruct((n, t, hv * dv), u0.dtype),
                   jax.ShapeDtypeStruct((n, hv, t // CHUNK, dk, dv),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dims.hb, dk, dv), jnp.float32)],
        compiler_params=_params(True), interpret=interpret,
        name="gdn_scan",
    )(q, k, w, u0, aux)


def _scan_bwd(q, k, v, w, u0, tinv, states, aux, do, dims, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, t, _ = k.shape
    sp = _specs(dims, t, reverse=True)
    return pl.pallas_call(
        dims.kernel(_scan_bwd_kernel, q_scale=dims.q_scale),
        grid=dims.grid(n, t),
        in_specs=[sp["key"], sp["key"], sp["val"], sp["w"], sp["val"],
                  sp["t"], sp["state"], sp["aux"], sp["val"]],
        out_specs=[sp["key"], sp["key"], sp["val"], sp["aux"]],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(aux.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dims.hb, dims.dk, dims.dv), jnp.float32)],
        compiler_params=_params(True), interpret=interpret,
        name="gdn_scan_bwd",
    )(q, k, v, w, u0, tinv, states, aux, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _rule(q, k, v, aux, dims, interpret):
    """``q``/``k (N, T, Hk Dk)``, ``v (N, T, Hv Dv)``, ``aux (N, Hv, T /
    C * ROWS, LANES)`` float32 -> ``o (N, T, Hv Dv)``; ``T`` whole
    stretches."""
    return _rule_fwd(q, k, v, aux, dims, interpret)[0]


#: the names (``jax.ad_checkpoint.checkpoint_name``) of what the two
#: forward kernels wrote — ``solve``'s ``T``, ``W`` and ``U0``, ``scan``'s
#: ``o`` and the states that entered each chunk: a ``jax.checkpoint`` whose
#: policy saves them (``nnet/net.REMAT_POLICY``) keeps the five across the
#: backward pass and its recompute runs neither kernel a second time
_SOLVED = ("gdn_tinv", "gdn_w", "gdn_u0")
_SCANNED = ("gdn_o", "gdn_states")
KEPT_NAMES = _SOLVED + _SCANNED


def _rule_fwd(q, k, v, aux, dims, interpret):
    # the named values are the primal output, what ``scan`` reads AND the
    # residuals, so every reader reads what a policy may keep
    tinv, w, u0 = map(checkpoint_name, _solve(k, v, aux, dims, interpret),
                      _SOLVED)
    o, states = map(checkpoint_name,
                    _scan(q, k, w, u0, aux, dims, interpret), _SCANNED)
    return o, (q, k, v, aux, tinv, w, u0, states)


def _rule_bwd(dims, interpret, res, do):
    q, k, v, aux, tinv, w, u0, states = res
    return _scan_bwd(q, k, v, w, u0, tinv, states, aux, do, dims, interpret)


_rule.defvjp(_rule_fwd, _rule_bwd)


def _aux_tiles(g, beta, doc, prev, c):
    """The per-token scalars of ``_chunked``, one ``(ROWS, LANES)`` tile a
    chunk and head: ``(N, H, T / C * ROWS, LANES)`` float32."""
    n, t, h = g.shape
    nc = t // c
    f32 = jnp.float32
    cut = lambda a: a.astype(f32).reshape(n, nc, c, h)  # noqa: E731
    cs = jnp.cumsum(cut(g), axis=2)                         # (N,NC,C,H)
    dq = doc.reshape(n, nc, c)
    end_doc = dq[:, :, -1:]
    prev_doc = prev.reshape(n, nc, c)[:, :, :1]
    fs = jnp.where((dq == prev_doc)[..., None], jnp.exp(cs), 0.0)
    te = jnp.where((dq == end_doc)[..., None],
                   jnp.exp(cs[:, :, -1:] - cs), 0.0)
    carry = jnp.where((end_doc == prev_doc)[..., None],
                      jnp.exp(cs[:, :, -1:]), 0.0)
    # (N, NC, C, H) -> (N, H, NC, LANES): the tile's lanes past C are 0
    lanes = lambda a: jnp.pad(  # noqa: E731
        jnp.transpose(a, (0, 3, 1, 2)), ((0, 0),) * 3 + ((0, LANES - c),))
    rows = [jnp.zeros((n, h, nc, LANES), f32)] * ROWS
    rows[R_CS], rows[R_BETA] = lanes(cs), lanes(cut(beta))
    rows[R_DOC] = lanes(jnp.broadcast_to(dq.astype(f32)[..., None],
                                         (n, nc, c, h)))
    rows[R_FS], rows[R_TE] = lanes(fs), lanes(te)
    # one number a chunk and head, along every lane (``_keep``)
    rows[R_CARRY] = jnp.broadcast_to(
        jnp.transpose(carry, (0, 3, 1, 2)), (n, h, nc, LANES))
    return jnp.stack(rows, axis=3).reshape(n, h, nc * ROWS, LANES)


def gated_delta_fused(q, k, v, g, beta, doc=None, unit=None,
                      q_scale: float = 1.0, interpret: bool = False):
    """``ops/gdn.gated_delta_scan`` through the kernels: ``q``/``k (N,
    T, Hk, Dk)``, ``v (N, T, Hv, Dv)`` with ``Hk`` dividing ``Hv``,
    ``g``/``beta (N, T, Hv)`` -> ``o (N, T, Hv, Dv)``.  With ``unit``
    (an eps) the kernels bring every row of ``q`` and ``k`` to unit
    length themselves, and ``q`` to ``q_scale``, tile by tile in VMEM:
    between a ``(N, T, H, D)`` tensor XLA reduces a head at a time and
    the ``(N, T, H D)`` rows a kernel reads lies a float32 copy of the
    whole tensor, each way (PERF.md, PR 34).  The caller has checked
    ``supported``."""
    n, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    c = CHUNK
    rep = hv // hk
    ncb = min(STRETCH, 2 * -(-t // (2 * c)))    # the solve pairs chunks
    pad = (-t) % (ncb * c)
    if doc is None:
        doc = jnp.zeros((n, t), jnp.int32)
    if pad:
        # as gated_delta_scan pads: g = 0 and beta = 0 change nothing
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
        g, beta = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (g, beta))
        doc = jnp.pad(doc, ((0, 0), (0, pad)), mode="edge")
    prev = jnp.concatenate(
        [jnp.full((n, 1), -1, doc.dtype), doc[:, :-1]], axis=1)
    aux = _aux_tiles(g, beta, doc, prev, c)
    # the value heads of one key head a grid step; two heads where every
    # head has its own, so that two chains of products interleave
    hb = rep if rep > 1 else (2 if hv % 2 == 0 else 1)
    flat = lambda a: a.reshape(n, t + pad, -1)  # noqa: E731
    dims = _Dims(hk, hv, dk, dv, ncb, hb,
                 None if unit is None else float(unit), float(q_scale))
    o = _rule(flat(q), flat(k), flat(v), aux, dims, bool(interpret))
    return o.reshape(n, t + pad, hv, dv)[:, :t]
