"""The chunked Mamba-2 scan of ``ops/ssd.py`` as Pallas TPU kernels with
their own backward: a chunk's ``(Q, Q)`` decay and score matrices and
the carried state stay in VMEM, where the ``jax.numpy`` form writes
``diff``, ``exp(diff)`` and ``m`` of every chunk and head to HBM as
whole-row float32 tensors and reads them back in the next fusion
(``(1, 32, 64, 256, 256)`` at 8192 tokens, 64 heads and chunks of 256:
537 MB each, forward, recomputed forward and backward).

The same mathematics as ``ops/ssd.ssd_xla`` in the same precisions
(decays float32 and in log space until the one ``exp`` of a difference
that is never positive; ``M`` and the entering state rounded to the
activations' dtype where the ``jax.numpy`` form rounds them; every
product accumulated in float32; the state float32), in two kernels
behind one ``jax.custom_vjp``:

* ``ssd_scan`` — the chunks of a row in order, the state in VMEM
  scratch.  Per chunk ``G = C B^T`` once for the heads of the step, per
  head ``M = (G . exp(diff))`` rounded, ``y = M (dt x) + from_start .
  (C S^T)`` and ``S <- carry S + ((dt x) . to_end)^T B``.  It keeps the
  state that entered each chunk for the backward (67 MB a layer pass at
  8192 tokens, 64 heads of 64 x 128).
* ``ssd_scan_bwd`` — the chunks in reverse, ``dS`` in VMEM scratch: the
  cotangents of ``x``, ``B``, ``C`` and of the per-token scalars.
  ``M^T`` is formed from ``B C^T`` and the same decays, so no matrix is
  turned in the kernel.

A grid step takes a stretch of chunks (an inner loop) for ``hb`` heads.
A head of 64 columns is half a lane tile, so two heads side by side are
one UNIT of 128 lanes: ``x`` and ``y`` are read and written as ``(N, T,
H P)`` rows a unit at a time, the state of a unit is kept turned, ``(S,
2 P)``, so that ``C S^T`` and the state's update are one full-width
product for both heads, and the two heads' ``M (dt x)`` products take
the unit's 128 columns each and keep their own half.  ``B`` and ``C``
are one group's: ``G`` is computed once a step for all its heads, and
their cotangents leave as one partial sum a step of heads (``H / hb``
of them, added outside).

The per-token scalars (the chunk's running log decay ``cs``, ``dt``,
the document index, and what ``ssd_xla`` calls ``from_start``,
``to_end``, ``carry``) are computed by plain ``jax.numpy`` outside and
handed in as one ``(ROWS, Q)`` float32 tile a chunk and head, tokens
along the lanes; the backward hands back the cotangent of that tile and
``jax.grad`` takes it through the ``cumsum``, the ``exp`` and the
``softplus`` to ``dt``, ``a_log`` and ``dt_bias``.  What multiplies the
rows of a ``(Q, P)`` operand a kernel reads down the sublanes: it turns
the step's tiles of a chunk once (``(8 hb, Q)`` -> ``(Q, 8 hb)``, one
transpose for all the step's heads) and takes a column a head and
scalar.  A sum over a head's lanes in the backward (the cotangents
of ``dt``, ``from_start``, ``to_end``, and the decay's own) is taken on
the TRANSPOSE, down the sublanes, and lands as a row of the tile's
cotangent: a lane reduction a token costs seven rotations a vector
register, the transpose one pass (0.7 of the backward's 1.8 ms a layer
went there, my chip run, PR 41).

Every ``pallas_call`` sits inside a module-level ``jax.jit``: the nine
mixers of a stack share one lowered function a kernel.

``interpret=True`` runs the identical kernels on the CPU for the tests
(the idiom of ``ops/flash.py``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

#: the chunks the kernels are written for (one or two lane tiles)
CHUNKS = (128, 256)
#: tokens a grid step walks, at most
STRETCH = 1024
#: heads a grid step takes, at most: ``G`` is computed once for them and
#: their tiles of a chunk are one block to turn (granite's forward 0.69
#: ms a layer at 8, 1.02 at 4, ~1.9 at 2; 16 read 0.68 alone and the same
#: 34 ms a step in the cell, for kernels twice as long to lower; my chip
#: runs, PR 41)
HEADS = 8
LANES = 128
#: rows of the lane-wise tile of per-token scalars, and what each holds
ROWS = 8
R_CS, R_CARRY, R_DOC, R_DT, R_FS, R_TE = range(6)

_HI = lax.Precision.HIGHEST
_VMEM_LIMIT = 64 * 1024 * 1024
_NN = ((1,), (0,))    # a @ b
_NT = ((1,), (1,))    # a @ b^T


def heads_per_step(h: int, p: int) -> int:
    """The most heads, up to ``HEADS``, that divide ``h`` and fill whole
    lane tiles; 0 where none does."""
    return next((hb for hb in (HEADS >> k for k in range(HEADS.bit_length()))
                 if h % hb == 0 and hb * p % LANES == 0), 0)


def supported(x, b, c, chunk: int) -> bool:
    """The shapes the kernels are written for: ``x (N, T, H, P)`` with
    ``P`` 64 or 128, one group's ``b``/``c (N, T, S)`` with ``S`` a
    multiple of 128, whole chunks of 128 or 256 tokens, bfloat16 or
    float32."""
    if x.ndim != 4 or b.ndim != 3 or c.ndim != 3:
        return False
    t, h, p = x.shape[1:]
    return (int(chunk) in CHUNKS and t > 0 and t % int(chunk) == 0
            and p in (64, 128) and heads_per_step(h, p) > 0
            and b.shape[-1] % LANES == 0
            and x.dtype == b.dtype == c.dtype
            and x.dtype in (jnp.bfloat16, jnp.float32))


# -- inside a kernel ------------------------------------------------------
def _dot(a, b, dims, prec=None):
    return lax.dot_general(a, b, (dims, ((), ())), precision=prec,
                           preferred_element_type=jnp.float32)


def _ds(start, size):
    """``size`` rows from ``start``, a multiple of ``size``."""
    from jax.experimental import pallas as pl

    if isinstance(start, int):
        return pl.ds(start, size)
    return pl.ds(pl.multiple_of(start, size), size)


def _rounded(by, cdt):
    """A float32 scalar as ``ssd_xla`` takes it to a product with the
    activations: rounded to their dtype."""
    return by.astype(cdt).astype(jnp.float32)


def _scaled(x, by, cdt):
    """``x * by`` as ``ssd_xla`` multiplies them: both on the
    activations' dtype."""
    return (x.astype(jnp.float32) * _rounded(by, cdt)).astype(cdt)


class _Unit:
    """The heads that share one run of lanes (two of 64 columns, or one
    of 128), and how a per-head column becomes the unit's ``(Q, lanes)``
    operand and back."""

    def __init__(self, q, p, hu):
        self.hu, self.p, self.width = hu, p, hu * p
        lane = lax.broadcasted_iota(jnp.int32, (q, self.width), 1)
        self.mine = [(lane >= j * p) & (lane < (j + 1) * p)
                     for j in range(hu)]
        lane1 = lax.broadcasted_iota(jnp.int32, (1, self.width), 1)
        self.mine_row = [(lane1 >= j * p) & (lane1 < (j + 1) * p)
                         for j in range(hu)]

    @staticmethod
    def _each(masks, parts):
        out = parts[0]
        for mask, part in zip(masks[1:], parts[1:]):
            out = jnp.where(mask, part, out)
        return out

    def spread(self, cols):
        """One ``(Q, 1)`` column a head -> each along its head's lanes."""
        return self._each(self.mine, cols)

    def spread_row(self, rows):
        """One ``(1, width)`` row a head -> each on its head's lanes."""
        return self._each(self.mine_row, rows)

    def own(self, j, z, zero=0.0):
        """``z`` on head ``j``'s lanes, ``zero`` on the others."""
        return z if self.hu == 1 else jnp.where(self.mine[j], z, zero)

    def own_row(self, j, z):
        """The same for a ``(1, width)`` row."""
        return z if self.hu == 1 else jnp.where(self.mine_row[j], z, 0.0)

    def sums(self, z):
        """``(Q, width)`` -> each head's sum over its lanes, as a ``(1,
        Q)`` row: down the sublanes of the transpose."""
        zt = z.T
        return [jnp.sum(zt[j * self.p:(j + 1) * self.p], axis=0,
                        keepdims=True) for j in range(self.hu)]


def _chunk_refs(i, q, c_ref, bt_ref, row_ref):
    """Chunk ``i`` of the stretch: its tokens, ``C``, ``B^T``, the
    step's tiles turned (``(Q, >= 8 hb)``: row ``r`` of head ``hd`` is
    column ``8 hd + r``, ``_column``) and who may see whom (``same``:
    one document)."""
    tok = _ds(i * q, q)
    hb = row_ref.shape[1]
    rows = row_ref[0, :, i].reshape(hb * ROWS, q)
    if hb * ROWS % LANES:
        rows = jnp.concatenate(
            [rows, jnp.zeros((-(hb * ROWS) % LANES, q), jnp.float32)],
            axis=0)
    colv = rows.T
    same = _column(colv, R_DOC, 0) == row_ref[0, 0, i][R_DOC:R_DOC + 1, :]
    return tok, c_ref[0, tok, :], bt_ref[0, i], colv, same


def _column(colv, r, hd):
    return colv[:, hd * ROWS + r:hd * ROWS + r + 1]


def _scan_kernel(x_ref, bt_ref, c_ref, row_ref, y_ref, s_ref, state,
                 *, ncb, hb, hu, q, p):
    from jax.experimental import pallas as pl

    cdt = y_ref.dtype
    act = _HI if cdt == jnp.float32 else None
    unit = _Unit(q, p, hu)
    uw = unit.width
    seen = (lax.broadcasted_iota(jnp.int32, (q, q), 0)
            >= lax.broadcasted_iota(jnp.int32, (q, q), 1))

    @pl.when(pl.program_id(2) == 0)
    def _start():
        state[...] = jnp.zeros_like(state)

    def body(i, loop):
        tok, cc, bt, colv, same = _chunk_refs(i, q, c_ref, bt_ref, row_ref)
        live = same & seen
        g = _dot(cc, bt, _NN, act)                          # (Q, Q)
        for u in range(hb // hu):
            heads = [u * hu + j for j in range(hu)]
            tiles = [row_ref[0, hd, i] for hd in heads]
            lanes = slice(u * uw, (u + 1) * uw)
            xd = _scaled(x_ref[0, tok, lanes],
                         unit.spread([_column(colv, R_DT, hd)
                                      for hd in heads]), cdt)
            y = None
            for j, hd in enumerate(heads):
                diff = jnp.where(
                    live, _column(colv, R_CS, hd) - tiles[j][R_CS:R_CS + 1],
                    -jnp.inf)
                m = (g * jnp.exp(diff)).astype(cdt)
                yj = _dot(m, xd, _NN, act)
                y = yj if y is None else jnp.where(unit.mine[j], yj, y)
            st = state[u]                                   # (S, uw)
            s_ref[0, u, i] = st
            fs = unit.spread([_column(colv, R_FS, hd) for hd in heads])
            y = y + fs * _dot(cc, st.astype(cdt), _NN, act)
            y_ref[0, tok, lanes] = y.astype(cdt)
            xdte = _scaled(xd, unit.spread([_column(colv, R_TE, hd)
                                            for hd in heads]), cdt)
            keep = unit.spread_row([t[R_CARRY:R_CARRY + 1, :uw]
                                    for t in tiles])
            state[u] = keep * st + _dot(bt, xdte, _NN, act)
        return loop

    lax.fori_loop(0, ncb, body, 0)


def _scan_bwd_kernel(x_ref, b_ref, bt_ref, c_ref, ct_ref, row_ref, s_ref,
                     dy_ref, dx_ref, dbt_ref, dc_ref, drow_ref, dstate,
                     *, ncb, hb, hu, q, p):
    from jax.experimental import pallas as pl

    cdt = x_ref.dtype
    f32 = jnp.float32
    act = _HI if cdt == f32 else None
    unit = _Unit(q, p, hu)
    uw = unit.width
    rowi = lax.broadcasted_iota(jnp.int32, (q, q), 0)
    coli = lax.broadcasted_iota(jnp.int32, (q, q), 1)
    sub = lax.broadcasted_iota(jnp.int32, (ROWS, q), 0)

    @pl.when(pl.program_id(2) == 0)
    def _start():
        dstate[...] = jnp.zeros_like(dstate)

    def body(back, loop):
        i = ncb - 1 - back
        tok, cc, bt, colv, same = _chunk_refs(i, q, c_ref, bt_ref, row_ref)
        bb, ct = b_ref[0, tok, :], ct_ref[0, i]
        live, live_t = same & (rowi >= coli), same & (coli >= rowi)
        g = _dot(cc, bt, _NN, act)                          # C B^T
        gt = _dot(bb, ct, _NN, act)                         # its transpose
        dg = jnp.zeros((q, q), f32)
        dc = jnp.zeros(cc.shape, f32)
        dbt = jnp.zeros(bt.shape, f32)
        for u in range(hb // hu):
            heads = [u * hu + j for j in range(hu)]
            tiles = [row_ref[0, hd, i] for hd in heads]
            lanes = slice(u * uw, (u + 1) * uw)
            xs, dys = x_ref[0, tok, lanes], dy_ref[0, tok, lanes]
            dt_c = _rounded(unit.spread([_column(colv, R_DT, hd)
                                         for hd in heads]), cdt)
            te_c = _rounded(unit.spread([_column(colv, R_TE, hd)
                                         for hd in heads]), cdt)
            fs = unit.spread([_column(colv, R_FS, hd) for hd in heads])
            xd = (xs.astype(f32) * dt_c).astype(cdt)
            xdf = xd.astype(f32)
            xdte = (xdf * te_c).astype(cdt)
            st = s_ref[0, u, i]
            sc = st.astype(cdt)
            ds1 = dstate[u]
            ds1c = ds1.astype(cdt)
            keep = unit.spread_row([t[R_CARRY:R_CARRY + 1, :uw]
                                    for t in tiles])
            dyf = dys.astype(f32)

            # -- y += fs (C S^T): the entering state's share
            dfs = unit.sums(dyf * _dot(cc, sc, _NN, act))
            dyc = (fs * dyf).astype(cdt)
            dc = dc + _dot(dyc, sc, _NT, act)
            dstate[u] = keep * ds1 + _dot(ct, dyc, _NN, act)
            # -- S' = carry S + B^T (xd te)
            dxdte = _dot(bb, ds1c, _NN, act)                 # (Q, uw)
            dbt = dbt + _dot(ds1c, xdte, _NT, act)
            dkeep = jnp.sum(ds1 * st, axis=0, keepdims=True)  # (1, uw)
            dte = unit.sums(dxdte * xdf)
            dxd = te_c * dxdte
            # -- y += M xd, a head at a time
            dcs = []
            for j, hd in enumerate(heads):
                cs_c = _column(colv, R_CS, hd)
                cs_r = tiles[j][R_CS:R_CS + 1]
                d = jnp.exp(jnp.where(live, cs_c - cs_r, -jnp.inf))
                dm = _dot(unit.own(j, dys, jnp.zeros((), cdt)), xd, _NT, act)
                dg = dg + dm * d
                w = dm * (g * d)           # the decay's own cotangent:
                # + its row sums, - its column sums, both as rows
                across = sum(w[:, k:k + LANES] for k in range(0, q, LANES))
                dcs.append(jnp.sum(across.T, axis=0, keepdims=True)
                           - jnp.sum(w, axis=0, keepdims=True))
                mt = (gt * jnp.exp(jnp.where(live_t, cs_r - cs_c, -jnp.inf))
                      ).astype(cdt)
                dxd = dxd + unit.own(j, _dot(mt, dys, _NN, act))
            ddt = unit.sums(dxd * xs.astype(f32))
            dx_ref[0, tok, lanes] = (dxd * dt_c).astype(cdt)
            for j, hd in enumerate(heads):
                mine = unit.own_row(j, dkeep)
                if q > uw:
                    mine = jnp.concatenate(
                        [mine, jnp.zeros((1, q - uw), f32)], axis=1)
                drow_ref[0, hd, i] = sum(
                    jnp.where(sub == r, row, 0.0) for r, row in (
                        (R_CS, dcs[j]), (R_CARRY, mine), (R_DT, ddt[j]),
                        (R_FS, dfs[j]), (R_TE, dte[j])))
        dgc = dg.astype(cdt)
        dc_ref[0, 0, tok, :] = dc + _dot(dgc, bb, _NN, act)
        dbt_ref[0, 0, i] = dbt + _dot(ct, dgc, _NN, act)
        return loop

    lax.fori_loop(0, ncb, body, 0)


# -- the calls --------------------------------------------------------------
class _Dims(NamedTuple):
    """What is static in a call: heads and their width, the state's, the
    chunk, chunks a stretch, heads a grid step."""
    h: int
    p: int
    s: int
    q: int
    ncb: int
    hb: int

    @property
    def hu(self):
        """Heads a unit of lanes."""
        return max(1, LANES // self.p)

    @property
    def uw(self):
        """A unit's lanes."""
        return self.hu * self.p

    def kernel(self, fn):
        return functools.partial(fn, ncb=self.ncb, hb=self.hb, hu=self.hu,
                                 q=self.q, p=self.p)

    def grid(self, n, t):
        return (n, self.h // self.hb, t // (self.ncb * self.q))


def _specs(dims, t, reverse=False):
    """Block specs of a stretch's operands, by what they are."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    h, p, s, q, ncb, hb = dims
    tb, nt = ncb * q, t // (ncb * q)
    at = (lambda i: nt - 1 - i) if reverse else (lambda i: i)

    def spec(block, index):
        return pl.BlockSpec(block, index, memory_space=pltpu.VMEM)

    return {
        # (N, T, H P): a step's heads' columns of a stretch's rows
        "x": spec((1, tb, hb * p), lambda b, j, i: (b, at(i), j)),
        # (N, T, S) and, turned a chunk, (N, T / Q, S, Q)
        "bc": spec((1, tb, s), lambda b, j, i: (b, at(i), 0)),
        "bct": spec((1, ncb, s, q), lambda b, j, i: (b, at(i), 0, 0)),
        # their cotangents, one partial sum a step of heads
        "dbc": spec((1, 1, tb, s), lambda b, j, i: (b, j, at(i), 0)),
        "dbct": spec((1, 1, ncb, s, q), lambda b, j, i: (b, j, at(i), 0, 0)),
        "row": spec((1, hb, ncb, ROWS, q),
                    lambda b, j, i: (b, j, at(i), 0, 0)),
        "state": spec((1, hb // dims.hu, ncb, s, dims.uw),
                      lambda b, j, i: (b, j, at(i), 0, 0)),
    }


def _params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _turned(a, q):
    """``(N, T, S)`` -> ``(N, T / Q, S, Q)``: every chunk's transpose."""
    n, t, s = a.shape
    return a.reshape(n, t // q, q, s).transpose(0, 1, 3, 2)


@functools.partial(jax.jit, static_argnames=("dims", "interpret"))
def _scan(x, b, c, row, dims, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, t, _ = x.shape
    h, _, s, q = dims[:4]
    sp = _specs(dims, t)
    return pl.pallas_call(
        dims.kernel(_scan_kernel), grid=dims.grid(n, t),
        in_specs=[sp["x"], sp["bct"], sp["bc"], sp["row"]],
        out_specs=[sp["x"], sp["state"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((n, h // dims.hu, t // q, s, dims.uw),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dims.hb // dims.hu, s, dims.uw),
                                   jnp.float32)],
        compiler_params=_params(), interpret=interpret, name="ssd_scan",
    )(x, _turned(b, q), c, row)


@functools.partial(jax.jit, static_argnames=("dims", "interpret"))
def _scan_bwd(x, b, c, row, states, dy, dims, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, t, _ = x.shape
    h, _, s, q, _, hb = dims
    sp = _specs(dims, t, reverse=True)
    f32 = jnp.float32
    dx, dbt, dc, drow = pl.pallas_call(
        dims.kernel(_scan_bwd_kernel), grid=dims.grid(n, t),
        in_specs=[sp["x"], sp["bc"], sp["bct"], sp["bc"], sp["bct"],
                  sp["row"], sp["state"], sp["x"]],
        out_specs=[sp["x"], sp["dbct"], sp["dbc"], sp["row"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((n, h // hb, t // q, s, q), f32),
                   jax.ShapeDtypeStruct((n, h // hb, t, s), f32),
                   jax.ShapeDtypeStruct(row.shape, f32)],
        scratch_shapes=[pltpu.VMEM((hb // dims.hu, s, dims.uw), f32)],
        compiler_params=_params(), interpret=interpret, name="ssd_scan_bwd",
    )(x, b, _turned(b, q), c, _turned(c, q), row, states, dy)
    # the steps of heads' partial sums; B's comes turned a chunk
    db = dbt.sum(axis=1).transpose(0, 1, 3, 2).reshape(n, t, s)
    return dx, db.astype(b.dtype), dc.sum(axis=1).astype(c.dtype), drow


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _rule(x, b, c, row, dims, interpret):
    """``x (N, T, H P)``, ``b``/``c (N, T, S)``, the per-token scalars'
    tiles ``row (N, H, T / Q, ROWS, Q)`` float32 -> ``y (N, T, H P)``;
    ``T`` whole stretches."""
    return _rule_fwd(x, b, c, row, dims, interpret)[0]


def _rule_fwd(x, b, c, row, dims, interpret):
    y, states = _scan(x, b, c, row, dims, interpret)
    return y, (x, b, c, row, states)


def _rule_bwd(dims, interpret, res, dy):
    return _scan_bwd(*res, dy, dims, interpret)


_rule.defvjp(_rule_fwd, _rule_bwd)


def _scalars(dt, a, doc, q):
    """The per-token scalars of ``ssd_xla``, one ``(ROWS, Q)`` tile a
    chunk and head, tokens along the lanes: ``(N, H, T / Q, ROWS, Q)``
    float32."""
    n, t, h = dt.shape
    nc = t // q
    f32 = jnp.float32
    dth = jnp.transpose(dt.astype(f32), (0, 2, 1)).reshape(n, h, nc, q)
    # log decay, summed from the chunk's start: (N, H, NC, Q), <= 0
    cs = jnp.cumsum(dth * a.astype(f32)[None, :, None, None], axis=3)
    dq = doc.reshape(n, 1, nc, q)
    end_doc = dq[..., -1:]                                   # (N,1,NC,1)
    # the document of the token before the chunk; -1 before the first
    prev_doc = jnp.concatenate(
        [jnp.full((n, 1, 1, 1), -1, dq.dtype), end_doc[:, :, :-1]], axis=2)
    rows = [jnp.zeros((n, h, nc, q), f32)] * ROWS
    rows[R_CS], rows[R_DT] = cs, dth
    rows[R_DOC] = jnp.broadcast_to(dq.astype(f32), (n, h, nc, q))
    rows[R_FS] = jnp.where(dq == prev_doc, jnp.exp(cs), 0.0)
    rows[R_TE] = jnp.where(dq == end_doc, jnp.exp(cs[..., -1:] - cs), 0.0)
    # one number a chunk and head, along every lane
    rows[R_CARRY] = jnp.broadcast_to(
        jnp.where(end_doc == prev_doc, jnp.exp(cs[..., -1:]), 0.0),
        (n, h, nc, q))
    return jnp.stack(rows, axis=3)


def ssd_fused(x, dt, a, b, c, doc=None, chunk: int = 256,
              interpret: bool = False):
    """``ops/ssd.ssd_scan`` through the kernels: ``x (N, T, H, P)``,
    ``dt (N, T, H)``, ``a (H,)``, ``b``/``c (N, T, S)``, ``doc (N, T)``
    or ``None`` -> ``y (N, T, H, P)``.  The caller has checked
    ``supported``."""
    n, t, h, p = x.shape
    q = int(chunk)
    nc = t // q
    hb = heads_per_step(h, p)
    ncb = next(k for k in range(max(1, STRETCH // q), 0, -1) if nc % k == 0)
    if doc is None:
        doc = jnp.zeros((n, t), jnp.int32)
    dims = _Dims(h, p, b.shape[-1], q, ncb, hb)
    row = _scalars(dt, a, doc, q)
    y = _rule(x.reshape(n, t, h * p), b, c, row, dims, bool(interpret))
    return y.reshape(n, t, h, p)
