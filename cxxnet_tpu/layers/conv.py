"""Spatial layers: conv, pooling family, LRN, batch-norm.

All operate on NHWC arrays and lower TPU-shaped: conv via
``lax.conv_general_dilated`` (MXU); pooling as shifted-slice max/add trees
(VPU — avoiding reduce_window's select-and-scatter backward); LRN via a
Pallas kernel on TPU.  No im2col-GEMM / mshadow ``pool`` expressions.

Parity sources:
* conv — ``/root/reference/src/layer/convolution_layer-inl.hpp``
  (grouped im2col GEMM; output shape ``(in + 2p - k) // s + 1``; weights
  init with fan_in = Cin/g*kh*kw, fan_out = Cout/g)
* pooling — ``/root/reference/src/layer/pooling_layer-inl.hpp`` (max /
  sum / avg / relu+max; **ceil** output shape
  ``min(in - k + s - 1, in - 1) // s + 1`` with partial edge windows;
  avg always divides by k*k regardless of window truncation)
* insanity_max_pooling — ``/root/reference/src/layer/
  insanity_pooling_layer-inl.hpp`` (train: each source pixel is replaced,
  with prob (1-keep)/4 each, by its up/down/left/right neighbour before a
  normal ceil max-pool; eval: plain max-pool)
* lrn — ``/root/reference/src/layer/lrn_layer-inl.hpp`` (cross-channel:
  ``out = x * (knorm + alpha/n * sum_win(x^2))^-beta``)
* batch_norm — ``/root/reference/src/layer/batch_norm_layer-inl.hpp``
  (per-channel batch stats; **eval also uses current-minibatch stats** —
  a documented reference quirk, doc/layer.md:235-240 — kept for parity)
"""

from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .base import Layer, Params, Shape, register


def _ceil_pool_shape(in_size: int, k: int, s: int, p: int = 0) -> int:
    """Reference pooling output size (pooling_layer-inl.hpp:100-104).

    ``p=0`` is the exact reference formula (it has no pooling pad).  With
    ``p>0`` (a framework extension needed for inception-style same-size
    pool branches) the shape follows the caffe convention the reference's
    formula derives from: ceil((in+2p-k)/s)+1, clipped so the last window
    starts inside the (left-padded) input.
    """
    if p == 0:
        return min(in_size - k + s - 1, in_size - 1) // s + 1
    out = (in_size + 2 * p - k + s - 1) // s + 1
    if (out - 1) * s >= in_size + p:
        out -= 1
    return out


def _pool_pad(in_size: int, k: int, s: int, p: int = 0) -> Tuple[int, int]:
    """(left, right) padding so VALID windows realize the ceil shape."""
    out = _ceil_pool_shape(in_size, k, s, p)
    return p, max(0, (out - 1) * s + k - in_size - p)


def _conv_s2d(x, w, s: int, py: int, px: int):
    """Strided conv as space-to-depth + stride-1 conv — mathematically
    exact (MLPerf-style stem-conv rewrite, generalized to any stride).

    A strided conv on a low-channel high-resolution input (GoogLeNet/
    ResNet 7x7 s2, AlexNet 11x11 s4 stems: C_in=3, 224px+) im2cols to a
    GEMM whose K = k·k·3 rows are read at stride s — poor MXU feeding.
    Decomposing tap index dy = s·t + a turns it into a stride-1 conv on
    the s×s space-to-depth input (1/s resolution, s²C channels) with
    the kernel taps regrouped the same way (k not divisible by s
    zero-pads the tail tap rows/cols; input extents not divisible by s
    zero-pad on the right and the junk tail outputs are sliced off):

        y[oy] = Σ_dy x̃[s·oy+dy]·W[dy] = Σ_{t,a} xs_a[oy+t]·W[s·t+a]

    Weights stay (kh, kw, C, O) — checkpoints, updaters, and visitors
    untouched; the regroup is a reshape/transpose autodiff reverses
    exactly.
    """
    kh, kw, c, o = w.shape
    n, h, wd = x.shape[0], x.shape[1], x.shape[2]
    oh = (h + 2 * py - kh) // s + 1
    ow = (wd + 2 * px - kw) // s + 1
    hp, wp = h + 2 * py, wd + 2 * px
    eh, ew = (-hp) % s, (-wp) % s
    xp = jnp.pad(x, ((0, 0), (py, py + eh), (px, px + ew), (0, 0)))
    hq, wq = (hp + eh) // s, (wp + ew) // s
    xs = (
        xp.reshape(n, hq, s, wq, s, c)
        .transpose(0, 1, 3, 2, 4, 5)
        .reshape(n, hq, wq, s * s * c)
    )
    k2h, k2w = -(-kh // s), -(-kw // s)
    wpad = jnp.pad(w, ((0, k2h * s - kh), (0, k2w * s - kw), (0, 0),
                       (0, 0)))
    ws = (
        wpad.reshape(k2h, s, k2w, s, c, o)
        .transpose(0, 2, 1, 3, 4, 5)
        .reshape(k2h, k2w, s * s * c, o)
    )
    assert hq - k2h + 1 >= oh and wq - k2w + 1 >= ow
    y = lax.conv_general_dilated(
        xs,
        ws,
        window_strides=(1, 1),
        padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return y[:, :oh, :ow, :]


# --- Winograd F(m x m, 3x3) (Lavin & Gray 2015) -------------------------
#
# Two tile sizes, selected by ``conv_wino``:
#
# * 1 -> F(4x4): 36 taps per 16 outputs = 2.25 MACs/output vs direct's
#   9 (the max FLOP win), transform constants up to |8| — bf16 GEMM
#   operands cost ~1e-2 relative error (the known fp16-Winograd
#   tradeoff; cuDNN's fp16 winograd has the same profile);
# * 2 -> F(2x2): 16 taps per 4 outputs = 4 MACs/output (a 2.25x
#   reduction), transform constants in {0, +-1, 1/2} — error within
#   ~3x of the direct bf16 conv (the tested bound).  The numerics
#   escape hatch.
#
# B^T/A^T products are bf16-exact or near-exact; G carries fractions,
# so U = GwG^T is computed in f32 and cast once.

_WG_F4 = (
    4,
    np.array(
        [
            [4, 0, -5, 0, 1, 0],
            [0, -4, -4, 1, 1, 0],
            [0, 4, -4, -1, 1, 0],
            [0, -2, -1, 2, 1, 0],
            [0, 2, -1, -2, 1, 0],
            [0, 4, 0, -5, 0, 1],
        ],
        np.float32,
    ),
    np.array(
        [
            [1 / 4, 0, 0],
            [-1 / 6, -1 / 6, -1 / 6],
            [-1 / 6, 1 / 6, -1 / 6],
            [1 / 24, 1 / 12, 1 / 6],
            [1 / 24, -1 / 12, 1 / 6],
            [0, 0, 1],
        ],
        np.float32,
    ),
    np.array(
        [
            [1, 1, 1, 1, 1, 0],
            [0, 1, -1, 2, -2, 0],
            [0, 1, 1, 4, 4, 0],
            [0, 1, -1, 8, -8, 1],
        ],
        np.float32,
    ),
)
_WG_F2 = (
    2,
    np.array(
        [
            [1, 0, -1, 0],
            [0, 1, 1, 0],
            [0, -1, 1, 0],
            [0, 1, 0, -1],
        ],
        np.float32,
    ),
    np.array(
        [
            [1, 0, 0],
            [1 / 2, 1 / 2, 1 / 2],
            [1 / 2, -1 / 2, 1 / 2],
            [0, 0, 1],
        ],
        np.float32,
    ),
    np.array(
        [
            [1, 1, 1, 0],
            [0, 1, -1, -1],
        ],
        np.float32,
    ),
)


def _conv_winograd3(x, w, py: int, px: int, variant: int = 1):
    """3x3 stride-1 conv via Winograd F(mxm, 3x3) — fewer MACs per
    output than the 9-tap im2col GEMM XLA:TPU lowers to (no Winograd
    rewrite in XLA; the cuDNN fast path the reference gets for free,
    ``cudnn_convolution_layer-inl.hpp``, re-derived as pure XLA ops).

    Everything is jnp — tile extraction as strided slices, the two
    small (m+2)x(m+2) transforms as f32 einsums (VPU work, fused by
    XLA), and the one heavy contraction as an (m+2)²-way batched GEMM
    in the input dtype with f32 accumulation — so XLA keeps fusing
    around it; no custom-call fence (the round-3 Pallas-pool lesson,
    doc/performance.md "Isolated-kernel wins do not survive fusion").

    Numerics: input/inverse transforms in f32, GEMM operands cast back
    to ``x.dtype`` (see the tile-size tradeoff at the matrices above).
    Autodiff reverses the whole pipeline, so the backward is Winograd
    too (the transposed transforms).
    """
    m, bt, g, at = _WG_F2 if variant == 2 else _WG_F4
    a = m + 2  # input tile edge
    n, h, wd, c = x.shape
    o = w.shape[3]
    oh, ow = h + 2 * py - 2, wd + 2 * px - 2
    th, tw = -(-oh // m), -(-ow // m)
    # padded extent must cover the last tile: m*(t-1) + a
    xp = jnp.pad(
        x,
        ((0, 0), (py, m * th + 2 - h - py), (px, m * tw + 2 - wd - px),
         (0, 0)),
    )
    # d[n, t, u, c, i, j] = xp[n, m*t+i, m*u+j, c]: a*a strided slices
    d = jnp.stack(
        [
            jnp.stack(
                [xp[:, i:i + m * th:m, j:j + m * tw:m, :] for j in range(a)],
                axis=-1,
            )
            for i in range(a)
        ],
        axis=-2,
    )  # (N, th, tw, C, a_i, a_j)
    v = jnp.einsum(
        "ai,ntucij,bj->abntuc",
        bt, d.astype(jnp.float32), bt,
    ).astype(x.dtype)
    u = jnp.einsum(
        "ak,klco,bl->abco",
        g, w.astype(jnp.float32), g,
    ).astype(x.dtype)
    # the MXU part: a² batched (N*th*tw, C) x (C, O) GEMMs
    mm = jnp.einsum(
        "abntuc,abco->abntuo", v, u,
        preferred_element_type=jnp.float32,
    )
    y = jnp.einsum("pa,abntuo,qb->ntupqo", at, mm, at)
    y = y.transpose(0, 1, 3, 2, 4, 5).reshape(n, m * th, m * tw, o)
    return y[:, :oh, :ow, :].astype(x.dtype)


@register
class ConvolutionLayer(Layer):
    type_name = "conv"

    def __init__(self) -> None:
        super().__init__()
        self.conv_s2d = 0  # opt-in space-to-depth rewrite (any stride>1)
        # opt-in Winograd for 3x3 s1 convs: 1 = F(4x4), 2 = F(2x2)
        self.conv_wino = 0

    def set_param(self, name, val):
        if name == "conv_s2d":
            self.conv_s2d = int(val)
        elif name == "conv_wino":
            if val not in ("0", "1", "2"):
                raise ValueError(
                    f"conv_wino must be 0 (off), 1 (F4x4) or 2 (F2x2), "
                    f"got {val!r}"
                )
            self.conv_wino = int(val)
        else:
            super().set_param(name, val)

    def infer_shape(self, in_shapes: Sequence[Shape]) -> List[Shape]:
        self._check_arity(in_shapes, 1)
        (shape,) = in_shapes
        if len(shape) != 4:
            raise ValueError("ConvolutionLayer: input must be an NHWC image node")
        p = self.param
        n, h, w, c = shape
        if c % p.num_group != 0:
            raise ValueError("input channels must divide group size")
        if p.num_channel <= 0 or p.num_channel % p.num_group != 0:
            raise ValueError("must set nchannel correctly (divisible by ngroup)")
        if p.kernel_height <= 0 or p.kernel_width <= 0:
            raise ValueError("must set kernel_size correctly")
        if p.kernel_width > w + 2 * p.pad_x or p.kernel_height > h + 2 * p.pad_y:
            raise ValueError("kernel size exceeds input")
        if p.num_input_channel == 0:
            p.num_input_channel = c
        elif p.num_input_channel != c:
            raise ValueError("ConvolutionLayer: inconsistent input channels")
        oh = (h + 2 * p.pad_y - p.kernel_height) // p.stride + 1
        ow = (w + 2 * p.pad_x - p.kernel_width) // p.stride + 1
        return [(n, oh, ow, p.num_channel)]

    def init_params(self, key, in_shapes) -> Params:
        p = self.param
        cin_g = in_shapes[0][3] // p.num_group
        # HWIO layout, O grouped in ngroup blocks (XLA feature_group_count)
        shape = (p.kernel_height, p.kernel_width, cin_g, p.num_channel)
        in_num = cin_g * p.kernel_height * p.kernel_width
        out_num = p.num_channel // p.num_group
        out: Params = {"wmat": p.rand_init_weight(key, shape, in_num, out_num)}
        if p.no_bias == 0:
            out["bias"] = jnp.full((p.num_channel,), p.init_bias, jnp.float32)
        return out

    def apply(self, params, inputs, *, train=False, rng=None, step=None):
        p = self.param
        x = inputs[0]
        if (self.conv_wino and p.stride == 1 and p.num_group == 1
                and p.kernel_height == 3 and p.kernel_width == 3
                and x.shape[3] >= 8):
            # cin < 8 (e.g. a VGG conv1_1 RGB input) keeps the direct
            # path: the Winograd GEMM contracts over K = cin, and K=3
            # starves the MXU worse than the 9-tap im2col's K=27
            y = _conv_winograd3(x, params["wmat"], p.pad_y, p.pad_x,
                                variant=self.conv_wino)
        elif self.conv_s2d and p.stride > 1 and p.num_group == 1:
            y = _conv_s2d(x, params["wmat"].astype(x.dtype), p.stride,
                          p.pad_y, p.pad_x)
        else:
            y = lax.conv_general_dilated(
                x,
                params["wmat"].astype(x.dtype),
                window_strides=(p.stride, p.stride),
                padding=((p.pad_y, p.pad_y), (p.pad_x, p.pad_x)),
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=p.num_group,
            )
        if "bias" in params:
            y = y + params["bias"].astype(x.dtype)
        return [y]


def _pool_geometry(h: int, w: int, kh: int, kw: int, s: int, py: int,
                   px: int):
    """((plh, prh), (plw, prw), oh, ow) for the ceil-shape pooling."""
    return (
        _pool_pad(h, kh, s, py),
        _pool_pad(w, kw, s, px),
        _ceil_pool_shape(h, kh, s, py),
        _ceil_pool_shape(w, kw, s, px),
    )


def _pad_for_pool(x, kh, kw, s, py, px, init_val):
    """(padded x, geometry): the common front of every pooling path."""
    geo = _pool_geometry(x.shape[1], x.shape[2], kh, kw, s, py, px)
    (plh, prh), (plw, prw), _, _ = geo
    xp = jnp.pad(
        x,
        ((0, 0), (plh, prh), (plw, prw), (0, 0)),
        constant_values=x.dtype.type(init_val),
    )
    return xp, geo


def _shifted_slices(xp, kh, kw, s, oh, ow):
    """Yield ((dy, dx), window-element slice) over the k*k offsets: the
    strided-slice tree shared by pooling forward and backward."""
    for dy in range(kh):
        for dx in range(kw):
            yield (dy, dx), xp[
                :,
                dy : dy + (oh - 1) * s + 1 : s,
                dx : dx + (ow - 1) * s + 1 : s,
                :,
            ]


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def _maxpool_eq(x, kh: int, kw: int, s: int, py: int, px: int):
    """Ceil-shape max pooling whose backward is the reference's unpool.

    Forward: max tree over the k*k statically-shifted strided slices
    (see _PoolBase._pool).  Backward (custom VJP): the mshadow
    ``unpool`` rule the reference's pooling layer uses
    (``pooling_layer-inl.hpp:66-75``) — every input position equal to
    its window's max receives that window's gradient:
    ``dx_i = sum_w [x_i == y_w] * g_w``.

    Two reasons to override autodiff here (measured on v5e, GoogLeNet
    b128, doc/performance.md): the max tree's autodiff backward is an
    8-deep select chain that materializes pred masks between fusions
    (~29ms/step across the 13 pools — 40% of the whole train step), and
    its single-winner tie handling differs from the reference.  The
    equality rule is k*k fused compare-multiplies expanded back onto
    the input grid with interior padding (the transpose of the strided
    slice), the same pad+add shape XLA already lowers well for the sum
    pool's backward.
    """
    xp, (_, _, oh, ow) = _pad_for_pool(x, kh, kw, s, py, px, -jnp.inf)
    acc = None
    for _, sl in _shifted_slices(xp, kh, kw, s, oh, ow):
        acc = sl if acc is None else lax.max(acc, sl)
    return acc


def _maxpool_eq_fwd(x, kh, kw, s, py, px):
    y = _maxpool_eq(x, kh, kw, s, py, px)
    return y, (x, y)


def _maxpool_eq_bwd(kh, kw, s, py, px, res, g):
    x, y = res
    h, w = x.shape[1], x.shape[2]
    xp, ((plh, _), (plw, _), oh, ow) = _pad_for_pool(
        x, kh, kw, s, py, px, -jnp.inf
    )
    hp, wp = xp.shape[1], xp.shape[2]
    zero = jnp.zeros((), g.dtype)
    if s > 1:
        dx_p = _unpool_strided(xp, y, g, kh, kw, s, oh, ow)
        dx_ = dx_p[:, plh : plh + h, plw : plw + w, :]
        return (dx_.astype(x.dtype),)
    # note: a gather-style s==1 formulation (read y/g at k*k shifts, one
    # pass at input resolution) measured SLOWER on v5e than this
    # pad-and-add form (2044 vs 2128 img/s GoogLeNet b128) — the pads
    # below fuse better than the 2k²+1-operand compare fusion
    total = None
    for (dy, dx), xw in _shifted_slices(xp, kh, kw, s, oh, ow):
        contrib = jnp.where(xw == y, g, zero)
        # transpose of the strided slice: interior-pad back onto the
        # padded-input grid, then the contributions just add
        exp = lax.pad(
            contrib,
            zero,
            (
                (0, 0, 0),
                (dy, hp - (dy + (oh - 1) * s + 1), s - 1),
                (dx, wp - (dx + (ow - 1) * s + 1), s - 1),
                (0, 0, 0),
            ),
        )
        total = exp if total is None else total + exp
    dx_ = total[:, plh : plh + h, plw : plw + w, :]
    return (dx_.astype(x.dtype),)


def _unpool_strided(xp, y, g, kh, kw, s, oh, ow):
    """The unpool-equality backward for s > 1 as a parity decomposition
    — scatter-free, one write per input position.

    The s == 1 pad-and-add form above interior-pads every one of the
    k*k window contributions back onto the FULL padded-input grid (for
    s=2 each dilated tensor is 3/4 zeros) and adds k*k of them: ~k*k
    full-resolution HBM writes.  Measured on the ResNet-50 stem pool
    (k3 s2 on 112x112x64, b128) that single pool's backward cost
    ~9 ms/step (doc/performance.md bisection).

    Strided pooling makes the transpose cheap instead: input row
    p = s*m + r (parity r = p mod s) collects contributions only from
    window elements dy ≡ r (mod s), shifted by t = (dy-r)/s in window
    index: ``sub_r[m] = sum_t c[r+s*t][m-t]``.  So build the s*s parity
    subgrids at window resolution (each 1/s² of the input area, at most
    ceil(k/s)² terms), then interleave them with one reshape.  Total
    traffic ~ k² window-size reads + one input-size write, vs k²
    input-size writes.
    """
    zero = jnp.zeros((), g.dtype)
    hp, wp = xp.shape[1], xp.shape[2]
    ohp = -(-hp // s)  # ceil: parity subgrids must cover every p < hp
    owp = -(-wp // s)
    contrib = {
        off: jnp.where(xw == y, g, zero)
        for off, xw in _shifted_slices(xp, kh, kw, s, oh, ow)
    }
    n, c = g.shape[0], g.shape[3]
    rows = []
    for ry in range(s):
        cols = []
        for rx in range(s):
            acc = None
            for dy in range(ry, kh, s):
                for dx in range(rx, kw, s):
                    t, u = (dy - ry) // s, (dx - rx) // s
                    # c[dy,dx][m-t, n-u] → pad t/u zeros in front, out to
                    # (ohp, owp) behind (window-resolution tensors: cheap)
                    term = lax.pad(
                        contrib[(dy, dx)],
                        zero,
                        (
                            (0, 0, 0),
                            (t, ohp - oh - t, 0),
                            (u, owp - ow - u, 0),
                            (0, 0, 0),
                        ),
                    )
                    acc = term if acc is None else acc + term
            cols.append(
                acc
                if acc is not None
                else jnp.zeros((n, ohp, owp, c), g.dtype)
            )
        rows.append(jnp.stack(cols, axis=3))  # (N, ohp, owp, s, C)
    big = jnp.stack(rows, axis=2)  # (N, ohp, s, owp, s, C)
    # interleave: p = s*m + ry, q = s*n + rx
    big = big.reshape(n, ohp * s, owp * s, c)
    return big[:, :hp, :wp, :]


_maxpool_eq.defvjp(_maxpool_eq_fwd, _maxpool_eq_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _maxpool_eq_pb(x, k: int, pad: int, interpret: bool):
    """Stride-1 max pooling: XLA forward tree (cheap, fuses well) with
    the one-pass Pallas backward (``ops/maxpool.maxpool_bwd_s1``) —
    ``pool_impl = pallas_bwd``.  Same unpool-equality semantics as
    ``_maxpool_eq``; that path is the pairtest golden."""
    return _maxpool_eq(x, k, k, 1, pad, pad)


def _maxpool_eq_pb_fwd(x, k, pad, interpret):
    y = _maxpool_eq(x, k, k, 1, pad, pad)
    return y, (x, y)


def _maxpool_eq_pb_bwd(k, pad, interpret, res, g):
    from ..ops.maxpool import maxpool_bwd_s1

    x, y = res
    return (maxpool_bwd_s1(x, y, g.astype(x.dtype), k, pad, interpret),)


_maxpool_eq_pb.defvjp(_maxpool_eq_pb_fwd, _maxpool_eq_pb_bwd)


class _PoolBase(Layer):
    """Shared ceil-shape pooling over NHWC (shifted-slice tree, see _pool)."""

    def __init__(self) -> None:
        super().__init__()
        self.pool_impl = "auto"  # auto = XLA; pallas is explicit opt-in

    def set_param(self, name, val):
        if name == "pool_impl":
            if val not in ("auto", "pallas", "pallas_bwd", "xla"):
                raise ValueError(
                    f"pool_impl must be auto|pallas|pallas_bwd|xla, "
                    f"got {val!r}"
                )
            self.pool_impl = val
        else:
            super().set_param(name, val)

    def infer_shape(self, in_shapes: Sequence[Shape]) -> List[Shape]:
        self._check_arity(in_shapes, 1)
        (shape,) = in_shapes
        if len(shape) != 4:
            raise ValueError(f"{self.type_name}: input must be an NHWC image node")
        p = self.param
        if p.kernel_height <= 0 or p.kernel_width <= 0:
            raise ValueError("must set kernel_size correctly")
        n, h, w, c = shape
        if p.kernel_width > w + 2 * p.pad_x or p.kernel_height > h + 2 * p.pad_y:
            raise ValueError("kernel size exceeds input")
        return [
            (
                n,
                _ceil_pool_shape(h, p.kernel_height, p.stride, p.pad_y),
                _ceil_pool_shape(w, p.kernel_width, p.stride, p.pad_x),
                c,
            )
        ]

    def _pool(self, x: jnp.ndarray, reducer, init_val) -> jnp.ndarray:
        """Pooling as a max/add tree over k*k statically-shifted strided
        slices — NOT ``lax.reduce_window``.

        TPU-shaped on purpose: the backward of ``reduce_window(max)`` is
        select-and-scatter, which XLA lowers poorly on TPU (orders of
        magnitude slower than the forward for overlapping windows, e.g.
        the stride-1 3x3 pools in every inception block).  A shifted
        max/add tree autodiffs to pad + select chains: pure VPU work,
        and XLA fuses the whole tree.
        """
        p = self.param
        kh, kw, s = p.kernel_height, p.kernel_width, p.stride
        xp, (_, _, oh, ow) = _pad_for_pool(
            x, kh, kw, s, p.pad_y, p.pad_x, init_val
        )
        acc = None
        for _, sl in _shifted_slices(xp, kh, kw, s, oh, ow):
            acc = sl if acc is None else reducer(acc, sl)
        return acc

    def _max_pool(self, x: jnp.ndarray) -> jnp.ndarray:
        """Max pooling with the unpool-equality backward: the XLA
        expression (``_maxpool_eq``) by default, the fused Pallas
        kernel (``ops/maxpool.py``) under ``pool_impl = pallas``, or
        XLA forward + the one-pass Pallas backward under ``pool_impl =
        pallas_bwd`` for the pools that kernel is defined for
        (same-size stride-1, odd k — every other pool in the net stays
        on the XLA path) — identical semantics, pair-tested.

        Both kernels are explicit opt-ins and ``auto`` never chooses
        them: ``pallas`` won isolated microbenchmarks but regressed the
        scanned train step's compile time pathologically on the v5e,
        and ``pallas_bwd`` lost in context (doc/performance.md).  An
        opt-in is honoured or fails: off the chip the kernel runs
        under the Pallas interpreter; on it a geometry Mosaic or libtpu
        refuses (stride > 1 needs a strided slice Mosaic lowers as an
        unsupported gather) fails the program's compile with the
        compiler's message — it is never swapped for the XLA path."""
        p = self.param
        interp = jax.default_backend() != "tpu"
        if self.pool_impl == "pallas_bwd" and (
            p.stride == 1
            and p.kernel_height == p.kernel_width
            and p.pad_y == p.pad_x
            and p.pad_y * 2 == p.kernel_height - 1
        ):
            return _maxpool_eq_pb(x, p.kernel_height, p.pad_y, interp)
        if self.pool_impl == "pallas":
            from ..ops.maxpool import maxpool_fused

            return maxpool_fused(
                x, p.kernel_height, p.kernel_width, p.stride, p.pad_y,
                p.pad_x, interp,
            )
        return _maxpool_eq(
            x, p.kernel_height, p.kernel_width, p.stride, p.pad_y, p.pad_x
        )


@register
class MaxPoolingLayer(_PoolBase):
    type_name = "max_pooling"

    def apply(self, params, inputs, *, train=False, rng=None, step=None):
        return [self._max_pool(inputs[0])]


@register
class SumPoolingLayer(_PoolBase):
    type_name = "sum_pooling"

    def apply(self, params, inputs, *, train=False, rng=None, step=None):
        return [self._pool(inputs[0], lax.add, 0.0)]


@register
class AvgPoolingLayer(_PoolBase):
    type_name = "avg_pooling"

    def apply(self, params, inputs, *, train=False, rng=None, step=None):
        p = self.param
        # parity: divide by full k*k even for truncated edge windows
        scale = 1.0 / (p.kernel_height * p.kernel_width)
        return [self._pool(inputs[0], lax.add, 0.0) * scale]


@register
class ReluMaxPoolingLayer(_PoolBase):
    type_name = "relu_max_pooling"

    def apply(self, params, inputs, *, train=False, rng=None, step=None):
        return [self._max_pool(jax.nn.relu(inputs[0]))]


@register
class InsanityPoolingLayer(_PoolBase):
    type_name = "insanity_max_pooling"

    def __init__(self) -> None:
        super().__init__()
        self.p_keep = 1.0

    def set_param(self, name, val):
        if name == "keep":
            self.p_keep = float(val)
        else:
            super().set_param(name, val)

    def apply(self, params, inputs, *, train=False, rng=None, step=None):
        x = inputs[0]
        if train and rng is not None and self.p_keep < 1.0:
            # jitter each source pixel to a neighbour with prob (1-keep)/4
            # per direction, border-clamped (insanity_pooling:70-100)
            flag = jax.random.uniform(rng, x.shape, x.dtype)
            up = jnp.concatenate([x[:, :1], x[:, :-1]], axis=1)
            down = jnp.concatenate([x[:, 1:], x[:, -1:]], axis=1)
            left = jnp.concatenate([x[:, :, :1], x[:, :, :-1]], axis=2)
            right = jnp.concatenate([x[:, :, 1:], x[:, :, -1:]], axis=2)
            d = (1.0 - self.p_keep) / 4.0
            x = jnp.where(
                flag < self.p_keep,
                x,
                jnp.where(
                    flag < self.p_keep + d,
                    up,
                    jnp.where(
                        flag < self.p_keep + 2 * d,
                        down,
                        jnp.where(flag < self.p_keep + 3 * d, left, right),
                    ),
                ),
            )
        return [self._max_pool(x)]


@register
class LRNLayer(Layer):
    type_name = "lrn"

    def __init__(self) -> None:
        super().__init__()
        self.nsize = 3
        self.alpha = 0.001
        self.beta = 0.75
        self.knorm = 1.0
        self.impl = "auto"  # auto = XLA; pallas is explicit opt-in

    def set_param(self, name, val):
        if name == "local_size":
            self.nsize = int(val)
        elif name == "alpha":
            self.alpha = float(val)
        elif name == "beta":
            self.beta = float(val)
        elif name == "knorm":
            self.knorm = float(val)
        elif name == "lrn_impl":
            if val not in ("auto", "pallas", "xla", "matmul"):
                raise ValueError(
                    f"lrn_impl must be auto|pallas|xla|matmul, got {val!r}"
                )
            self.impl = val
        else:
            super().set_param(name, val)

    def infer_shape(self, in_shapes: Sequence[Shape]) -> List[Shape]:
        self._check_arity(in_shapes, 1)
        return [tuple(in_shapes[0])]

    def apply(self, params, inputs, *, train=False, rng=None, step=None):
        from ..ops.lrn import lrn, lrn_matmul, lrn_xla

        x = inputs[0]
        if self.impl == "pallas":
            # explicit opt-in, honoured or failed (never swapped for
            # lrn_xla): interpreter off the chip, Mosaic on it.  auto
            # stays on XLA — in the scanned GoogLeNet step the kernel
            # took compile from ~47s to >25min on the v5e for a ~0
            # step-time difference (doc/performance.md)
            interp = jax.default_backend() != "tpu"
            y = lrn(x, self.nsize, self.alpha, self.beta, self.knorm, interp)
        elif self.impl == "matmul":
            y = lrn_matmul(x, self.nsize, self.alpha, self.beta, self.knorm)
        else:
            y = lrn_xla(x, self.nsize, self.alpha, self.beta, self.knorm)
        return [y]


@register
class BatchNormLayer(Layer):
    type_name = "batch_norm"

    def __init__(self) -> None:
        super().__init__()
        self.init_slope = 1.0
        self.init_bias_bn = 0.0
        self.eps = 1e-10
        self.bn_eval = "batch"  # reference parity; "running" for EMA stats
        self.bn_momentum = 0.9
        self.bn_stats = "twopass"  # "onepass": E[x^2]-E[x]^2, one read

    def set_param(self, name, val):
        if name == "init_slope":
            self.init_slope = float(val)
        elif name == "init_bias":
            self.init_bias_bn = float(val)
        elif name == "eps":
            self.eps = float(val)
        elif name == "bn_eval":
            if val not in ("batch", "running"):
                raise ValueError("bn_eval must be batch or running")
            self.bn_eval = val
        elif name == "bn_momentum":
            self.bn_momentum = float(val)
        elif name == "bn_stats":
            if val not in ("twopass", "onepass"):
                raise ValueError("bn_stats must be twopass or onepass")
            self.bn_stats = val
        else:
            super().set_param(name, val)

    def infer_shape(self, in_shapes: Sequence[Shape]) -> List[Shape]:
        self._check_arity(in_shapes, 1)
        return [tuple(in_shapes[0])]

    def init_params(self, key, in_shapes) -> Params:
        ch = in_shapes[0][-1]
        return {
            "wmat": jnp.full((ch,), self.init_slope, jnp.float32),
            "bias": jnp.full((ch,), self.init_bias_bn, jnp.float32),
        }

    def init_aux(self, in_shapes):
        """EMA statistics state (only with ``bn_eval = running``).

        The reference always normalized with *current-minibatch* stats,
        even at eval (doc/layer.md:235-240 caveat) — that stays the
        default; ``bn_eval = running`` upgrades eval to the standard
        moving-average statistics carried as trainer aux state."""
        if self.bn_eval != "running":
            return {}
        ch = in_shapes[0][-1]
        return {
            "rmean": jnp.zeros((ch,), jnp.float32),
            "rvar": jnp.ones((ch,), jnp.float32),
        }

    def _normalize(self, x, mean, var, params):
        inv = lax.rsqrt(var + jnp.float32(self.eps))
        slope = params["wmat"].astype(jnp.float32)
        bias = params["bias"].astype(jnp.float32)
        return ((x.astype(jnp.float32) - mean) * inv * slope + bias).astype(
            x.dtype
        )

    def _batch_stats(self, x):
        # statistics always in f32: bf16 mean/var loses too many mantissa
        # bits over a 100k-element reduction
        axes = tuple(range(x.ndim - 1))  # all but channel
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=axes)
        if self.bn_stats == "onepass":
            # one read of x: E[x^2]-E[x]^2, both reductions fuse into a
            # single pass (the two-pass form serializes: var needs mean).
            # f32 accumulation over activations in [-5,5] keeps ~7
            # significant digits — fine for BN, and each step's stats are
            # recomputed so no error accumulates across steps.
            var = jnp.maximum(jnp.mean(xf * xf, axis=axes) - mean * mean,
                              0.0)
        else:
            var = jnp.mean((xf - mean) ** 2, axis=axes)
        return mean, var

    def apply(self, params, inputs, *, train=False, rng=None, step=None):
        x = inputs[0]
        mean, var = self._batch_stats(x)
        return [self._normalize(x, mean, var, params)]

    def apply_stateful(self, params, aux, inputs, *, train=False, rng=None,
                       step=None):
        """(outs, new_aux): batch stats + EMA update in train, running
        stats at eval.  Only routed when init_aux returned state."""
        x = inputs[0]
        if train:
            mean, var = self._batch_stats(x)
            mom = jnp.float32(self.bn_momentum)
            new_aux = {
                "rmean": aux["rmean"] * mom + (1.0 - mom) * mean,
                "rvar": aux["rvar"] * mom + (1.0 - mom) * var,
            }
            return [self._normalize(x, mean, var, params)], new_aux
        return [
            self._normalize(x, aux["rmean"], aux["rvar"], params)
        ], aux
