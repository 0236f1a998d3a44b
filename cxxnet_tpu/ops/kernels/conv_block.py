"""Fused conv+bias(+relu) block kernel for the sibling-1x1 groups.

The stock lowering of a fused 1x1 sibling group (``nnet/net.py
_apply_fused_1x1``) is three XLA ops per group: one
``conv_general_dilated`` over the scatter-assembled block kernel, a
``slice_in_dim`` per member, and a bias add per member.  A 1x1 conv IS a
GEMM — output pixel ``(n,y,x)`` is ``x_row @ W`` — so this kernel runs
the whole group as ONE Pallas GEMM with the bias add (and optionally
the following relu) in the epilogue: the MXU tile is written back to
VMEM exactly once, already biased, instead of round-tripping through
HBM between the conv and the elementwise ops.  Strides subsample the
input on the host side first (exact for a 1x1/pad-0 conv: output pixel
``(i,j)`` reads only ``x[i*s, j*s]``).

Numerics: the contraction accumulates in f32 whatever the activation
dtype (``preferred_element_type`` — Mosaic's ``tpu.matmul`` refuses a
bf16 accumulator outright), the bias joins in f32 and the tile is
rounded to the activation dtype once.  For f32 that is the stock conv's
own arithmetic; for bf16 it is one rounding where the stock
conv-then-bias-add takes two, so the kernel sits within one bf16 ulp of
the stock lowering, not on it (tests/test_kernels.py states both
tolerances).

Tiling: M (pixels) is cut into ``_ROWS``-row blocks and wide O into
128-lane multiples; K always stays whole, so every output element is a
single full-K contraction.  Whole-axis blocks are only what small
operands get: at GoogLeNet i3a b128 the activation is 100352x192 — a
~38 MB block against a 16 MiB scoped VMEM limit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_ROWS = 512  # M-block: 512 x K(<=2048) bf16 stays ~2 MB double-buffered


def _pick_block(t: int, want: int) -> int:
    b = min(want, t)
    while t % b:
        b //= 2
    return max(b, 1)


def row_tiles(m: int, bm: int = 0):
    """``(block_rows, padded_rows)`` for an M (row) axis: an explicit
    ``bm`` is shrunk until it divides ``m``; otherwise a small axis is
    one whole block (legal for Mosaic whatever its size) and a large
    one is padded up to ``_ROWS``-row blocks.  Shared by the three
    kernel-library launchers."""
    if bm:
        return _pick_block(m, bm), m
    if m <= _ROWS:
        return m, m
    return _ROWS, -(-m // _ROWS) * _ROWS


def col_tile(o: int, bn: int = 0) -> int:
    """Block width for an O (lane) axis: explicit ``bn`` shrunk to a
    divisor, else the whole axis up to 512 lanes, else the widest
    128-multiple that divides it (Mosaic wants lane blocks that are
    128-multiples or the whole axis)."""
    if bn:
        return _pick_block(o, bn)
    if o <= 512:
        return o
    return next((b for b in (512, 384, 256, 128) if o % b == 0), o)


def _gemm_bias_kernel(x_ref, w_ref, b_ref, o_ref, *, relu, has_bias):
    # one full-K dot per output tile, f32 accumulator; bias and relu
    # ride the f32 tile before its single rounding
    y = jax.lax.dot_general(
        x_ref[:], w_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    if has_bias:
        y = y + b_ref[:].astype(jnp.float32)
    if relu:
        y = jnp.maximum(y, 0.0)
    o_ref[:] = y.astype(o_ref.dtype)


def fused_block_gemm(x2d, w2d, bias=None, *, relu: bool = False,
                     interpret: bool = False, bm: int = 0, bn: int = 0):
    """``relu?(x2d @ w2d + bias)`` as one Pallas program.

    ``x2d`` is ``(M, K)``, ``w2d`` ``(K, O)``, ``bias`` ``(O,)`` or
    None.  ``bm``/``bn`` pin the M/O tiles (tests); 0 tiles from the
    shapes (``row_tiles`` / ``col_tile``).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = x2d.shape
    k2, o = w2d.shape
    if k != k2:
        raise ValueError(f"fused_block_gemm: K mismatch {k} vs {k2}")
    has_bias = bias is not None
    b2 = (bias.reshape(1, o).astype(x2d.dtype) if has_bias
          else jnp.zeros((1, 1), x2d.dtype))
    bm, mp = row_tiles(m, bm)
    bn = col_tile(o, bn)
    if mp > m:
        x2d = jnp.pad(x2d, ((0, mp - m), (0, 0)))
    kern = functools.partial(_gemm_bias_kernel, relu=relu,
                             has_bias=has_bias)
    bspec = (pl.BlockSpec((1, bn), lambda i, j: (0, j),
                          memory_space=pltpu.VMEM) if has_bias
             else pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                               memory_space=pltpu.VMEM))
    y = pl.pallas_call(
        kern,
        grid=(mp // bm, o // bn),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, bn), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
            bspec,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((mp, o), x2d.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x2d, w2d, b2)
    return y[:m] if mp > m else y


def conv1x1_block(x, wk, bias=None, *, stride: int = 1,
                  relu: bool = False, interpret: bool = False,
                  bm: int = 0, bn: int = 0):
    """The group's 1x1 conv as the fused GEMM: ``x`` NHWC, ``wk``
    ``(1,1,C,O)`` (or already ``(C,O)``), ``bias`` the concatenated
    ``(O,)`` member biases.  Returns NHWC with ``O`` channels."""
    if stride > 1:
        x = x[:, ::stride, ::stride, :]
    n, h, w, c = x.shape
    w2d = wk.reshape(wk.shape[-2], wk.shape[-1])
    y = fused_block_gemm(x.reshape(-1, c), w2d, bias, relu=relu,
                         interpret=interpret, bm=bm, bn=bn)
    return y.reshape(n, h, w, -1)


def probe(backend: str, x=None, wk=None, **_kw):
    """Capability probe: None when launchable, else the reject reason.
    Shape arguments are optional — a conf-time probe only has the
    backend; a trace-time probe has the real operands."""
    if x is not None:
        if x.ndim != 4:
            return f"input must be NHWC, got ndim={x.ndim}"
        if x.dtype not in (jnp.float32, jnp.bfloat16):
            return f"unsupported activation dtype {x.dtype}"
    if wk is not None and wk.ndim == 4 and wk.shape[:2] != (1, 1):
        return f"kernel must be 1x1, got {wk.shape[:2]}"
    return None
