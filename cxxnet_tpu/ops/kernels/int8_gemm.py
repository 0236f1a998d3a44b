"""int8 GEMM with the per-channel rescale inside the kernel epilogue.

The PR-10 quant scheme (``ops/quant.py``) feeds RAW int8 codes to the
contraction and folds the per-output-channel rescale into the f32 bias
add OUTSIDE it — correct because the scale commutes out of the
contraction, but spelled as separate XLA ops the fusion of which is the
compiler's mood.  This kernel pins the whole chain —
cast(int8)→MXU→rescale→bias→activation — into ONE Pallas program: the
f32 accumulator tile is rescaled, biased and (optionally) relu'd while
still in VMEM, and only the finished activation-dtype tile is written
back.

Bit contract (the acceptance bar): with default full-array blocks the
kernel replays the stock ``fc_apply_q`` ops in the identical order —
``dot_general(x, q.astype(x.dtype), preferred_element_type=f32)``,
``* scale``, ``+ bias``, ``astype(x.dtype)`` — so interpret mode on CPU
is BIT-EQUAL to the PR-10 dequant-free reference
(tests/test_kernels.py pins it with ``np.array_equal``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .conv_block import col_tile, row_tiles


def _int8_kernel(x_ref, q_ref, s_ref, b_ref, o_ref, *, relu, has_bias):
    # identical op chain to ops/quant.fc_apply_q + _rescale_bias: the
    # int8 codes are cast to the activation dtype (exact: |codes| <= 127
    # fit bf16's mantissa), contracted with f32 accumulation, and the
    # epilogue rescales in f32
    y = jax.lax.dot_general(
        x_ref[:], q_ref[:].astype(x_ref.dtype),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    y = y * s_ref[:].astype(jnp.float32)
    if has_bias:
        y = y + b_ref[:].astype(jnp.float32)
    if relu:
        y = jnp.maximum(y, 0.0)
    o_ref[:] = y.astype(o_ref.dtype)


def int8_gemm_rescale(x2d, q, scale, bias=None, *, relu: bool = False,
                      interpret: bool = False, bm: int = 0, bn: int = 0):
    """``relu?((x2d @ q.T) * scale + bias).astype(x.dtype)`` fused.

    ``x2d`` is ``(M, K)`` f32/bf16, ``q`` ``(O, K)`` int8 (the fullc
    layout — the int8 array itself is the program operand; weights at
    rest stay 1 byte/element), ``scale`` ``(O,)`` f32, ``bias`` ``(O,)``
    or None.  ``bm``/``bn`` pin the M/O tiles (tests); 0 tiles from
    the shapes (``conv_block.row_tiles`` / ``col_tile``).  K stays
    whole so each output element is one full-K contraction in f32.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = x2d.shape
    o, k2 = q.shape
    if k != k2:
        raise ValueError(f"int8_gemm_rescale: K mismatch {k} vs {k2}")
    has_bias = bias is not None
    s2 = scale.reshape(1, o)
    b2 = (bias.reshape(1, o) if has_bias
          else jnp.zeros((1, 1), jnp.float32))
    bm, mp = row_tiles(m, bm)
    bn = col_tile(o, bn)
    if mp > m:
        x2d = jnp.pad(x2d, ((0, mp - m), (0, 0)))
    kern = functools.partial(_int8_kernel, relu=relu, has_bias=has_bias)
    row = lambda i, j: (0, j)  # noqa: E731 - (1, bn) per-channel rows
    bspec = (pl.BlockSpec((1, bn), row, memory_space=pltpu.VMEM)
             if has_bias
             else pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                               memory_space=pltpu.VMEM))
    y = pl.pallas_call(
        kern,
        grid=(mp // bm, o // bn),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, k), lambda i, j: (j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bn), row, memory_space=pltpu.VMEM),
            bspec,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((mp, o), x2d.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x2d, q, s2, b2)
    return y[:m] if mp > m else y


def probe(backend: str, x=None, q=None, **_kw):
    """None when launchable, else the reject reason."""
    if x is not None and x.dtype not in (jnp.float32, jnp.bfloat16):
        return f"unsupported activation dtype {x.dtype}"
    if q is not None and q.dtype != jnp.int8:
        return f"codes must be int8, got {q.dtype}"
    return None
