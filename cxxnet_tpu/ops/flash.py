"""Fused flash attention as Pallas TPU kernels.

``ops/attention.mha`` is the golden model: it materializes the full
``(B, H, T, T)`` score matrix in HBM, which is both the memory ceiling
for long sequences (8k tokens at b8/h16 is ~32 GB of scores in f32) and
an extra HBM round-trip per step.  This kernel runs the standard
flash-attention recurrence — blockwise scores with an online
(log-sum-exp) softmax — entirely in VMEM: scores never touch HBM, and
memory is O(T) instead of O(T^2).

The backward pass is the flash recomputation scheme: the forward saves
only the per-row LSE (``m + log l``); two backward kernels re-derive the
probability blocks from (q, k, lse) and accumulate

* ``dq_i  = sum_j  [p_ij * (do_i . v_j - delta_i)] k_j * scale``
* ``dk_j  = sum_i  [p_ij * (do_i . v_j - delta_i)] q_i * scale``
* ``dv_j  = sum_i  p_ij^T do_i``

with ``delta_i = sum_d dO_id O_id`` computed once in XLA.

Layout contract matches ``ops/attention``: ``q, k, v`` are
``(B, T, H, Dh)``; internally heads fold into the grid's batch dim and
blocks are ``(block, Dh)`` tiles.  Causal masking predicates whole
skipped blocks (``pl.when``), so the causal kernel does ~half the FLOPs.
All accumulation is f32 regardless of input dtype (bf16 in, bf16 out,
f32 recurrence — the same discipline as the XLA path's
``preferred_element_type``).

``interpret=True`` runs the identical kernels on CPU for golden tests
(the PairTest discipline, SURVEY §4.1).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


NEG_INF = -1e30


def _dims(seq):
    return dict(dimension_semantics=seq)


def _mask(tq: int, tk: int, q_off, k_off):
    from jax import lax

    qi = q_off + lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
    ki = k_off + lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
    return qi >= ki


def _live(qo_ref, ko_ref, iq, ik, bq, bk, causal, dyn):
    """Causal block-liveness: can this (iq, ik) block contribute at all?
    Static offsets fold at trace time (the plain flash path); dynamic
    offsets read the SMEM scalars — ``pl.when`` accepts traced
    predicates, so a fully-future ring hop skips all compute."""
    if not causal:
        return True
    if dyn:
        return (qo_ref[0, 0] + iq * bq + bq - 1
                >= ko_ref[0, 0] + ik * bk)
    return iq * bq + bq - 1 >= ik * bk


def _fwd_kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc, m, l, *, bq, bk, causal, dyn, scale):
    from jax.experimental import pallas as pl

    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m[:] = jnp.full_like(m, NEG_INF)
        l[:] = jnp.zeros_like(l)

    live = _live(qo_ref, ko_ref, iq, ik, bq, bk, causal, dyn)

    @pl.when(live)
    def _block():
        qb = q_ref[0].astype(jnp.float32)
        kb = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            s = jnp.where(
                _mask(bq, bk, qo_ref[0, 0] + iq * bq,
                      ko_ref[0, 0] + ik * bk),
                s, NEG_INF,
            )
        m_prev = m[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if causal:
            # a query row fully masked within a live block leaves m_new at
            # NEG_INF, making exp(s - m_new) = 1 for every masked entry;
            # zero such rows so `out` alone is valid even under the
            # non-block-aligned offsets the public flash_mha_lse allows
            p = jnp.where(m_new > NEG_INF * 0.5, p, 0.0)
        l[:, :1] = l[:, :1] * corr + p.sum(axis=-1, keepdims=True)
        m[:, :1] = m_new
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc[:] = acc[:] * corr + pv

    @pl.when(ik == nk - 1)
    def _done():
        lf = jnp.maximum(l[:, :1], 1e-30)
        o_ref[0] = (acc[:] / lf).astype(o_ref.dtype)
        lse_ref[0] = m[:, :1] + jnp.log(lf)


def _dq_kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               dl_ref, dq_ref, acc, *, bq, bk, causal, dyn, scale):
    from jax.experimental import pallas as pl

    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    live = _live(qo_ref, ko_ref, iq, ik, bq, bk, causal, dyn)

    @pl.when(live)
    def _block():
        qb = q_ref[0].astype(jnp.float32)
        kb = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        p = jnp.exp(s - lse_ref[0])
        if causal:
            p = jnp.where(
                _mask(bq, bk, qo_ref[0, 0] + iq * bq,
                      ko_ref[0, 0] + ik * bk),
                p, 0.0,
            )
        dob = do_ref[0].astype(jnp.float32)
        dp = jax.lax.dot_general(
            dob, v_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - dl_ref[0])
        acc[:] += jax.lax.dot_general(
            ds, kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    @pl.when(ik == nk - 1)
    def _done():
        dq_ref[0] = acc[:].astype(dq_ref.dtype)


def _dkv_kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                dl_ref, dk_ref, dv_ref, kacc, vacc,
                *, bq, bk, causal, dyn, scale):
    from jax.experimental import pallas as pl

    ik = pl.program_id(1)
    iq = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        kacc[:] = jnp.zeros_like(kacc)
        vacc[:] = jnp.zeros_like(vacc)

    live = _live(qo_ref, ko_ref, iq, ik, bq, bk, causal, dyn)

    @pl.when(live)
    def _block():
        qb = q_ref[0].astype(jnp.float32)
        kb = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        p = jnp.exp(s - lse_ref[0])
        if causal:
            p = jnp.where(
                _mask(bq, bk, qo_ref[0, 0] + iq * bq,
                      ko_ref[0, 0] + ik * bk),
                p, 0.0,
            )
        dob = do_ref[0].astype(jnp.float32)
        vacc[:] += jax.lax.dot_general(
            p, dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            dob, v_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - dl_ref[0])
        kacc[:] += jax.lax.dot_general(
            ds, qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    @pl.when(iq == nq - 1)
    def _done():
        dk_ref[0] = kacc[:].astype(dk_ref.dtype)
        dv_ref[0] = vacc[:].astype(dv_ref.dtype)


def _pick_block(t: int, want: int) -> int:
    b = min(want, t)
    while t % b:
        b //= 2
    return max(b, 1)


def _offs(q_off, k_off):
    """Normalize offsets to the (1,1) int32 SMEM operands the kernels
    read; None → zeros (the plain static path)."""
    z = jnp.zeros((1, 1), jnp.int32)
    qo = z if q_off is None else jnp.asarray(q_off, jnp.int32).reshape(1, 1)
    ko = z if k_off is None else jnp.asarray(k_off, jnp.int32).reshape(1, 1)
    return qo, ko


def _smem_spec():
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _flash_fwd_raw(q, k, v, causal, bq, bk, interpret,
                   q_off=None, k_off=None):
    """(BH, T, D) folded layout -> (out, lse).  lse is (BH, T, 1) f32 —
    the lane-1 layout keeps T in sublanes so the kernel writes it
    without a relayout.  ``q_off``/``k_off`` are dynamic global
    position offsets for the causal mask (ring hops); None keeps the
    static-offset fast path (block-level causal skip)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    dyn = q_off is not None or k_off is not None
    qo, ko = _offs(q_off, k_off)
    bh, t, d = q.shape
    tk = k.shape[1]
    nq, nk = t // bq, tk // bk
    scale = 1.0 / math.sqrt(d)
    kern = functools.partial(
        _fwd_kernel, bq=bq, bk=bk, causal=causal, dyn=dyn, scale=scale
    )
    qspec = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM)
    kspec = pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kern,
        grid=(bh, nq, nk),
        in_specs=[_smem_spec(), _smem_spec(), qspec, kspec, kspec],
        out_specs=[
            qspec,
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, t, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            **_dims(("parallel", "parallel", "arbitrary"))
        ),
        interpret=interpret,
    )(qo, ko, q, k, v)


def _flash_bwd_raw(q, k, v, do, lse, delta, causal, bq, bk, interpret,
                   q_off=None, k_off=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    dyn = q_off is not None or k_off is not None
    qo, ko = _offs(q_off, k_off)
    bh, t, d = q.shape
    tk = k.shape[1]
    nq, nk = t // bq, tk // bk
    scale = 1.0 / math.sqrt(d)

    qspec = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM)
    kspec = pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM)
    rspec = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, bq=bq, bk=bk, causal=causal,
                          dyn=dyn, scale=scale),
        grid=(bh, nq, nk),
        in_specs=[_smem_spec(), _smem_spec(),
                  qspec, kspec, kspec, qspec, rspec, rspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            **_dims(("parallel", "parallel", "arbitrary"))
        ),
        interpret=interpret,
    )(qo, ko, q, k, v, do, lse, delta)

    # k/v grid: kv block is the resident operand, q sweeps innermost
    qspec2 = pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0),
                          memory_space=pltpu.VMEM)
    kspec2 = pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0),
                          memory_space=pltpu.VMEM)
    rspec2 = pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0),
                          memory_space=pltpu.VMEM)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, bq=bq, bk=bk, causal=causal,
                          dyn=dyn, scale=scale),
        grid=(bh, nk, nq),
        in_specs=[_smem_spec(), _smem_spec(),
                  qspec2, kspec2, kspec2, qspec2, rspec2, rspec2],
        out_specs=[kspec2, kspec2],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, tk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            **_dims(("parallel", "parallel", "arbitrary"))
        ),
        interpret=interpret,
    )(qo, ko, q, k, v, do, lse, delta)
    return dq, dk, dv


def _fold(x):
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _unfold(x, b, h):
    bh, t, d = x.shape
    return x.reshape(b, h, t, d).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_mha(q, k, v, causal: bool = False, block_q: int = 512,
              block_k: int = 512, interpret: bool = False):
    """Flash attention on ``(B, T, H, Dh)`` tensors — drop-in for
    ``attention.mha``.  ``_pick_block`` halves the block until it
    divides T; callers (the layer's ``auto`` dispatch) should route T
    whose largest dividing block is tiny back to ``mha`` — a block-1
    kernel is valid but pathologically slow."""
    out, _ = _flash_fwd(q, k, v, causal, block_q, block_k, interpret)
    return out


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
    b, t, h, d = q.shape
    bq = _pick_block(t, block_q)
    bk = _pick_block(k.shape[1], block_k)
    out, lse = _flash_fwd_raw(
        _fold(q), _fold(k), _fold(v), causal, bq, bk, interpret
    )
    return _unfold(out, b, h), (q, k, v, _unfold(out, b, h), lse)


def _flash_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    b, t, h, d = q.shape
    bq = _pick_block(t, block_q)
    bk = _pick_block(k.shape[1], block_k)
    gf = _fold(g)
    of = _fold(out)
    delta = (gf.astype(jnp.float32) * of.astype(jnp.float32)).sum(
        -1, keepdims=True
    )
    dq, dk, dv = _flash_bwd_raw(
        _fold(q), _fold(k), _fold(v), gf, lse, delta, causal, bq, bk,
        interpret,
    )
    return _unfold(dq, b, h), _unfold(dk, b, h), _unfold(dv, b, h)


flash_mha.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
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
    lse ~ -1e30 and washes out of the merge).

    The VJP accepts cotangents for BOTH outputs: ``dL/dlse`` folds into
    the backward kernels as ``ds = p * (dp - (delta - dlse))`` — the
    same two kernels serve both flash entry points.
    """
    out, lse, _ = _flash_lse_fwd_impl(
        q, k, v, q_off, k_off, causal, block_q, block_k, interpret
    )
    return out, lse


def _flash_lse_fwd_impl(q, k, v, q_off, k_off, causal, block_q, block_k,
                        interpret):
    b, t, h, d = q.shape
    bq = _pick_block(t, block_q)
    bk = _pick_block(k.shape[1], block_k)
    out, lse = _flash_fwd_raw(
        _fold(q), _fold(k), _fold(v), causal, bq, bk, interpret,
        q_off=q_off, k_off=k_off,
    )
    # lse (BH, T, 1) -> (B, T, H)
    lse_o = lse[:, :, 0].reshape(b, h, t).transpose(0, 2, 1)
    return _unfold(out, b, h), lse_o, (out, lse)


def _flash_lse_fwd(q, k, v, q_off, k_off, causal, block_q, block_k,
                   interpret):
    out_u, lse_o, (out_f, lse_f) = _flash_lse_fwd_impl(
        q, k, v, q_off, k_off, causal, block_q, block_k, interpret
    )
    return (out_u, lse_o), (q, k, v, q_off, k_off, out_f, lse_f)


def _flash_lse_bwd(causal, block_q, block_k, interpret, res, cts):
    g, g_lse = cts
    q, k, v, q_off, k_off, out_f, lse_f = res
    b, t, h, d = q.shape
    bq = _pick_block(t, block_q)
    bk = _pick_block(k.shape[1], block_k)
    gf = _fold(g)
    # dL/dlse_i adds p_ij * dlse_i to ds_ij; the kernels compute
    # ds = p * (dp - dl) so dl = delta - dlse absorbs it
    dlse = jnp.zeros((b * h, t, 1), jnp.float32) if g_lse is None else (
        g_lse.transpose(0, 2, 1).reshape(b * h, t, 1).astype(jnp.float32)
    )
    delta = (gf.astype(jnp.float32) * out_f.astype(jnp.float32)).sum(
        -1, keepdims=True
    )
    dq, dk, dv = _flash_bwd_raw(
        _fold(q), _fold(k), _fold(v), gf, lse_f, delta - dlse,
        causal, bq, bk, interpret, q_off=q_off, k_off=k_off,
    )
    return (_unfold(dq, b, h), _unfold(dk, b, h), _unfold(dv, b, h),
            None, None)


flash_mha_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)
