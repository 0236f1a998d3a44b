"""Fused shard-local sgd update-step kernel for ``_apply_updates``.

The ZeRO update path (``nnet/trainer.py _apply_updates``) applies the
per-tensor updater rules as separate XLA elementwise ops — momentum
read, clip, wd-fold, momentum write, weight write — each a full HBM
round-trip over the (shard-local) tensor.  The sgd rule

    m' = mom * m - lr * (clip(g) + wd * w);  w' = w + m'

is one fused read-modify-write: this kernel streams each (w, g, m)
tile through VMEM exactly once and writes both outputs from registers.
The math is purely elementwise, so the shard-local contract
(doc/parallel.md: each replica updates only its 1/N slice) holds
untouched — the kernel never sees, and never needs, the other shards.

Parity contract: the kernel body replays ``updater.SGDUpdater.apply``
(including the ``clip_gradient != 0`` NaN-zeroing clip quirk,
sgd_updater-inl.hpp:72-84) op for op — interpret mode on CPU is
bit-equal to the stock rule (tests/test_kernels.py pins it, NaNs
included).  lr/momentum arrive as traced (1,1) SMEM scalars (they are
schedule functions of the traced epoch); wd/clip are trace-time
constants, exactly as in the stock closure.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .conv_block import row_tiles

_LANES = 128


def _sgd_kernel(lr_ref, mom_ref, w_ref, g_ref, m_ref, wo_ref, mo_ref,
                *, wd, clip):
    lr = lr_ref[0, 0]
    mom = mom_ref[0, 0]
    g = g_ref[:]
    if clip != 0.0:
        # the reference's built-in NaN guard (_nan_clip): zero NaNs,
        # then clamp — only when clip_gradient is set
        g = jnp.where(jnp.isnan(g), 0.0, g)
        g = jnp.clip(g, -clip, clip)
    m = mom * m_ref[:] - lr * (g + wd * w_ref[:])
    wo_ref[:] = w_ref[:] + m
    mo_ref[:] = m


def sgd_update(w, g, m, lr, mom, *, wd: float = 0.0, clip: float = 0.0,
               interpret: bool = False, br: int = 0):
    """One fused sgd step over an arbitrary-shape tensor.

    Returns ``(new_w, new_m)`` with ``w``'s shape/dtype.  ``lr``/``mom``
    are (traced) scalars already cast to ``w.dtype`` (the stock rule's
    spelling); ``wd``/``clip`` are static floats.  The tensor is
    flattened and padded to a ``(rows, 128)`` lane layout; ``br`` pins
    the row tile (tests), 0 tiles from the size
    (``conv_block.row_tiles``) — a whole 1000x1024 classifier weight is
    five 4 MB operands, past the scoped VMEM limit in one block.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    shape = w.shape
    n = int(w.size)
    br, rows = row_tiles(max(1, -(-n // _LANES)), br)
    total = rows * _LANES

    def lanes(a):
        f = a.reshape(-1)
        if total > n:
            f = jnp.pad(f, (0, total - n))
        return f.reshape(rows, _LANES)

    sc = lambda v: jnp.asarray(v, w.dtype).reshape(1, 1)  # noqa: E731
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vspec = pl.BlockSpec((br, _LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM)
    out = jax.ShapeDtypeStruct((rows, _LANES), w.dtype)
    w2, m2 = pl.pallas_call(
        functools.partial(_sgd_kernel, wd=float(wd), clip=float(clip)),
        grid=(rows // br,),
        in_specs=[smem, smem, vspec, vspec, vspec],
        out_specs=[vspec, vspec],
        out_shape=[out, out],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(sc(lr), sc(mom), lanes(w), lanes(g), lanes(m))
    return (w2.reshape(-1)[:n].reshape(shape),
            m2.reshape(-1)[:n].reshape(shape))


def probe(backend: str, w=None, updater=None, **_kw):
    """None when launchable, else the reject reason.  Only the sgd rule
    is fused (elementwise, single-state); lars/lamb need layer-global
    norms and adam/nag/rmsprop/adagrad stay on the stock path until
    they earn their own measured verdicts."""
    if updater is not None and getattr(updater, "type_name", "") != "sgd":
        return (f"updater {getattr(updater, 'type_name', '?')!r} not "
                "fused (sgd only)")
    if w is not None and w.dtype != jnp.float32:
        return f"master params must be f32, got {w.dtype}"
    return None
