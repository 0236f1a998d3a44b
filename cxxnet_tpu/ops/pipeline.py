"""GPipe-style pipeline parallelism over a mesh axis.

New TPU-first scope (the reference has no pipeline parallelism, SURVEY
§2.8).  The scaling-book recipe: stage ``s`` of ``S`` (one per device on
the pipeline mesh axis) owns the parameters of layers ``[s*L/S,
(s+1)*L/S)``; microbatches march through the stages, activations hop to
the next device with ``lax.ppermute`` each tick, and the whole schedule
is one ``lax.scan`` of ``T + S - 1`` ticks inside the SPMD program —
bubble fraction ``(S-1)/(T+S-1)``.

The primitive operates on a *homogeneous block stack*: ``block_fn(params,
x) -> y`` applied ``L`` times with stacked params (leading dim ``L``).
Stage-local sub-stacks run under ``lax.scan`` so each tick does its
``L/S`` blocks.  The trainer-facing wrapper below shards the stacked
params over the pipeline axis; everything differentiates with ``jax.grad``
(the backward schedule is the transposed pipeline, derived by autodiff).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax


def _stage_apply(block_fn: Callable, stage_params, x):
    """Run this stage's L/S blocks sequentially on one activation."""

    def body(h, p):
        return block_fn(p, h), None

    y, _ = lax.scan(body, x, stage_params)
    return y


def gpipe(
    block_fn: Callable,
    stage_params,
    x_mb: jnp.ndarray,
    *,
    axis_name: str,
) -> jnp.ndarray:
    """Pipelined application of the full block stack.

    Call under ``shard_map``: ``stage_params`` is this device's
    ``(L/S, ...)`` parameter sub-stack (the global ``(L, ...)`` stack
    sharded on ``axis_name``); ``x_mb`` is ``(T, mb, ...)`` microbatches,
    replicated.  Stage 0 feeds microbatches in, activations hop stages on
    a ``ppermute`` ring each tick, the last stage stores results, and a
    final ``psum`` replicates the output buffer (other stages contribute
    zeros).  ``T + S - 1`` ticks total — bubble ``(S-1)/(T+S-1)``."""
    s = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    t = x_mb.shape[0]
    n_tick = t + s - 1
    perm = [(i, (i + 1) % s) for i in range(s)]

    out0 = jnp.zeros_like(x_mb)
    reg0 = jnp.zeros_like(x_mb[0])

    def tick(carry, k):
        reg, out = carry
        mb_idx = jnp.clip(k, 0, t - 1)
        reg = jnp.where(idx == 0, x_mb[mb_idx], reg)
        y = _stage_apply(block_fn, stage_params, reg)
        done_idx = jnp.clip(k - (s - 1), 0, t - 1)
        store = jnp.logical_and(idx == s - 1, k >= s - 1)
        out = out.at[done_idx].set(jnp.where(store, y, out[done_idx]))
        reg = lax.ppermute(y, axis_name, perm)
        return (reg, out), None

    (_, out), _ = lax.scan(tick, (reg0, out0), jnp.arange(n_tick))
    return lax.psum(out, axis_name)


def pipeline_apply(
    block_fn: Callable,
    params_stacked,
    x: jnp.ndarray,
    mesh,
    *,
    n_microbatch: int,
    stage_axis: str = "model",
    data_axis: str = "data",
):
    """Trainer-facing wrapper: global ``(L, ...)`` param stack, global
    ``(B, ...)`` batch → pipelined ``block_fn^L`` application.

    The batch splits into ``n_microbatch`` microbatches; params shard
    over ``stage_axis``; output layout matches the input batch.
    """
    from jax.sharding import PartitionSpec as P

    b = x.shape[0]
    if b % n_microbatch != 0:
        raise ValueError(
            f"batch {b} must divide into {n_microbatch} microbatches"
        )
    n_stage = mesh.shape[stage_axis]
    l = jax.tree_util.tree_leaves(params_stacked)[0].shape[0]
    if l % n_stage != 0:
        raise ValueError(f"{l} blocks must divide over {n_stage} stages")
    mb = b // n_microbatch
    x_mb = x.reshape((n_microbatch, mb) + x.shape[1:])

    # keep each data replica on its own microbatch rows (no redundant
    # recompute across the data axis); replicate only when indivisible
    n_data = mesh.shape.get(data_axis, 1) if hasattr(mesh.shape, "get") \
        else dict(mesh.shape)[data_axis]
    if data_axis in mesh.axis_names and mb % n_data == 0 and n_data > 1:
        row_spec = P(None, data_axis)
    else:
        row_spec = P()

    pspec = jax.tree_util.tree_map(
        lambda v: P(stage_axis, *([None] * (v.ndim - 1))), params_stacked
    )
    out = jax.shard_map(
        functools.partial(gpipe, block_fn, axis_name=stage_axis),
        mesh=mesh, in_specs=(pspec, row_spec), out_specs=row_spec,
        check_vma=False,  # ppermute under scan confuses the checker
    )(params_stacked, x_mb)
    return out.reshape((b,) + out.shape[2:])
