"""The overlapped async train step: grouped backward + per-group
dispatch-ordered gradient exchange.

The synchronous fused step is ONE program: forward, backward, the
cross-replica gradient reduction and the updater math all inside a
single dispatch — nothing overlaps with anything outside it, and one
slow replica stalls the single collective everyone sits in.  This
module re-expresses the same math as a *dispatch pipeline*
(``async_overlap = 1``):

1. **grad program** — a ``shard_map`` over the data axis computes each
   shard's summed-loss gradient and returns the PER-SHARD partials,
   stacked on a sharded leading axis.  No cross-replica collective
   runs here at all (the compiled-HLO suite asserts no ``all-reduce``
   anywhere in the pipeline);
2. **per-group reduce programs** — one per gradient-exchange group
   (``groups.partition_groups``): ``all-gather`` over the data axis +
   the trace-time-unrolled ORDERED fold (``((g0+g1)+g2)+…`` — the same
   fold, in the same order, as the ``det_reduce`` synchronous step, so
   ``staleness = 0`` is bitwise-equal to it).  Groups are dispatched in
   REVERSE layer order — the order backward materializes gradients —
   so the exchange of the net's tail groups is in flight while the
   head groups' reduce/apply still queue;
3. **per-group apply programs** — the updater registry's math over one
   group's tensors, fed through the bounded-staleness
   Push/PullReq/PullWait buffers (``updater.AsyncUpdater``).

Every dispatch is asynchronous: the host never blocks inside a step,
and the device executes group k's apply while group k+1's reduction is
still exchanging — on a real accelerator that is backprop/exchange
overlap; on the CPU test mesh it is the same dependency graph, which
is what the parity suites pin.  The only fences are
:meth:`AsyncStepper.round_end` (the round boundary; also the
``mesh.replica`` fault site, so an injected straggler delay is paid
ONCE per round instead of once per step) and the hard re-sync barrier
every ``async_resync_period`` rounds, which drains the staleness
buffers first.

``async_overlap_fraction`` reports, per round, the fraction of wall
time the host was NOT blocked in a fence — the measurable overlap win.
"""

from __future__ import annotations

import time
from typing import List, Optional

import jax
import jax.numpy as jnp

from ...obs import events as obs_events
from ...obs.registry import registry as obs_registry
from .groups import group_param_counts, partition_groups, subtree
from .updater import AsyncUpdater


def _overlap_gauge():
    return obs_registry().gauge(
        "async_overlap_fraction",
        "Per-round fraction of wall time the host was not blocked in a "
        "device fence (1.0 = fully overlapped dispatch).",
    )


class AsyncStepper:
    """Owns the async-mode programs and drives one trainer's pipeline.

    Built lazily by ``NetTrainer`` at the first async update; dropped
    whenever the net/mesh/jit cache is rebuilt (programs close over
    both).  All math-bearing configuration (group partition, staleness,
    resync period) is read from the trainer's conf keys once, here.
    """

    def __init__(self, trainer) -> None:
        self.trainer = trainer
        self.groups = partition_groups(trainer.params,
                                       trainer.async_groups)
        self.resync_period = max(1, int(trainer.async_resync_period))
        self.updater = AsyncUpdater(
            trainer, self.groups, staleness=trainer.staleness,
            apply_fn=self._apply_fn)
        self._grad_prog = None
        self._reduce_progs: List[Optional[object]] = [None] * len(self.groups)
        self._apply_progs: List[Optional[object]] = [None] * len(self.groups)
        self._round_t0: Optional[float] = None
        self._blocked_s = 0.0
        self.last_overlap_fraction = 0.0
        obs_events.emit(
            "async.armed", groups=len(self.groups),
            staleness=self.updater.staleness,
            resync_period=self.resync_period,
            group_params=group_param_counts(trainer.params, self.groups))

    # ------------------------------------------------------------------
    # programs
    def _grad_fn(self):
        """Per-shard summed-loss gradients, stacked ``[n_data, ...]`` on
        a sharded leading axis — backward with NO cross-replica
        collective; the exchange belongs to the per-group reduces."""
        if self._grad_prog is not None:
            return self._grad_prog
        tr = self.trainer
        plan = tr.mesh_plan
        # the backward itself is the trainer's SHARED per-shard grad
        # closure — the det_reduce step traces the identical one, which
        # is what keeps the staleness=0 bitwise-parity contract honest
        per_shard_grad = tr._shard_grad_fn()
        from jax.sharding import PartitionSpec as P

        def per_shard(params, data, labels, mask, rng, epoch):
            g, loss, out = per_shard_grad(
                params, data, labels, mask, rng, epoch)
            gstack = jax.tree_util.tree_map(lambda x: x[None], g)
            return gstack, loss[None], out

        sm = jax.shard_map(
            per_shard, mesh=plan.mesh,
            in_specs=(P(), P("data"), P("data"), P("data"), P(), P()),
            out_specs=(P("data"), P("data"), P("data")),
            check_vma=False,
        )
        rep, dsh, _ = tr._sh()
        psh, _ = tr._param_sh()
        self._grad_prog = tr._jit(
            sm,
            (psh, dsh, dsh, dsh, rep, rep),
            (dsh, dsh, dsh),
            kind="train_async", data_arg=1,
        )
        return self._grad_prog

    def _reduce_fn(self, gid: int):
        """One group's cross-replica exchange: ``all-gather`` over the
        data axis + the ordered fold — the det_reduce fold, scoped to
        this group's tensors, as its OWN dispatch."""
        if self._reduce_progs[gid] is not None:
            return self._reduce_progs[gid]
        tr = self.trainer
        plan = tr.mesh_plan
        n = plan.n_data
        from jax.sharding import PartitionSpec as P

        def per_shard(gsub):
            def fold(x):
                parts = jax.lax.all_gather(x, "data")  # (n, 1, *shape)
                acc = parts[0][0]
                for i in range(1, n):
                    acc = acc + parts[i][0]
                return acc

            return jax.tree_util.tree_map(fold, gsub)

        sm = jax.shard_map(
            per_shard, mesh=plan.mesh,
            in_specs=(P("data"),), out_specs=P(),
            check_vma=False,
        )
        rep, dsh, _ = tr._sh()
        # no donation: the sharded partial stack cannot alias the
        # replicated fold output (XLA would warn every compile); the
        # stacks are gradient-sized transients and die on their own
        self._reduce_progs[gid] = tr._jit(
            sm, (dsh,), rep,
            kind="async_reduce",
        )
        return self._reduce_progs[gid]

    def _apply_fn(self, gid: int):
        """One group's updater math (the existing registry, unchanged),
        donated so the old weight buffers die with the apply."""
        if self._apply_progs[gid] is not None:
            return self._apply_progs[gid]
        tr = self.trainer
        updaters = dict(tr.updaters)
        apply_updates = tr._apply_updates

        def f(psub, usub, gsub, epoch):
            return apply_updates(updaters, psub, usub, gsub, epoch,
                                 gspec=None)

        rep = tr._sh()[0]
        self._apply_progs[gid] = tr._jit(
            f, (rep, rep, rep, rep), (rep, rep),
            donate_argnums=(0, 1),
            kind="async_apply",
        )
        return self._apply_progs[gid]

    # ------------------------------------------------------------------
    def step(self, data, labels, mask, rng, epoch):
        """One async train step: dispatch backward, then each group's
        reduce → push → pull_req, reverse layer order.  Returns
        ``(per_shard_losses, out_rows)`` — both still device-async."""
        if self._round_t0 is None:
            self._round_t0 = time.perf_counter()
            self._blocked_s = 0.0
        tr = self.trainer
        gstack, losses, out = self._grad_fn()(
            tr.params, data, labels, mask, rng,
            jnp.asarray(epoch, jnp.int32))
        ep = int(epoch)
        # reverse layer order: backward materializes the tail groups'
        # gradients first, so their exchange dispatches first and is in
        # flight while the earlier groups' reduce/apply still queue
        for gid in range(len(self.groups) - 1, -1, -1):
            reduced = self._reduce_fn(gid)(
                subtree(gstack, self.groups[gid]))
            self.updater.push(gid, reduced, ep)
            self.updater.pull_req(gid)
        return losses, out

    def add_blocked(self, dt: float) -> None:
        """Host-blocking time spent OUTSIDE the stepper — the trainer's
        opt-in per-step fetches (divergence guard, train metrics) fence
        the pipeline too, and must count against the round's overlap
        fraction or the gauge would report ~1.0 for an effectively
        synchronous run."""
        if self._round_t0 is not None:
            self._blocked_s += dt

    def round_end(self, round_: int) -> bool:
        """Round-boundary fence; every ``async_resync_period`` rounds it
        is the HARD re-sync barrier (staleness buffers drained first,
        so weights catch up to every pushed gradient).  Returns True
        when this boundary resynced.  The fence goes through
        ``NetTrainer.sync`` — the ``mesh.replica`` fault site — so an
        injected straggler delay lands once per round here, not once
        per step."""
        resync = (round_ % self.resync_period) == 0
        drained = self.updater.drain() if resync else 0
        t0 = time.perf_counter()
        self.trainer.sync()
        self._blocked_s += time.perf_counter() - t0
        now = time.perf_counter()
        wall = (now - self._round_t0) if self._round_t0 else 0.0
        frac = max(0.0, 1.0 - self._blocked_s / wall) if wall > 0 else 0.0
        self.last_overlap_fraction = frac
        try:
            _overlap_gauge().set(frac)
        except Exception:  # noqa: BLE001 - telemetry never aborts
            pass
        if resync:
            obs_events.emit("async.resync", round=round_,
                            drained=drained,
                            overlap_fraction=round(frac, 4))
        self._round_t0 = None
        return resync

    def snapshot(self) -> dict:
        d = self.updater.snapshot()
        d["overlap_fraction"] = round(self.last_overlap_fraction, 4)
        d["resync_period"] = self.resync_period
        return d
