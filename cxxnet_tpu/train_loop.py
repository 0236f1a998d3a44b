"""The round loop: one round's feed, from the train iterator to the fence.

    iterator --next--> batch --copy--> ChunkAssembler --stack--> [K,B,...]
        --update_scan--> step program --fence--> timer

``scan_steps`` batches are copied once each into a recycled host block
(io/chunk.py) and run as ONE device program (``NetTrainer.update_scan``,
a ``lax.scan`` over the fused step): host dispatch cost is per program,
not per step.  What cannot be scanned — the trainer says what
(``scan_refusal``), and a padded tail batch — goes through ``update``
one batch at a time, after the open chunk, so update order is kept.

Every scan dispatch is asynchronous: the device chews chunk k while the
host decodes, copies and uploads chunk k+1 (the reference's two-stage
ThreadBuffer overlap, here through XLA's dispatch queue), and chunk k
is fenced only after k+1 is dispatched — at most two chunks in flight,
so host and device memory stay bounded.  With ``eval_train = 1`` the
chunk's train-metric sums are device arrays the trainer keeps pending;
they are collected at the chunk's fence, oldest first, so the
accumulators see the chunks in the order they were trained.  Before a
batch goes through ``update`` and before the round returns, everything
in flight is fenced, and so collected: a round's printed train metrics
hold exactly the rows it trained.

A fence ends a ``chunk`` stage (the fence-to-fence period that ``next``
/ ``copy`` / ``stack`` and the fence's ``device_wait`` here and ``h2d``
/ ``dispatch`` / ``metric`` in the trainer tile; benchmarks/lib/window.py
takes its edges from the same fences) and is one ``timer.add(dt, n_steps)``, in
:meth:`RoundLoop._lap` and nowhere else.

The same fences bill the DEVICE's time, untraced (doc/observability.md
has the table).  A chunk dispatched while its predecessor ran starts
when the predecessor ends, so where the loop blocked at both fences the
time between the two returns of ``block_until_ready`` is the step
program's run: stage ``run`` (and whatever the device waited in it for
the chunk's rows, where an upload outlasts the run before it: a host
cannot tell the two apart, a trace can).  A chunk dispatched onto an empty device —
a round's first, one after a drain, or one whose predecessor had landed
before the dispatch (counted ``chunks_starved``) — is the exposed tail
of its upload plus its run, from the dispatch's return to its fence:
``run_exposed``.  A chunk that had landed before the loop asked
(``chunks_late``) has no known end: it bills neither, and nor does its
successor.  ``head`` is the host's feed of a round's first dispatch,
with the chip empty, and ``boundary`` what lies between a round's last
fence and the next round's :meth:`RoundLoop.begin`, billed to that next
round.  The spans ``train.round`` (``begin`` to the last fence) and
``train.boundary`` tile the loop's thread and never overlap.

This module knows the trainer by its public methods only and nothing of
the CLI: ``cli.LearnTask._train_one_round`` is the round's entry and
exit, and calls in here for what lies between.
"""

from __future__ import annotations

import time
from typing import Callable, List, Optional, Sequence, Tuple

import jax

from .io.chunk import ChunkAssembler
from .io.data import DataBatch
from .obs import device as obs_device
from .utils.profiler import pipeline_stats, stage


def _landed(handle) -> bool:
    """Whether every leaf of a dispatched chunk's handle is ready; a
    leaf that is no device array (a host value) is."""
    return all(leaf.is_ready() for leaf in jax.tree_util.tree_leaves(handle)
               if hasattr(leaf, "is_ready"))


class RoundLoop:
    """One per task, kept across rounds: the assembler's blocks outlive
    a round, ``global_step`` counts the task's steps, ``first_fence_at``
    is the ``perf_counter`` of the task's first fence.

    A round is ``begin(trainer)`` — its first chunk's period starts
    there, so the caller's rewind of the iterator lies inside it — and
    ``run(itr, timer, ...)``, which leaves ``boundary`` open for the
    next ``begin`` to bill; :meth:`close` where no round follows, or
    one raised.  ``update_scan`` / ``update`` / ``sync`` are looked up
    on the trainer at each call: whoever replaced one on the instance
    is called."""

    def __init__(self, scan_steps: int, test_io: bool = False) -> None:
        self.scan_steps = int(scan_steps)
        self.test_io = bool(test_io)  # pull batches, train nothing
        self.chunks = ChunkAssembler(self.scan_steps)
        self.global_step = 0
        self.first_fence_at: Optional[float] = None
        # the round in progress
        self.trainer = None
        self.timer = None
        self.tracers: Sequence = ()
        # (handle, n_steps, the dispatch's return, onto an empty device)
        self.in_flight: List[Tuple[object, int, float, bool]] = []
        # the open stages, outermost first: ``round`` or ``boundary``,
        # and inside a round ``chunk``, and ``head`` inside its first
        self.round = self.boundary = self.chunk = self.head = None
        self.pipe_mark = 0.0    # the last fence
        self.ran_until: Optional[float] = None  # the last fenced chunk's
        # end, where the loop saw it: None after one that had landed

    def begin(self, trainer, round_no: int = 0) -> None:
        """Open the round, its first chunk's period and its head; the
        boundary behind the last round ends here, billed to this one
        (the caller has reset the round's stages by now)."""
        if self.boundary is not None:
            self.boundary.end()
            self.boundary = None
        self.trainer = trainer
        self.chunks.reset()
        self.in_flight = []
        self.ran_until = None
        step = trainer.epoch_counter
        self.round = stage("round", round=round_no).begin()
        self.chunk = stage("chunk", step=step).begin()
        self.head = stage("head", step=step).begin()

    def close(self) -> None:
        """Close the spans the loop holds open, innermost first, and
        bill none: the round's at its last fence, a round's that
        raised, the task's last boundary."""
        for name in ("head", "chunk", "round", "boundary"):
            held = getattr(self, name)
            if held is not None:
                held.drop()
                setattr(self, name, None)

    def run(self, itr, timer, tracers: Sequence = (),
            on_batch: Optional[Callable[[int], bool]] = None,
            ) -> Tuple[int, bool]:
        """Feed the round's batches; returns ``(batches taken, stopped
        early)``.  ``tracers`` each get ``.step(global_step)`` before a
        dispatch.  ``on_batch(n)`` is called at every batch boundary and
        stops the round there by returning True: no further batch is
        taken, the open chunk is trained and everything in flight is
        fenced before this returns."""
        trainer = self.trainer
        self.timer, self.tracers = timer, tracers
        self.pipe_mark = time.perf_counter()
        # multi-process scan is safe: sharded train iterators run equal
        # batch counts per round (equal-steps contract), so every
        # process flushes identical [K, ...] stacks at the same points
        scan = (not self.test_io and self.scan_steps > 1
                and trainer.scan_refusal() is None)
        n_batches, stopped = 0, False
        while not stopped:
            with stage("next", step=trainer.epoch_counter) as st:
                more = itr.next()
                batch = itr.value() if more and not self.test_io else None
                st.rows = trainer.batch_size if more else 0
            if not more:
                break
            if batch is None:
                pass  # test_io: the pull was the work
            elif scan and not batch.num_batch_padd:
                # the one copy: iterator buffers are reused by next()
                with stage("copy", rows=trainer.batch_size,
                           step=trainer.epoch_counter):
                    self.chunks.add(batch.data, batch.label)
                if len(self.chunks) >= self.scan_steps:
                    self._flush()
            else:
                self._step(batch)
            n_batches += 1
            stopped = on_batch is not None and bool(on_batch(n_batches))
        self._flush()  # tail chunk shorter than scan_steps
        self._fence(drain_all=True)  # round boundary: the queue is empty
        # what follows the last fence is in no chunk and not the round's
        # span: it is the boundary, which the next begin() bills
        self.close()
        self.boundary = stage("boundary", step=trainer.epoch_counter).begin()
        return n_batches, stopped

    # ------------------------------------------------------------------
    def _lap(self, n_steps: int, since: Optional[float] = None) -> None:
        """A fence: bill the chunk that ends here, open the next and
        give the timer its span — the one place the timer is written,
        and so the one place that says what it records.  ``since=None``
        is fence to fence (every scanned chunk, the drain and a tail of
        one batch): decode, copy, dispatch and device wait of one chunk,
        so the laps tile the round's wall time and samples/sec is the
        PIPELINE rate.  ``since=t`` is the per-batch path's own span
        from ``t`` (``update`` + ``sync``): the step time, with the
        host's feed before ``t`` in no span."""
        trainer = self.trainer
        self.chunk.end(rows=n_steps * trainer.batch_size)
        self.chunk = stage("chunk", step=trainer.epoch_counter).begin()
        now = time.perf_counter()
        if self.first_fence_at is None:
            self.first_fence_at = now
        self.timer.add(now - (self.pipe_mark if since is None else since),
                       n_steps)
        self.pipe_mark = now

    def _fence(self, drain_all: bool) -> None:
        """Block on dispatched chunks, oldest first, a lap each, and
        have the trainer add each one's train-metric sums there.
        ``drain_all=False`` keeps the newest running — the double
        buffer: chunk k lands only after k+1 is dispatched, and is
        counted in ``chunks_overlapped`` of ``chunks_fenced``."""
        trainer = self.trainer
        stats = pipeline_stats()
        while len(self.in_flight) > (0 if drain_all else 1):
            handle, n, sent, onto_empty = self.in_flight.pop(0)
            late = _landed(handle)  # before the host asked: end unknown
            with stage("device_wait", rows=n * trainer.batch_size,
                       step=trainer.epoch_counter):
                jax.block_until_ready(handle)
            self._bill_run(n, sent, onto_empty, late, time.perf_counter())
            trainer.collect_scan_metrics()
            stats.count("chunks_fenced")
            if self.in_flight:
                stats.count("chunks_overlapped")
            self._lap(n)

    def _bill_run(self, n_steps: int, sent: float, onto_empty: bool,
                  late: bool, done: float) -> None:
        """The device's time for the chunk whose fence returned at
        ``done``, from the loop's own stamps (the module's docstring
        says which interval is what); a ``run`` is also one
        observation of ``train_step_device_seconds``, per step."""
        stats = pipeline_stats()
        rows = n_steps * self.trainer.batch_size
        if late:
            stats.count("chunks_late")
        elif onto_empty:
            stats.add("run_exposed", done - sent, rows)
        elif self.ran_until is not None:
            stats.add("run", done - self.ran_until, rows)
            obs_device.observe_step((done - self.ran_until) / n_steps)
        self.ran_until = None if late else done

    def _end_head(self) -> None:
        """The round's first dispatch has returned."""
        if self.head is not None:
            self.head.end()
            self.head = None

    def _mark_step(self) -> None:
        for tracer in self.tracers:
            tracer.step(self.global_step)

    def _flush(self) -> None:
        """The open chunk as one device program.  The chunk is the
        assembler's block as it stands: ``copy`` put each batch in its
        slot, ``stack`` only closes it (a short tail is a leading
        slice), and ``update_scan`` gets the very bytes ``np.stack`` of
        the batches would hold.  A one-batch tail goes through
        ``update``: a scan of one step would be a program of its own."""
        n = len(self.chunks)
        if not n:
            return
        trainer = self.trainer
        self._mark_step()
        with stage("stack", rows=n * trainer.batch_size,
                   step=trainer.epoch_counter):
            data, labels = self.chunks.take()
        if n == 1:
            # update() fetches or syncs: what is in flight lands first,
            # with its sums, and keeps its own lap
            self._fence(drain_all=True)
            self._update(DataBatch(data=data[0], label=labels[0]), None)
        else:
            # mid-round the chunk before this one should still be
            # running: if it has landed, the device starved
            starved = bool(self.in_flight) and _landed(self.in_flight[-1][0])
            onto_empty = starved or not self.in_flight
            handle = trainer.update_scan(
                data, labels, sync=False,
                # sharded iterators guarantee equal K per process — skip
                # the collective K-check so the overlap stays unbroken
                check_steps=False)
            self.in_flight.append(
                (handle, n, time.perf_counter(), onto_empty))
            self._end_head()
            stats = pipeline_stats()
            stats.count("chunks_dispatched")
            if starved:
                stats.count("chunks_starved")
            self._fence(drain_all=False)
        self.global_step += n

    def _step(self, batch) -> None:
        """One batch through ``update``: the only path for
        ``update_period > 1``, extras, node-bound train metrics, the
        async stepper and a padded tail batch."""
        self._flush()  # keep update order
        # or update()'s sync would fence leftovers inside the timed
        # span, and score this batch before their sums are collected
        self._fence(drain_all=True)
        self._mark_step()
        self._update(batch, time.perf_counter())
        self.global_step += 1

    def _update(self, batch, since: Optional[float]) -> None:
        """``update`` and its fence: a ``sync`` here, unless the step
        fetched its metrics (``eval_train``) or the trainer fences at
        the round's end (the async stepper's dispatches run free until
        ``async_round_end``)."""
        trainer = self.trainer
        trainer.update(batch)
        self._end_head()
        if not trainer.eval_train and not trainer.fence_at_round_end:
            with stage("device_wait", rows=trainer.batch_size,
                       step=trainer.epoch_counter):
                trainer.sync()
        self._lap(1, since)
