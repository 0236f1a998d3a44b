"""The round loop (cxxnet_tpu/train_loop.py) on its own: a fake trainer
that records its calls, a list for an iterator, a timer that records
its fences.  No conf, no files, no program — what is pinned here is the
ORDER of calls and what each fence is told; that the surviving paths
train the same weights is ``test_cli.py::test_scan_steps_trains_identically``
and ``test_chunk.py::test_cli_round_trains_what_the_stack_by_hand_trains``.
"""

import time

import numpy as np
import pytest

from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.train_loop import RoundLoop
from cxxnet_tpu.utils.profiler import pipeline_stats

B = 4  # rows a batch


class FakeTrainer:
    """The trainer as the loop sees it.  Every method call lands in the
    shared ``log``; attribute reads do not."""

    def __init__(self, log, eval_train=1, refusal=None,
                 fence_at_round_end=False):
        self.log = log
        self.batch_size = B
        self.epoch_counter = 0
        self.eval_train = eval_train
        self.fence_at_round_end = fence_at_round_end
        self._refusal = refusal
        self.pending = []  # chunks whose sums nobody collected yet

    def scan_refusal(self):
        self.log.append(("scan_refusal",))
        return self._refusal

    def update_scan(self, data, labels, sync=True, check_steps=True):
        # values, not views: the block is the assembler's to recycle
        self.log.append(("update_scan", np.array(data), np.array(labels),
                         sync, check_steps))
        self.epoch_counter += len(data)
        if self.eval_train:
            self.pending.append([int(d[0, 0]) for d in data])
        return np.zeros(len(data), np.float32)

    def collect_scan_metrics(self, all_pending=False):
        # like the real one: the oldest pending chunk's sums, or nothing
        assert not all_pending  # a fence collects its own chunk only
        if self.pending:
            self.log.append(("collect", self.pending.pop(0)))

    def update(self, batch):
        self.log.append(("update", np.array(batch.data),
                         batch.num_batch_padd, len(self.pending)))
        self.epoch_counter += 1

    def sync(self):
        self.log.append(("sync",))


class ListIter:
    """``next`` / ``value`` over a list; like a real iterator it hands
    out ONE buffer and overwrites it at every ``next``."""

    def __init__(self, batches):
        self.batches, self.at = batches, -1
        self.buf = np.zeros((B, 3), np.float32)
        self.lab = np.zeros((B, 1), np.float32)

    def next(self):
        self.at += 1
        if self.at >= len(self.batches):
            return False
        value, padd = self.batches[self.at]
        self.buf[:] = value
        self.lab[:] = value
        self.cur = DataBatch(data=self.buf, label=self.lab,
                             num_batch_padd=padd)
        return True

    def value(self):
        return self.cur


class Timer:
    """What ``StepTimer`` is to the loop: ``add`` at every fence."""

    def __init__(self, log):
        self.log, self.laps = log, []

    def add(self, dt, n_steps=1):
        self.laps.append((dt, n_steps))
        self.log.append(("fence", n_steps))


class Tracer:
    def __init__(self):
        self.steps = []

    def step(self, g):
        self.steps.append(g)


def run_round(n_batches, scan_steps=4, padded=(), on_batch=None,
              test_io=False, **trainer_kw):
    """One round over batches whose every value is their index."""
    log = []
    tr = FakeTrainer(log, **trainer_kw)
    it = ListIter([(i, 1 if i in padded else 0) for i in range(n_batches)])
    timer, tracer = Timer(log), Tracer()
    loop = RoundLoop(scan_steps, test_io=test_io)
    pipeline_stats().reset()
    loop.begin(tr)
    got = loop.run(it, timer, (tracer,), on_batch)
    return loop, log, timer, tracer, got


def calls(log, *names):
    return [e for e in log if e[0] in names]


def names(log):
    """The order of the calls that train or fence."""
    return [e[0] for e in log if e[0] not in ("scan_refusal", "collect")]


def batch_values(entry):
    """The batch indices an ``update_scan`` / ``update`` entry trained."""
    data = entry[1]
    return ([int(data[0, 0])] if entry[0] == "update"
            else [int(d[0, 0]) for d in data])


# ----------------------------------------------------------------------
def test_k_full_batches_make_one_scan_and_one_fence_of_k_steps():
    loop, log, timer, tracer, got = run_round(4, scan_steps=4)
    assert got == (4, False)
    assert [e[0] for e in log] == ["scan_refusal", "update_scan", "collect",
                                   "fence"]
    _, data, labels, sync, check_steps = log[1]
    assert data.shape == (4, B, 3) and labels.shape == (4, B, 1)
    # each slot holds its own batch, though the iterator reused its buffer
    assert batch_values(log[1]) == [0, 1, 2, 3]
    # never drained inside the call, whatever eval_train says
    assert sync is False and check_steps is False
    assert timer.laps[0][1] == 4
    assert loop.global_step == 4 and tracer.steps == [0]
    assert loop.first_fence_at is not None
    st = pipeline_stats().snapshot()
    assert st["chunk"]["count"] == 1 and st["chunk"]["rows"] == 4 * B
    assert st["copy"]["count"] == 4 and st["stack"]["count"] == 1
    assert st["next"]["count"] == 5  # the fifth found the end


def test_a_tail_shorter_than_scan_steps_is_a_shorter_scan():
    loop, log, timer, tracer, got = run_round(7, scan_steps=4)
    scans = calls(log, "update_scan")
    assert [batch_values(e) for e in scans] == [[0, 1, 2, 3], [4, 5, 6]]
    assert [n for _, n in timer.laps] == [4, 3]
    assert not calls(log, "update")
    assert loop.global_step == 7 and tracer.steps == [0, 4]


@pytest.mark.parametrize("eval_train", [1, 0])
def test_a_tail_of_one_batch_goes_through_update(eval_train):
    loop, log, timer, _, _ = run_round(5, scan_steps=4,
                                       eval_train=eval_train)
    # the first chunk is fenced before update(), which fetches (its
    # metrics) or syncs anyway
    assert names(log) == (
        ["update_scan", "fence", "update", "fence"] if eval_train else
        ["update_scan", "fence", "update", "sync", "fence"])
    assert batch_values(calls(log, "update")[0]) == [4]
    assert [n for _, n in timer.laps] == [4, 1]
    assert loop.global_step == 5


def test_a_padded_batch_flushes_the_open_chunk_first():
    loop, log, timer, _, _ = run_round(6, scan_steps=4, padded={2})
    trained = [(e[0], batch_values(e))
               for e in calls(log, "update_scan", "update")]
    assert trained == [("update_scan", [0, 1]), ("update", [2]),
                       ("update_scan", [3, 4, 5])]
    assert calls(log, "update")[0][2] == 1  # handed on with its padding
    assert [n for _, n in timer.laps] == [2, 1, 3]
    assert loop.global_step == 6


@pytest.mark.parametrize("eval_train", [1, 0])
def test_async_chunks_fence_k_only_after_k_plus_1_is_dispatched(eval_train):
    loop, log, timer, _, _ = run_round(12, scan_steps=4,
                                       eval_train=eval_train)
    assert all(e[3] is False for e in calls(log, "update_scan"))
    in_flight = deepest = 0
    for e in log:
        in_flight += {"update_scan": 1, "fence": -1}.get(e[0], 0)
        deepest = max(deepest, in_flight)
    assert deepest == 2 and in_flight == 0
    assert names(log) == [
        "update_scan", "update_scan", "fence", "update_scan", "fence",
        "fence"]
    assert not loop.in_flight and not calls(log, "sync")
    # two of the three fences found a later chunk already dispatched
    counters = pipeline_stats().counters()
    assert counters["chunks_fenced"] == 3
    assert counters["chunks_overlapped"] == 2


@pytest.mark.parametrize("eval_train", [1, 0])
def test_the_laps_and_the_drain_tile_the_round(eval_train):
    log = []
    tr = FakeTrainer(log, eval_train=eval_train)
    it = ListIter([(i, 0) for i in range(10)])
    timer = Timer(log)
    loop = RoundLoop(4)
    loop.begin(tr)
    t0 = time.perf_counter()
    loop.run(it, timer)
    wall = time.perf_counter() - t0
    assert [n for _, n in timer.laps] == [4, 4, 2]
    covered = sum(dt for dt, _ in timer.laps)
    assert 0 < covered <= wall
    assert wall - covered < 0.05  # what is before run()'s mark and after


@pytest.mark.parametrize("eval_train", [1, 0])
def test_a_stop_request_trains_the_open_chunk_drains_and_says_so(eval_train):
    taken = []

    def on_batch(n):
        taken.append(n)
        return n == 6

    loop, log, timer, _, got = run_round(12, scan_steps=4,
                                         eval_train=eval_train,
                                         on_batch=on_batch)
    assert got == (6, True) and taken == [1, 2, 3, 4, 5, 6]
    assert [batch_values(e) for e in calls(log, "update_scan")] == [
        [0, 1, 2, 3], [4, 5]]
    assert [n for _, n in timer.laps] == [4, 2] and not loop.in_flight
    assert loop.global_step == 6
    # the request found chunk 1 in flight: chunk 2 is dispatched behind
    # it, then both are fenced, and collected, before run() returns
    assert names(log) == ["update_scan", "update_scan", "fence", "fence"]
    assert loop.trainer.pending == []
    assert [e[1] for e in calls(log, "collect")] == (
        [[0, 1, 2, 3], [4, 5]] if eval_train else [])


def test_the_sums_are_collected_once_a_chunk_at_its_fence_in_order():
    _, log, _, _, _ = run_round(14, scan_steps=4)
    # a chunk's sums are asked for after the NEXT chunk's dispatch,
    # straight before its own lap, and nowhere else
    assert [e[0] for e in log if e[0] != "scan_refusal"] == [
        "update_scan", "update_scan", "collect", "fence",
        "update_scan", "collect", "fence",
        "update_scan", "collect", "fence", "collect", "fence"]
    assert [e[1] for e in calls(log, "collect")] == [
        [0, 1, 2, 3], [4, 5, 6, 7], [8, 9, 10, 11], [12, 13]]
    counters = pipeline_stats().counters()
    assert (counters["chunks_fenced"], counters["chunks_overlapped"]) == (4, 3)


def test_eval_train_0_leaves_nothing_to_collect():
    loop, log, _, _, _ = run_round(12, scan_steps=4, eval_train=0)
    assert not calls(log, "collect") and loop.trainer.pending == []
    assert pipeline_stats().counters()["chunks_fenced"] == 3


@pytest.mark.parametrize("n_batches,padded,scanned", [
    (9, (), [[0, 1, 2, 3], [4, 5, 6, 7]]),       # a tail of one batch
    (10, {9}, [[0, 1, 2, 3], [4, 5, 6, 7], [8]]),  # a padded last batch:
    # the open chunk of one batch goes through update() too
    (11, {6}, [[0, 1, 2, 3], [4, 5], [7, 8, 9, 10]]),  # padded mid-round
])
def test_every_pending_sum_is_collected_before_update_is_called(
        n_batches, padded, scanned):
    loop, log, timer, _, _ = run_round(n_batches, scan_steps=4,
                                       padded=padded)
    chunks = [c for c in scanned if len(c) > 1]
    assert [batch_values(e) for e in calls(log, "update_scan")] == chunks
    assert [e[1] for e in calls(log, "collect")] == chunks
    updates = calls(log, "update")
    assert updates and all(e[3] == 0 for e in updates)  # none pending
    # and every chunk trained before an update() was collected before it
    for i, e in enumerate(log):
        before = [x[0] for x in log[:i]]
        assert e[0] != "update" or (
            before.count("collect") == before.count("update_scan"))
    assert loop.trainer.pending == [] and not loop.in_flight
    assert sum(n for _, n in timer.laps) == n_batches


def test_the_round_returns_with_every_sum_collected():
    loop, log, _, _, _ = run_round(8, scan_steps=4)
    assert log[-2][0] == "collect" and log[-1] == ("fence", 4)
    assert loop.trainer.pending == []


@pytest.mark.parametrize("eval_train,at_round_end,syncs", [
    (1, False, 0),  # the step fetched its metrics: fenced already
    (0, False, 3),
    (0, True, 0),   # the async stepper: async_round_end fences
])
def test_a_refusal_sends_every_batch_through_update(eval_train,
                                                    at_round_end, syncs):
    loop, log, timer, tracer, _ = run_round(
        3, scan_steps=4, eval_train=eval_train,
        refusal="update_scan requires update_period == 1",
        fence_at_round_end=at_round_end)
    assert not calls(log, "update_scan")
    assert [batch_values(e) for e in calls(log, "update")] == [[0], [1], [2]]
    assert len(calls(log, "sync")) == syncs
    assert [n for _, n in timer.laps] == [1, 1, 1]
    assert tracer.steps == [0, 1, 2] and len(loop.chunks) == 0
    assert pipeline_stats().snapshot()["copy"]["count"] == 0


def test_scan_steps_1_never_asks_and_never_scans():
    _, log, timer, _, _ = run_round(3, scan_steps=1)
    assert [e[0] for e in log] == ["update", "fence"] * 3


def test_test_io_calls_no_trainer_method():
    loop, log, timer, tracer, got = run_round(5, scan_steps=4, test_io=True)
    assert got == (5, False)
    assert log == [] and timer.laps == [] and tracer.steps == []
    assert loop.global_step == 0 and loop.first_fence_at is None
    assert pipeline_stats().snapshot()["next"]["count"] == 6


def test_the_loop_outlives_a_round_and_recycles_its_block():
    log = []
    tr = FakeTrainer(log)
    loop = RoundLoop(4)
    for _ in range(3):
        loop.begin(tr)
        loop.run(ListIter([(i, 0) for i in range(4)]), Timer(log))
    # reset() zeroes the counts each round; the third round's one block
    # is the first round's, which nobody holds
    assert (loop.chunks.allocated, loop.chunks.recycled) == (0, 1)
    assert loop.global_step == 12


def test_a_method_replaced_on_the_instance_is_the_one_called():
    """benchmarks/run.py puts its own ``update_scan`` on the trainer
    after init(): the loop looks the name up at every call."""
    log = []
    tr = FakeTrainer(log)
    loop = RoundLoop(2)
    loop.begin(tr)
    inner, seen = tr.update_scan, []

    def update_scan(data, labels, **kw):
        seen.append(len(data))
        return inner(data, labels, **kw)

    tr.update_scan = update_scan
    loop.run(ListIter([(i, 0) for i in range(4)]), Timer(log))
    assert seen == [2, 2]


# ----------------------------------------------------------------------
# The device's time from the loop's own fences: a simulated clock that
# only the fakes move, so every stamp the loop takes is known exactly.
class Clock:
    """``perf_counter`` in train_loop's and the stage helper's place."""

    def __init__(self):
        self.now = 0.0

    def perf_counter(self):
        return self.now


class Handle:
    """What ``update_scan`` hands back: ready once the clock passes the
    chunk's end; blocking on it moves the clock there."""

    def __init__(self, clock, end):
        self.clock, self.end = clock, end

    def is_ready(self):
        return self.clock.now >= self.end

    def block_until_ready(self):
        self.clock.now = max(self.clock.now, self.end)
        return self


class SimTrainer(FakeTrainer):
    """A device that runs one chunk at a time: a dispatch costs the
    host ``DISPATCH``, a chunk sent onto an idle device waits ``UPLOAD``
    for its rows, then runs for the next of ``runs`` seconds."""

    DISPATCH, UPLOAD = 2.0, 30.0

    def __init__(self, log, clock, runs, **kw):
        super().__init__(log, **kw)
        self.clock, self.runs = clock, list(runs)
        self.free_at = 0.0
        self.sent, self.ends = [], []  # D_k and the chunks' true ends

    def update_scan(self, data, labels, sync=True, check_steps=True):
        super().update_scan(data, labels, sync, check_steps)
        self.clock.now += self.DISPATCH
        sent = self.clock.now
        start = self.free_at if self.free_at > sent else sent + self.UPLOAD
        self.free_at = start + self.runs.pop(0)
        self.sent.append(sent)
        self.ends.append(self.free_at)
        return Handle(self.clock, self.free_at)

    def update(self, batch):
        super().update(batch)
        self.clock.now += self.DISPATCH


class SlowIter(ListIter):
    """Every ``next`` costs the host ``feed`` seconds."""

    def __init__(self, batches, clock, feed):
        super().__init__(batches)
        self.clock, self.feed = clock, feed

    def next(self):
        self.clock.now += self.feed
        return super().next()


@pytest.fixture
def clock(monkeypatch):
    from cxxnet_tpu import train_loop
    from cxxnet_tpu.utils import profiler

    c = Clock()
    monkeypatch.setattr(train_loop, "time", c)
    monkeypatch.setattr(profiler, "time", c)
    return c


def sim_round(clock, n_batches, runs, feed=1.0, loop=None, **trainer_kw):
    log = []
    tr = SimTrainer(log, clock, runs, **trainer_kw)
    loop = loop or RoundLoop(4)
    pipeline_stats().reset()
    began = clock.now
    loop.begin(tr)
    loop.run(SlowIter([(i, 0) for i in range(n_batches)], clock, feed),
             Timer(log))
    st = pipeline_stats().snapshot()
    bills = {k: (int(st[k]["count"]), st[k]["total_s"], int(st[k]["rows"]))
             for k in ("head", "run", "run_exposed", "boundary")}
    return loop, tr, began, bills, pipeline_stats().counters()


def test_a_round_of_three_chunks_bills_a_head_an_exposed_run_and_two_runs(
        clock):
    clock.now = 1000.0
    _, tr, began, bills, counters = sim_round(clock, 12, [100.0] * 3)
    # the head: begin() to the first dispatch's return, the chip empty
    assert bills["head"] == (1, tr.sent[0] - began, 0)
    assert tr.sent[0] - began == 4 * 1.0 + SimTrainer.DISPATCH
    # chunk 1 went onto an empty device: upload's tail + run, from its
    # dispatch's return to its fence
    assert bills["run_exposed"] == (1, tr.ends[0] - tr.sent[0], 4 * B)
    assert tr.ends[0] - tr.sent[0] == SimTrainer.UPLOAD + 100.0
    # chunks 2 and 3 ran back to back with the one before: fence to fence
    assert bills["run"] == (
        2, (tr.ends[1] - tr.ends[0]) + (tr.ends[2] - tr.ends[1]), 8 * B)
    assert bills["run"][1] == 200.0
    assert bills["boundary"] == (0, 0.0, 0)  # a first round has none
    assert counters["chunks_dispatched"] == 3
    assert "chunks_starved" not in counters and "chunks_late" not in counters
    # head + exposed run + runs tile the round's chunk periods
    chunk_s = pipeline_stats().snapshot()["chunk"]["total_s"]
    assert chunk_s == pytest.approx(
        bills["head"][1] + bills["run_exposed"][1] + bills["run"][1])


def test_a_starved_chunk_counts_and_bills_an_exposed_run(clock):
    # the feed of a chunk (4 x 40) outlasts a chunk's run (100): every
    # chunk after the first is dispatched onto a device that ran dry,
    # and every chunk but the last has landed before its fence
    _, tr, _, bills, counters = sim_round(clock, 12, [100.0] * 3, feed=40.0)
    assert counters["chunks_dispatched"] == 3
    assert counters["chunks_starved"] == 2
    assert counters["chunks_late"] == 2
    assert bills["run"] == (0, 0.0, 0)
    # the last chunk, starved too, was still running at its fence
    assert bills["run_exposed"] == (1, tr.ends[2] - tr.sent[2], 4 * B)
    assert tr.ends[2] - tr.sent[2] == SimTrainer.UPLOAD + 100.0


def test_a_late_chunk_bills_no_run_and_poisons_its_successors(clock):
    # chunk 2 runs 5 s: still running when chunk 3 is dispatched behind
    # it, landed when the loop asks — its end is unknown, so neither it
    # nor chunk 3 (whose start is that end) has a run; chunk 4 has
    _, tr, _, bills, counters = sim_round(
        clock, 16, [100.0, 5.0, 100.0, 100.0])
    assert counters["chunks_dispatched"] == 4
    assert counters["chunks_late"] == 1
    assert "chunks_starved" not in counters
    assert bills["run_exposed"] == (1, tr.ends[0] - tr.sent[0], 4 * B)
    assert bills["run"] == (1, tr.ends[3] - tr.ends[2], 4 * B)
    assert bills["run"][1] == 100.0


def test_the_boundary_lands_in_the_next_rounds_record(clock):
    loop, _, _, bills, _ = sim_round(clock, 8, [100.0] * 2)
    assert bills["boundary"] == (0, 0.0, 0) and loop.boundary is not None
    ended = clock.now  # the round's last fence
    clock.now += 50.0  # metric line, evaluation, checkpoint, ...
    _, _, began, bills, _ = sim_round(clock, 8, [100.0] * 2, loop=loop)
    assert bills["boundary"] == (1, began - ended, 0)
    assert began - ended == 50.0
    assert bills["head"][0] == 1 and bills["run"][0] == 1
    # where no round follows, the open boundary is closed and not billed
    loop.close()
    assert loop.boundary is None
    assert pipeline_stats().snapshot()["boundary"]["count"] == 1


def test_the_per_batch_path_bills_a_head_and_no_run(clock):
    loop, tr, began, bills, counters = sim_round(
        clock, 3, [], eval_train=0,
        refusal="update_scan requires update_period == 1")
    # the head ends where the first update() returns
    assert bills["head"] == (1, 1.0 + SimTrainer.DISPATCH, 0)
    assert bills["run"] == bills["run_exposed"] == (0, 0.0, 0)
    assert "chunks_dispatched" not in counters
    assert loop.head is None and loop.chunk is None and loop.round is None


def test_a_round_that_dispatched_nothing_drops_its_head(clock):
    loop, _, _, bills, _ = sim_round(clock, 0, [])
    assert bills["head"] == (0, 0.0, 0) and loop.head is None


def test_every_run_is_one_observation_of_the_device_step(clock, monkeypatch):
    """``train_step_device_seconds``: the run / the chunk's steps, with
    no key set and no fence of the loop's own (test_device_obs.py holds
    the histogram's count against the runs billed)."""
    from cxxnet_tpu.obs import device as obs_device

    seen = []
    monkeypatch.setattr(obs_device, "observe_step", seen.append)
    sim_round(clock, 11, [100.0, 80.0, 60.0])
    assert seen == [80.0 / 4, 60.0 / 3]  # the tail chunk has three steps
