"""The chunk assembler (``io/chunk.py``): a chunk is ``np.stack`` of its
batches bit for bit, made with one copy of each into a block that is
written again only when nothing references the chunk handed out."""

import gc
import json

import numpy as np
import pytest

from cxxnet_tpu.io.chunk import ChunkAssembler

K = 4


def _batches(n, seed, dtype=np.float32, shape=(6, 5, 5, 3)):
    rng = np.random.default_rng(seed)
    return [((rng.random(shape) * 1000).astype(dtype),
             rng.integers(0, 10, (shape[0], 1)).astype(np.float32))
            for _ in range(n)]


def _chunk(asm, batches):
    for d, l in batches:
        asm.add(d, l)
    return asm.take()


@pytest.mark.parametrize("n, dtype, shape", [
    (K, np.float32, (6, 5, 5, 3)),   # float32 images
    (K, np.int32, (6, 16)),          # integer tokens: no cast
    (K - 1, np.float32, (6, 5, 5, 3)),  # a short tail
    (1, np.uint8, (6, 7)),           # one batch
])
def test_a_chunk_is_the_stack_of_its_batches(n, dtype, shape):
    asm = ChunkAssembler(K)
    bs = _batches(n, 1, dtype, shape)
    data, labels = _chunk(asm, bs)
    want_d, want_l = np.stack([d for d, _ in bs]), np.stack(
        [l for _, l in bs])
    assert data.dtype == want_d.dtype and data.shape == want_d.shape
    assert labels.dtype == want_l.dtype and labels.shape == want_l.shape
    assert data.tobytes() == want_d.tobytes()
    assert labels.tobytes() == want_l.tobytes()
    assert data.flags["C_CONTIGUOUS"] and labels.flags["C_CONTIGUOUS"]
    assert len(asm) == 0  # taken and forgotten


def test_add_copies_so_the_iterator_may_reuse_its_buffer():
    asm = ChunkAssembler(K)
    buf_d = np.zeros((6, 5), np.float32)
    buf_l = np.zeros((6, 1), np.float32)
    for i in range(K):
        buf_d[...] = i
        buf_l[...] = 10 + i
        asm.add(buf_d, buf_l)
    data, labels = asm.take()
    assert [float(x) for x in data[:, 0, 0]] == [0.0, 1.0, 2.0, 3.0]
    assert [float(x) for x in labels[:, 0, 0]] == [10.0, 11.0, 12.0, 13.0]


def test_a_held_chunk_is_never_rewritten():
    asm = ChunkAssembler(K)
    first = _batches(K, 0)
    data0, labels0 = _chunk(asm, first)
    part = data0[1:3].reshape(2, -1)  # a slice of it, reshaped
    want = np.stack([d for d, _ in first])
    assert asm.allocated == 1 and asm.recycled == 0
    del data0  # the labels and the slice still reference the block
    for seed in (1, 2, 3):
        _chunk(asm, _batches(K, seed))  # each dropped at once
    assert np.array_equal(part, want[1:3].reshape(2, -1))
    assert np.array_equal(labels0, np.stack([l for _, l in first]))
    # the held block stayed out: one more was mapped, and that one served
    assert asm.allocated == 2 and asm.recycled == 2


def test_a_dropped_chunks_block_is_the_next_chunks_memory():
    asm = ChunkAssembler(K)
    data, labels = _chunk(asm, _batches(K, 0))
    at = data.ctypes.data
    del data, labels
    for seed in (1, 2, 3):
        data, labels = _chunk(asm, _batches(K, seed))
        assert data.ctypes.data == at
        del data, labels
    assert asm.allocated == 1 and asm.recycled == 3


def test_a_short_tail_holds_the_whole_block():
    asm = ChunkAssembler(K)
    bs = _batches(2, 0)
    data, labels = _chunk(asm, bs)
    assert data.shape[0] == 2
    del labels
    _chunk(asm, _batches(K, 1))
    assert np.array_equal(data, np.stack([d for d, _ in bs]))
    assert asm.allocated == 2


def test_a_change_of_shape_or_dtype_takes_a_new_block():
    asm = ChunkAssembler(K)
    _chunk(asm, _batches(K, 0))
    _chunk(asm, _batches(K, 1))
    assert (asm.allocated, asm.recycled) == (1, 1)
    d, _ = _chunk(asm, _batches(K, 2, shape=(3, 5, 5, 3)))
    assert d.shape == (K, 3, 5, 5, 3) and asm.allocated == 2
    del d
    d, _ = _chunk(asm, _batches(K, 3, dtype=np.int32, shape=(3, 5, 5, 3)))
    assert d.dtype == np.int32 and asm.allocated == 3
    assert asm.recycled == 1


def test_a_batch_of_another_layout_inside_a_chunk_raises():
    asm = ChunkAssembler(K)
    d, l = _batches(1, 0)[0]
    asm.add(d, l)
    with pytest.raises(ValueError, match="chunk's first"):
        asm.add(d[:, :, :, :1], l)  # would broadcast into the slot
    with pytest.raises(ValueError, match="chunk's first"):
        asm.add(d.astype(np.float64), l)


def test_reset_forgets_an_open_chunk_and_the_counts_but_not_the_blocks():
    asm = ChunkAssembler(K)
    _chunk(asm, _batches(K, 0))
    d, l = _batches(1, 1)[0]
    asm.add(d, l)  # a round raised here
    asm.reset()
    assert len(asm) == 0 and (asm.allocated, asm.recycled) == (0, 0)
    bs = _batches(K, 2)
    data, _ = _chunk(asm, bs)
    assert np.array_equal(data, np.stack([d for d, _ in bs]))
    assert (asm.allocated, asm.recycled) == (0, 1)


def test_a_device_array_of_a_chunk_keeps_its_values():
    """On the CPU backend ``jnp.asarray`` of an aligned host array may
    alias its memory for the device array's life: the block is then held
    through the device array, whoever else dropped it."""
    import jax.numpy as jnp

    asm = ChunkAssembler(K)
    bs = _batches(K, 0)
    data, labels = _chunk(asm, bs)
    assert data.ctypes.data % 4096 == 0  # page start: aliased, not copied
    dev = jnp.asarray(data)
    want = np.stack([d for d, _ in bs])
    del data, labels
    for seed in (1, 2, 3):
        _chunk(asm, _batches(K, seed))
    assert np.array_equal(np.asarray(dev), want)
    del dev
    gc.collect()


# ----------------------------------------------------------------------
# through the CLI: a round of 6 full batches and a padded one at
# scan_steps = 4 is a chunk of 4, a short chunk of 2 and one update()
CONF = """
data = train
iter = csv
  filename = {csv}
  input_shape = 1,1,12
  round_batch = 0
iter = threadbuffer
iter = end

netconfig=start
layer[0->f1] = fullc:fc1
  nhidden = 16
  init_sigma = 0.1
layer[f1->r1] = relu
layer[r1->f2] = fullc:fc2
  nhidden = {nclass}
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end

input_shape = 1,1,12
batch_size = 8
dev = cpu
save_model = 0
num_round = 2
scan_steps = 4
eta = 0.1
momentum = 0.9
metric = error
random_type = gaussian
seed = 7
silent = 1
telemetry = 1
telemetry_path = {out}/telemetry.jsonl
model_dir = {out}/models
eval_train = {eval_train}
"""


def _write_conf(tmp_path, eval_train, more="", nclass=3):
    rows = np.hstack([np.arange(51)[:, None] % nclass,
                      np.random.RandomState(0).randn(51, 12)])
    np.savetxt(tmp_path / "d.csv", rows, delimiter=",")
    text = CONF.format(csv=tmp_path / "d.csv", out=tmp_path,
                       eval_train=eval_train, nclass=nclass) + more
    conf = tmp_path / "scan.conf"
    conf.write_text(text)
    return str(conf), text


class ByHand:
    """What :func:`_by_hand` trained and what it saw on the way."""

    def __init__(self):
        self.fed, self.losses, self.printed = [], [], []
        self.params = self.ustates = self.metric_state = None


def _metric_state(tr):
    return [(m.sum_metric, m.cnt_inst) for m in tr.train_metric.metrics]


def _by_hand(text, stop_after_chunk=None):
    """The same conf without the round loop: ``update_scan`` fed the
    ``np.stack`` of each chunk's batches and drained at once
    (``sync=True``), ``update`` the padded one.  ``stop_after_chunk=n``
    ends the run after its n-th chunk, where a stop request found the
    CLI, with that round's train metrics still in the accumulators."""
    import jax

    from cxxnet_tpu import config as cfgmod
    from cxxnet_tpu.io.data import create_iterator
    from cxxnet_tpu.nnet.trainer import NetTrainer

    pairs = cfgmod.parse_pairs(text)
    tr = NetTrainer()
    tr.set_params(pairs)
    tr.init_model()
    split = cfgmod.split_sections(pairs)
    it = create_iterator(split.find("data")[0].entries)
    for n, v in split.global_entries:
        it.set_param(n, v)
    it.init()
    got = ByHand()
    for rnd in (1, 2):
        tr.start_round(rnd)
        it.before_first()
        full, padded = [], []
        while it.next():
            b = it.value()
            (padded if b.num_batch_padd else full).append(
                (np.array(b.data), np.array(b.label), b))
        assert len(full) == 6 and len(padded) == 1
        for lo, hi in ((0, 4), (4, 6)):
            data = np.stack([d for d, _, _ in full[lo:hi]])
            labels = np.stack([l for _, l, _ in full[lo:hi]])
            got.fed.append((data, labels))
            got.losses.append(tr.update_scan(data, labels, sync=True))
            if len(got.fed) == stop_after_chunk:
                break
        else:
            tr.update(padded[0][2])
            got.printed.append(tr.evaluate(None, "train"))
            continue
        break
    it.close()
    got.params = jax.device_get(tr.params)
    got.ustates = jax.device_get(tr.ustates)
    got.metric_state = _metric_state(tr)
    return got


def _same_bytes(got, want):
    import jax

    got, want = (jax.tree_util.tree_leaves_with_path(jax.device_get(t))
                 for t in (got, want))
    assert [k for k, _ in got] == [k for k, _ in want]
    for (key, g), (_, w) in zip(got, want):
        assert np.asarray(g).tobytes() == np.asarray(w).tobytes(), key


def _cli_task(conf, on_chunk=None):
    """A ``LearnTask`` whose trainer's ``update_scan`` and ``evaluate``
    are recorded: ``seen`` holds what the loop handed ``update_scan`` by
    reference, ``handles`` what it returned, ``printed`` every round's
    train-metric text.  ``on_chunk(task, n)`` runs before the n-th
    ``update_scan``."""
    from cxxnet_tpu.cli import LearnTask

    task = LearnTask()
    task.seen, task.handles, task.printed = [], [], []
    create = task._create_trainer

    def create_trainer():
        tr = create()
        inner, inner_eval = tr.update_scan, tr.evaluate

        def update_scan(data, labels, *a, **kw):
            task.seen.append((data, labels))
            if on_chunk is not None:
                on_chunk(task, len(task.seen))
            task.handles.append(inner(data, labels, *a, **kw))
            return task.handles[-1]

        def evaluate(*a, **kw):
            task.printed.append(inner_eval(*a, **kw))
            return task.printed[-1]

        tr.update_scan, tr.evaluate = update_scan, evaluate
        return tr

    task._create_trainer = create_trainer
    assert task.run([conf]) == 0
    return task


@pytest.mark.parametrize("eval_train", [1, 0])
def test_cli_round_trains_what_the_stack_by_hand_trains(tmp_path,
                                                        eval_train):
    import os
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(repo, "tools"))
    import obs_dump

    conf, text = _write_conf(tmp_path, eval_train)
    task = _cli_task(conf)
    seen = task.seen
    want = _by_hand(text)

    # every chunk, kept by reference through both rounds, still reads as
    # the stack of its batches: no held block was written again
    assert [d.shape[0] for d, _ in seen] == [4, 2, 4, 2]
    for (data, labels), (want_d, want_l) in zip(seen, want.fed):
        assert data.tobytes() == want_d.tobytes()
        assert labels.tobytes() == want_l.tobytes()
    _same_bytes(task.net_trainer.params, want.params)

    path = str(tmp_path / "telemetry.jsonl")
    assert obs_dump.validate_telemetry(path) == []
    with open(path) as f:
        recs = [json.loads(x) for x in f if x.strip()]
    assert len(recs) == 2
    for rec in recs:
        ch = rec["chunks"]
        assert ch["allocated"] + ch["recycled"] == 2  # two chunks a round
        assert rec["stages"]["copy"]["count"] == 6
        assert rec["stages"]["stack"]["count"] == 2
    # the test holds all four chunks: each one forced a block of its own
    assert sum(r["chunks"]["allocated"] for r in recs) == 4
    task.itr_train.close()


METRICS = "metric = rec@1\nmetric = rec@5\n"  # beside CONF's error


def test_cli_round_with_train_metrics_is_update_scan_drained_by_hand(
        tmp_path):
    """``eval_train = 1`` down the asynchronous chunk path: a chunk's
    sums are collected a chunk later than ``update_scan(sync=True)``
    adds them, and the weights, the momentum, every loss and the
    printed error / rec@1 / rec@5 come out bit for bit the same —
    through a tail chunk of 2 at ``scan_steps = 4``, the padded batch's
    ``update()`` behind it, and a stop request that finds round 2's
    first chunk in flight."""
    import jax

    conf, text = _write_conf(tmp_path, 1, METRICS, nclass=6)

    def on_chunk(task, n):
        if n == 3:  # round 2's first chunk is about to be dispatched:
            # the batch boundary behind it reads the request
            task._preempt.requested = True

    task = _cli_task(conf, on_chunk)
    want = _by_hand(text, stop_after_chunk=3)
    tr = task.net_trainer

    assert [d.shape[0] for d, _ in task.seen] == [4, 2, 4]
    # undrained, the loop got device arrays; drained, the hand got numpy
    assert all(isinstance(h, jax.Array) for h in task.handles)
    assert all(isinstance(l, np.ndarray) for l in want.losses)
    for got_l, want_l in zip(task.handles, want.losses):
        assert np.asarray(got_l).tobytes() == want_l.tobytes()
    _same_bytes(tr.params, want.params)
    _same_bytes(tr.ustates, want.ustates)
    # round 1 printed what the hand printed; round 2 was stopped before
    # it printed, with its one chunk's sums already in the accumulators
    assert task.printed == want.printed and len(want.printed) == 1
    for name in ("train-error:", "train-rec@1:", "train-rec@5:"):
        assert name in task.printed[0]
    assert not tr._scan_sums
    assert _metric_state(tr) == want.metric_state
    assert [cnt for _, cnt in want.metric_state] == [4 * 8] * 3
    with open(tmp_path / "telemetry.jsonl") as f:
        recs = [json.loads(x) for x in f if x.strip()]
    assert len(recs) == 1  # the stopped round writes no record
    c = recs[0]["counters"]
    # two chunks, the first fenced behind the second's dispatch
    assert (c["chunks_fenced"], c["chunks_overlapped"]) == (2, 1)
    assert c["metric_rows_device"] == 6 * 8
    assert c["metric_rows"] == 6 * 8 + 3  # and the padded batch's rows
    task.itr_train.close()


def test_update_scan_undrained_with_train_metrics_returns_the_losses(
        tmp_path):
    """``update_scan(sync=False)`` with ``eval_train = 1`` raised until
    PR 32.  It returns the ``[K]`` losses and nothing else, keeps the
    sums pending, and ``collect_scan_metrics`` adds them oldest first:
    the accumulators end where ``sync=True`` leaves them."""
    import jax

    from cxxnet_tpu import config as cfgmod
    from cxxnet_tpu.nnet.trainer import NetTrainer

    _, text = _write_conf(tmp_path, 1, METRICS, nclass=6)
    rs = np.random.RandomState(3)
    chunks = [(rs.randn(k, 8, 12).astype(np.float32),
               rs.randint(0, 6, (k, 8, 1)).astype(np.float32))
              for k in (4, 2, 4)]

    def trainer():
        tr = NetTrainer()
        tr.set_params(cfgmod.parse_pairs(text))
        tr.init_model()
        return tr

    a, b = trainer(), trainer()
    for data, labels in chunks:
        losses = a.update_scan(data, labels, sync=False)
        assert isinstance(losses, jax.Array) and losses.shape == (len(data),)
        want = b.update_scan(data, labels)  # sync=True: collected at once
        assert isinstance(want, np.ndarray) and not b._scan_sums
        assert np.asarray(losses).tobytes() == want.tobytes()
    assert len(a._scan_sums) == 3 and _metric_state(a) == [(0.0, 0)] * 3
    a.collect_scan_metrics()  # the oldest chunk alone
    assert len(a._scan_sums) == 2
    assert [cnt for _, cnt in _metric_state(a)] == [4 * 8] * 3
    a.collect_scan_metrics(all_pending=True)
    assert not a._scan_sums and _metric_state(a) == _metric_state(b)
    a.collect_scan_metrics()  # nothing pending: nothing to do
    assert _metric_state(a) == _metric_state(b)
    # a caller that never collects still prints every row it trained
    a.update_scan(*chunks[0], sync=False)
    b.update_scan(*chunks[0])
    assert a.evaluate(None, "train") == b.evaluate(None, "train")
    _same_bytes(a.params, b.params)


def test_cli_recycles_the_block_of_a_chunk_nobody_holds(tmp_path):
    from cxxnet_tpu.cli import LearnTask

    conf, _ = _write_conf(tmp_path, 1, "num_round = 4\n")
    task = LearnTask()
    assert task.run([conf]) == 0
    with open(tmp_path / "telemetry.jsonl") as f:
        recs = [json.loads(x)["chunks"] for x in f if x.strip()]
    # the runtime may hold a chunk until a later call of its own, so a
    # second block (a third, if it is slow to let go) can appear; after
    # that every chunk goes into mapped memory
    assert 1 <= sum(c["allocated"] for c in recs) <= 3
    assert all(c["allocated"] + c["recycled"] == 2 for c in recs)
    assert recs[-1] == {"allocated": 0, "recycled": 2}
    task.itr_train.close()


# ----------------------------------------------------------------------
# the benchmark's reader of the counter
def test_chunk_recycled_pct_reads_the_windows_records():
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks import run

    mod = run.load_metric("chunk_recycled_pct")
    read = mod.read
    rounds = [{"round": 1, "chunks": {"allocated": 1, "recycled": 2}},
              {"round": 2, "chunks": {"allocated": 0, "recycled": 3}}]
    assert read({"telemetry": rounds}) == pytest.approx(100.0 * 5 / 6)
    # the parent commit writes no counter; a per-batch run counts nothing
    assert read({"telemetry": [{"round": 1, "stages": {}}]}) is None
    assert read({"telemetry": [
        {"round": 1, "chunks": {"allocated": 0, "recycled": 0}}]}) is None
    assert read({"telemetry": []}) is None
    with open(os.path.join(os.path.dirname(run.HERE),
                           "BENCHMARK.json")) as f:
        entry = [m for m in json.load(f)["per_layer"]
                 if m["name"] == "chunk_recycled_pct"]
    assert len(entry) == 1
    assert (entry[0]["layer"], entry[0]["unit"], entry[0]["source"],
            entry[0]["moves"], entry[0]["better"]) == (
        mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES, "higher")
