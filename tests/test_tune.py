"""Self-tuning runtime tests: controller decision loop, runtime knob
resize (decode pool, micro-batcher), speculative prewarm, and the
double-buffered device feed's bitwise neutrality.

The controller tests drive :meth:`KnobController.step_once` manually
with a synthetic clock and a simulated environment (knob value →
throughput), so every decision sequence is deterministic.
"""

import itertools
import os
import tempfile

import numpy as np
import pytest

from cxxnet_tpu import config as cfgmod
from cxxnet_tpu.tune import (
    Knob,
    KnobController,
    band_verdict,
    batcher_knobs,
    find_pipeline,
    options_from_cfg,
    pipeline_knobs,
)


# ----------------------------------------------------------------------
# primitives
def test_band_verdict_orientation():
    assert band_verdict(120, 100, 0.1) == "better"
    assert band_verdict(80, 100, 0.1) == "worse"
    assert band_verdict(105, 100, 0.1) == "noise"
    # lower-is-better flips the directions (latencies)
    assert band_verdict(80, 100, 0.1, lower_is_better=True) == "better"
    assert band_verdict(120, 100, 0.1, lower_is_better=True) == "worse"
    # nothing can be concluded against a missing/zero baseline
    assert band_verdict(50, None, 0.1) == "noise"
    assert band_verdict(50, 0.0, 0.1) == "noise"


def test_knob_propose_clamps_and_rounds():
    store = {"v": 3}
    k = Knob("k", lambda: store["v"], lambda v: store.__setitem__("v", v),
             lo=1, hi=8)
    assert k.propose(+1) == 6
    assert k.propose(-1) == 2  # 3/2 rounds to 2
    store["v"] = 8
    assert k.propose(+1) is None  # pinned at hi
    store["v"] = 1
    assert k.propose(-1) is None  # pinned at lo
    store["v"] = 7
    assert k.propose(+1) == 8  # clamped, still a move
    f = Knob("f", lambda: 2.0, lambda v: None, lo=0.25, hi=50.0,
             integer=False)
    assert f.propose(+1) == 4.0
    assert f.propose(-1) == 1.0


def test_options_from_cfg():
    opt = options_from_cfg([
        ("controller", "1"), ("tune_period_s", "0.5"),
        ("tune_band", "0.2"), ("tune_targets", "batcher"),
    ])
    assert opt.enabled == 1
    assert opt.period_s == 0.5
    assert opt.band == 0.2
    assert opt.wants("batcher") and not opt.wants("pipeline")
    assert options_from_cfg([]).wants("pipeline")  # auto = everything


# ----------------------------------------------------------------------
# decision loop (synthetic environment: knob value -> rows/sec)
def _drive(ctrl, work, rate_fn, ticks, t0=0.0):
    """Advance a simulated second per tick: accumulate work at the
    CURRENT knob setting, then let the controller observe it."""
    t = t0
    decisions = []
    for _ in range(ticks):
        t += 1.0
        work[0] += rate_fn()
        decisions.append(ctrl.step_once(now=t))
    return decisions, t


def test_controller_climbs_to_plateau():
    state = {"w": 1}
    work = [0.0]
    k = Knob("w", lambda: state["w"],
             lambda v: state.__setitem__("w", v), lo=1, hi=16)
    ctrl = KnobController(lambda: work[0], [k], band=0.1,
                          measure_ticks=2, settle_ticks=1,
                          cooldown_ticks=4, name="t_climb")
    decisions, _ = _drive(ctrl, work,
                          lambda: 100.0 * min(state["w"], 4), 40)
    assert state["w"] == 4  # the plateau knee, not the hi bound
    actions = [d["action"] for d in decisions]
    assert "adjust" in actions and "keep" in actions
    # the move past the knee (4 -> 8) measured as noise and was REVERTED
    assert "revert" in actions


def test_controller_rolls_back_regression_and_flips():
    state = {"w": 4}
    work = [0.0]
    k = Knob("w", lambda: state["w"],
             lambda v: state.__setitem__("w", v), lo=1, hi=16)
    ctrl = KnobController(lambda: work[0], [k], band=0.1,
                          measure_ticks=2, settle_ticks=1,
                          cooldown_ticks=4, name="t_rollback")
    decisions, _ = _drive(ctrl, work, lambda: 100.0 / state["w"], 40)
    actions = [d["action"] for d in decisions]
    assert "rollback" in actions  # the up-probe regressed and reverted
    assert state["w"] == 1        # then climbed DOWN to the optimum


def test_controller_hysteresis_no_oscillation_on_noise():
    state = {"w": 4}
    work = [0.0]
    noise = itertools.cycle([0.97, 1.04, 1.0, 0.95, 1.05])
    k = Knob("w", lambda: state["w"],
             lambda v: state.__setitem__("w", v), lo=1, hi=16)
    ctrl = KnobController(lambda: work[0], [k], band=0.15,
                          measure_ticks=2, settle_ticks=1,
                          cooldown_ticks=6, name="t_noise")
    seen = set()
    t = 0.0
    kept = 0
    for _ in range(80):
        t += 1.0
        work[0] += 100.0 * next(noise)
        d = ctrl.step_once(now=t)
        kept += d["action"] == "keep"
        seen.add(state["w"])
    # every probe was reverted: the value always returns to 4 and no
    # move was ever KEPT on noise — no drift, bounded oscillation
    assert state["w"] == 4
    assert kept == 0
    assert seen <= {2, 4, 8}
    # after both directions failed, the knob cooled down: far fewer
    # probes than free oscillation (80 ticks / ~5-tick decisions)
    snap = ctrl.snapshot()
    assert snap["knobs"]["w"] == 4


def test_controller_round_robins_multiple_knobs():
    state = {"a": 1, "b": 1}
    work = [0.0]
    ka = Knob("a", lambda: state["a"],
              lambda v: state.__setitem__("a", v), lo=1, hi=8)
    kb = Knob("b", lambda: state["b"],
              lambda v: state.__setitem__("b", v), lo=1, hi=8)
    ctrl = KnobController(lambda: work[0], [ka, kb], band=0.1,
                          measure_ticks=2, settle_ticks=1,
                          cooldown_ticks=2, name="t_rr")
    # both knobs contribute independently; both should climb to the
    # knee and stay there (modulo the bounded hysteresis probes that
    # may be in flight at whatever tick the loop happens to stop)
    hist_a, hist_b = [], []
    t = 0.0
    for _ in range(120):
        t += 1.0
        work[0] += (50.0 * min(state["a"], 4)
                    + 50.0 * min(state["b"], 4))
        ctrl.step_once(now=t)
        hist_a.append(state["a"])
        hist_b.append(state["b"])
    for hist in (hist_a, hist_b):
        tail = hist[60:]
        assert max(tail, key=tail.count) == 4  # the settled value
        assert 2 <= min(tail) and max(tail) <= 8  # probes stay bounded


def test_controller_emits_events_and_gauges():
    from cxxnet_tpu.obs import recent
    from cxxnet_tpu.obs.registry import registry

    state = {"w": 1}
    work = [0.0]
    k = Knob("evt_w", lambda: state["w"],
             lambda v: state.__setitem__("w", v), lo=1, hi=8)
    ctrl = KnobController(lambda: work[0], [k], band=0.1,
                          measure_ticks=1, settle_ticks=0,
                          cooldown_ticks=2, name="t_events")
    _drive(ctrl, work, lambda: 100.0 * min(state["w"], 2), 12)
    kinds = [e["kind"] for e in recent(100)]
    assert "tune.adjust" in kinds
    snap = registry().snapshot()
    eff = snap.get("tune_effective", {})
    assert f'tune_effective{{knob="evt_w"}}' in eff
    assert eff[f'tune_effective{{knob="evt_w"}}'] == state["w"]
    assert any(name.startswith("tune_adjustments_total")
               for name in snap.get("tune_adjustments_total", {}))


def test_stop_rolls_back_unconcluded_probe():
    """A stop() landing between adjust and conclude must restore the
    pre-probe value — the autotune verdicts read snapshot()['knobs']
    as the chosen configuration."""
    state = {"w": 4}
    work = [0.0]
    k = Knob("w", lambda: state["w"],
             lambda v: state.__setitem__("w", v), lo=1, hi=16)
    ctrl = KnobController(lambda: work[0], [k], band=0.1,
                          measure_ticks=2, settle_ticks=1,
                          cooldown_ticks=4, name="t_stop")
    t = 0.0
    # drive exactly until a probe is APPLIED (action == adjust), then stop
    for _ in range(20):
        t += 1.0
        work[0] += 100.0
        if ctrl.step_once(now=t)["action"] == "adjust":
            break
    assert state["w"] != 4  # probe applied
    ctrl.stop()
    assert state["w"] == 4  # restored
    assert ctrl.snapshot()["knobs"]["w"] == 4


def test_consecutive_shrinks_never_over_poison():
    """Back-to-back request_workers() shrinks must account for poison
    tokens still in flight: the pool keeps >= target workers and the
    consumer never wedges."""
    with tempfile.TemporaryDirectory() as wd:
        _imgbin(wd)
        it = _chain(wd, 32, 4, queue_depth=2)
        assert it.effective_workers() == 4
        # three shrinks in a row before any token can be consumed
        it.request_workers(3)
        it.request_workers(2)
        it.request_workers(1)
        assert it._poison_pending <= 3  # never more tokens than surplus
        got = _epoch_stream(it)         # consumer must not wedge
        assert len(got) > 0
        assert it.effective_workers() >= 1
        # growth after the shrink burst converges back up
        it.request_workers(3)
        got2 = _epoch_stream(it)
        assert len(got2) == len(got)
        assert it.effective_workers() == 3
        it.close()


def test_controller_objective_error_is_survivable():
    def broken():
        raise RuntimeError("boom")

    k = Knob("x", lambda: 1, lambda v: None, lo=1, hi=4)
    ctrl = KnobController(broken, [k], name="t_broken")
    assert ctrl.step_once(now=1.0)["action"] == "error"
    assert ctrl.step_once(now=2.0)["action"] == "error"


# ----------------------------------------------------------------------
# runtime pipeline resize
def _imgbin(workdir, n=48, size=32):
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    import io_bench

    io_bench.generate_imgbin(workdir, n, size)


def _chain(workdir, size, workers, queue_depth=0):
    from cxxnet_tpu.io.augment import AugmentIterator
    from cxxnet_tpu.io.imgbin import ImageBinIterator
    from cxxnet_tpu.io.pipeline import ParallelAugmentIterator

    crop = size - size // 8
    it = ParallelAugmentIterator(AugmentIterator(ImageBinIterator()))
    for k, v in [
        ("image_bin", f"{workdir}/bench.bin"),
        ("image_list", f"{workdir}/bench.lst"),
        ("num_decode_workers", str(workers)),
        ("silent", "1"),
        ("rand_crop", "1"),
        ("rand_mirror", "1"),
        ("input_shape", f"3,{crop},{crop}"),
        ("batch_size", "8"),
        ("label_width", "1"),
    ]:
        it.set_param(k, v)
    if queue_depth:
        it.set_param("decode_queue_depth", str(queue_depth))
    it.init()
    return it


def _epoch_stream(it, epoch=7):
    """One epoch's instances, with the augmentation epoch ANCHORED so
    streams from different iterators / rewind counts compare bitwise
    (the same augment_epoch contract the CLI round loop uses)."""
    out = []
    it.before_first()
    it.set_param("augment_epoch", str(epoch))
    while it.next():
        v = it.value()
        out.append((v.index, np.array(v.data), np.array(v.label)))
    return out


def test_pipeline_runtime_resize_bitwise_and_thread_counts():
    with tempfile.TemporaryDirectory() as wd:
        _imgbin(wd)
        serial = _chain(wd, 32, 0)
        ref = _epoch_stream(serial)
        serial.close()

        it = _chain(wd, 32, 2, queue_depth=1)
        assert it.effective_workers() == 2
        # grow mid-run (applies immediately on a live pool)
        it.request_workers(4)
        it.set_queue_depth(4)
        got = _epoch_stream(it)
        assert it.effective_workers() == 4
        # shrink: poison tokens retire surplus workers
        it.request_workers(1)
        got2 = _epoch_stream(it)
        deadline_threads = it.effective_workers()
        assert deadline_threads <= 2  # drains toward 1; never below
        it.close()
    for a, b in ((got, ref), (got2, ref)):
        assert len(a) == len(b)
        for (ia, da, la), (ib, db, lb) in zip(a, b):
            assert ia == ib and la == lb
            assert np.array_equal(da, db)  # resize is bitwise-neutral


def test_pipeline_serial_to_pool_at_epoch_boundary():
    with tempfile.TemporaryDirectory() as wd:
        _imgbin(wd)
        it = _chain(wd, 32, 1)  # serial pass-through (no pool)
        ref = _epoch_stream(it)
        assert it.effective_workers() == 0
        it.request_workers(2)
        assert it.effective_workers() == 0  # mid-epoch: deferred
        got = _epoch_stream(it)             # before_first grew the pool
        assert it.effective_workers() == 2
        it.close()
    assert len(got) == len(ref)
    for (ia, da, la), (ib, db, lb) in zip(got, ref):
        assert ia == ib and np.array_equal(da, db)


def test_find_pipeline_walks_chain():
    from cxxnet_tpu.io.data import create_iterator

    with tempfile.TemporaryDirectory() as wd:
        _imgbin(wd)
        crop = 32 - 32 // 8
        it = create_iterator([
            ("iter", "imgbin"),
            ("image_bin", f"{wd}/bench.bin"),
            ("image_list", f"{wd}/bench.lst"),
            ("silent", "1"),
            ("input_shape", f"3,{crop},{crop}"),
            ("batch_size", "8"),
            ("label_width", "1"),
            ("iter", "threadbuffer"),
            ("iter", "end"),
        ])
        pipe = find_pipeline(it)
        assert pipe is not None
        knobs = pipeline_knobs(pipe)
        assert [k.name for k in knobs] == ["num_decode_workers",
                                           "decode_queue_depth"]
        it.close()


# ----------------------------------------------------------------------
# serve-side live knobs + prewarm
MLP_CFG = """
netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 16
  init_sigma = 0.1
layer[+1:a1] = relu:a1
layer[a1->out] = fullc:fc2
  nhidden = 4
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end
input_shape = 1,1,16
batch_size = 32
dev = cpu
eta = 0.1
"""


def _engine(**kw):
    from cxxnet_tpu import serve
    from cxxnet_tpu.nnet.trainer import NetTrainer

    tr = NetTrainer()
    tr.set_params(cfgmod.parse_pairs(MLP_CFG))
    tr.set_param("seed", "0")
    tr.init_model()
    kw.setdefault("max_batch_size", 32)
    kw.setdefault("batch_timeout_ms", 1.0)
    return serve.Engine(trainer=tr, **kw)


def test_batcher_live_setters_and_statsz():
    eng = _engine()
    try:
        out1 = eng.predict(np.zeros((4, 16), np.float32))
        eng.set_max_batch_size(8, prewarm=False)
        eng.set_batch_timeout_ms(0.5)
        assert eng.batcher.max_batch_size == 8
        assert eng.batcher.batch_timeout == pytest.approx(0.5e-3)
        out2 = eng.predict(np.zeros((4, 16), np.float32))
        assert np.array_equal(np.asarray(out1), np.asarray(out2))
        stats = eng.snapshot_stats()
        assert stats["tune_effective"]["max_batch_size"] == 8
        assert stats["tune_effective"]["batch_timeout_ms"] == \
            pytest.approx(0.5)
        # request-shape histogram: 4-row requests land in bucket 4
        assert stats["request_buckets"].get("4") == 2
        # clamped to the engine's configured capacity
        assert eng.set_max_batch_size(10_000, prewarm=False) == 32
        from cxxnet_tpu.obs.registry import registry

        eff = registry().snapshot()["tune_effective"]
        assert eff['tune_effective{knob="max_batch_size"}'] == 32
    finally:
        eng.close()


def test_engine_prewarm_from_histogram():
    eng = _engine()
    try:
        eng.predict(np.zeros((3, 16), np.float32))  # bucket 4 (now warm)
        # histogram-driven prewarm: nothing new -> no work
        assert eng.prewarm_buckets() == []
        # a pending bigger bucket in the histogram, not yet compiled
        with eng._req_lock:
            eng._req_buckets[(16, (16,))] = 5
        assert eng.prewarm_buckets() == [16]
        cache_buckets = {k[3] for k in eng._cache.keys_snapshot()}
        assert 16 in cache_buckets
        assert eng.prewarm_buckets() == []  # idempotent
        # buckets above the live limit are never compiled speculatively
        eng.set_max_batch_size(4, prewarm=False)
        with eng._req_lock:
            eng._req_buckets[(32, (16,))] = 9
        assert eng.prewarm_buckets() == []
    finally:
        eng.close()


def test_prewarm_is_row_shape_aware():
    """Programs specialize per row shape: a bucket warm for one shape
    must not mark another shape's program warm (the flat wrapper
    spelling vs the native shape are distinct compiles)."""
    eng = _engine()
    try:
        # simulate traffic of a hypothetical second row shape in the
        # histogram: the warm-check must key on (bucket, shape)
        assert eng._warm_bucket(8, (16,)) is True
        assert eng._warm_bucket(8, (16,)) is False   # now warm
        assert eng._dominant_row_shape() == (16,)    # native fallback
        eng.predict(np.zeros((2, 16), np.float32))
        assert eng._dominant_row_shape() == (16,)
    finally:
        eng.close()


def test_set_max_batch_prewarms_before_apply():
    eng = _engine()
    try:
        eng.predict(np.zeros((1, 16), np.float32))
        before = {k[3] for k in eng._cache.keys_snapshot()}
        assert 16 not in before
        eng.set_max_batch_size(16)  # prewarm=True default
        after = {k[3] for k in eng._cache.keys_snapshot()}
        assert 16 in after
    finally:
        eng.close()


def test_batcher_knobs_bind_engine():
    eng = _engine()
    try:
        knobs = {k.name: k for k in batcher_knobs(eng)}
        assert knobs["max_batch_size"].hi == 32
        knobs["max_batch_size"].apply(8)
        assert eng.batcher.max_batch_size == 8
        knobs["batch_timeout_ms"].apply(4.0)
        assert eng.batcher.batch_timeout == pytest.approx(4e-3)
    finally:
        eng.close()
