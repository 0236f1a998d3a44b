"""The stage helper (``utils/profiler.stage``) and the stages it bills.

One helper, three sinks: a ``PipelineStats`` entry, an ``obs.trace`` span
``train.<name>`` and a ``jax.profiler.TraceAnnotation`` of the same name.
The round loop and the trainer bill every host stage of a scanned chunk
through it, so the seven stages on the loop's thread tile ``chunk``, the
fence-to-fence period (doc/observability.md).
"""

import glob
import json
import os
import sys

import numpy as np
import pytest

from cxxnet_tpu.obs import trace as obs_trace
from cxxnet_tpu.utils.profiler import PipelineStats, pipeline_stats, stage

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import obs_dump  # noqa: E402

from conftest import run_cli  # noqa: E402 - shared CLI harness

CHILDREN = ("next", "copy", "stack", "h2d", "dispatch", "device_wait",
            "metric")


@pytest.fixture(autouse=True)
def _clean():
    pipeline_stats().reset()
    obs_trace.tracer().reset()
    yield
    pipeline_stats().reset()
    obs_trace.tracer().reset()


def test_stage_bills_one_entry_and_one_span_under_its_parent():
    tr = obs_trace.tracer()
    tr.enable(ring=64)
    with obs_trace.span("train.round", round=3) as rnd:
        with stage("h2d", rows=5, step=40):
            pass
    snap = pipeline_stats().snapshot()
    assert snap["h2d"]["count"] == 1 and snap["h2d"]["rows"] == 5
    assert snap["h2d"]["total_s"] > 0
    spans = {s.name: s for s in tr.spans()}
    assert set(spans) == {"train.round", "train.h2d"}
    assert spans["train.h2d"].parent_id == rnd.span_id
    assert spans["train.h2d"].args == {"step": 40}


def test_stage_costs_nothing_but_the_bill_when_tracing_is_off():
    with stage("copy", rows=2, step=1):
        pass
    assert obs_trace.tracer().spans() == []
    assert pipeline_stats().snapshot()["copy"]["count"] == 1


def test_begin_end_sets_rows_late_and_drop_does_not_bill():
    tr = obs_trace.tracer()
    tr.enable(ring=64)
    chunk = stage("chunk", step=8).begin()
    with stage("next", step=8):
        pass
    assert chunk.end(rows=16) > 0
    tail = stage("chunk", step=16).begin()
    tail.drop()
    snap = pipeline_stats().snapshot()
    assert snap["chunk"]["count"] == 1 and snap["chunk"]["rows"] == 16
    by_name = {}
    for s in tr.spans():
        by_name.setdefault(s.name, []).append(s)
    # the open chunk is the parent of what runs inside it
    assert by_name["train.next"][0].parent_id == by_name[
        "train.chunk"][0].span_id
    assert len(by_name["train.chunk"]) == 2  # the dropped one is a span too


def test_an_abandoned_stage_does_not_become_a_later_spans_parent():
    tr = obs_trace.tracer()
    tr.enable(ring=64)
    with pytest.raises(RuntimeError):
        with obs_trace.span("train.round", round=0):
            stage("chunk", step=0).begin()  # never ended: the round raised
            raise RuntimeError("diverged")
    with obs_trace.span("train.round", round=1) as rnd:
        with stage("next", step=0):
            pass
    spans = [s for s in tr.spans() if s.name == "train.next"]
    assert rnd.parent_id is None
    assert spans[0].parent_id == rnd.span_id


def test_stage_is_a_host_plane_event_in_a_profiler_session(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with stage("device_wait", rows=4, step=24):
            (jnp.ones((8, 8)) + 1).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    found = [(plane.name, dict(ev.stats))
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name == "train.device_wait"]
    assert len(found) == 1
    assert found[0][0].startswith("/host:") and found[0][1] == {"step": 24}


def test_the_loops_spans_tile_its_thread_in_a_profiler_session(tmp_path):
    """``train.round`` and ``train.boundary`` never overlap, the chunks
    lie inside the round and ``train.head`` inside its first chunk, on
    the profiler's clock: an idle gap of the device can be laid over
    them."""
    import jax
    from jax.profiler import ProfileData

    from cxxnet_tpu.train_loop import RoundLoop
    from test_round_loop import FakeTrainer, ListIter, Timer

    log = []
    loop = RoundLoop(4)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for rnd in range(2):
            loop.begin(FakeTrainer(log), rnd)
            loop.run(ListIter([(i, 0) for i in range(8)]), Timer(log))
        loop.close()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("train."):
                    spans.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats)))
    for rows in spans.values():
        rows.sort()
    rounds, bounds = spans["train.round"], spans["train.boundary"]
    assert [r[2] for r in rounds] == [{"round": 0}, {"round": 1}]
    assert len(bounds) == 2  # the second is the one close() dropped
    # round, boundary, round, boundary: each starts where the last ended
    tiles = sorted(rounds + bounds)
    assert [t[2].keys() for t in tiles] == [
        {"round"}, {"step"}, {"round"}, {"step"}]
    for a, b in zip(tiles, tiles[1:]):
        assert a[1] <= b[0] and b[0] - a[1] < 5e6  # < 5 ms of no span
    chunks, heads = spans["train.chunk"], spans["train.head"]
    # a round's last fence opens a chunk that the round's end drops
    assert len(heads) == 2 and len(chunks) == 6
    for rnd, head in zip(rounds, heads):
        first = min(c for c in chunks if c[0] >= rnd[0])
        assert rnd[0] <= first[0] <= head[0] and head[1] <= first[1]
        assert first[1] <= rnd[1]
        # the head holds the first chunk's feed and dispatch, and ends
        # before that chunk's fence
        inside = [n for n in ("train.next", "train.copy", "train.stack")
                  if any(head[0] <= x[0] and x[1] <= head[1]
                         for x in spans[n])]
        assert inside == ["train.next", "train.copy", "train.stack"]
        assert all(w[0] >= head[1] for w in spans["train.device_wait"]
                   if w[0] >= rnd[0] and w[1] <= rnd[1])


def test_snapshot_and_validator_know_the_same_stages():
    assert PipelineStats.STAGES == obs_dump.TELEMETRY_STAGES
    assert set(CHILDREN) | {"chunk"} <= set(PipelineStats.STAGES)
    assert PipelineStats.STAGES[-4:] == ("head", "run", "run_exposed",
                                         "boundary")
    snap = PipelineStats().snapshot()
    assert all(snap[s]["count"] == 0 for s in PipelineStats.STAGES)


# ----------------------------------------------------------------------
# the CLI's scanned path bills all eight, and the seven tile the chunk
CONF = """
data = train
iter = synthetic
  nsample = 768
  input_shape = 3,32,32
  nclass = 10
  label_width = 1
  seed_data = 3
iter = threadbuffer
iter = end

netconfig=start
layer[0->c1] = conv:conv1
  kernel_size = 3
  nchannel = 16
  pad = 1
layer[c1->r1] = relu
layer[r1->p1] = max_pooling
  kernel_size = 2
  stride = 2
layer[p1->f1] = flatten
layer[f1->fc] = fullc:fc
  nhidden = 10
  init_sigma = 0.05
layer[+0] = softmax
netconfig=end

input_shape = 3,32,32
batch_size = 32
dev = cpu
save_model = 0
num_round = 3
scan_steps = 8
eval_train = 1
eta = 0.05
momentum = 0.9
metric = error
metric = rec@1
random_type = gaussian
silent = 1
telemetry = 1
"""


@pytest.fixture(scope="module")
def telemetry(tmp_path_factory):
    d = tmp_path_factory.mktemp("stages")
    conf = d / "scan.conf"
    conf.write_text(CONF + f"telemetry_path = {d}/telemetry.jsonl\n"
                    f"model_dir = {d}/models\n")
    r = run_cli([str(conf)], str(d))
    assert r.returncode == 0, r.stderr + r.stdout
    path = str(d / "telemetry.jsonl")
    with open(path) as f:
        return path, [json.loads(x) for x in f if x.strip()]


def test_scanned_round_bills_all_eight_stages(telemetry):
    _, recs = telemetry
    assert len(recs) == 3
    for rec in recs:
        st = rec["stages"]
        for name in CHILDREN + ("chunk",):
            assert st[name]["count"] > 0, name
        assert st["h2d"]["total_s"] > 0
        assert st["chunk"]["count"] == 3 and rec["steps"] == 24
        assert st["chunk"]["rows"] == st["metric"]["rows"] == 24 * 32
        assert st["copy"]["count"] == 24 and st["stack"]["count"] == 3


def test_scanned_round_bills_the_device_from_its_fences(telemetry):
    """One head a round, a boundary in every record but the first, and
    every chunk either an exposed run, a run, late, or behind a late
    one: a plain scanned round on the CPU, no key set."""
    _, recs = telemetry
    for i, rec in enumerate(recs):
        st, counters = rec["stages"], rec["counters"]
        assert st["head"]["count"] == 1
        assert st["boundary"]["count"] == (1 if i else 0)
        assert counters["chunks_dispatched"] == 3
        assert counters.get("chunks_starved", 0) <= 2
        billed = st["run"]["count"] + st["run_exposed"]["count"]
        late = counters.get("chunks_late", 0)
        assert billed + late <= 3 <= billed + 2 * late
        assert st["head"]["total_s"] + st["run_exposed"]["total_s"] + st[
            "run"]["total_s"] <= st["chunk"]["total_s"]
        assert st["run"]["rows"] == st["run"]["count"] * 8 * 32
    # the last chunk of a round is fenced as soon as the one before has
    # landed: it is still running then, whatever the machine's load
    assert sum(r["stages"]["run"]["count"]
               + r["stages"]["run_exposed"]["count"] for r in recs) >= 1


def test_the_seven_stages_tile_the_chunk(telemetry):
    _, recs = telemetry
    for rec in recs[1:]:  # round 0 compiles inside ``dispatch``
        st = rec["stages"]
        covered = sum(st[name]["total_s"] for name in CHILDREN)
        assert covered == pytest.approx(st["chunk"]["total_s"], rel=0.05)
        assert covered <= st["chunk"]["total_s"]


def test_telemetry_record_validates_and_carries_the_setup_block(telemetry):
    path, recs = telemetry
    assert obs_dump.validate_telemetry(path) == []
    setup = recs[0]["setup"]
    assert set(setup) == {"conf_s", "iterators_s", "model_s",
                          "first_fence_s"}
    assert all(v > 0 for v in setup.values())
    assert recs[-1]["setup"] == setup  # lifetime block, like ``device``
    dev = recs[-1]["device"]
    assert dev["trace_seconds"] > 0 and dev["lower_seconds"] > 0
    assert dev["cache_retrieval_seconds"] == 0  # the suite runs uncached


# ----------------------------------------------------------------------
def test_every_layers_operations_carry_its_conf_name():
    """``jax.named_scope(<l<index>_<conf name or type>>)`` around each
    layer's apply: the names come back from the lowered step's text."""
    import jax

    from cxxnet_tpu import config as cfgmod
    from cxxnet_tpu.nnet.trainer import NetTrainer

    net = CONF[CONF.index("netconfig=start"):CONF.index("silent")]
    tr = NetTrainer()
    tr.set_params(cfgmod.parse_pairs(net))
    tr.init_model()
    assert [tr.net.layer_scope(i) for i in range(6)] == [
        "l0_conv1", "l1_relu", "l2_max_pooling", "l3_flatten", "l4_fc",
        "l5_softmax"]
    data = np.zeros((2, 32, 32, 32, 3), np.float32)
    labels = np.zeros((2, 32, 1), np.float32)
    fn = tr._scan_step_fn(2, True, True)
    text = fn.lower(
        tr.params, tr.ustates, tr.aux, data, labels, tr._next_rng(),
        jax.numpy.asarray(0, jax.numpy.int32)).as_text(debug_info=True)
    for scope in ("l0_conv1", "l2_max_pooling", "l4_fc"):
        assert scope in text, scope
    # forward and backward operations both sit under the layer's scope
    assert "transpose" in text and "jvp" in text
