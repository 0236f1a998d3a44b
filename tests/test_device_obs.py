"""Device-plane telemetry tests (cxxnet_tpu/obs/device.py).

The trainer's jitted programs, the serve bucket cache's compiled
predicts, and the loop fine-tuner all flow through the same
instrumentation, so these tests assert the acceptance surface on the
CPU backend: per-program compile-time gauges labeled {kind,bucket},
the programs count, cumulative compile seconds from the jax.monitoring listener, the
step histogram fed by the round loop's fences, disabled-path passthrough, and the telemetry summary.
"""

import numpy as np
import pytest

from cxxnet_tpu import config as cfgmod
from cxxnet_tpu import serve
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.obs import device as obs_device
from cxxnet_tpu.obs.registry import registry

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


@pytest.fixture(autouse=True)
def _default_device_state():
    """Every test starts from the default (telemetry on) and leaks no
    disabled flag."""
    obs_device.configure([("device_telemetry", "1")])
    yield
    obs_device.configure([("device_telemetry", "1")])


def make_trainer(seed=0):
    tr = NetTrainer()
    tr.set_params(cfgmod.parse_pairs(MLP_CFG))
    tr.set_param("seed", str(seed))
    tr.init_model()
    return tr


def _family(name):
    return registry().snapshot().get(name, {})


def _sample(name, **labels):
    for key, v in _family(name).items():
        if all(f'{k}="{val}"' in key for k, val in labels.items()):
            return v
    return None


# ----------------------------------------------------------------------
def test_trainer_programs_report_flops_bytes_and_compile_time():
    tr = make_trainer()
    x = np.random.RandomState(0).rand(32, 1, 1, 16).astype(np.float32)
    y = np.zeros((32, 1), np.float32)
    before = obs_device.summary()
    tr.update_all(x, y)
    tr.sync()
    # the fused train step registered under its kind with the batch
    # size as the bucket, with its cold-call time
    cold = _sample("xla_program_compile_seconds",
                   kind="train_fused", bucket="32")
    assert cold and cold > 0
    assert _sample("xla_programs_total", kind="train_fused") >= 1
    # the monitoring listener accounted the backend compile
    after = obs_device.summary()
    assert after["programs"] > before["programs"]
    assert after["compiles"] > before["compiles"]
    assert after["compile_seconds"] > before["compile_seconds"]
    assert _family("xla_compile_seconds_total")[
        "xla_compile_seconds_total"] > 0
    # a second, identical-shape step is a cache hit: no new program
    tr.update_all(x, y)
    tr.sync()
    assert obs_device.summary()["programs"] == after["programs"]


def test_eval_program_and_serve_buckets_labeled_by_batch_dim():
    tr = make_trainer(seed=1)
    eng = serve.Engine(trainer=tr, max_batch_size=32, batch_timeout_ms=1)
    evals = _sample("xla_programs_total", kind="eval") or 0
    try:
        eng.predict(np.random.RandomState(1).randn(3, 16)
                    .astype(np.float32))
        eng.predict(np.random.RandomState(2).randn(7, 16)
                    .astype(np.float32))
    finally:
        eng.close()
    # 3 rows pad to bucket 4, 7 rows to bucket 8 — each bucket is its
    # own compiled program, its own labeled gauge sample and one more
    # in the count of its kind
    assert _sample("xla_program_compile_seconds",
                   kind="eval", bucket="4") > 0
    assert _sample("xla_program_compile_seconds",
                   kind="eval", bucket="8") > 0
    assert _sample("xla_programs_total", kind="eval") == evals + 2


def test_the_round_loops_fences_feed_the_step_histogram():
    """``train_step_device_seconds`` is filled by a plain scanned round,
    with no key set and no fence of its own: one observation for every
    ``run`` the round loop bills (a chunk that ran back to back with the
    one before, fenced at both ends), the run / the chunk's steps."""
    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.train_loop import RoundLoop
    from cxxnet_tpu.utils.profiler import StepTimer, pipeline_stats

    def hist(field):
        return _family("train_step_device_seconds").get(
            "train_step_device_seconds_" + field, 0.0)

    class Batches:
        """24 batches of one buffer, like a real iterator's."""

        def __init__(self, n):
            self.left = n
            self.cur = DataBatch(
                data=np.random.RandomState(3).rand(
                    256, 1024).astype(np.float32),
                label=np.zeros((256, 1), np.float32))

        def next(self):
            self.left -= 1
            return self.left >= 0

        def value(self):
            return self.cur

    tr = NetTrainer()
    tr.set_params(cfgmod.parse_pairs(
        MLP_CFG.replace("nhidden = 16", "nhidden = 1024")
        .replace("1,1,16", "1,1,1024").replace("= 32", "= 256")))
    tr.init_model()
    loop = RoundLoop(8)
    count, total = hist("count"), hist("sum")
    runs = seconds = 0
    for _ in range(2):  # the first round compiles inside its head
        pipeline_stats().reset()
        loop.begin(tr)
        loop.run(Batches(24), StepTimer())
        st = pipeline_stats().snapshot()["run"]
        runs += int(st["count"])
        seconds += st["total_s"]
    loop.close()
    # a round's last chunk is fenced the moment the one before lands
    assert runs >= 1
    assert hist("count") == count + runs
    assert hist("sum") - total == pytest.approx(seconds / 8)
    obs_device.configure([("device_telemetry", "0")])
    loop.begin(tr)
    loop.run(Batches(24), StepTimer())
    loop.close()
    assert hist("count") == count + runs  # the plane's switch holds


def test_disabled_telemetry_is_passthrough():
    obs_device.configure([("device_telemetry", "0")])
    try:
        before = obs_device.summary()
        tr = make_trainer(seed=3)
        x = np.random.RandomState(4).rand(32, 1, 1, 16).astype(np.float32)
        tr.update_all(x, np.zeros((32, 1), np.float32))
        tr.sync()
        after = obs_device.summary()
        # no program accounting happened (the jit wrapper was skipped
        # entirely at build time — zero per-call cost)
        assert after["programs"] == before["programs"]
        assert "fused" in tr._jit_cache
        assert not isinstance(tr._jit_cache["fused"],
                              obs_device.InstrumentedJit)
    finally:
        obs_device.configure([("device_telemetry", "1")])


def test_instrumented_wrapper_fails_open():
    calls = []

    class NoShape:
        """An argument the shape signature cannot be read from."""

        @property
        def shape(self):
            raise RuntimeError("no shape here")

    def fn(*args):
        calls.append(args)
        return "out"

    wrapped = obs_device.InstrumentedJit(fn, kind="t_broken")
    arg = NoShape()
    assert wrapped(arg) == "out"      # accounting failed, call fine
    assert wrapped(arg) == "out"
    assert len(calls) == 2
    # the failure was event-logged once, not raised
    from cxxnet_tpu.obs import event_log

    assert event_log().suppressed_count("obs.device.key:t_broken") >= 1


def test_a_program_is_lowered_once():
    """The wrapper never lowers: the first call is the program's one
    trace, and later calls of the same shapes none."""
    import jax

    traces = []

    def f(x):
        traces.append(x.shape)
        return x * 2

    wrapped = obs_device.instrument(jax.jit(f), kind="t_once", data_arg=0)
    assert isinstance(wrapped, obs_device.InstrumentedJit)
    for _ in range(3):
        wrapped(np.ones(5, np.float32))
    assert traces == [(5,)]
    assert _sample("xla_programs_total", kind="t_once") == 1


def test_memory_collector_absent_on_cpu_but_scrape_valid():
    """CPU reports no memory_stats, so the family must be ABSENT (not
    zero/sentinel) while the exposition stays schema-valid."""
    import os
    import sys

    obs_device.register_memory_collector()
    text = registry().render_prometheus()
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    from obs_dump import validate_prometheus_text

    assert validate_prometheus_text(text) == []
    assert "xla_device_memory_bytes{" not in text


def test_summary_totals_monotonic_and_jsonable():
    import json

    s = obs_device.summary()
    json.dumps(s)
    for key in ("programs", "compiles", "compile_seconds",
                "cold_call_seconds", "trace_seconds",
                "lower_seconds", "cache_retrieval_seconds"):
        assert key in s and s[key] >= 0
