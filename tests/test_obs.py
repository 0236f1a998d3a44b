"""Observability subsystem tests: registry, spans, events, facades.

Covers the obs/ primitives (metrics registry + Prometheus exposition,
span tracing, event log) and the satellite fixes that rode along with
them: window-consistent PercentileTracker summaries, swap-atomic
PipelineStats.reset, and the queue-depth error counter replacing the
``-1`` sentinel.  The exposition text is validated with the SAME parser
``tools/obs_dump.py --check`` uses in the OBS=1 CI lane, so the test
and the lane can never disagree about what "valid" means.
"""

import json
import os
import sys
import threading
import time

import pytest

from cxxnet_tpu.obs.events import EventLog
from cxxnet_tpu.obs.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    escape_label_value,
    registry,
)
from cxxnet_tpu.obs.trace import Tracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import obs_dump  # noqa: E402 - the CI lane's validator, under test too


@pytest.fixture(autouse=True)
def _fresh_obs_state():
    """What ran before this file in the worker must not decide its
    outcome, nor what it leaves behind the next file's: the process-wide
    tracer, alert evaluator and device-plane state start and end each
    test at their defaults.  The shared registry keeps its families —
    live code holds references to them — and renders every label-less
    one from its birth, observed or not (obs/registry.py)."""
    from cxxnet_tpu.obs import alerts as obs_alerts
    from cxxnet_tpu.obs import device as obs_device
    from cxxnet_tpu.obs import tracer

    def reset():
        tracer().reset()
        obs_alerts.reset()
        obs_device.reset()

    reset()
    yield
    reset()


# ----------------------------------------------------------------------
# PercentileTracker (the facade over obs.PercentileWindow)
def test_tracker_empty_window():
    from cxxnet_tpu.utils.profiler import PercentileTracker

    t = PercentileTracker(window=8)
    assert t.summary() == {"count": 0}
    assert t.percentiles() == {}
    assert t.count == 0


def test_tracker_window_one():
    from cxxnet_tpu.utils.profiler import PercentileTracker

    t = PercentileTracker(window=1)
    for v in (10.0, 20.0, 30.0):
        t.add(v)
    s = t.summary()
    # the window is exactly the newest sample; lifetime covers all three
    assert s["count"] == 3
    assert s["mean"] == 30.0 == s["p50"] == s["p95"] == s["p99"]
    assert s["lifetime_mean"] == pytest.approx(20.0)


def test_tracker_exact_ring_wraparound():
    from cxxnet_tpu.utils.profiler import PercentileTracker

    t = PercentileTracker(window=4)
    for v in (1.0, 2.0, 3.0, 4.0):  # fills the ring exactly
        t.add(v)
    assert t.summary()["mean"] == pytest.approx(2.5)
    for v in (10.0, 20.0, 30.0, 40.0):  # overwrites every slot once
        t.add(v)
    s = t.summary()
    assert s["count"] == 8
    # window == the second batch only; mean is window-consistent with
    # the percentiles (the old code reported the lifetime mean here)
    assert s["mean"] == pytest.approx(25.0)
    assert s["lifetime_mean"] == pytest.approx(110.0 / 8)
    assert s["p50"] == 20.0 and s["p99"] == 40.0


def test_tracker_summary_scale_applies_to_all_values():
    from cxxnet_tpu.utils.profiler import PercentileTracker

    t = PercentileTracker(window=4)
    t.add(0.5)
    s = t.summary(scale=1e3)
    assert s["mean"] == s["lifetime_mean"] == s["p50"] == 500.0


# ----------------------------------------------------------------------
# metrics registry
def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("req_total", "requests", labelnames=("outcome",))
    c.labels(outcome="ok").inc()
    c.labels(outcome="ok").inc(2)
    c.labels(outcome="shed").inc()
    assert c.labels(outcome="ok").value == 3
    with pytest.raises(ValueError):
        c.labels(outcome="ok").inc(-1)  # counters only go up
    g = reg.gauge("depth", "queue depth")
    g.set(5)
    g.dec()
    assert g.get() == 4
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    (name, labels, acc1), (_, _, acc2), (_, _, inf), (_, _, total), \
        (_, _, count) = h.samples()
    assert name == "lat_seconds_bucket" and 'le="0.1"' in labels
    assert (acc1, acc2, inf) == (1, 2, 3)  # cumulative
    assert total == pytest.approx(5.55) and count == 3


def test_registry_get_or_create_and_conflicts():
    reg = MetricsRegistry()
    a = reg.counter("x_total", "x")
    assert reg.counter("x_total") is a  # shared, not forked
    with pytest.raises(ValueError):
        reg.gauge("x_total")  # same name, different kind
    with pytest.raises(ValueError):
        reg.counter("x_total", labelnames=("k",))  # different labels
    with pytest.raises(ValueError):
        reg.counter("0bad")  # invalid metric name
    with pytest.raises(ValueError):
        reg.counter("ok_total", labelnames=("0bad",))
    h = reg.histogram("h", buckets=(1, 2))
    assert reg.histogram("h", buckets=(1, 2)) is h
    with pytest.raises(ValueError):
        reg.histogram("h", buckets=(1, 2, 3))


def test_label_escaping_and_exposition_validity():
    reg = MetricsRegistry()
    c = reg.counter("esc_total", 'tricky "help"\nwith newline',
                    labelnames=("path",))
    nasty = 'a\\b"c\nd'
    c.labels(path=nasty).inc()
    text = reg.render_prometheus()
    assert '\\\\b\\"c\\nd' in text  # escaped, single line
    assert text.count("\n# ") <= text.count("# ")  # still line-structured
    problems = obs_dump.validate_prometheus_text(text)
    assert problems == [], problems
    # the escaped value round-trips through the lane's parser
    line = [l for l in text.splitlines() if l.startswith("esc_total{")][0]
    labels = obs_dump._parse_labels(line[len("esc_total"):line.rindex(" ")])
    assert labels == {"path": nasty}
    assert escape_label_value("plain") == "plain"


def test_full_registry_exposition_is_valid():
    reg = MetricsRegistry()
    reg.counter("a_total", "a").inc()
    reg.gauge("b", "b").set(-1.5)
    reg.histogram("c_seconds", "c", labelnames=("op",),
                  buckets=(0.01, 0.1)).labels(op="x").observe(0.05)

    def collector():
        return [("d_rows_total", "counter", "collected",
                 [({"stage": "decode"}, 7)])]

    reg.register_collector(collector)
    text = reg.render_prometheus()
    assert 'd_rows_total{stage="decode"} 7' in text
    problems = obs_dump.validate_prometheus_text(text)
    assert problems == [], problems


def test_label_less_families_render_from_birth():
    """A histogram nobody has observed yet still renders its buckets:
    the live registry holds ``train_step_device_seconds`` from the first
    ``device_metrics()`` call, sampled or not, and a scrape of it must
    validate whatever ran before (the order dependence ISSUE 25 found)."""
    reg = MetricsRegistry()
    reg.histogram("t_wait_seconds", "never observed", buckets=(0.1, 1.0))
    reg.counter("t_events_total", "never incremented")
    reg.histogram("t_labeled_seconds", "no child yet", labelnames=("k",))
    text = reg.render_prometheus()
    assert 't_wait_seconds_bucket{le="+Inf"} 0' in text
    assert "t_wait_seconds_count 0" in text and "t_events_total 0" in text
    probs = obs_dump.validate_prometheus_text(text)
    assert probs == ["t_labeled_seconds: histogram with no _bucket samples"]


def test_gauge_function_failure_yields_absent_sample():
    reg = MetricsRegistry()
    g = reg.gauge("live", "live gauge")
    g.set_function(lambda: 1 / 0)
    text = reg.render_prometheus()
    assert "# TYPE live gauge" in text
    assert "\nlive " not in text  # sample absent, not a sentinel
    assert obs_dump.validate_prometheus_text(text) == []


def test_exposition_validator_catches_breakage():
    bad = "# TYPE h histogram\nh_bucket{le=\"1\"} 5\nh_bucket{le=\"+Inf\"} 3\n"
    probs = obs_dump.validate_prometheus_text(bad)
    assert any("cumulative" in p for p in probs)
    assert any("_sum/_count" in p for p in probs)
    assert obs_dump.validate_prometheus_text("x{bad} 1\n")
    assert obs_dump.validate_prometheus_text("x 1 2 3 4\n")


# ----------------------------------------------------------------------
# span tracing
def test_span_nesting_and_parent_tracking():
    t = Tracer()
    t.enable()
    with t.span("outer", round=3) as outer:
        with t.span("inner"):
            pass
        outer.set(rows=5)
    spans = {s.name: s for s in t.spans()}
    assert spans["inner"].parent_id == spans["outer"].span_id
    assert spans["outer"].parent_id is None
    assert spans["outer"].args == {"round": 3, "rows": 5}
    assert spans["inner"].dur_us <= spans["outer"].dur_us


def test_span_nesting_across_threads():
    """Parent tracking is thread-local: a span opened on a worker thread
    must not parent under the main thread's open span, and each span
    carries its own thread id for the trace viewer."""
    t = Tracer()
    t.enable()
    done = threading.Event()

    def worker():
        with t.span("worker_span"):
            pass
        done.set()

    with t.span("main_span"):
        th = threading.Thread(target=worker)
        th.start()
        th.join()
    assert done.wait(5)
    spans = {s.name: s for s in t.spans()}
    assert spans["worker_span"].parent_id is None
    assert spans["worker_span"].tid != spans["main_span"].tid
    doc = t.to_chrome_trace()
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"main_span", "worker_span", "thread_name"} <= names
    for e in doc["traceEvents"]:
        if e["name"] == "thread_name":
            continue
        assert e["ph"] == "X" and e["dur"] >= 0


def test_span_ring_is_bounded_and_disabled_is_noop():
    t = Tracer(ring=4)
    t.enable()
    for i in range(10):
        with t.span(f"s{i}"):
            pass
    assert len(t.spans()) == 4
    assert t.dropped == 6
    assert [s.name for s in t.spans()] == ["s6", "s7", "s8", "s9"]
    t2 = Tracer()  # disabled: shared no-op, nothing recorded
    with t2.span("never") as s:
        s.set(ignored=1)
    assert t2.spans() == []


def test_trace_export_and_step_window(tmp_path):
    t = Tracer()
    t.configure([("trace_dir", str(tmp_path)), ("trace_steps", "2")])
    assert t.enabled
    with t.span("step_work"):
        pass
    t.step(0)
    assert os.listdir(tmp_path) == []  # window still open
    t.step(1)
    files = os.listdir(tmp_path)
    assert len(files) == 1 and files[0].endswith(".json")
    doc = json.load(open(tmp_path / files[0]))
    assert any(e["name"] == "step_work" for e in doc["traceEvents"])
    assert not t.enabled  # one-window discipline
    t.step(2)  # idempotent after the flush
    assert len(os.listdir(tmp_path)) == 1


# ----------------------------------------------------------------------
# event log
def test_event_log_ring_and_reserved_fields():
    log = EventLog(ring=3)
    log.emit("a.b", x=1)
    rec = log.emit("c.d", kind="field-kind", ts=123)
    assert rec["kind"] == "c.d"  # the envelope wins
    assert rec["kind_"] == "field-kind" and rec["ts_"] == 123
    for i in range(5):
        log.emit("spam", i=i)
    assert len(log.recent(50)) == 3  # bounded ring
    assert log.recent(50, kind="a.b") == []  # aged out


def test_event_log_rotation(tmp_path):
    log = EventLog()
    path = str(tmp_path / "events.jsonl")
    log.configure([("event_log", path),
                   ("event_log_max_bytes", "2048"),
                   ("event_log_backups", "2")])
    for i in range(300):
        log.emit("rot.test", i=i, pad="x" * 30)
    names = sorted(os.listdir(tmp_path))
    assert names == ["events.jsonl", "events.jsonl.1", "events.jsonl.2"]
    for name in names:
        assert os.path.getsize(tmp_path / name) <= 2048 + 256
        for line in open(tmp_path / name, encoding="utf-8"):
            assert json.loads(line)["kind"] == "rot.test"
    assert log.dropped == 0
    # the validator the CI lane runs accepts what rotation produced
    assert obs_dump.validate_events(path) == []


def test_event_log_never_raises(tmp_path):
    log = EventLog()
    # a path component beyond NAME_MAX: makedirs/open fail with OSError
    log.configure([("event_log", str(tmp_path / ("n" * 300) / "x.jsonl"))])
    log.emit("unwritable", data=object())  # coerced, swallowed
    assert log.dropped >= 0  # no exception is the assertion
    assert log.recent(1)[0]["kind"] == "unwritable"


def test_emit_once_dedupes_recurring_facts():
    log = EventLog()
    assert log.emit_once("ck:/m/0007.model:crc", "checkpoint.skipped",
                         path="/m/0007.model")
    for _ in range(5):  # the reload poll hitting the same bad checkpoint
        assert not log.emit_once("ck:/m/0007.model:crc",
                                 "checkpoint.skipped", path="/m/0007.model")
    assert len(log.recent(50, kind="checkpoint.skipped")) == 1
    assert log.suppressed_count("ck:/m/0007.model:crc") == 6


def test_failed_flush_still_disables_tracing(tmp_path):
    t = Tracer()
    blocker = tmp_path / "not_a_dir"
    blocker.write_text("file where trace_dir should be")
    t.configure([("trace_dir", str(blocker / "sub")), ("trace_steps", "1")])
    with t.span("s"):
        pass
    t.step(0)  # export fails (parent is a file) — must not raise
    assert not t.enabled  # ...and must not keep paying span cost


def test_registry_snapshot_includes_collectors():
    reg = MetricsRegistry()
    reg.counter("direct_total").inc(2)
    reg.register_collector(lambda: [
        ("collected", "gauge", "", [({"stage": "x"}, 1.5)]),
    ])
    snap = reg.snapshot()
    assert snap["direct_total"] == {"direct_total": 2.0}
    assert snap["collected"] == {'collected{stage="x"}': 1.5}


def test_log_exception_once_dedupes():
    log = EventLog()
    assert log.log_exception_once("site", ValueError("boom"), kind="err")
    assert not log.log_exception_once("site", ValueError("boom"), kind="err")
    assert log.suppressed_count("site") == 2
    assert len(log.recent(50, kind="err")) == 1
    rec = log.recent(50, kind="err")[0]
    assert "boom" in rec["error"] and rec["deduped"] is True


# ----------------------------------------------------------------------
# facades: PipelineStats atomicity, queue-depth errors
def test_pipeline_stats_reset_is_swap_atomic():
    """Concurrent add() during reset(): every sample lands wholly in one
    epoch — the snapshot's count and the tracker's count can never
    disagree (the old code could add to a discarded tracker)."""
    from cxxnet_tpu.utils.profiler import PipelineStats

    ps = PipelineStats(window=64)
    stop = threading.Event()
    errors = []

    def adder():
        try:
            while not stop.is_set():
                ps.add("decode", 0.001, rows=2)
        except BaseException as e:  # noqa: BLE001 - must fail the test
            errors.append(e)

    def resetter():
        for _ in range(200):
            ps.reset()

    threads = [threading.Thread(target=adder) for _ in range(4)]
    for th in threads:
        th.start()
    try:
        resetter()
    finally:
        stop.set()
        for th in threads:
            th.join(5)
    snap = ps.snapshot()["decode"]
    # rows are recorded 2-per-add atomically with the count
    assert snap["rows"] == 2 * snap["count"]
    if snap["count"]:
        assert "mean_ms" in snap and "lifetime_mean_ms" in snap
    assert not errors


def test_serving_stats_queue_depth_error_counter():
    from cxxnet_tpu.serve.metrics import ServingStats

    s = ServingStats()
    s.bind_queue_depth(lambda: 7)
    snap = s.snapshot()
    assert snap["queue_depth"] == 7 and snap["queue_depth_errors"] == 0

    def broken():
        raise RuntimeError("gauge wiring broke")

    s.bind_queue_depth(broken)
    snap = s.snapshot()
    assert "queue_depth" not in snap  # no -1 sentinel
    assert snap["queue_depth_errors"] == 1
    s.snapshot()
    assert s.snapshot()["queue_depth_errors"] == 3
    # the failure was event-logged once, not per scrape
    from cxxnet_tpu.obs import event_log

    recs = event_log().recent(50, kind="serve.gauge_error")
    assert len(recs) == 1 and "gauge wiring broke" in recs[0]["error"]


def test_serving_stats_feeds_shared_registry():
    from cxxnet_tpu.obs import registry
    from cxxnet_tpu.serve.metrics import ServingStats

    s = ServingStats()
    before = registry().counter(
        "serve_request_outcomes_total", labelnames=("outcome",)
    ).labels(outcome="ok").value
    s.record_request(4)
    s.record_outcome("ok", latency_s=0.005)
    after = registry().counter(
        "serve_request_outcomes_total", labelnames=("outcome",)
    ).labels(outcome="ok").value
    assert after == before + 1
    text = registry().render_prometheus()
    assert obs_dump.validate_prometheus_text(text) == [], "live registry"
    assert "serve_request_latency_seconds_bucket" in text


# ----------------------------------------------------------------------
# telemetry / event schema validators (the OBS=1 lane contract)
def test_validate_telemetry(tmp_path):
    good = {
        "ts": 1.0, "round": 0, "steps": 4, "eval": {"train-error": 0.5},
        "stages": {st: {"count": 0} for st in obs_dump.TELEMETRY_STAGES},
    }
    p = tmp_path / "telemetry.jsonl"
    with open(p, "w") as f:
        f.write(json.dumps(good) + "\n")
        f.write(json.dumps({**good, "round": 1}) + "\n")
    assert obs_dump.validate_telemetry(str(p)) == []
    with open(p, "a") as f:
        f.write(json.dumps({**good, "round": 0}) + "\n")  # backwards
    assert any("backwards" in x for x in obs_dump.validate_telemetry(str(p)))
    bad = dict(good)
    del bad["stages"]
    with open(p, "w") as f:
        f.write(json.dumps(bad) + "\n")
    assert obs_dump.validate_telemetry(str(p))
    assert obs_dump.validate_telemetry(str(tmp_path / "missing.jsonl"))


def test_validate_events_schema(tmp_path):
    p = tmp_path / "events.jsonl"
    with open(p, "w") as f:
        f.write(json.dumps({"ts": 1.0, "kind": "a"}) + "\n")
    assert obs_dump.validate_events(str(p)) == []
    with open(p, "a") as f:
        f.write(json.dumps({"ts": "notanumber", "kind": ""}) + "\n")
    probs = obs_dump.validate_events(str(p))
    assert any("ts" in x for x in probs) and any("kind" in x for x in probs)


# ----------------------------------------------------------------------
# concurrent scrapes (ISSUE 7 satellite): /metricsz + /alertz bodies
# rendered while worker threads hammer every pillar
def test_concurrent_scrapes_with_live_writers():
    """Concurrent exposition + alert-status reads while spans, events,
    counters and histograms are being recorded from worker threads: no
    torn exposition (every scrape parses clean), no deadlock, and the
    alert evaluator keeps evaluating throughout."""
    import json as _json

    from cxxnet_tpu.obs import alerts as obs_alerts
    from cxxnet_tpu.obs import device as obs_device
    from cxxnet_tpu.obs import emit, span, tracer

    sys.path.insert(0, os.path.join(REPO, "tools"))
    from obs_dump import validate_alertz, validate_prometheus_text

    tracer().enable(ring=256)
    reg = registry()
    c = reg.counter("t_scrape_total", "scrape test", labelnames=("k",))
    h = reg.histogram("t_scrape_seconds", "scrape test")
    obs_alerts.reset()
    ev = obs_alerts.evaluator()
    ev.configure([("alert", "t_scrape_busy:t_scrape_rate:>:1e12")])
    stop = threading.Event()
    errors = []

    def writer(i):
        k = f"w{i}"
        while not stop.is_set():
            try:
                c.labels(k=k).inc()
                h.observe(0.001 * i)
                with span("t.scrape", worker=i):
                    emit("t.scrape", worker=i)
            except Exception as e:  # noqa: BLE001 - collected below
                errors.append(e)
                return

    def scraper():
        while not stop.is_set():
            try:
                text = reg.render_prometheus()
                probs = validate_prometheus_text(text)
                if probs:
                    errors.append(AssertionError(probs[:3]))
                    return
                body = _json.loads(_json.dumps(ev.status()))
                probs = validate_alertz(body)
                if probs:
                    errors.append(AssertionError(probs[:3]))
                    return
                ev.evaluate_once()
            except Exception as e:  # noqa: BLE001 - collected below
                errors.append(e)
                return

    threads = ([threading.Thread(target=writer, args=(i,))
                for i in range(4)]
               + [threading.Thread(target=scraper) for _ in range(3)])
    for t in threads:
        t.start()
    time.sleep(1.0)
    stop.set()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive(), "scrape/writer thread deadlocked"
    assert errors == []
    assert ev.evaluations > 0
    # the device-plane families render alongside without tearing either
    obs_device.device_metrics()
    assert validate_prometheus_text(reg.render_prometheus()) == []
    obs_alerts.reset()
    tracer().reset()
