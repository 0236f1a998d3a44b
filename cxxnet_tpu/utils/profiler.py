"""Step timing + XLA trace capture: the tracing/profiling subsystem.

The reference's only observability was elapsed-time progress lines every
``print_step`` batches (``/root/reference/src/cxxnet_main.cpp:378-386``)
and a ``GetTime`` helper (``src/utils/timer.h``).  SURVEY §5 calls for the
TPU-native upgrade: per-step wall-time statistics plus on-demand XLA
profiler traces (xplane protos viewable in TensorBoard/XProf).

Config keys (all global):

* ``profile = 1`` — capture a jax.profiler trace window: the device's
  planes and the program's own spans (``train.*``, the stages below) on
  one clock; no Python frames, which slow the host they are meant to
  show
* ``profile_dir = <dir>`` — trace output dir (default ``profile_out``)
* ``profile_start = 5`` — global step index to start the trace
* ``profile_steps = 10`` — number of steps to trace
"""

from __future__ import annotations

import sys
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

from ..obs import trace as _obs_trace
from ..obs.registry import PercentileWindow, registry as _obs_registry

ConfigEntry = Tuple[str, str]


class PercentileTracker(PercentileWindow):
    """Thread-safe sliding-window percentile estimator (serving latency).

    A thin facade over :class:`cxxnet_tpu.obs.registry.PercentileWindow`
    — the shared observability primitive — kept under its historical
    name so serving and pipeline call sites read unchanged.  Unlike
    :class:`StepTimer` (one round of a single-threaded train loop) this
    is written for many concurrent request threads recording into one
    tracker for the whole server lifetime, so it is locked and bounded.

    ``summary()`` reports a window-consistent ``mean`` (same samples as
    p50/p95/p99) plus the all-time ``lifetime_mean``/``count`` — the old
    mixed report (lifetime mean next to window percentiles) read as a
    contradiction whenever behavior shifted mid-run."""


class PipelineStats:
    """Per-stage host timing of a training round, each stage on a
    :class:`PercentileTracker` with total-time and row accounting (plus
    any custom stage name).  ``decode`` / ``augment`` / ``batch`` are
    billed by the iterator's threads and overlap the rest; ``next`` /
    ``copy`` / ``stack`` / ``h2d`` / ``dispatch`` / ``device_wait`` /
    ``metric`` are billed on the round loop's thread through
    :func:`stage` and tile ``chunk``, the fence-to-fence period of one
    dispatch; ``head`` (a round's first feed, inside its first chunk)
    and ``boundary`` (between rounds, in no chunk) are the loop's too,
    and ``run`` / ``run_exposed`` are the DEVICE's time as the loop
    reads it from its own fences, with no span on the host
    (doc/observability.md has the table).

    One process-wide instance (:func:`pipeline_stats`) so the io/ chain,
    the trainer's transfer path, and the CLI's round loop all record
    into the same registry without plumbing.  Thread-safe — decode pool
    workers record concurrently.  A stage's ``rows_per_sec`` is its
    LOCAL rate (rows / time spent inside the stage), i.e. what the
    stage could sustain if it were the only bottleneck; comparing
    stages shows where the host pipeline's time actually goes
    (``tools/io_bench.py`` emits the same snapshot as JSON).
    """

    STAGES = ("decode", "augment", "batch", "next", "copy", "stack",
              "h2d", "dispatch", "device_wait", "metric", "chunk",
              "head", "run", "run_exposed", "boundary")

    def __init__(self, window: int = 2048) -> None:
        self._window = window
        self._lock = threading.Lock()
        self._stages: Dict[str, list] = {}  # name -> [tracker, total_s, rows]
        self._counts: Dict[str, int] = {}

    def count(self, name: str, n: int = 1) -> None:
        """Add ``n`` to the round's counter ``name``: what is counted
        and is no stage's time (``io/tokens.py``: tokens, documents,
        documents cut; ``nnet/trainer.py``: ``metric_rows``, the rows
        whose train metrics were scored, and ``metric_rows_device``,
        those scored inside a step program; ``train_loop.py``:
        ``chunks_fenced``, the scanned chunks the loop blocked on,
        ``chunks_overlapped``, those whose fence found a later chunk
        already dispatched, ``chunks_dispatched``, ``chunks_starved``,
        those dispatched mid-round onto a device that had run dry, and
        ``chunks_late``, those whose fence found them done)."""
        with self._lock:
            self._counts[name] = self._counts.get(name, 0) + int(n)

    def counters(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)

    def add(self, stage: str, dt_s: float, rows: int = 1) -> None:
        # the whole record happens under the lock: a concurrent reset()
        # swaps the stage dict, and an add must land entirely in one
        # epoch's dict — recording the tracker outside the lock let a
        # reset discard the entry between the totals and the sample
        with self._lock:
            ent = self._stages.get(stage)
            if ent is None:
                ent = [PercentileTracker(self._window), 0.0, 0]
                self._stages[stage] = ent
            ent[1] += float(dt_s)
            ent[2] += int(rows)
            ent[0].add(dt_s)

    def reset(self) -> None:
        """Start a new accounting epoch.  Swap-atomic: the old stage
        dict is replaced wholesale under the lock, so an ``add()``
        racing from a decode-pool worker lands either entirely in the
        discarded epoch or entirely in the new one — never half in
        each, and never into a tracker the snapshot can no longer
        reach."""
        with self._lock:
            self._stages = {}
            self._counts = {}

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        """``{stage: {count, rows, total_s, rows_per_sec, mean_ms,
        p50_ms, p95_ms, p99_ms}}`` — every canonical stage is present
        (zeroed when it never ran) so consumers can rely on the schema."""
        with self._lock:
            items = {k: (ent[0], ent[1], ent[2])
                     for k, ent in self._stages.items()}
        out: Dict[str, Dict[str, float]] = {}
        for name in (*self.STAGES, *sorted(set(items) - set(self.STAGES))):
            if name not in items:
                out[name] = {"count": 0, "rows": 0, "total_s": 0.0,
                             "rows_per_sec": 0.0}
                continue
            tracker, total_s, rows = items[name]
            row = {
                "count": float(tracker.count),
                "rows": float(rows),
                "total_s": total_s,
                "rows_per_sec": rows / total_s if total_s > 0 else 0.0,
            }
            summ = tracker.summary(scale=1e3)
            for k, v in summ.items():
                if k != "count":
                    row[f"{k}_ms"] = v
            out[name] = row
        return out

    def report(self) -> str:
        """One line per active stage: local rows/sec + mean ms/op."""
        parts = []
        for name, row in self.snapshot().items():
            if not row["count"]:
                continue
            parts.append(
                f"{name} {row['rows_per_sec']:.0f} rows/s "
                f"({row.get('mean_ms', 0.0):.2f} ms/op)"
            )
        return " | ".join(parts)

    def collect(self):
        """Scrape-time exporter for the metrics registry (registered on
        the process-wide instance), labeled ``{stage=...}`` —
        ``/metricsz`` coverage without double-writing every sample.
        Everything exports as GAUGES: the totals are per-epoch (the
        round loop calls :meth:`reset` each round), and a counter that
        sawtooths to zero would poison ``rate()``/``increase()`` on any
        Prometheus-compatible scraper."""
        snap = self.snapshot()
        fams = []
        for name, kind, help_, field in (
            ("pipeline_stage_rows", "gauge",
             "Rows processed per host-pipeline stage (current epoch; "
             "resets each round).", "rows"),
            ("pipeline_stage_seconds", "gauge",
             "Seconds spent inside each host-pipeline stage "
             "(current epoch; resets each round).", "total_s"),
            ("pipeline_stage_mean_ms", "gauge",
             "Window-mean milliseconds per operation, per stage.",
             "mean_ms"),
            ("pipeline_stage_p99_ms", "gauge",
             "Window p99 milliseconds per operation, per stage.",
             "p99_ms"),
        ):
            samples = [({"stage": st}, row[field])
                       for st, row in snap.items() if field in row]
            fams.append((name, kind, help_, samples))
        return fams


_PIPELINE_STATS = PipelineStats()
_obs_registry().register_collector(_PIPELINE_STATS.collect)


def pipeline_stats() -> PipelineStats:
    """The process-wide per-stage pipeline timing registry."""
    return _PIPELINE_STATS


def _annotate(label: str, args: dict):
    """An entered ``jax.profiler.TraceAnnotation``, or None in a process
    that never imported jax (no profiler session can be open there, and
    the io/ tools that only time iterators stay free of it)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    ann = jax.profiler.TraceAnnotation(label, **args)
    ann.__enter__()
    return ann


class Stage:
    """One timed host stage of training, with three sinks: on exit it
    bills ``pipeline_stats().add(name, dt, rows)``; it is the
    ``obs.trace`` span ``train.<name>`` (parent tracked per thread) when
    ``trace_dir`` tracing is on; and it is a
    ``jax.profiler.TraceAnnotation("train.<name>", **args)``, so in any
    profiler session (``profile = 1``, a benchmark's) the span lies in
    the xplane's host plane on the device trace's clock.  With neither
    on it costs two ``perf_counter`` calls, one locked ``add`` and a
    no-op annotation.

    Use :func:`stage` as a context manager.  A stage that cannot be a
    ``with`` block (the round loop's fence-to-fence ``chunk``, its
    ``head`` and ``boundary``) calls :meth:`begin` and then :meth:`end`,
    or :meth:`drop` to close the spans without billing (``round``: a
    span and an annotation only)."""

    __slots__ = ("name", "rows", "args", "_t0", "_span", "_ann")

    def __init__(self, name: str, rows: int, args: dict) -> None:
        self.name = name
        self.rows = rows
        self.args = args

    def begin(self) -> "Stage":
        label = "train." + self.name
        self._span = _obs_trace.span(label, **self.args)
        self._span.__enter__()
        self._ann = _annotate(label, self.args)
        self._t0 = time.perf_counter()
        return self

    def drop(self) -> float:
        """Close the spans; returns the seconds since :meth:`begin`."""
        dt = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self._span.__exit__(None, None, None)
        return dt

    def end(self, rows: Optional[int] = None) -> float:
        """Close the spans and bill the stage; returns its seconds."""
        if rows is not None:
            self.rows = rows
        dt = self.drop()
        _PIPELINE_STATS.add(self.name, dt, self.rows)
        return dt

    __enter__ = begin

    def __exit__(self, *exc) -> None:
        self.end()


def stage(name: str, rows: int = 0, **args) -> Stage:
    """``with stage("h2d", rows=n, step=first_step): ...`` — see
    :class:`Stage`.  ``args`` go to both span systems; the stages of one
    chunk carry its first global step as ``step``."""
    return Stage(name, rows, args)


class StepTimer:
    """Wall-clock statistics over training steps (one round at a time)."""

    def __init__(self) -> None:
        self._times: List[float] = []

    def add(self, dt: float, n_steps: int = 1) -> None:
        """Record a span covering ``n_steps`` steps — the round loop
        calls this at every fence (``train_loop.RoundLoop._lap``) — as
        ``n_steps`` entries of the per-step average, so the round
        statistics stay per-step comparable."""
        per = dt / max(1, n_steps)
        self._times.extend([per] * max(1, n_steps))

    def clear(self) -> None:
        self._times = []

    @property
    def count(self) -> int:
        return len(self._times)

    def summary(self, batch_size: int = 0) -> Dict[str, float]:
        """mean/p50/p99 step ms (+ samples/sec if batch_size given).

        The first step of a round is dropped when there are enough
        samples — it absorbs compile time.
        """
        if not self._times:
            return {}
        ts = sorted(self._times[1:] if len(self._times) > 4 else self._times)
        n = len(ts)
        mean = sum(ts) / n
        out = {
            "steps": float(len(self._times)),
            "mean_ms": mean * 1e3,
            "p50_ms": ts[n // 2] * 1e3,
            "p99_ms": ts[min(n - 1, int(n * 0.99))] * 1e3,
        }
        if batch_size:
            out["samples_per_sec"] = batch_size / mean
        return out

    def report(self, batch_size: int = 0) -> str:
        s = self.summary(batch_size)
        if not s:
            return ""
        msg = (
            f"step {s['mean_ms']:.1f} ms avg "
            f"(p50 {s['p50_ms']:.1f}, p99 {s['p99_ms']:.1f})"
        )
        if "samples_per_sec" in s:
            msg += f", {s['samples_per_sec']:.1f} samples/sec"
        return msg


class TraceController:
    """Starts/stops a jax.profiler trace over a configured step window."""

    def __init__(self) -> None:
        self.enabled = 0
        self.trace_dir = "profile_out"
        self.start_step = 5
        self.num_steps = 10
        self._active = False
        self._done = False

    def set_param(self, name: str, val: str) -> None:
        if name == "profile":
            self.enabled = int(val)
        elif name == "profile_dir":
            self.trace_dir = val
        elif name == "profile_start":
            self.start_step = int(val)
        elif name == "profile_steps":
            self.num_steps = int(val)

    def configure(self, cfg: Sequence[ConfigEntry]) -> None:
        for n, v in cfg:
            self.set_param(n, v)

    def step(self, global_step: int) -> None:
        """Call once per training step with the global step index."""
        if not self.enabled or self._done:
            return
        import jax

        if not self._active and global_step >= self.start_step:
            opts = jax.profiler.ProfileOptions()
            # the Python tracer doubled a chunk's period and host level
            # 2 adds the runtime's own waits (PERF.md, PR 24): a session
            # that slows the host misreports the device's idle time, and
            # the stages name the gaps
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._active = True
            self._stop_at = global_step + self.num_steps
        elif self._active and global_step >= self._stop_at:
            jax.profiler.stop_trace()
            self._active = False
            self._done = True

    def close(self) -> None:
        if self._active:
            import jax

            jax.profiler.stop_trace()
            self._active = False
            self._done = True
