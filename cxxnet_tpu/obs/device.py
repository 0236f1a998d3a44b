"""Device-plane telemetry: XLA programs, compile time, memory.

The fourth observability pillar (doc/observability.md).  The host-side
pillars (registry / spans / events) say what the PROCESS is doing; this
module says what the CHIP is being asked to do — wall-clock compile
time for every program the trainer / serve cache / loop fine-tuner
jits, live and peak device-memory watermarks where the backend reports
them, and the step programs' device time as the round loop reads it
from its own fences.
All of it lands in the shared metrics registry, so ``GET /metricsz``
exposes the device plane next to the host plane:

* ``xla_program_compile_seconds{kind,bucket}`` — cold-call wall time
  (trace + backend compile + first run) of the first dispatch of the
  most recent program of that kind and leading data dimension
  (``bucket``); ``xla_programs_total{kind}`` counts them;
* ``xla_compile_seconds_total`` / ``xla_compiles_total`` — cumulative
  backend-compile time and count, process-wide, captured exactly via
  ``jax.monitoring``'s compile-duration events (cache hits from the
  persistent compile cache do not count — they did not compile);
* ``xla_device_memory_bytes{device,stat}`` — live (``bytes_in_use``) and
  peak (``peak_bytes_in_use``) allocator watermarks from
  ``device.memory_stats()``, sampled at scrape time; absent on backends
  that do not report them (CPU);
* ``train_step_device_seconds`` — a histogram of the step program's
  device time per step, observed once for every scanned chunk the round
  loop bills a ``run`` for (``train_loop.RoundLoop``: the time between
  two fences that both blocked, ÷ the chunk's steps).  No fence of its
  own and no key: the loop's fences are there anyway.

Instrumentation is wrapper-based and fail-open: :func:`instrument` wraps
a jitted callable; the wrapped call is a straight pass-through except
the FIRST call per argument-shape signature, which is timed (the cold
call).  A program is lowered once, by that call; XLA's cost analysis
is not asked (it reads 0 FLOPs on the TPU, and cost a second lowering
of every program).  Any failure inside the accounting path is
event-logged once — telemetry must never take down the program it
measures.  With ``device_telemetry = 0`` the wrapper is a single flag
check per call.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, Optional, Sequence, Tuple

from . import events as obs_events
from .registry import registry as obs_registry

__all__ = [
    "configure",
    "enabled",
    "instrument",
    "InstrumentedJit",
    "install_compile_listener",
    "register_memory_collector",
    "observe_step",
    "mark_kernel_selected",
    "set_train_state_bytes",
    "summary",
    "device_metrics",
    "reset",
]

ConfigEntry = Tuple[str, str]

#: compile-fence buckets (seconds): cold XLA compiles run 10ms-minutes
COMPILE_BUCKETS = (0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
                   30.0, 60.0, 120.0, 300.0)
#: device-step buckets (seconds)
STEP_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                0.25, 0.5, 1.0, 2.5, 5.0, 10.0)


class _State:
    """Module config + lifetime totals (the telemetry.jsonl summary)."""

    def __init__(self) -> None:
        # CXXNET_DEVICE_TELEMETRY=0 is the environment kill switch —
        # reachable without a conf edit (CI bisection, emergency opt-out)
        import os

        self.enabled = os.environ.get(
            "CXXNET_DEVICE_TELEMETRY", "1") != "0"
        self.lock = threading.Lock()
        self.programs = 0
        self.compiles = 0
        self.compile_seconds = 0.0
        self.cold_call_seconds = 0.0
        # what a program costs before and beside its backend compile:
        # jax's own trace / lowering / cache-load durations
        self.trace_seconds = 0.0
        self.lower_seconds = 0.0
        self.cache_retrieval_seconds = 0.0


_STATE = _State()


class _DeviceMetrics:
    """Lazy registry families for the device plane (shared process-wide)."""

    def __init__(self) -> None:
        reg = obs_registry()
        self.program_compile = reg.gauge(
            "xla_program_compile_seconds",
            "Cold-call wall time (trace + compile + first run) of this "
            "kind/bucket's most recent program.",
            labelnames=("kind", "bucket"),
        )
        self.programs = reg.counter(
            "xla_programs_total",
            "Distinct (function, argument shapes) programs instrumented.",
            labelnames=("kind",),
        )
        self.compiles = reg.counter(
            "xla_compiles_total",
            "XLA backend compiles observed process-wide.")
        self.compile_seconds = reg.counter(
            "xla_compile_seconds_total",
            "Cumulative XLA backend-compile wall time, process-wide.")
        self.compile_hist = reg.histogram(
            "xla_backend_compile_seconds",
            "Per-compile backend-compile durations.",
            buckets=COMPILE_BUCKETS,
        )
        self.step_seconds = reg.histogram(
            "train_step_device_seconds",
            "Device time of one train step, from the round loop's "
            "fences (a scanned chunk's run / its steps).",
            buckets=STEP_BUCKETS,
        )


_METRICS: Optional[_DeviceMetrics] = None
_METRICS_LOCK = threading.Lock()


def device_metrics() -> _DeviceMetrics:
    global _METRICS
    with _METRICS_LOCK:
        if _METRICS is None:
            _METRICS = _DeviceMetrics()
        return _METRICS


# ----------------------------------------------------------------------
# config
def configure(cfg: Sequence[ConfigEntry]) -> None:
    """Arm from the ordered config stream (``device_telemetry``);
    unknown keys ignored."""
    for name, val in cfg:
        if name == "device_telemetry":
            _STATE.enabled = bool(int(val))
    if _STATE.enabled:
        install_compile_listener()
        register_memory_collector()


def enabled() -> bool:
    return _STATE.enabled


def reset() -> None:
    """Test isolation: restore defaults and zero the lifetime totals
    (registered listeners/collectors stay — they are idempotent)."""
    global _METRICS
    _STATE.enabled = True
    with _STATE.lock:
        _STATE.programs = 0
        _STATE.compiles = 0
        _STATE.compile_seconds = 0.0
        _STATE.cold_call_seconds = 0.0
        _STATE.trace_seconds = 0.0
        _STATE.lower_seconds = 0.0
        _STATE.cache_retrieval_seconds = 0.0
    with _METRICS_LOCK:
        _METRICS = None


# ----------------------------------------------------------------------
# process-wide compile accounting (jax.monitoring)
_LISTENER_INSTALLED = False
_LISTENER_LOCK = threading.Lock()


#: jax.monitoring duration events totalled into ``summary()`` as they
#: are (the backend compile also feeds the registry, below)
_DURATION_TOTALS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_seconds",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_seconds",
    "/jax/compilation_cache/cache_retrieval_time_sec":
        "cache_retrieval_seconds",
}


def _on_event_duration(name: str, duration: float, **_kw) -> None:
    total = _DURATION_TOTALS.get(name)
    if total is not None:
        with _STATE.lock:
            setattr(_STATE, total, getattr(_STATE, total) + duration)
        return
    if not name.endswith("backend_compile_duration"):
        return
    try:
        m = device_metrics()
        m.compiles.inc()
        m.compile_seconds.inc(duration)
        m.compile_hist.observe(duration)
        with _STATE.lock:
            _STATE.compiles += 1
            _STATE.compile_seconds += duration
    except Exception:  # noqa: BLE001 - telemetry must never raise
        pass


def install_compile_listener() -> bool:
    """Register the ``jax.monitoring`` duration listener once; every XLA
    backend compile in the process then feeds the compile counters, no
    matter which subsystem triggered it.  Returns True when installed
    (now or previously)."""
    global _LISTENER_INSTALLED
    with _LISTENER_LOCK:
        if _LISTENER_INSTALLED:
            return True
        try:
            from jax import monitoring

            monitoring.register_event_duration_secs_listener(
                _on_event_duration)
        except Exception as e:  # noqa: BLE001 - jax too old / absent
            obs_events.log_exception_once(
                "obs.device.listener", e, kind="obs.device_error")
            return False
        _LISTENER_INSTALLED = True
        return True


# ----------------------------------------------------------------------
# device-memory watermarks (scrape-time collector)
_MEM_REGISTERED = False
_MEM_LOCK = threading.Lock()

#: memory_stats keys exported, renamed to a stable label value
_MEM_STATS = (("bytes_in_use", "bytes_in_use"),
              ("peak_bytes_in_use", "peak_bytes_in_use"),
              ("bytes_limit", "bytes_limit"))


def _memory_collector():
    """Collector: ``xla_device_memory_bytes{device,stat}`` samples from
    every addressable device that reports ``memory_stats()``."""
    try:
        import jax

        samples = []
        for d in jax.local_devices():
            try:
                stats = d.memory_stats()
            except Exception:  # noqa: BLE001 - backend-dependent API
                stats = None
            if not stats:
                continue
            dev = f"{d.platform}:{d.id}"
            for key, label in _MEM_STATS:
                v = stats.get(key)
                if v is not None:
                    samples.append(({"device": dev, "stat": label},
                                    float(v)))
        if not samples:
            return []
        return [("xla_device_memory_bytes", "gauge",
                 "Device allocator watermarks from memory_stats() "
                 "(absent on backends that do not report them).",
                 samples)]
    except Exception:  # noqa: BLE001 - scrape must survive
        return []


def register_memory_collector() -> None:
    global _MEM_REGISTERED
    with _MEM_LOCK:
        if _MEM_REGISTERED:
            return
        obs_registry().register_collector(_memory_collector)
        _MEM_REGISTERED = True


# ----------------------------------------------------------------------
# per-program instrumentation
def _shape_key(args) -> tuple:
    """Hashable signature of a call's argument shapes/dtypes — the same
    granularity XLA specializes on.  Cheap: one flatten + a tuple of
    small tuples; non-array leaves key by type."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(args)
    sig = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        if shape is None:
            sig.append((type(leaf).__name__, repr(leaf)))
        else:
            sig.append((tuple(shape), str(getattr(leaf, "dtype", "?")),
                        bool(getattr(leaf, "weak_type", False))))
    return (treedef, tuple(sig))


class InstrumentedJit:
    """Accounting wrapper around one jitted callable.

    Dispatch is untouched — every call goes to the wrapped function, so
    jax's own compilation cache (and the persistent on-disk cache)
    behaves exactly as without the wrapper.  The first call per argument
    signature is additionally timed (the cold call, compile included).
    Everything lands in the shared registry labeled ``{kind, bucket}``
    where ``bucket`` is the leading dimension of the designated data
    argument (the serve cache's power-of-two bucket; the trainer's
    batch size / scan depth).
    """

    __slots__ = ("fn", "kind", "data_arg", "_seen", "_fast", "_lock",
                 "_broken")

    def __init__(self, fn: Callable, kind: str,
                 data_arg: Optional[int] = None) -> None:
        self.fn = fn
        self.kind = kind
        self.data_arg = data_arg
        self._seen: Dict[tuple, bool] = {}
        # warm-path shortcut: the data argument's (shape, dtype) is the
        # only signature dimension that varies call to call in practice,
        # so once a full signature is accounted its data key lands here
        # and steady-state calls skip the full-pytree flatten + lock.
        # Benign miss semantics: a program differing ONLY in a non-data
        # argument's shape (a wider label tensor, say) may skip its own
        # accounting — it still executes correctly through fn.
        self._fast: set = set()
        self._lock = threading.Lock()
        self._broken = False

    # pass through the AOT surface so wrapped fns stay lowerable
    def lower(self, *args, **kw):
        return self.fn.lower(*args, **kw)

    def _bucket(self, args) -> str:
        if self.data_arg is None or self.data_arg >= len(args):
            return ""
        shape = getattr(args[self.data_arg], "shape", None)
        return str(shape[0]) if shape else ""

    def _fast_key(self, args) -> Optional[tuple]:
        if self.data_arg is None or self.data_arg >= len(args):
            return None
        arr = args[self.data_arg]
        shape = getattr(arr, "shape", None)
        if shape is None:
            return None
        return (tuple(shape), str(getattr(arr, "dtype", "")))

    def __call__(self, *args):
        if not _STATE.enabled or self._broken:
            return self.fn(*args)
        fk = self._fast_key(args)
        if fk is not None and fk in self._fast:
            return self.fn(*args)
        try:
            key = _shape_key(args)
        except Exception as e:  # noqa: BLE001 - fail open, once
            self._broken = True
            obs_events.log_exception_once(
                f"obs.device.key:{self.kind}", e, kind="obs.device_error",
                program=self.kind)
            return self.fn(*args)
        with self._lock:
            fresh = key not in self._seen
            if fresh:
                # mark before the call: a concurrent caller with the
                # same shapes must not double-account the program
                self._seen[key] = True
        if not fresh:
            if fk is not None:
                self._fast.add(fk)
            return self.fn(*args)
        bucket = self._bucket(args)
        t0 = time.perf_counter()
        out = self.fn(*args)
        cold_s = time.perf_counter() - t0
        try:  # after the call: pure-Python metric and event writes
            m = device_metrics()
            m.program_compile.labels(kind=self.kind,
                                     bucket=bucket).set(cold_s)
            m.programs.labels(kind=self.kind).inc()
            with _STATE.lock:
                _STATE.programs += 1
                _STATE.cold_call_seconds += cold_s
            obs_events.emit("device.program", kind=self.kind,
                            bucket=bucket, cold_call_s=cold_s)
        except Exception as e:  # noqa: BLE001 - best-effort
            obs_events.log_exception_once(
                f"obs.device.account:{self.kind}", e,
                kind="obs.device_error", program=self.kind)
        return out


def instrument(fn: Callable, kind: str,
               data_arg: Optional[int] = None) -> Callable:
    """Wrap a jitted callable for device accounting (see
    :class:`InstrumentedJit`); also makes sure the process-wide compile
    listener is armed.  Returns ``fn`` unchanged when telemetry is
    disabled at wrap time — the zero-cost path."""
    if not _STATE.enabled:
        return fn
    install_compile_listener()
    register_memory_collector()
    return InstrumentedJit(fn, kind, data_arg=data_arg)


# ----------------------------------------------------------------------
# kernel-library selection (ops/kernels) — which Pallas kernels the
# selector activated, per backend, made scrapeable next to the
# per-kernel xla_program_*{kind="kernel_<name>"} families the A/B
# driver's instrumented standalone launches record
def mark_kernel_selected(name: str, backend: str, active: bool) -> None:
    """Publish ``kernel_selected{name,backend}`` (1 = the Pallas path
    runs, 0 = selected-off/rejected).  Called by the kernel selector at
    every dispatch decision (trace time — cheap)."""
    try:
        obs_registry().gauge(
            "kernel_selected",
            "Kernel-library selection state: 1 when the named Pallas "
            "kernel is active on this backend (kernel_lib conf + "
            "recorded verdicts + capability probe), else 0.",
            labelnames=("name", "backend"),
        ).labels(name=name, backend=backend).set(1.0 if active else 0.0)
    except Exception:  # noqa: BLE001 - telemetry must never raise
        pass


# ----------------------------------------------------------------------
# train-state residency (the ZeRO memory win, made scrapeable)
def set_train_state_bytes(per_device, total: float) -> None:
    """Publish the trainer's state-residency gauges.

    ``train_state_shard_bytes{device}`` — bytes of params + updater
    state addressable on each local device after placement (the
    ``xla_device_memory_bytes``-adjacent number CPU backends cannot
    report from ``memory_stats()``); ``train_state_total_bytes`` — what
    ONE full replica costs.  On an N-way ZeRO mesh the per-device gauge
    sits at ~total/N; per-device == total is the replicated baseline.
    Called by ``NetTrainer`` whenever state is (re)placed — init, load,
    copy — so a resume onto a different mesh re-reports immediately.
    """
    try:
        reg = obs_registry()
        g = reg.gauge(
            "train_state_shard_bytes",
            "Params + updater-state bytes resident per device "
            "(~1/N of the replicated total on a ZeRO mesh).",
            labelnames=("device",),
        )
        for dev, nbytes in sorted(per_device.items()):
            g.labels(device=dev).set(float(nbytes))
        reg.gauge(
            "train_state_total_bytes",
            "Bytes one full (replicated) copy of params + updater "
            "state costs — the ZeRO memory-win denominator.",
        ).set(float(total))
    except Exception:  # noqa: BLE001 - telemetry must never raise
        pass


# ----------------------------------------------------------------------
# the step programs' device time
def observe_step(seconds: float) -> None:
    """One observation of ``train_step_device_seconds``: a train step's
    device time as the round loop read it from its own fences (a
    scanned chunk's ``run`` ÷ its steps)."""
    if not _STATE.enabled:
        return
    try:
        device_metrics().step_seconds.observe(seconds)
    except Exception:  # noqa: BLE001 - telemetry must never raise
        pass


# ----------------------------------------------------------------------
def summary() -> Dict[str, float]:
    """Lifetime totals for the per-round telemetry record (cli.py):
    programs instrumented, backend compiles and their cumulative
    seconds, and the seconds jax spent tracing,
    lowering and loading programs from the persistent cache."""
    with _STATE.lock:
        return {
            "programs": _STATE.programs,
            "compiles": _STATE.compiles,
            "compile_seconds": round(_STATE.compile_seconds, 6),
            "cold_call_seconds": round(_STATE.cold_call_seconds, 6),
            "trace_seconds": round(_STATE.trace_seconds, 6),
            "lower_seconds": round(_STATE.lower_seconds, 6),
            "cache_retrieval_seconds": round(
                _STATE.cache_retrieval_seconds, 6),
        }
