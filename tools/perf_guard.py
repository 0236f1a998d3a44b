"""Local perf-regression sentinel over io_bench / serve_bench results.

Bench results used to live in ad-hoc JSON files nobody appended to.
This tool makes bench artifacts first-class and loss-proof:

* **history** — every run is appended to a committed-format JSONL file
  (one ``{"ts", "bench", "host", "metrics": {...}}`` object per line;
  the file is meant to be committed next to the code it measures, so a
  lost session costs one entry, not the whole series);
* **rolling baseline** — each metric is compared against the median of
  the last ``--window`` (default 5) prior entries of the same bench;
* **noise band** — a metric only counts as a regression/improvement
  when it leaves the ``--band`` (default 20%) envelope around the
  baseline, orientation-aware: ``*_per_sec``-style metrics regress
  downward, ``p50/p95/p99``/``*_ms``-style metrics regress upward;
* **verdict** — one schema-stable JSON document on stdout (and
  ``--json``): ``verdict`` is ``baseline`` (not enough history), ``ok``
  or ``regression``; regressions also emit an ``alert.perf_regression``
  structured event (``--event-log`` to persist it) and bump
  ``perf_regressions_total{bench}``.

Usage::

    python tools/io_bench.py --json /tmp/io.json
    python tools/perf_guard.py --bench io_bench --input /tmp/io.json \\
        --history bench_history.jsonl
    python tools/serve_bench.py > /tmp/serve.json
    python tools/perf_guard.py --bench serve_bench --input /tmp/serve.json
    python tools/perf_guard.py --smoke        # the OBS=1 CI lane

Exit code: 0 on ``ok``/``baseline``; 1 on schema problems, or on
``regression`` when ``--strict`` is given (CI lanes stay green on slow
hardware days unless they opt in).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import platform
import sys
import time
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

VERDICTS = ("baseline", "ok", "regression")

#: substrings marking a metric as lower-is-better (latencies, and the
#: mesh lane's compile counts — MORE compiles is the re-jit regression)
_LOWER_MARKERS = ("latency", "_ms", "p50", "p95", "p99", "wall_s",
                  "compiles", "programs", "rebuild_wall_s",
                  "restart_wall_s", "shed_ratio", "final_err",
                  "elapsed_s", "disk_bytes_final", "violations",
                  "overhead_ratio", "detect_rounds")


def lower_is_better(name: str) -> bool:
    # match against the FULL dotted name: a latency metric whose leaf
    # carries no marker (latency_ms.mean, latency_ms.max) must still
    # regress upward, not get its direction inverted
    return any(m in name for m in _LOWER_MARKERS)


# ----------------------------------------------------------------------
# flatteners: bench JSON documents -> {metric_name: float}
def _walk_numbers(prefix: str, obj, out: Dict[str, float]) -> None:
    if isinstance(obj, dict):
        for k, v in obj.items():
            _walk_numbers(f"{prefix}.{k}" if prefix else str(k), v, out)
    elif isinstance(obj, (int, float)) and not isinstance(obj, bool):
        if math.isfinite(obj):
            out[prefix] = float(obj)


def flatten_io_bench(doc: dict) -> Dict[str, float]:
    """Per-mode throughput rates from an ``io_bench --json`` report."""
    out: Dict[str, float] = {}
    for row in doc.get("results", []):
        mode = row.get("mode", "?")
        for key in ("img_per_sec", "decode_augment_per_sec"):
            v = row.get(key)
            if isinstance(v, (int, float)) and math.isfinite(v):
                out[f"{mode}.{key}"] = float(v)
    return out


def flatten_serve_bench(doc: dict) -> Dict[str, float]:
    """Throughput + latency percentiles from a serve_bench report."""
    out: Dict[str, float] = {}
    closed = doc.get("closed_loop", {})
    for leg in ("sequential", "concurrent"):
        d = closed.get(leg, {})
        for key in ("req_per_sec", "rows_per_sec"):
            v = d.get(key)
            if isinstance(v, (int, float)) and math.isfinite(v):
                out[f"closed.{leg}.{key}"] = float(v)
        _walk_numbers(f"closed.{leg}.latency_ms",
                      d.get("latency_ms", {}), out)
    v = closed.get("speedup")
    if isinstance(v, (int, float)) and math.isfinite(v):
        out["closed.speedup"] = float(v)
    _flatten_burst(doc.get("open_loop_burst", {}), out)
    return out


def _flatten_burst(burst: dict, out: Dict[str, float]) -> None:
    """The burst-profile series shared by the serve_bench and
    fleet_bench lanes: achieved rate, shed ratio (admission pressure),
    and the sustained latency percentiles."""
    v = burst.get("achieved_req_per_sec")
    if isinstance(v, (int, float)) and math.isfinite(v):
        out["burst.achieved_req_per_sec"] = float(v)
    sent, shed = burst.get("sent"), burst.get("shed")
    if (isinstance(sent, (int, float)) and sent
            and isinstance(shed, (int, float))):
        out["burst.shed_ratio"] = float(shed) / float(sent)
    _walk_numbers("burst.latency_ms", burst.get("latency_ms", {}), out)


def flatten_wire_bench(doc: dict) -> Dict[str, float]:
    """The WIRE lane's series (``serve_bench --wire-ab``): both wire
    formats' HTTP closed-loop throughput and latency, the binary/JSON
    speedup, and the bitwise score-parity bit (1.0 = equal).  A change
    that quietly erodes the zero-copy win — an extra decode copy, a
    lost keep-alive — drifts the speedup down here even while the hard
    >= 1.5x lane assertion still passes."""
    out: Dict[str, float] = {}
    ab = doc.get("wire_ab", {})
    for leg in ("json", "binary"):
        d = ab.get(leg, {})
        for key in ("req_per_sec", "rows_per_sec"):
            v = d.get(key)
            if isinstance(v, (int, float)) and math.isfinite(v):
                out[f"{leg}.{key}"] = float(v)
        _walk_numbers(f"{leg}.latency_ms", d.get("latency_ms", {}), out)
    v = ab.get("speedup")
    if isinstance(v, (int, float)) and math.isfinite(v):
        out["speedup"] = float(v)
    out["bitwise_equal_scores"] = float(
        bool(ab.get("bitwise_equal_scores")))
    _flatten_burst(doc.get("open_loop_burst", {}), out)
    return out


def flatten_mesh_parity(doc: dict) -> Dict[str, float]:
    """Wall time + compile/program counts from a ``tools/mesh_parity.py``
    verdict — the one-program claim as a banded series: a change that
    starts re-jitting per replica moves ``multi.compiles`` (orientation:
    lower is better) far outside the noise band, and the sentinel flags
    it even if the lane's exact-count assertions were ever loosened."""
    out: Dict[str, float] = {}
    for side in ("multi", "single"):
        d = doc.get(side, {})
        for key in ("wall_sec", "compiles", "programs"):
            v = d.get(key)
            if isinstance(v, (int, float)) and math.isfinite(v):
                out[f"{side}.{key}"] = float(v)
    return out


def flatten_quant_bench(doc: dict) -> Dict[str, float]:
    """The QUANT lane's series (``tools/quant_smoke.py`` /
    ``serve_bench --quant``): both legs' throughput and latency, the
    quant/f32 speedup, the weight-bytes ratio, and — when the document
    carries the export verdict — the gate's measured agreement.  A
    change that quietly shrinks the bytes win or the agreement drifts
    out of the band here even while the hard lane assertions pass."""
    out: Dict[str, float] = {}
    ab = doc.get("quant_ab", {})
    for leg in ("f32", "quant"):
        d = ab.get(leg, {})
        for key in ("req_per_sec", "rows_per_sec"):
            v = d.get(key)
            if isinstance(v, (int, float)) and math.isfinite(v):
                out[f"{leg}.{key}"] = float(v)
        _walk_numbers(f"{leg}.latency_ms", d.get("latency_ms", {}), out)
    for key in ("speedup", "bytes_ratio"):
        v = ab.get(key)
        if isinstance(v, (int, float)) and math.isfinite(v):
            out[key] = float(v)
    v = (doc.get("export") or {}).get("agreement")
    if isinstance(v, (int, float)) and math.isfinite(v):
        out["agreement"] = float(v)
    return out


def flatten_elastic(doc: dict) -> Dict[str, float]:
    """The ELASTIC lane's series (``tools/elastic_kill.py``): recovery
    cost as regression-tracked numbers — rebuild wall time (lower is
    better: a change that slows detection, teardown, or the consensus
    reload drifts it up), the recovered post-rebuild training rate, and
    the parity bit itself (crc_equal as 0/1 — a run that stops being
    bitwise equal collapses far outside any noise band)."""
    out: Dict[str, float] = {}
    out["crc_equal"] = 1.0 if doc.get("crc_equal") else 0.0
    for side in ("churn", "planned"):
        d = doc.get(side, {})
        for key in ("wall_sec", "rebuild_wall_s",
                    "recovered_samples_per_sec"):
            v = d.get(key)
            if isinstance(v, (int, float)) and math.isfinite(v):
                out[f"{side}.{key}"] = float(v)
    return out


def flatten_fleet_bench(doc: dict) -> Dict[str, float]:
    """The FLEET lane's series (``tools/fleet_smoke.py``): replica
    restart wall-clock (lower is better — a change that slows
    detection, backoff, or replica startup drifts it up), sustained
    p50/p99 under the burst profile, the achieved rate, and the shed
    ratio (admission pressure; a change that sheds much more under the
    same offered load leaves the band even while the hard zero-error
    assertions still pass)."""
    out: Dict[str, float] = {}
    v = doc.get("restart_wall_s")
    if isinstance(v, (int, float)) and math.isfinite(v):
        out["restart_wall_s"] = float(v)
    _flatten_burst(doc.get("burst", {}), out)
    return out


def flatten_async_bench(doc: dict) -> Dict[str, float]:
    """The ASYNC lane's series (``tools/async_ab.py``): the parity bit
    (crc_equal as 0/1 — a run that stops being bitwise equal collapses
    far outside any band), per-leg final error (lower is better — a
    staleness leg drifting from the sync baseline shows up here even
    inside the lane's --tol) and wall seconds, and the overlap
    micro-bench's step-wall/overlap-fraction pair (step_wall lower is
    better, overlap_fraction higher — a change that silently
    de-overlaps the dispatch pipeline drags the fraction down)."""
    out: Dict[str, float] = {}
    parity = doc.get("parity")
    if isinstance(parity, dict):
        out["parity.crc_equal"] = 1.0 if parity.get("crc_equal") else 0.0
        for key in ("sync_wall_sec", "async_wall_sec"):
            v = parity.get(key)
            if isinstance(v, (int, float)) and math.isfinite(v):
                out[f"parity.{key}"] = float(v)
    for name, leg in ((doc.get("ab") or {}).get("legs") or {}).items():
        if not isinstance(leg, dict):
            continue
        for key in ("final_err", "wall_sec", "overlap_fraction"):
            v = leg.get(key)
            if isinstance(v, (int, float)) and math.isfinite(v):
                out[f"ab.{name}.{key}"] = float(v)
    overlap = doc.get("overlap")
    if isinstance(overlap, dict):
        for key in ("sync_step_wall_sec", "async_step_wall_sec",
                    "overlap_fraction", "speedup"):
            v = overlap.get(key)
            if isinstance(v, (int, float)) and math.isfinite(v):
                out[f"overlap.{key}"] = float(v)
    return out


def flatten_tenant_bench(doc: dict) -> Dict[str, float]:
    """The TENANT lane's series (``tools/tenant_smoke.py``): per-tenant
    publish counts, the compaction yield (reclaimed shards/bytes — a
    change that silently stops compacting collapses these to zero far
    outside any band), the residual disk footprint after retention
    (lower is better: a retention bug shows up as the log growing
    again), the SLO overlay's engagement (alerts_fired/sheds must stay
    0 under the lane's light load), the crash-window CRC bit, and the
    end-to-end wall clock."""
    out: Dict[str, float] = {}
    for key in ("records", "compactions", "compacted_shards",
                "compacted_bytes", "alerts_fired", "sheds",
                "elapsed_s"):
        v = doc.get(key)
        if isinstance(v, (int, float)) and math.isfinite(v):
            out[key] = float(v)
    out["crc_ok_after_kill"] = (
        1.0 if doc.get("crc_ok_after_kill") else 0.0)
    for tname, n in (doc.get("published") or {}).items():
        if isinstance(n, (int, float)) and math.isfinite(n):
            out[f"published.{tname}"] = float(n)
    for tname, v in (doc.get("disk_bytes_final") or {}).items():
        if isinstance(v, (int, float)) and math.isfinite(v):
            out[f"disk_bytes_final.{tname}"] = float(v)
    return out


def flatten_integrity_bench(doc: dict) -> Dict[str, float]:
    """The SDC lane's series (``tools/sdc_smoke.py``): detection
    latency in rounds (lower is better — with ``integrity_every = 1``
    it must stay at 1; a cadence or vote regression drifts it up), the
    fingerprint sweep's share of the round wall clock (lower is
    better, bounded at 2% by the lane itself), the quarantine rebuild
    wall time, the bitwise-parity and canary bits as 0/1 (a run that
    stops being bit-equal, or a canary that stops detecting/
    readmitting, collapses far outside any noise band), and the
    end-to-end wall clocks."""
    out: Dict[str, float] = {}
    for key in ("detect_rounds", "overhead_ratio", "rebuild_wall_s",
                "flip_wall_sec", "clean_wall_sec"):
        v = doc.get(key)
        if isinstance(v, (int, float)) and math.isfinite(v):
            out[key] = float(v)
    for key in ("crc_equal", "canary_detected", "canary_readmitted"):
        out[key] = 1.0 if doc.get(key) else 0.0
    return out


def flatten_crash_audit(doc: dict) -> Dict[str, float]:
    """The CRASH lane's series (``tools/crash_audit.py``): coverage
    (states explored / distinct — a change that quietly shrinks the
    audited state space collapses these far outside any band),
    violations (lower is better; nonzero already hard-fails the lane,
    the series keeps the zero pinned in history), and the audit wall
    time."""
    out: Dict[str, float] = {}
    for key in ("states_explored", "distinct_states",
                "violations_count", "wall_s"):
        v = doc.get(key)
        if isinstance(v, (int, float)) and math.isfinite(v):
            out[key.replace("violations_count", "violations")] = float(v)
    return out


def flatten_elastic_crash(doc: dict) -> Dict[str, float]:
    """The elastic kill -9 crash-window series (``tools/elastic_kill.py
    --kill-checkpoint``): the torn-tmp sighting (1.0 means the SIGKILL
    really landed inside the atomic-publish window — losing it means the
    kill hook drifted off the race), the consensus round resumed from,
    restart latency (lower is better), the final CRC-valid round count,
    and the end-to-end wall clock."""
    out: Dict[str, float] = {}
    out["tmp_orphan"] = 1.0 if doc.get("tmp_orphan") else 0.0
    for key in ("resumed_from", "restart_wall_s", "rounds_final",
                "wall_sec"):
        v = doc.get(key)
        if isinstance(v, (int, float)) and math.isfinite(v):
            out[key] = float(v)
    return out


def flatten_kernel_bench(doc: dict) -> Dict[str, float]:
    """The KERNEL lane's series (``tools/kernel_ab.py``): per kernel,
    the parity bit (1.0 must stay pinned — a drop below baseline is the
    loudest possible regression), both timed legs (lower is better via
    the ``_ms`` marker) and the kernel/stock throughput ratio the
    promotion band reads."""
    out: Dict[str, float] = {}
    for k in doc.get("kernels") or []:
        name = k.get("name")
        if not name:
            continue
        out[f"{name}_parity"] = 1.0 if k.get("parity") else 0.0
        for key in ("stock_ms", "kernel_ms", "ratio"):
            v = k.get(key)
            if isinstance(v, (int, float)) and math.isfinite(v):
                out[f"{name}_{key}"] = float(v)
    return out


def flatten_dataservice_bench(doc: dict) -> Dict[str, float]:
    """The DSVC lane's series (``tools/io_bench.py --service``): the
    local-chain baseline and both service legs as img/sec (the 2-client
    aggregate is the multi-tenant amortization claim — a fall back
    toward the 1-client rate means clients stopped sharing decodes),
    plus the chunk-cache hit rate, which the lane pins > 0."""
    out: Dict[str, float] = {}
    sv = doc.get("service")
    if not isinstance(sv, dict):
        return out
    for key in ("local_img_per_sec", "service_1c_img_per_sec",
                "service_2c_img_per_sec", "blocks_produced"):
        v = sv.get(key)
        if isinstance(v, (int, float)) and math.isfinite(v):
            out[key] = float(v)
    hr = (sv.get("cache") or {}).get("hit_rate")
    if isinstance(hr, (int, float)) and math.isfinite(hr):
        out["cache_hit_rate"] = float(hr)
    return out


FLATTENERS = {"io_bench": flatten_io_bench,
              "dataservice_bench": flatten_dataservice_bench,
              "kernel_bench": flatten_kernel_bench,
              "crash_audit": flatten_crash_audit,
              "elastic_crash": flatten_elastic_crash,
              "serve_bench": flatten_serve_bench,
              "wire_bench": flatten_wire_bench,
              # the >= 10^6-request binary burst verdict
              # (fleet_smoke --no-kill --wire binary) shares the
              # fleet verdict shape but is its own series — mixing it
              # into fleet_bench would band the kill-lane numbers
              # against a different config
              "wire_burst": flatten_fleet_bench,
              "mesh_parity": flatten_mesh_parity,
              "quant_bench": flatten_quant_bench,
              "elastic": flatten_elastic,
              "fleet_bench": flatten_fleet_bench,
              "async_bench": flatten_async_bench,
              "tenant_bench": flatten_tenant_bench,
              "integrity_bench": flatten_integrity_bench}


# ----------------------------------------------------------------------
# history
def load_history(path: str, bench: str) -> List[dict]:
    """Prior entries of ``bench``, oldest first; torn/foreign lines are
    skipped (the file survives crashes and hand edits)."""
    out: List[dict] = []
    try:
        with open(path, "r", encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    ent = json.loads(line)
                except ValueError:
                    continue
                if (isinstance(ent, dict) and ent.get("bench") == bench
                        and isinstance(ent.get("metrics"), dict)):
                    out.append(ent)
    except OSError:
        pass
    return out


def append_history(path: str, entry: dict) -> None:
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "a", encoding="utf-8") as f:
        f.write(json.dumps(entry, separators=(",", ":")) + "\n")
        f.flush()
        os.fsync(f.fileno())


def _median(vals: List[float]) -> float:
    s = sorted(vals)
    n = len(s)
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


# ----------------------------------------------------------------------
# comparison
def compare(bench: str, metrics: Dict[str, float], history: List[dict],
            window: int = 5, band: float = 0.2) -> dict:
    """Build the verdict document for one run vs the rolling baseline.

    ``history`` holds PRIOR entries only (the current run is appended
    separately, after comparison — a run must never be its own
    baseline)."""
    baseline: Dict[str, float] = {}
    tail = history[-window:]
    for name in metrics:
        prior = [e["metrics"][name] for e in tail
                 if isinstance(e["metrics"].get(name), (int, float))]
        if prior:
            baseline[name] = _median(prior)
    # orientation-aware noise banding shared with the self-tuning
    # controller's keep/rollback verdicts (cxxnet_tpu/tune): a bench
    # delta the controller would keep is exactly one the sentinel
    # would call an improvement, and vice versa
    from cxxnet_tpu.tune.controller import band_verdict

    regressions, improvements = [], []
    for name, value in sorted(metrics.items()):
        base = baseline.get(name)
        if base is None or base == 0:
            continue
        ratio = value / base
        verdict_ = band_verdict(value, base, band,
                                lower_is_better=lower_is_better(name))
        row = {"metric": name, "value": value, "baseline": base,
               "ratio": round(ratio, 4)}
        if verdict_ == "worse":
            regressions.append(row)
        elif verdict_ == "better":
            improvements.append(row)
    verdict = ("baseline" if not baseline
               else "regression" if regressions else "ok")
    return {
        "bench": bench,
        "ts": time.time(),
        "host": platform.node(),
        "metrics": metrics,
        "window": window,
        "noise_band": band,
        "history_len": len(history),
        "baseline": baseline or None,
        "regressions": regressions,
        "improvements": improvements,
        "verdict": verdict,
    }


def validate_verdict(doc: dict) -> List[str]:
    """Schema problems of a verdict document (empty == valid) — what
    the CI lane asserts; throughput itself is hardware weather."""
    problems: List[str] = []
    for key in ("bench", "ts", "metrics", "window", "noise_band",
                "history_len", "regressions", "improvements", "verdict"):
        if key not in doc:
            problems.append(f"verdict: missing key {key!r}")
    if doc.get("verdict") not in VERDICTS:
        problems.append(f"verdict: bad verdict {doc.get('verdict')!r}")
    if not isinstance(doc.get("metrics"), dict) or not doc.get("metrics"):
        problems.append("verdict: metrics missing/empty")
    else:
        for k, v in doc["metrics"].items():
            if not (isinstance(v, (int, float)) and math.isfinite(v)):
                problems.append(f"verdict: metric {k}={v!r} not finite")
    for key in ("regressions", "improvements"):
        for row in doc.get(key) or []:
            for f in ("metric", "value", "baseline", "ratio"):
                if f not in row:
                    problems.append(f"verdict: {key} row missing {f!r}")
    return problems


# ----------------------------------------------------------------------
def _emit_alert(doc: dict, event_log: str = "") -> None:
    """Regression → structured event + registry counter (in this
    process; a scraping service sees it when the guard runs embedded)."""
    from cxxnet_tpu.obs import events as obs_events
    from cxxnet_tpu.obs.registry import registry

    if event_log:
        obs_events.configure([("event_log", event_log)])
    registry().counter(
        "perf_regressions_total",
        "perf_guard verdicts that found a regression.",
        labelnames=("bench",),
    ).labels(bench=doc["bench"]).inc()
    worst = max(doc["regressions"], key=lambda r: abs(r["ratio"] - 1.0))
    obs_events.emit(
        "alert.perf_regression", bench=doc["bench"],
        regressions=[r["metric"] for r in doc["regressions"]],
        worst_metric=worst["metric"], worst_ratio=worst["ratio"],
        history_len=doc["history_len"])


def run_once(bench: str, input_doc: dict, history_path: str,
             window: int, band: float, event_log: str = "") -> dict:
    metrics = FLATTENERS[bench](input_doc)
    if not metrics:
        raise ValueError(
            f"perf_guard: no {bench} metrics found in the input document")
    history = load_history(history_path, bench)
    doc = compare(bench, metrics, history, window=window, band=band)
    append_history(history_path, {
        "ts": doc["ts"], "bench": bench, "host": doc["host"],
        "metrics": metrics,
    })
    if doc["verdict"] == "regression":
        try:
            _emit_alert(doc, event_log)
        except Exception as e:  # noqa: BLE001 - the verdict still stands
            print(f"# perf_guard: alert emission failed: {e}",
                  file=sys.stderr)
    return doc


# ----------------------------------------------------------------------
def _smoke(history_path: str, window: int, band: float) -> dict:
    """Two tiny real io_bench measurements through the full pipeline:
    the first seeds the history (verdict ``baseline``), the second
    compares against it — proving append, rolling baseline, banding and
    the verdict schema on real numbers in seconds."""
    import tempfile

    import io_bench

    docs = []
    with tempfile.TemporaryDirectory() as workdir:
        io_bench.generate_imgbin(workdir, 48, 48)
        for _ in range(2):
            rate, stages = io_bench.run_epoch(workdir, 48, 0)
            bench_doc = {"results": [{
                "mode": "serial", "img_per_sec": rate,
                "decode_augment_per_sec": rate, "stages": stages,
            }]}
            docs.append(run_once("io_bench", bench_doc, history_path,
                                 window, band))
    final = docs[-1]
    final["smoke"] = {"runs": len(docs),
                      "first_verdict": docs[0]["verdict"]}
    return final


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--bench", choices=sorted(FLATTENERS),
                    default="io_bench")
    ap.add_argument("--input", default="",
                    help="bench JSON report ('-' for stdin)")
    ap.add_argument("--history", default="bench_history.jsonl",
                    help="append-only history JSONL (committed format)")
    ap.add_argument("--window", type=int, default=5,
                    help="rolling-baseline width (prior runs)")
    ap.add_argument("--band", type=float, default=0.2,
                    help="noise band around the baseline (fraction)")
    ap.add_argument("--json", dest="json_path", default="",
                    help="also write the verdict document here")
    ap.add_argument("--event-log", default="",
                    help="persist regression alert events to this JSONL")
    ap.add_argument("--strict", action="store_true",
                    help="exit 1 on a regression verdict")
    ap.add_argument("--smoke", action="store_true",
                    help="two tiny real runs end to end (CI lane)")
    args = ap.parse_args()

    if args.smoke:
        doc = _smoke(args.history, args.window, args.band)
    else:
        if not args.input:
            ap.error("--input is required (or use --smoke)")
        if args.input == "-":
            input_doc = json.load(sys.stdin)
        else:
            with open(args.input, "r", encoding="utf-8") as f:
                input_doc = json.load(f)
        doc = run_once(args.bench, input_doc, args.history,
                       args.window, args.band, event_log=args.event_log)

    problems = validate_verdict(doc)
    print(json.dumps(doc, indent=1))
    if args.json_path:
        with open(args.json_path, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=1)
    for p in problems:
        print(f"FAIL {p}", file=sys.stderr)
    if problems:
        return 1
    if args.strict and doc["verdict"] == "regression":
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
