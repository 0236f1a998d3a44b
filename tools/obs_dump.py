"""Observability dump/validate tool: metrics, telemetry, event logs.

The command-line companion of ``cxxnet_tpu/obs/`` (doc/observability.md)
— and the schema gate the ``OBS=1`` lane of ``tools/run_tier1.sh``
asserts.  Three artifact kinds:

* **metrics** — Prometheus text exposition, either scraped to a file or
  fetched live (``--metrics http://host:port/metricsz``).  The
  validator checks the exposition grammar line by line: HELP/TYPE
  placement, metric/label name syntax, label-value escaping, float
  sample values, duplicate sample detection, and histogram invariants
  (cumulative non-decreasing ``le`` buckets, ``+Inf`` == ``_count``,
  ``_sum``/``_count`` present).
* **telemetry** — the per-round ``telemetry.jsonl`` a ``telemetry=1``
  train run appends (one JSON object per line with ``ts`` / ``round``
  / ``steps`` / ``eval`` / ``stages``).
* **events** — the rotating structured event log (``event_log=...``):
  one JSON object per line with ``ts`` + ``kind``.
* **alertz** — the ``GET /alertz`` JSON body (``--alertz`` file or
  URL): configured rules with live firing state.
* **healthz** — a ``GET /healthz`` JSON body (``--healthz`` file or
  URL), single engine or fleet aggregate: closed status vocabulary
  plus the machine-readable ``reasons`` token list the fleet
  supervisor's probe parses (doc/serving.md "Serving fleet").

``--require fam1,fam2`` additionally asserts that the exposition text
carries those metric families — how the CI lane pins the device-plane
families (``xla_program_compile_seconds``, ``xla_compile_seconds_total``, ...).

``--lineage MODEL_DIR [--feedback DIR]`` answers "which requests
trained the model now serving": reads ``PUBLISHED.json``'s lineage
block (feedback-record id range + counts) and, given the feedback log
directory, resolves the range to the committed pages/shards holding
those records.

Usage:
  python tools/obs_dump.py --check --metrics /tmp/metricsz.txt \\
      --telemetry telemetry.jsonl --events events.jsonl \\
      --alertz /tmp/alertz.json --require xla_program_compile_seconds
  python tools/obs_dump.py --tail 20 --events events.jsonl
  python tools/obs_dump.py --summary --events events.jsonl
  python tools/obs_dump.py --lineage models/ --feedback loop/feedback

``--check`` exits non-zero on the first schema violation, printing
every problem found; ``--tail``/``--summary`` are the human front-end.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_NAME_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")
_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{.*\})?\s+(\S+)(?:\s+(-?\d+))?$"
)
_METRIC_KINDS = ("counter", "gauge", "histogram", "summary", "untyped")

#: keys every per-round telemetry record must carry
TELEMETRY_REQUIRED = ("ts", "round", "steps", "eval", "stages")
#: canonical pipeline stages every record's ``stages`` must include
TELEMETRY_STAGES = ("decode", "augment", "batch", "next", "copy", "stack",
                    "h2d", "dispatch", "device_wait", "metric", "chunk",
                    "head", "run", "run_exposed", "boundary")


def _parse_labels(text: str) -> Optional[Dict[str, str]]:
    """Parse ``{a="b",c="d"}``; None on malformed text (bad escapes,
    unquoted values, bad label names)."""
    if not (text.startswith("{") and text.endswith("}")):
        return None
    body = text[1:-1]
    out: Dict[str, str] = {}
    i, n = 0, len(body)
    while i < n:
        j = body.find("=", i)
        if j < 0:
            return None
        name = body[i:j].strip()
        if not _LABEL_NAME_RE.match(name):
            return None
        if j + 1 >= n or body[j + 1] != '"':
            return None
        k = j + 2
        val: List[str] = []
        while k < n:
            c = body[k]
            if c == "\\":
                if k + 1 >= n or body[k + 1] not in ('"', "\\", "n"):
                    return None
                val.append({"n": "\n"}.get(body[k + 1], body[k + 1]))
                k += 2
                continue
            if c == '"':
                break
            if c == "\n":
                return None
            val.append(c)
            k += 1
        else:
            return None
        if name in out:
            return None  # duplicate label name
        out[name] = "".join(val)
        i = k + 1
        if i < n:
            if body[i] != ",":
                return None
            i += 1
    return out


def _parse_value(text: str) -> Optional[float]:
    if text in ("+Inf", "Inf"):
        return math.inf
    if text == "-Inf":
        return -math.inf
    if text == "NaN":
        return math.nan
    try:
        return float(text)
    except ValueError:
        return None


def validate_prometheus_text(text: str) -> List[str]:
    """Return a list of problems (empty == valid exposition text)."""
    problems: List[str] = []
    types: Dict[str, str] = {}
    seen_samples: set = set()
    samples: List[Tuple[str, Dict[str, str], float]] = []
    if text and not text.endswith("\n"):
        problems.append("exposition must end with a newline")
    for ln, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            parts = line.split(" ", 3)
            if len(parts) < 3 or not _NAME_RE.match(parts[2]):
                problems.append(f"line {ln}: malformed HELP: {line!r}")
            continue
        if line.startswith("# TYPE "):
            parts = line.split(" ")
            if len(parts) != 4 or not _NAME_RE.match(parts[2]):
                problems.append(f"line {ln}: malformed TYPE: {line!r}")
                continue
            name, kind = parts[2], parts[3]
            if kind not in _METRIC_KINDS:
                problems.append(f"line {ln}: unknown metric kind {kind!r}")
            if name in types:
                problems.append(f"line {ln}: duplicate TYPE for {name}")
            types[name] = kind
            continue
        if line.startswith("#"):
            continue  # comment
        m = _SAMPLE_RE.match(line)
        if not m:
            problems.append(f"line {ln}: unparseable sample: {line!r}")
            continue
        name, labeltext, valtext = m.group(1), m.group(2), m.group(3)
        labels = _parse_labels(labeltext) if labeltext else {}
        if labels is None:
            problems.append(f"line {ln}: malformed labels: {labeltext!r}")
            continue
        value = _parse_value(valtext)
        if value is None:
            problems.append(f"line {ln}: bad sample value {valtext!r}")
            continue
        key = (name, tuple(sorted(labels.items())))
        if key in seen_samples:
            problems.append(f"line {ln}: duplicate sample {line!r}")
        seen_samples.add(key)
        samples.append((name, labels, value))
    # histogram invariants per family and labelset (excluding 'le')
    hist_names = {n for n, k in types.items() if k == "histogram"}
    for base in sorted(hist_names):
        buckets: Dict[Tuple, List[Tuple[float, float]]] = {}
        sums: Dict[Tuple, float] = {}
        counts: Dict[Tuple, float] = {}
        for name, labels, value in samples:
            rest = tuple(sorted(
                (k, v) for k, v in labels.items() if k != "le"
            ))
            if name == base + "_bucket":
                if "le" not in labels:
                    problems.append(f"{base}: bucket sample without le")
                    continue
                le = _parse_value(labels["le"])
                if le is None:
                    problems.append(
                        f"{base}: unparseable le {labels['le']!r}")
                    continue
                buckets.setdefault(rest, []).append((le, value))
            elif name == base + "_sum":
                sums[rest] = value
            elif name == base + "_count":
                counts[rest] = value
        if not buckets:
            problems.append(f"{base}: histogram with no _bucket samples")
        for rest, bl in buckets.items():
            bl.sort()
            vals = [v for _, v in bl]
            if any(vals[i + 1] < vals[i] for i in range(len(vals) - 1)):
                problems.append(
                    f"{base}{dict(rest)}: buckets not cumulative")
            if not bl or not math.isinf(bl[-1][0]):
                problems.append(f"{base}{dict(rest)}: missing +Inf bucket")
            if rest not in sums or rest not in counts:
                problems.append(f"{base}{dict(rest)}: missing _sum/_count")
            elif bl and math.isinf(bl[-1][0]) and bl[-1][1] != counts[rest]:
                problems.append(
                    f"{base}{dict(rest)}: +Inf bucket {bl[-1][1]} != "
                    f"_count {counts[rest]}")
    return problems


def _read_jsonl(path: str) -> List[Tuple[int, object]]:
    out = []
    with open(path, "r", encoding="utf-8") as f:
        for ln, line in enumerate(f, 1):
            if line.strip():
                out.append((ln, json.loads(line)))
    return out


def validate_telemetry(path: str) -> List[str]:
    """Schema-check a ``telemetry.jsonl``; returns problems (empty=ok)."""
    problems: List[str] = []
    try:
        rows = _read_jsonl(path)
    except (OSError, ValueError) as e:
        return [f"{path}: {type(e).__name__}: {e}"]
    if not rows:
        return [f"{path}: no telemetry records"]
    last_round = None
    for ln, rec in rows:
        if not isinstance(rec, dict):
            problems.append(f"line {ln}: not an object")
            continue
        for key in TELEMETRY_REQUIRED:
            if key not in rec:
                problems.append(f"line {ln}: missing key {key!r}")
        if not isinstance(rec.get("stages"), dict):
            problems.append(f"line {ln}: stages is not an object")
        else:
            for st in TELEMETRY_STAGES:
                if st not in rec["stages"]:
                    problems.append(f"line {ln}: missing stage {st!r}")
        if not isinstance(rec.get("eval"), dict):
            problems.append(f"line {ln}: eval is not an object")
        if "chunks" in rec:  # records older than the assembler lack it
            ch = rec["chunks"]
            for key in ("allocated", "recycled"):
                v = ch.get(key) if isinstance(ch, dict) else None
                if not isinstance(v, int) or v < 0:
                    problems.append(
                        f"line {ln}: chunks.{key} is not a count")
        r = rec.get("round")
        if isinstance(r, int):
            if last_round is not None and r < last_round:
                problems.append(
                    f"line {ln}: round went backwards ({last_round}->{r})")
            last_round = r
        else:
            problems.append(f"line {ln}: round is not an int")
    return problems


def exposition_families(text: str) -> set:
    """Family names present in an exposition text: TYPE declarations
    plus bare sample names (suffix-stripped for histogram parts)."""
    fams = set()
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            parts = line.split(" ")
            if len(parts) >= 3:
                fams.add(parts[2])
            continue
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        if m:
            name = m.group(1)
            fams.add(name)
            for suffix in ("_bucket", "_sum", "_count"):
                if name.endswith(suffix):
                    fams.add(name[: -len(suffix)])
    return fams


_ALERT_STATES = ("ok", "pending", "firing")
_ALERT_RULE_KEYS = ("name", "metric", "op", "threshold", "for_s", "state")


def validate_alertz(obj) -> List[str]:
    """Schema-check a ``GET /alertz`` body; returns problems."""
    problems: List[str] = []
    if not isinstance(obj, dict):
        return ["alertz: body is not an object"]
    for key in ("period_s", "rules", "firing"):
        if key not in obj:
            problems.append(f"alertz: missing key {key!r}")
    rules = obj.get("rules")
    if not isinstance(rules, list):
        problems.append("alertz: rules is not a list")
        rules = []
    names = set()
    for i, rule in enumerate(rules):
        if not isinstance(rule, dict):
            problems.append(f"alertz: rule[{i}] is not an object")
            continue
        for key in _ALERT_RULE_KEYS:
            if key not in rule:
                problems.append(f"alertz: rule[{i}] missing {key!r}")
        if rule.get("state") not in _ALERT_STATES:
            problems.append(
                f"alertz: rule[{i}] bad state {rule.get('state')!r}")
        if rule.get("op") not in (">", "<", ">=", "<="):
            problems.append(f"alertz: rule[{i}] bad op {rule.get('op')!r}")
        names.add(rule.get("name"))
    firing = obj.get("firing")
    if not isinstance(firing, list):
        problems.append("alertz: firing is not a list")
    else:
        # str() both sides: a malformed rule with no name must yield a
        # reported problem, not a None-vs-str sort TypeError
        expect = sorted(str(r.get("name")) for r in rules
                        if isinstance(r, dict)
                        and r.get("state") == "firing")
        if sorted(str(n) for n in firing) != expect:
            problems.append(
                f"alertz: firing {firing} inconsistent with rule "
                f"states {expect}")
    return problems


_HEALTH_STATUSES = ("ok", "degraded", "down", "closed")

#: stable engine degrade-reason tokens (doc/serving.md;
#: ``integrity_failed`` = golden-canary drift, doc/robustness.md
#: "Integrity plane").  ``alert:<rule>`` rides alongside for firing
#: alert rules; fleet aggregates prefix every token ``replica<i>:``
#: and additionally emit out-of-rotation replica STATES.
_HEALTH_REASON_TOKENS = ("reload_breaker_open", "mesh_rebuilding",
                         "integrity_failed")
_HEALTH_REPLICA_STATES = ("starting", "slow", "quarantined", "wedged",
                          "gone", "backoff", "failed")


def _reason_token_ok(tok: str) -> bool:
    base = tok
    m = re.match(r"^replica\d+:(.+)$", tok)
    if m:
        base = m.group(1)
        if base in _HEALTH_REPLICA_STATES:
            return True
    if base in _HEALTH_REASON_TOKENS:
        return True
    return base.startswith("alert:") and len(base) > len("alert:")


def validate_healthz(obj) -> List[str]:
    """Schema-check a ``GET /healthz`` body — single engine or fleet
    aggregate.  The machine-readable contract the fleet supervisor's
    probe (and any external load balancer) parses: a ``status`` from
    the closed vocabulary plus a ``reasons`` list of stable string
    tokens spelling out every degrade condition
    (``serve/engine.py::healthz``, ``serve/fleet.py::healthz``)."""
    problems: List[str] = []
    if not isinstance(obj, dict):
        return ["body is not an object"]
    status = obj.get("status")
    if status not in _HEALTH_STATUSES:
        problems.append(f"bad status {status!r} (want one of "
                        f"{'/'.join(_HEALTH_STATUSES)})")
    reasons = obj.get("reasons")
    if not isinstance(reasons, list) or any(
            not isinstance(x, str) for x in reasons):
        problems.append("reasons must be a list of strings")
    else:
        if status == "degraded" and not reasons:
            problems.append(
                "degraded with an empty reasons list (every degrade "
                "condition must carry a machine-readable token)")
        if status == "ok" and reasons:
            problems.append(f"ok but reasons non-empty: {reasons}")
        unknown = [t for t in reasons if not _reason_token_ok(t)]
        if unknown:
            problems.append(
                f"unknown reason token(s) {unknown} (want "
                f"{'/'.join(_HEALTH_REASON_TOKENS)}, alert:<rule>, or "
                "a replica<i>:-prefixed engine token / out-of-rotation "
                "state)")
    if not isinstance(obj.get("round"), int):
        problems.append("round missing or not an integer")
    if obj.get("fleet"):
        reps = obj.get("replicas")
        if not isinstance(reps, dict) or not isinstance(
                reps.get("total"), int):
            problems.append("fleet body needs a replicas object with "
                            "an integer total")
        elif any(not isinstance(v, int) for v in reps.values()):
            problems.append("replicas state counts must be integers")
        if not isinstance(obj.get("rotation"), int):
            problems.append("fleet body needs an integer rotation")
    else:
        # the pre-fleet fields stay alongside reasons (compat contract)
        for key in ("model", "reload_breaker"):
            if key not in obj:
                problems.append(f"missing legacy key {key!r}")
    return problems


def validate_events(path: str) -> List[str]:
    """Schema-check an event log; returns problems (empty == valid)."""
    problems: List[str] = []
    try:
        rows = _read_jsonl(path)
    except (OSError, ValueError) as e:
        return [f"{path}: {type(e).__name__}: {e}"]
    if not rows:
        return [f"{path}: no events"]
    for ln, rec in rows:
        if not isinstance(rec, dict):
            problems.append(f"line {ln}: not an object")
            continue
        if not isinstance(rec.get("ts"), (int, float)):
            problems.append(f"line {ln}: missing/bad ts")
        if not (isinstance(rec.get("kind"), str) and rec["kind"]):
            problems.append(f"line {ln}: missing/bad kind")
    return problems


# ----------------------------------------------------------------------
# lineage: PUBLISHED.json -> feedback-log pages
_SHARD_COMMIT_RE = re.compile(r"^feedback-(\d{6})\.bin\.commit$")


def _feedback_pages(feedback_dir: str) -> List[Tuple[int, Dict]]:
    """All committed page entries ``(shard_idx, entry)`` across the
    log's ``.commit`` sidecars, shard order (same trust rules as the
    reader: stop a shard at the first torn/foreign line)."""
    out: List[Tuple[int, Dict]] = []
    try:
        names = sorted(os.listdir(feedback_dir))
    except OSError:
        return out
    for n in names:
        m = _SHARD_COMMIT_RE.match(n)
        if not m:
            continue
        idx = int(m.group(1))
        try:
            with open(os.path.join(feedback_dir, n), "r",
                      encoding="utf-8") as f:
                text = f.read()
        except OSError:
            continue
        for line in text.split("\n"):
            line = line.strip()
            if not line:
                continue
            try:
                ent = json.loads(line)
            except ValueError:
                break
            # same required keys as FeedbackReader._read_commits — an
            # entry the reader would refuse must not count as trained-on
            if isinstance(ent, dict) and {"off", "bytes", "crc32",
                                          "nrec"} <= set(ent):
                out.append((idx, ent))
            else:
                break
    return out


def resolve_lineage(model_dir: str,
                    feedback_dir: str = "") -> Tuple[dict, List[str]]:
    """Answer "which requests trained the published model": the publish
    pointer's lineage block, plus (with the feedback-log dir) the
    committed pages covering the id range.  Returns ``(report,
    problems)`` — problems non-empty when the chain does not resolve."""
    problems: List[str] = []
    ptr_path = os.path.join(model_dir, "PUBLISHED.json")
    try:
        with open(ptr_path, "r", encoding="utf-8") as f:
            ptr = json.load(f)
    except (OSError, ValueError) as e:
        return {}, [f"lineage: cannot read {ptr_path}: {e}"]
    report = {
        "round": ptr.get("round"),
        "path": ptr.get("path"),
        "metric": ptr.get("metric"),
        "published_ts": ptr.get("time"),
        "lineage": ptr.get("lineage"),
    }
    lin = ptr.get("lineage")
    if not isinstance(lin, dict):
        problems.append(
            f"lineage: {ptr_path} carries no lineage block (published "
            "before the lineage format, or by a bare write)")
        return report, problems
    first, last = lin.get("first_seq"), lin.get("last_seq")
    if feedback_dir and first is not None and last is not None:
        pages = []
        covered = 0
        for idx, ent in _feedback_pages(feedback_dir):
            s0 = ent.get("seq0")
            if s0 is None:
                continue
            lo, hi = int(s0), int(s0) + int(ent["nrec"]) - 1
            if hi < first or lo > last:
                continue
            overlap = min(hi, last) - max(lo, first) + 1
            covered += overlap
            pages.append({"shard": idx, "off": ent["off"],
                          "seq": [lo, hi], "overlap": overlap})
        report["resolved"] = {
            "feedback_dir": feedback_dir,
            "pages": pages,
            "records_in_range": covered,
        }
        if not pages:
            problems.append(
                f"lineage: no committed page in {feedback_dir} covers "
                f"seq range [{first}, {last}]")
    return report, problems


# ----------------------------------------------------------------------
# human front-end
def _load_metrics_text(src: str) -> str:
    if src.startswith(("http://", "https://")):
        import urllib.request

        with urllib.request.urlopen(src, timeout=10) as r:
            return r.read().decode("utf-8")
    with open(src, "r", encoding="utf-8") as f:
        return f.read()


def _load_json_obj(src: str):
    return json.loads(_load_metrics_text(src))


def _tail(path: str, n: int) -> None:
    rows = _read_jsonl(path)
    for _, rec in rows[-n:]:
        print(json.dumps(rec, sort_keys=True))


def _summarize_events(path: str) -> None:
    counts: Dict[str, int] = {}
    first = last = None
    for _, rec in _read_jsonl(path):
        k = rec.get("kind", "?")
        counts[k] = counts.get(k, 0) + 1
        ts = rec.get("ts")
        if isinstance(ts, (int, float)):
            first = ts if first is None else min(first, ts)
            last = ts if last is None else max(last, ts)
    span = (last - first) if first is not None else 0.0
    print(f"{sum(counts.values())} event(s) over {span:.1f}s:")
    for k in sorted(counts, key=counts.get, reverse=True):
        print(f"  {counts[k]:6d}  {k}")


def _summarize_telemetry(path: str) -> None:
    rows = [rec for _, rec in _read_jsonl(path)]
    print(f"{len(rows)} round record(s)")
    if not rows:
        return
    hdr = f"{'round':>6} {'steps':>6} {'step_ms':>9} {'samp/s':>9}  eval"
    print(hdr)
    for rec in rows:
        step = rec.get("step") or {}
        ev = rec.get("eval") or {}
        evtxt = " ".join(f"{k}={v:g}" for k, v in sorted(ev.items()))
        print(f"{rec.get('round', -1):>6} {rec.get('steps', 0):>6} "
              f"{step.get('mean_ms', 0.0):>9.2f} "
              f"{step.get('samples_per_sec', 0.0):>9.1f}  {evtxt}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--check", action="store_true",
                    help="schema-validate the given artifacts; exit 1 on "
                         "any violation")
    ap.add_argument("--metrics", default="",
                    help="Prometheus exposition text: file path or URL")
    ap.add_argument("--telemetry", default="",
                    help="per-round telemetry.jsonl path")
    ap.add_argument("--events", default="", help="event-log JSONL path")
    ap.add_argument("--alertz", default="",
                    help="GET /alertz JSON body: file path or URL")
    ap.add_argument("--healthz", default="",
                    help="GET /healthz JSON body (engine or fleet): "
                         "file path or URL")
    ap.add_argument("--require", default="",
                    help="comma-separated metric families the exposition "
                         "must carry (device-plane pinning)")
    ap.add_argument("--lineage", default="",
                    help="model_dir: resolve PUBLISHED.json's "
                         "contributing-feedback lineage")
    ap.add_argument("--feedback", default="",
                    help="feedback-log dir for --lineage page resolution")
    ap.add_argument("--tail", type=int, default=0,
                    help="print the last N records of --events/--telemetry")
    ap.add_argument("--summary", action="store_true",
                    help="aggregate the given --events/--telemetry")
    args = ap.parse_args()

    if args.lineage:
        report, problems = resolve_lineage(args.lineage, args.feedback)
        print(json.dumps(report, indent=1))
        for p in problems:
            print(f"FAIL {p}", file=sys.stderr)
        return 1 if problems else 0

    if not (args.metrics or args.telemetry or args.events or args.alertz
            or args.healthz):
        ap.error("give at least one of --metrics/--telemetry/--events/"
                 "--alertz/--healthz (or --lineage)")
    if (args.tail or args.summary) and not (args.events or args.telemetry):
        ap.error("--tail/--summary need --events or --telemetry")

    if args.check:
        problems: List[str] = []
        if args.metrics:
            try:
                text = _load_metrics_text(args.metrics)
            except OSError as e:
                problems.append(f"metrics {args.metrics}: {e}")
            else:
                probs = validate_prometheus_text(text)
                if args.require:
                    fams = exposition_families(text)
                    for need in args.require.split(","):
                        need = need.strip()
                        if need and need not in fams:
                            probs.append(
                                f"required family {need!r} absent")
                problems += [f"metrics: {p}" for p in probs]
                if not probs:
                    n = sum(1 for l in text.splitlines()
                            if l and not l.startswith("#"))
                    print(f"metrics: OK ({n} samples)")
        if args.alertz:
            try:
                obj = _load_json_obj(args.alertz)
            except (OSError, ValueError) as e:
                problems.append(f"alertz {args.alertz}: {e}")
            else:
                probs = validate_alertz(obj)
                problems += [f"alertz: {p}" for p in probs]
                if not probs:
                    print(f"alertz: OK ({len(obj.get('rules', []))} "
                          f"rule(s), {len(obj.get('firing', []))} firing)")
        if args.healthz:
            try:
                obj = _load_json_obj(args.healthz)
            except (OSError, ValueError) as e:
                problems.append(f"healthz {args.healthz}: {e}")
            else:
                probs = validate_healthz(obj)
                problems += [f"healthz: {p}" for p in probs]
                if not probs:
                    kind = "fleet" if obj.get("fleet") else "engine"
                    print(f"healthz: OK ({kind}, status "
                          f"{obj.get('status')}, "
                          f"{len(obj.get('reasons', []))} reason(s))")
        if args.telemetry:
            probs = validate_telemetry(args.telemetry)
            problems += [f"telemetry: {p}" for p in probs]
            if not probs:
                print("telemetry: OK")
        if args.events:
            probs = validate_events(args.events)
            problems += [f"events: {p}" for p in probs]
            if not probs:
                print("events: OK")
        for p in problems:
            print(f"FAIL {p}", file=sys.stderr)
        return 1 if problems else 0

    if args.tail:
        _tail(args.events or args.telemetry, args.tail)
        return 0
    if args.summary:
        if args.events:
            _summarize_events(args.events)
        if args.telemetry:
            _summarize_telemetry(args.telemetry)
        return 0
    # default view: summarize whatever was given
    if args.metrics:
        print(_load_metrics_text(args.metrics), end="")
    if args.alertz:
        print(json.dumps(_load_json_obj(args.alertz), indent=1))
    if args.healthz:
        print(json.dumps(_load_json_obj(args.healthz), indent=1))
    if args.events:
        _summarize_events(args.events)
    if args.telemetry:
        _summarize_telemetry(args.telemetry)
    return 0


if __name__ == "__main__":
    sys.exit(main())
