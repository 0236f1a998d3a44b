"""Device time by the program's scopes, from a run's own trace.

``lib/tracered.reduce`` keeps a trace's busy time and its top
operations by HLO name; the scope an operation ran under
(``jax.named_scope``: a layer's ``l<index>_<name>``, a mixer's stages
inside it, the updater's ``update_<type>``) is in no event's name but
in its metadata: the device plane keeps it as the ``tf_op`` statistic of
the event's metadata entry, which ``jax.profiler.ProfileData`` does not
hand out.  The readers of the token cells' per-layer metrics need device
time by scope, and a reader is handed the run's record only, so this
module finds the run's directory the way ``run.py`` names it
(``bench_out/<workload>/seed<n>_trace<t>``, from the process's own
arguments; a test hands it as ``run["out"]``), finds the ``.xplane.pb``
through ``tracered.find_xplane``, reads the few fields it needs from
the file's wire format (``device_events``) and sums the ``XLA Ops``
events of the first chip.

Everything here returns ``None`` where there is nothing to read: an
untraced run, a program without the scopes (an older commit), a trace
whose events carry no scope.  A reader then returns ``None`` and the
metric is left out of the line.
"""

from __future__ import annotations

import glob
import importlib.util
import os
import re
import sys
from typing import Dict, Iterable, Optional, Tuple

from benchmarks.lib import tracered

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

LAYER = re.compile(r"\bl(\d+)_[A-Za-z0-9_.\-]+")
UPDATE = re.compile(r"\bupdate_[a-z]+\b")
#: the stages a ``mamba2`` layer names inside its own scope
STAGES = ("in_proj", "conv", "scan", "gate_norm", "out_proj")

_CACHE: Dict[str, Optional[dict]] = {}


def run_dir(run: dict) -> Optional[str]:
    """The directory ``run.py`` keeps this run's files in."""
    if run.get("out"):
        return run["out"]
    args = dict(zip(sys.argv[1::], sys.argv[2::]))
    try:
        return os.path.join(
            ROOT, "bench_out", args["--workload"],
            f"seed{int(args['--seed'])}_trace{int(args.get('--trace', 0))}")
    except (KeyError, ValueError):
        return None


def conf_layers(out: str) -> Optional[Tuple[str, list]]:
    """(the conf as run, [(type, name)] of its layers in order)."""
    path = os.path.join(out, "cell.conf")
    if not os.path.exists(path):
        return None
    with open(path, "r", encoding="utf-8") as f:
        text = f.read()
    layers = [tuple((m.group(1) + ":").split(":")[:2]) for m in re.finditer(
        r"^\s*layer\[[^\]]*\]\s*=\s*(\S+)", text, re.M)]
    return text, layers


def classify(scope: Optional[str]) -> Tuple[Optional[int], Optional[str]]:
    """(layer index or None, the mixer's stage / ``"update"`` / None)."""
    if scope is None:
        return None, None
    m = LAYER.search(scope)
    if m is None:
        return None, "update" if UPDATE.search(scope) else None
    parts = scope[m.end():].split("/")
    stage = next((s for s in STAGES if s in parts), None)
    return int(m.group(1)), stage


def reduce_events(events: Iterable[Tuple[str, int, Optional[str]]]) -> dict:
    """``events``: (HLO name, duration in ns, scope or None) of one
    chip's ``XLA Ops``.  A ``while`` spans its body's operations, which
    are counted themselves."""
    layers: Dict[int, Dict[str, int]] = {}
    update = other = scoped = 0
    for name, dur, scope in events:
        if dur <= 0 or name.lstrip("%").startswith("while"):
            continue
        index, stage = classify(scope)
        if index is None and stage is None:
            other += dur
            continue
        scoped += 1
        if index is None:
            update += dur
            continue
        row = layers.setdefault(index, {"total": 0})
        row["total"] += dur
        if stage:
            row[stage] = row.get(stage, 0) + dur
    return {"layers": layers, "update_ns": update, "other_ns": other,
            "scoped_events": scoped}


# -- the trace file, read as what it is: a protocol buffer ---------------
# ``jax.profiler.ProfileData`` hands out an event's own statistics; the
# scope is a statistic (``tf_op``) of the event's METADATA, which it does
# not.  So the few fields needed are read from the wire format directly
# (tensorflow/tsl/profiler/protobuf/xplane.proto): XSpace.planes = 1;
# XPlane name = 2, lines = 3, event_metadata = 4 and stat_metadata = 5
# (maps: key = 1, value = 2); XLine name = 2, events = 4; XEvent
# metadata_id = 1, duration_ps = 3; XEventMetadata name = 2, stats = 5;
# XStatMetadata name = 2; XStat metadata_id = 1, str_value = 5,
# bytes_value = 6, ref_value = 7.
def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = buf[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, the
    bytes of a length-delimited field; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, val


def _map_entry(buf) -> Tuple[int, bytes]:
    got = dict(_fields(buf))
    return got.get(1, 0), got.get(2, b"")


def device_events(xplane_path: str):
    """(HLO name, duration in ns, the operation's ``tf_op`` or None) of
    every ``XLA Ops`` event of the file's first TPU plane."""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    for field, plane in _fields(space):
        if field != 1:
            continue
        parts = list(_fields(plane))
        name = next((bytes(v).decode() for k, v in parts if k == 2), "")
        if not name.startswith("/device:TPU:"):
            continue
        stat_names = {}
        for k, v in parts:
            if k == 5:
                key, meta = _map_entry(v)
                stat_names[key] = bytes(dict(_fields(meta)).get(2, b""))
        ops = {}  # metadata id -> (HLO name, tf_op or None)
        for k, v in parts:
            if k != 4:
                continue
            key, meta = _map_entry(v)
            hlo, scope = "", None
            for mk, mv in _fields(meta):
                if mk == 2:
                    hlo = bytes(mv).decode("utf-8", "replace")
                elif mk == 5:
                    stat = dict(_fields(mv))
                    if stat_names.get(stat.get(1)) == b"tf_op":
                        text = (stat.get(5) or stat.get(6)
                                or stat_names.get(stat.get(7), b""))
                        scope = bytes(text).decode("utf-8", "replace")
            ops[key] = (hlo, scope)
        for k, v in parts:
            if k != 3:
                continue
            line = list(_fields(v))
            if next((bytes(x) for j, x in line if j == 2), b"") != \
                    tracered.OPS_LINE.encode():
                continue
            for j, ev in line:
                if j == 4:
                    got = dict(_fields(ev))
                    hlo, scope = ops.get(got.get(1), ("", None))
                    yield hlo, got.get(3, 0) // 1000, scope
        return  # the first chip


def load(xplane_path: str) -> dict:
    return reduce_events(device_events(xplane_path))


def by_scope(run: dict) -> Optional[dict]:
    """The run's traced device time by scope, with the conf's layer
    types: ``{"types": {index: type}, "layers": {index: {"total": ns,
    stage: ns}}, "update_ns", "other_ns", "steps", "conf", "out"}``."""
    t = run.get("trace")
    out = run_dir(run)
    if not t or not t.get("steps") or out is None:
        return None
    if out not in _CACHE:
        found = None
        conf = conf_layers(out)
        dirs = sorted(glob.glob(os.path.join(out, "trace_round*")))
        if conf is not None and dirs:
            try:
                found = load(tracered.find_xplane(dirs[-1]))
            except (FileNotFoundError, OSError, ValueError):
                found = None
            if found is not None and not found["scoped_events"]:
                found = None
            if found is not None:
                found["conf"] = conf[0]
                found["types"] = {i: kind for i, (kind, _) in
                                  enumerate(conf[1])}
                found["out"] = out
        _CACHE[out] = found
    got = _CACHE[out]
    return None if got is None else dict(got, steps=t["steps"])


def ms_per_step(run: dict, types: Iterable[str], stage: str = "total"
                ) -> Optional[float]:
    """Device milliseconds a training step under the layers of the
    given conf types (``stage``: one of a mixer's stages, or all)."""
    got = by_scope(run)
    if got is None:
        return None
    kinds = set(types)
    hit = [row for i, row in got["layers"].items()
           if got["types"].get(i) in kinds]
    if not hit:
        return None
    return sum(row.get(stage, 0) for row in hit) / 1e6 / got["steps"]


def reference_of(run: dict, rel: str):
    """(the reference module at ``rel``, its reading of the conf as
    run), for a reader that needs counts from the shapes."""
    got = by_scope(run)
    if got is None:
        return None
    spec = importlib.util.spec_from_file_location(
        "bench_scopes_reference", os.path.join(ROOT, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod, mod.describe(got["conf"], run["batch"])
