"""From a profiler trace to numbers.

``load_events`` flattens an ``.xplane.pb`` (read with nothing but
``jax.profiler.ProfileData``) into a table of plain rows; every
reduction below works on that table, so it can be checked against a
small recorded table kept as a fixture (``fixtures/trace_events.json``).

A row is ``[plane, line, name, start_ns, dur_ns]``.  On a TPU the
device planes are named ``/device:TPU:<n>``; their line ``XLA Ops``
holds one event per executed operation and ``XLA Modules`` one per
executed program.  Host threads are lines of the ``/host:CPU`` plane.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Sequence, Tuple

Row = Sequence  # [plane, line, name, start_ns, dur_ns]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_events(xplane_path: str) -> List[list]:
    from jax.profiler import ProfileData

    rows = []
    for plane in ProfileData.from_file(xplane_path).planes:
        for line in plane.lines:
            for ev in line.events:
                rows.append([plane.name, line.name, ev.name,
                             int(ev.start_ns), int(ev.duration_ns)])
    return rows


def describe(rows: Sequence[Row]) -> Dict[str, Dict[str, int]]:
    """plane -> line -> number of events: what a trace holds, for a
    look by hand before trusting a reduction."""
    out: Dict[str, Dict[str, int]] = {}
    for plane, line, *_ in rows:
        out.setdefault(plane, {}).setdefault(line, 0)
        out[plane][line] += 1
    return out


def device_planes(rows: Sequence[Row]) -> List[str]:
    return sorted({r[0] for r in rows if r[0].startswith("/device:TPU:")
                   and r[1] == OPS_LINE})


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_intervals(rows: Sequence[Row], plane: str) -> List[Tuple[int, int]]:
    return _union([(r[3], r[3] + r[4]) for r in rows
                   if r[0] == plane and r[1] == OPS_LINE and r[4] > 0])


def busy_seconds(rows: Sequence[Row]) -> float:
    """Seconds in which an operation ran on the device: the union of
    the operations' intervals, averaged over the chips in the trace."""
    planes = device_planes(rows)
    if not planes:
        return 0.0
    tot = sum(e - s for p in planes for s, e in busy_intervals(rows, p))
    return tot / len(planes) / 1e9


def module_gap_seconds(rows: Sequence[Row]) -> Tuple[float, int]:
    """(seconds the device sat between the end of one program and the
    start of the next, number of programs), on the first chip."""
    planes = device_planes(rows)
    if not planes:
        return 0.0, 0
    mods = sorted((r[3], r[3] + r[4]) for r in rows
                  if r[0] == planes[0] and r[1] == MODULES_LINE)
    gap = sum(max(0, b[0] - a[1]) for a, b in zip(mods, mods[1:]))
    return gap / 1e9, len(mods)


def top_ops(rows: Sequence[Row], n: int = 10) -> List[list]:
    """The operations with most device time on the first chip, by name.
    A ``while`` is left out: it is the scan's loop and spans the
    operations of its body, which are counted themselves."""
    planes = device_planes(rows)
    tot: Dict[str, int] = {}
    for r in rows:
        if planes and r[0] == planes[0] and r[1] == OPS_LINE:
            name = op_name(r[2])
            if not name.startswith("while"):
                tot[name] = tot.get(name, 0) + r[4]
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in best]


def op_name(text: str) -> str:
    """``%fusion.3 = bf16[256,28,28,192]{...} fusion(...)`` ->
    ``fusion.3_bf16_256_28_28_192``: the operation and what it makes."""
    head, _, rest = text.partition(" = ")
    shape = rest.split("{", 1)[0].split(" ", 1)[0] if rest else ""
    return _clean(head.lstrip("%") + ("_" + shape if shape else "")).strip("_")


def _clean(name: str) -> str:
    keep = "".join(c if (c.isalnum() or c in ".-") else "_" for c in name)
    while "__" in keep:
        keep = keep.replace("__", "_")
    return keep[:64]


def idle_gaps(rows: Sequence[Row], span: Tuple[int, int], n: int = 5
              ) -> List[list]:
    """The longest stretches of ``span`` in which no operation ran on
    the first chip, each named by the innermost host event (a Python
    frame or a program span) that covers all of it."""
    planes = device_planes(rows)
    if not planes:
        return []
    edges = [(span[0], span[0])] + [
        (max(s, span[0]), min(e, span[1]))
        for s, e in busy_intervals(rows, planes[0])
        if e > span[0] and s < span[1]] + [(span[1], span[1])]
    gaps = sorted(((b[0] - a[1], a[1], b[0]) for a, b in zip(edges, edges[1:])
                   if b[0] > a[1]), reverse=True)[:n]
    host = [r for r in rows if r[0].startswith("/host:") and r[4] > 0]
    out = []
    for dur, s, e in gaps:
        cover = [r for r in host if r[3] <= s and r[3] + r[4] >= e]
        name = (_clean(min(cover, key=lambda r: r[4])[2]) if cover
                else "round_loop_outside_bench_spans")
        out.append([name, dur / 1e9])
    return out


def reduce(rows: Sequence[Row], steps: int) -> dict:
    """Everything the per-layer readers and the result line take from one
    trace session that covered ``steps`` training steps.  The traced
    span runs from the first event of any plane to the last."""
    timed = [r for r in rows if r[4] > 0]
    lo = min((r[3] for r in timed), default=0)
    hi = max((r[3] + r[4] for r in timed), default=0)
    gap_s, modules = module_gap_seconds(rows)
    return {"busy_s": busy_seconds(rows), "window_s": (hi - lo) / 1e9,
            "steps": steps, "gap_s": gap_s, "modules": modules,
            "top_ops": top_ops(rows, 10),
            "gaps": idle_gaps(rows, (lo, hi), 5)}
