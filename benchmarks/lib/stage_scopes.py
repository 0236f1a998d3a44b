"""Device time by ANY scope a layer names inside its own, and the
expert layers' numbers from the conf as run.

``lib/scopes.py`` sums a traced run's ``XLA Ops`` by layer and by the
five stages a mixer names (its ``STAGES``).  The routed expert layers
(``routed_experts``: ``route``, ``dispatch``, ``experts``, ``combine``,
``shared``) and attention's ``qk_norm`` / ``rotary`` name others, so
this module reads the same trace once more and keeps, a layer, the time
under every part of an event's scope path.

One thing no scope can give: the TPU compiler rewrites
``jax.lax.ragged_dot`` into kernels of its own and names them
``ragged-dot-*`` (``ragged-dot-none``, ``ragged-dot-metadata``) with
the layer's scope dropped (read from a compile for a described v5e, PR
33).  They are the grouped products, whichever layer's: their time is
kept under ``RAGGED`` and the expert readers add it to ``experts`` and
to the expert layers' total.

Everything returns ``None`` where there is nothing to read (an untraced
run, a program without the scopes): the reader then returns ``None``.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, Optional, Tuple

from benchmarks.lib import scopes, tracered

RAGGED = "ragged-dot"   # the compiler's name for a grouped product
EXPERTS = "routed_experts"

_CACHE: Dict[str, Optional[dict]] = {}


def reduce_parts(events: Iterable[Tuple[str, int, Optional[str]]]) -> dict:
    """``{"parts": {(layer index, part of the scope path): ns},
    "ragged_ns": ns}`` of one chip's ``XLA Ops``."""
    parts: Dict[Tuple[int, str], int] = {}
    ragged = 0
    for name, dur, scope in events:
        if dur <= 0 or name.lstrip("%").startswith("while"):
            continue
        if scope is not None and scope.startswith(RAGGED):
            ragged += dur
            continue
        m = scopes.LAYER.search(scope or "")
        if m is None:
            continue
        for part in set(scope[m.end():].split("/")):
            key = (int(m.group(1)), part)
            parts[key] = parts.get(key, 0) + dur
    return {"parts": parts, "ragged_ns": ragged}


def by_part(run: dict) -> Optional[dict]:
    """``lib/scopes.by_scope`` of the run, with ``parts`` and
    ``ragged_ns`` beside it."""
    got = scopes.by_scope(run)
    if got is None:
        return None
    out = got["out"]
    if out not in _CACHE:
        found = None
        dirs = sorted(glob.glob(os.path.join(out, "trace_round*")))
        if dirs:
            try:
                found = reduce_parts(scopes.device_events(
                    tracered.find_xplane(dirs[-1])))
            except (FileNotFoundError, OSError, ValueError):
                found = None
        _CACHE[out] = found
    if _CACHE[out] is None:
        return None
    return dict(got, **_CACHE[out])


def ms_per_step(run: dict, kind: str, parts: Optional[Iterable[str]] = None,
                ragged: bool = False) -> Optional[float]:
    """Device milliseconds a training step under the layers of conf
    type ``kind``: under the named ``parts`` of their scopes, or (``None``)
    under all of them; ``ragged`` adds the compiler's grouped-product
    kernels.  ``None`` where the conf has no such layer or the trace no
    time under one."""
    got = by_part(run)
    if got is None:
        return None
    idx = {i for i, t in got["types"].items() if t == kind}
    if parts is None:
        ns = sum(row["total"] for i, row in got["layers"].items() if i in idx)
    else:
        want = set(parts)
        ns = sum(v for (i, p), v in got["parts"].items()
                 if i in idx and p in want)
    if not idx or not ns:
        return None
    if ragged:
        ns += got["ragged_ns"]
    return ns / 1e6 / got["steps"]


def expert_layers(run: dict) -> Optional[Tuple[int, int]]:
    """(expert layers, experts held in each) of the conf as run."""
    out = scopes.run_dir(run)
    conf = scopes.conf_layers(out) if out else None
    if conf is None:
        return None
    text, layers = conf
    n = sum(1 for kind, _ in layers if kind == EXPERTS)
    held = [int(v) for v in re.findall(r"^\s*nheld\s*=\s*(\d+)", text, re.M)]
    if not n or len(held) != n or len(set(held)) != 1:
        return None
    return n, held[0]


def counter(run: dict, name: str) -> Optional[Tuple[int, int]]:
    """(the counter's sum, the steps) over the window's whole rounds;
    ``None`` where the program counts no such thing."""
    rows = [r.get("counters") or {} for r in run["telemetry"]]
    steps = sum(int(r.get("steps", 0)) for r in run["telemetry"])
    if not steps or not any(name in c for c in rows):
        return None
    return sum(int(c.get(name, 0)) for c in rows), steps
