"""The program's own host stages, read from its telemetry records.

The round loop and the trainer bill every host stage of a training
chunk to ``PipelineStats`` where it happens (``utils/profiler.stage``),
and ``telemetry = 1`` writes each round's totals into
``telemetry.jsonl`` under ``stages``.  ``record["telemetry"]`` holds
the records of the window's whole rounds; a reader in ``metrics/``
turns one stage into milliseconds per training step.  A program that
does not bill the stage (an older commit, another path through the
loop) gives ``None``, and the metric is then left out of the line.
"""

from __future__ import annotations

from typing import Optional, Sequence

#: the stages billed on the round loop's thread, which tile ``chunk``
CHILDREN = ("next", "copy", "stack", "h2d", "dispatch", "device_wait",
            "metric")


def seconds(run: dict, stage: str) -> Optional[float]:
    """Seconds inside ``stage`` over the window's whole rounds, or
    ``None`` where it never ran."""
    rows = [r.get("stages", {}).get(stage, {}) for r in run["telemetry"]]
    if not sum(s.get("count", 0) for s in rows):
        return None
    return sum(s.get("total_s", 0.0) for s in rows)


def steps(run: dict) -> int:
    return sum(int(r.get("steps", 0)) for r in run["telemetry"])


def ms_per_step(run: dict, stage: str) -> Optional[float]:
    s, n = seconds(run, stage), steps(run)
    return None if s is None or not n else 1e3 * s / n


def self_ms_per_step(run: dict, parent: str = "chunk",
                     children: Sequence[str] = CHILDREN) -> Optional[float]:
    """The parent's self time: its seconds minus what its children
    cover, per training step."""
    s, n = seconds(run, parent), steps(run)
    if s is None or not n:
        return None
    return 1e3 * (s - sum(seconds(run, c) or 0.0 for c in children)) / n
