"""Tokens the program fed a training step: the ``tokens`` counter of
the token iterator (``io/tokens.py``) / the steps, over the window's
whole rounds; every one trains (no padding).  ``None`` where the
program counts no tokens."""

LAYER = "input pipeline"
UNIT = "tokens/step"
SOURCE = "program_counter"
MOVES = "train_samples_s_chip"


def read(run):
    rows = [r["counters"] for r in run["telemetry"] if r.get("counters")]
    steps = sum(int(r.get("steps", 0)) for r in run["telemetry"])
    if not rows or not steps or not any("tokens" in c for c in rows):
        return None
    return sum(int(c.get("tokens", 0)) for c in rows) / steps
