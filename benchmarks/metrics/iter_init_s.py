"""Seconds inside ``LearnTask._create_iterators`` (every iterator's
``init``: the synthetic set drawn, a shard opened), from the ``setup``
block the program carries in every telemetry record."""

LAYER = "input pipeline"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
    tele = run["telemetry"]
    setup = tele[0].get("setup") if tele else None
    if not setup or "iterators_s" not in setup:
        return None
    return float(setup["iterators_s"])
