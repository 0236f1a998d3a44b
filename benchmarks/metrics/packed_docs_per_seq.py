"""Documents that end in a row the program fed, per sequence: the
``docs`` counter of the token iterator (separators in the rows fed,
``io/tokens.py``) / the sequences trained, over the window's whole
rounds.  ``None`` where the program counts no documents."""

LAYER = "input pipeline"
UNIT = "docs/seq"
SOURCE = "program_counter"
MOVES = "train_samples_s_chip"


def read(run):
    rows = [r["counters"] for r in run["telemetry"] if r.get("counters")]
    seqs = sum(int(r.get("steps", 0)) for r in run["telemetry"]) * run["batch"]
    if not rows or not seqs or not any("docs" in c for c in rows):
        return None
    return sum(int(c.get("docs", 0)) for c in rows) / seqs
