"""Rows a second through decode and augment while they run:
``PipelineStats`` stages ``decode`` + ``augment`` of the window's whole
rounds, rows / the two stages' seconds (the native decode pool bills
its rows to ``augment`` alone).  Image mixes only: a mix with no
decoding has nothing to read."""

LAYER = "input pipeline"
UNIT = "rows/s"
SOURCE = "program_span"
MOVES = "train_samples_s_chip"


def read(run):
    rows = secs = 0.0
    for r in run["telemetry"]:
        dec = r.get("stages", {}).get("decode", {})
        aug = r.get("stages", {}).get("augment", {})
        rows += max(dec.get("rows", 0.0), aug.get("rows", 0.0))
        secs += dec.get("total_s", 0.0) + aug.get("total_s", 0.0)
    if rows <= 0 or secs <= 0:
        return None
    return rows / secs
