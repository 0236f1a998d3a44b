"""Share of the rows whose train metrics were scored inside a step
program (``utils/metric_device.py``: the scanned step reduces its out
node to the metrics' sums) among all rows scored, by any path: the
``metric_rows_device`` and ``metric_rows`` counters of the program's
telemetry records, over the window's whole rounds.  100 where no output
row is fetched to be scored on the host; ``None`` where no row was
scored (``eval_train = 0``) or the program counts none."""

LAYER = "step programs"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "train_samples_s_chip"


def read(run):
    rows = [r["counters"] for r in run["telemetry"] if r.get("counters")]
    scored = sum(int(c.get("metric_rows", 0)) for c in rows)
    if not scored:
        return None
    on_device = sum(int(c.get("metric_rows_device", 0)) for c in rows)
    return 100.0 * on_device / scored
