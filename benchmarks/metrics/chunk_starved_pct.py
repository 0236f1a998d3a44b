"""Share of the scanned chunks that were dispatched mid-round onto a
device that had run dry — the chunk before had landed before the
dispatch (``train_loop.RoundLoop._flush``): the ``chunks_starved`` and
``chunks_dispatched`` counters of the program's telemetry records, over
the window's whole rounds.  0 where the host keeps ahead of the device;
a cell whose feed is slower than its step reads near 100 (n - 1) / n.  A
program that counts no dispatch gives ``None``."""

LAYER = "round loop"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "train_samples_s_chip"


def read(run):
    rows = [r["counters"] for r in run["telemetry"] if r.get("counters")]
    dispatched = sum(int(c.get("chunks_dispatched", 0)) for c in rows)
    if not dispatched:
        return None
    return 100.0 * sum(int(c.get("chunks_starved", 0))
                       for c in rows) / dispatched
