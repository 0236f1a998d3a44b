"""Share of the scanned chunks whose fence found a later chunk already
dispatched (``train_loop.RoundLoop._fence``): the ``chunks_overlapped``
and ``chunks_fenced`` counters of the program's telemetry records, over
the window's whole rounds.  Such a chunk's successor was fed, uploaded
and dispatched while it ran; a round's last chunk has none, so a round
of n chunks reads 100 (n - 1) / n.  A program that counts no fence gives
``None``."""

LAYER = "round loop"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "train_samples_s_chip"


def read(run):
    rows = [r["counters"] for r in run["telemetry"] if r.get("counters")]
    fenced = sum(int(c.get("chunks_fenced", 0)) for c in rows)
    if not fenced:
        return None
    overlapped = sum(int(c.get("chunks_overlapped", 0)) for c in rows)
    return 100.0 * overlapped / fenced
