"""Share of the round loop's chunk blocks that were taken back from the
assembler's free list and not mapped anew (``io/chunk.py``): the
``chunks`` counter of the program's telemetry records, over the window's
whole rounds.  100 where every chunk is written into memory that is
already mapped; a program that writes no counter gives ``None``."""

LAYER = "round loop"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "train_samples_s_chip"


def read(run):
    rows = [r["chunks"] for r in run["telemetry"] if r.get("chunks")]
    allocated = sum(int(c.get("allocated", 0)) for c in rows)
    recycled = sum(int(c.get("recycled", 0)) for c in rows)
    if not allocated + recycled:
        return None
    return 100.0 * recycled / (allocated + recycled)
