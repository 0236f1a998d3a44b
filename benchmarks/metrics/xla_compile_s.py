"""Seconds XLA spent compiling (or loading from the cache) by the end
of set-up: the program's compile listener, cumulative."""

LAYER = "compile cache"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(run):
    a = run["device_at_setup"]
    return float(a["compile_seconds"]) if a else None
