"""XLA backend compiles between the end of warm-up and the stop: the
program's compile listener (``obs/device.py``), a delta.  Expected 0,
reported as the count plus nothing: 0 is 0."""

LAYER = "step programs"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "train_samples_s_chip"


def read(run):
    a, b = run["device_at_setup"], run["device_at_stop"]
    if not a or not b:
        return None
    return float(b["compiles"] - a["compiles"])
