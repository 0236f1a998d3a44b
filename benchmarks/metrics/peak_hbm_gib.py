"""Peak device memory of the program: ``memory_stats()`` of the fullest
chip, read after the window and before the reference runs."""

LAYER = "device"
UNIT = "GiB"
SOURCE = "program_counter"
MOVES = "train_samples_s_chip"


def read(run):
    b = run["memory_peak_bytes"]
    return b / 2.0 ** 30 if b else None
