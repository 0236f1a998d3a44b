"""Device time of one training step: the union of the device's busy
intervals in the traced chunks / the steps traced."""

LAYER = "step programs"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "train_samples_s_chip"


def read(run):
    t = run["trace"]
    if not t or not t["steps"] or t["busy_s"] <= 0:
        return None
    return 1e3 * t["busy_s"] / t["steps"]
