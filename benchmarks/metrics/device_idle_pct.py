"""Share of the time inside rounds in which no operation ran on the
device: 1 - busy per step (profiler trace) / chunk period per step (the
untraced window, host clock).  The result line's ``busy_s`` and
``window_s`` are the traced session's own, and read idler: the profiler
slows the host (PERF.md, PR 24)."""

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_samples_s_chip"


def read(run):
    t, w = run["trace"], run["window"]
    if not t or not t["steps"] or t["busy_s"] <= 0:
        return None
    period = w["sum_periods_s"] / w["steps"]
    return 100.0 * (1.0 - t["busy_s"] / t["steps"] / period)
