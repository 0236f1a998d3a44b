"""Time per training step, inside rounds, in which no program runs on
the device: the window's chunk period per step (host clock, untraced)
minus the device's busy time per step (profiler trace).  The gap is not
read off the trace itself: under the profiler the host runs about one
and a half times slower and the gaps with it (PERF.md, PR 24)."""

LAYER = "round loop"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "train_samples_s_chip"


def read(run):
    t, w = run["trace"], run["window"]
    if not t or not t["steps"] or t["busy_s"] <= 0:
        return None
    return 1e3 * (w["sum_periods_s"] / w["steps"] - t["busy_s"] / t["steps"])
