"""Host time inside ``update_scan`` per training step, over the
window's chunks: the chunk's float32 stack handed to the device, the
dispatch, the wait for the scanned program and the fetch of its outputs
for the train metrics.  The span is the benchmark's own, around the
call.  (The transfer alone cannot be split off from outside: it is
enqueued asynchronously and ends somewhere inside the wait.)"""

LAYER = "input pipeline"
UNIT = "ms/step"
SOURCE = "host_clock"
MOVES = "train_samples_s_chip"


def read(run):
    t = run["spans"].get("update_scan")
    if not t or t <= 0:
        return None
    return 1e3 * t / run["window"]["steps"]
