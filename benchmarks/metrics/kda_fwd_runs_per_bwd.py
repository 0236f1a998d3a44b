"""How often the Kimi delta rule's two forward kernels run for one run of
its backward: the operations of the traced round's ``XLA Ops`` whose name
ends ``kda_solve/pallas_call`` plus those ending ``kda_scan/pallas_call``,
over twice those ending ``kda_scan_bwd/pallas_call`` (``ops/kda_fused.py``:
one ``kda_scan_bwd`` a ``kimi_delta`` layer's backward pass).  2.0 where a
layer's ``remat`` recompute runs both kernels a second time; 1.5 where the
net's ``jax.checkpoint`` policy keeps what ``solve`` wrote; 1.0 where it
keeps ``scan``'s outputs too.  Counted as ``gdn_fwd_runs_per_bwd`` counts:
the DISTINCT operations of the step program that ran (an event's HLO
name), not events and not time, on the first chip; a name is the event's
``tf_op`` without its ``:<type>``.  ``None`` without a trace, or where the
trace holds none of the kernels (the ``jax.numpy`` form of the rule; a
commit without them)."""

import glob
import os

from benchmarks.lib import scopes, tracered

LAYER = "layers and kernels"
UNIT = "x"
SOURCE = "device_trace"
MOVES = "train_samples_s_chip"

FWD = ("kda_solve/pallas_call", "kda_scan/pallas_call")
BWD = "kda_scan_bwd/pallas_call"


def runs_per_bwd(events):
    """``events``: (HLO instruction, duration in ns, ``tf_op`` or None)
    of one chip's ``XLA Ops``."""
    ops = {end: set() for end in FWD + (BWD,)}
    for hlo, dur, scope in events:
        if dur <= 0 or scope is None:
            continue
        name = scope.split(":")[0]
        for end, seen in ops.items():
            if name.endswith(end):
                seen.add(hlo.split(" = ")[0])
    fwd, bwd = sum(len(ops[end]) for end in FWD), len(ops[BWD])
    return fwd / (len(FWD) * bwd) if fwd and bwd else None


def read(run):
    t = run.get("trace")
    out = scopes.run_dir(run)
    if not t or not t.get("steps") or out is None:
        return None
    dirs = sorted(glob.glob(os.path.join(out, "trace_round*")))
    if not dirs:
        return None
    try:
        return runs_per_bwd(scopes.device_events(
            tracered.find_xplane(dirs[-1])))
    except (FileNotFoundError, OSError, ValueError):
        return None
