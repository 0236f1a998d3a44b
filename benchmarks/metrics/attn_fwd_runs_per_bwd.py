"""How often the flash forward kernel runs for one run of the backward:
the operations of the traced round's ``XLA Ops`` whose name ends
``flash_fwd/pallas_call``, over those whose name ends
``flash_dq/pallas_call`` (``ops/flash.py``: one ``flash_dq`` a layer's
backward pass, whichever layer — ``attention``'s masked path,
``latent_attention``).  The name is the event's ``tf_op``, ``<the scope
path the program gave it>:<type>``, the type empty on a v5e's trace.
2.0 where a layer's ``remat`` recompute runs the forward kernel a second
time only to rebuild ``o`` and ``lse``; 1.0 where the net's
``jax.checkpoint`` policy keeps the two (PR 44) — and 2.0 again the day
a change to ``nnet/net.py`` or to jax loses the policy.  Counted as the
DISTINCT operations of the step program that ran (an event's HLO name),
not as events and not as time: the traced round begins and ends inside
a step, so it holds a few backward events more than forward ones (188
to 96 and 93 to 96 in a round of JoyAI's, PR 44).  Events of the first
chip (``lib/scopes.device_events``).  ``None`` without a trace, or where
the trace holds neither kernel (the conv cells, ``mha``'s row
blocks)."""

import glob
import os

from benchmarks.lib import scopes, tracered

LAYER = "layers and kernels"
UNIT = "x"
SOURCE = "device_trace"
MOVES = "train_samples_s_chip"

FWD = "flash_fwd/pallas_call"
BWD = "flash_dq/pallas_call"


def runs_per_bwd(events):
    """``events``: (HLO instruction, duration in ns, ``tf_op`` or None)
    of one chip's ``XLA Ops``."""
    ops = {FWD: set(), BWD: set()}
    for hlo, dur, scope in events:
        if dur <= 0 or scope is None:
            continue
        name = scope.split(":")[0]
        for end, seen in ops.items():
            if name.endswith(end):
                seen.add(hlo.split(" = ")[0])
    fwd, bwd = len(ops[FWD]), len(ops[BWD])
    return fwd / bwd if fwd and bwd else None


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
