"""The state-space scan's share of its roofline in a Nemotron-H cell.
The name is ISSUE 40's; what the one cell that lists it READS is a
ONE-group scan — one tensor-parallel rank's share of a grouped mixer,
16 heads of 64, state 128, chunk 128 — because the cell holds one
group: no cell runs ``ngroup > 1``, which the CPU tests alone hold.

The least time the chip could take for the recurrence of one training
step — the larger of its operations / the bf16 peak and its least
bytes / the HBM peak, both counted from the shapes by the family's
reference module (``scan_flops``: 5 P S a token and head;
``scan_min_bytes``: ``x``, every held group's ``B`` and ``C``, ``dt``
and ``y`` once each way; forward and the two gradients, a recomputed
forward does not count) — over ``ssd_scan_ms_step``, the time measured
under the mixers' ``scan`` scope.  A twin of ``ssd_scan_roofline_pct``
but for ``REFERENCE``: that accepted file names another family's
reference by a literal path and may not be edited here; PERF.md section
7 queues the fold-back (and this name with it) for a ``benchmark``
issue.  ``None`` without a trace or without the scope."""

from benchmarks.lib import scopes

LAYER = "layers and kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_samples_s_chip"

REFERENCE = "benchmarks/references/nemotron_h.py"


def read(run):
    ms = scopes.ms_per_step(run, ("mamba2",), "scan")
    if not ms or not run.get("peaks"):
        return None
    mod, net = scopes.reference_of(run, REFERENCE)
    least_s = max(mod.scan_flops(net) / run["peaks"]["bf16_flops"],
                  mod.scan_min_bytes(net) / run["peaks"]["hbm_bytes_s"])
    return 100.0 * least_s / run["chips"] / (ms / 1e3)
