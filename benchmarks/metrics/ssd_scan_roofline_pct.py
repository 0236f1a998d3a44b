"""The state-space scan's share of its roofline: the least time the
chip could take for the recurrence of one training step — the larger
of its operations / the bf16 peak and its least bytes / the HBM peak,
both counted from the shapes by the configuration's reference module
(``scan_flops``, ``scan_min_bytes``: forward and the two gradients, a
recomputed forward does not count) — over ``ssd_scan_ms_step``, the
time measured under the mixers' ``scan`` scope.  Whatever computes the
scan (plain XLA today, a Pallas kernel later) is read by this metric.
``None`` without a trace, without the scope, or for a configuration
whose reference counts no scan."""

from benchmarks.lib import scopes

LAYER = "layers and kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_samples_s_chip"

REFERENCE = "benchmarks/references/granite_hybrid.py"


def read(run):
    ms = scopes.ms_per_step(run, ("mamba2",), "scan")
    if not ms or not run.get("peaks"):
        return None
    mod, net = scopes.reference_of(run, REFERENCE)
    least_s = max(mod.scan_flops(net) / run["peaks"]["bf16_flops"],
                  mod.scan_min_bytes(net) / run["peaks"]["hbm_bytes_s"])
    return 100.0 * least_s / run["chips"] / (ms / 1e3)
