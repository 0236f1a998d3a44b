"""The gated delta rule's share of its roofline: the least time the chip
could take for the recurrence of one training step — the larger of its
operations / the bf16 peak and its least bytes / the HBM peak, both
counted from the shapes by the configuration's reference module
(``scan_flops``: 7 Dk Dv a token and value head, ``scan_min_bytes``: its
inputs and outputs once each way; forward and the two gradients, a
recomputed forward does not count) — over ``gdn_scan_ms_step``, the
time measured under the mixers' ``scan`` scope.  Whatever computes the
scan (plain XLA today, a fused kernel later) is read by this metric.
``None`` without a trace or without the scope."""

from benchmarks.lib import scopes

LAYER = "layers and kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_samples_s_chip"

REFERENCE = 'benchmarks/references/qwen3_next.py'


def read(run):
    ms = scopes.ms_per_step(run, ('gated_deltanet',), 'scan')
    if not ms or not run.get('peaks'):
        return None
    mod, net = scopes.reference_of(run, REFERENCE)
    least_s = max(mod.scan_flops(net) / run['peaks']['bf16_flops'],
                  mod.scan_min_bytes(net) / run['peaks']['hbm_bytes_s'])
    return 100.0 * least_s / run['chips'] / (ms / 1e3)
