"""The Kimi delta rule's share of its roofline: the least time the chip
could take for the recurrence of one training step — the larger of its
operations / the bf16 peak and its least bytes / the HBM peak, both
counted from the shapes by the configuration's reference module
(``scan_flops``: 7 Dk Dv a token and head, ``scan_min_bytes``: its inputs
and outputs once each way; forward and the two gradients, a recomputed
forward does not count) — over ``kda_scan_ms_step``, the time measured
under the mixers' ``scan`` scope (the kernels, the gate and the layout
changes around them).  Whatever computes the scan is read by this metric.

The reference is found through the configuration, as
``attn_core_roofline_pct`` finds its own: the run's cell names its
configuration in ``BENCHMARK.json``, whose file names its ``reference``.
``None`` without a trace, without the scope, or where the configuration's
reference has no ``scan_flops``."""

import os

from benchmarks.lib import scopes

LAYER = "layers and kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_samples_s_chip"


def read(run):
    ms = scopes.ms_per_step(run, ('kimi_delta',), 'scan')
    if not ms or not run.get('peaks'):
        return None
    from benchmarks import run as harness

    rel = harness.load_metric('attn_core_roofline_pct').reference_path(run)
    if rel is None or not os.path.exists(os.path.join(scopes.ROOT, rel)):
        return None
    mod, net = scopes.reference_of(run, rel)
    if not hasattr(mod, 'scan_flops'):
        return None
    least_s = max(mod.scan_flops(net) / run['peaks']['bf16_flops'],
                  mod.scan_min_bytes(net) / run['peaks']['hbm_bytes_s'])
    return 100.0 * least_s / run['chips'] / (ms / 1e3)
