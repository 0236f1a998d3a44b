"""The held latent experts' two grouped products' share of their
roofline: the least time the chip could take for them in one training
step — the larger of their operations / the bf16 peak (3 x 2 x 2 x
pairs x L x F: up and down, ungated) and their least bytes / the HBM
peak (the held matrices once each way and a pair's L-wide rows), both
counted by the configuration's reference module (``expert_flops``,
``expert_min_bytes``) at the pairs a step the run itself counted
(``expert_pairs`` / steps, over the window's whole rounds) — over the
time of ``expert_matmul_ms_step`` (the ``experts`` scope and the
compiler's ``ragged-dot-*`` kernels).  A twin of
``expert_matmul_roofline_pct`` but for ``REFERENCE`` (that accepted
file names another family's reference by a literal path and may not be
edited here; PERF.md section 7 queues the fold-back for a ``benchmark``
issue).  ``None`` without a trace, without the scope or without the
counter."""

from benchmarks.lib import scopes, stage_scopes

LAYER = "layers and kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_samples_s_chip"

REFERENCE = "benchmarks/references/nemotron_h.py"


def read(run):
    ms = stage_scopes.ms_per_step(
        run, stage_scopes.EXPERTS, ('experts',), True)
    pairs = stage_scopes.counter(run, 'expert_pairs')
    if not ms or pairs is None or not run.get('peaks'):
        return None
    mod, net = scopes.reference_of(run, REFERENCE)
    a_step = pairs[0] / pairs[1]
    least_s = max(mod.expert_flops(net, a_step) / run['peaks']['bf16_flops'],
                  mod.expert_min_bytes(net, a_step)
                  / run['peaks']['hbm_bytes_s'])
    return 100.0 * least_s / run['chips'] / (ms / 1e3)
