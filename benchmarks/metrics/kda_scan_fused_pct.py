"""Share of the tokens through the Kimi delta rule's scan that the fused
kernels computed (``ops/kda_fused.py``): the ``kda_scan_tokens_fused``
counter over ``kda_scan_tokens`` (tokens x layers, counted inside the
step programs by the branch that ran), over the window's whole rounds.
100 where the kernels engaged, 0 where the ``jax.numpy`` form ran,
``None`` where the program counts neither (an older commit)."""

from benchmarks.lib import stage_scopes

LAYER = "layers and kernels"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "train_samples_s_chip"


def read(run):
    tokens = stage_scopes.counter(run, 'kda_scan_tokens')
    if tokens is None or not tokens[0]:
        return None
    fused = stage_scopes.counter(run, 'kda_scan_tokens_fused')
    return 100.0 * (fused[0] if fused else 0) / tokens[0]
