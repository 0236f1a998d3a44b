"""Share of the tokens through masked attention whose backward ran as the
ONE kernel ``flash_bwd`` (``ops/flash.py``: ``dq``, ``dk`` and ``dv`` from
one derivation of a tile's scores, chosen from the shapes where a
key-value head's row fits the kernel's VMEM budget) and not as
``flash_dq`` + ``flash_dkv``: the ``attn_tokens_bwd_fused`` counter over
``attn_tokens`` (tokens x layers, counted inside the step programs by the
branch that ran), over the window's whole rounds.  100 where the one
kernel ran, 0 where the two did or ``mha``'s row blocks, ``None`` where
the program does not count it (an older commit)."""

from benchmarks.lib import stage_scopes

LAYER = "layers and kernels"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "train_samples_s_chip"


def read(run):
    tokens = stage_scopes.counter(run, 'attn_tokens')
    fused = stage_scopes.counter(run, 'attn_tokens_bwd_fused')
    if fused is None or tokens is None or not tokens[0]:
        return None
    return 100.0 * fused[0] / tokens[0]
