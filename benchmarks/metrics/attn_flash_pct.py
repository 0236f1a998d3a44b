"""Share of the tokens through masked attention that the flash kernels
computed (``ops/flash.py``, chosen by ``ops/attention.attend``): the
``attn_tokens_flash`` counter over ``attn_tokens`` (tokens x layers,
counted inside the step programs by the branch that ran — ``attention``'s
masked path and ``latent_attention``), over the window's whole rounds.
100 where the kernels engaged, 0 where ``mha``'s row blocks ran, ``None``
where the program counts neither (an older commit)."""

from benchmarks.lib import stage_scopes

LAYER = "layers and kernels"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "train_samples_s_chip"


def read(run):
    tokens = stage_scopes.counter(run, 'attn_tokens')
    if tokens is None or not tokens[0]:
        return None
    flash = stage_scopes.counter(run, 'attn_tokens_flash')
    return 100.0 * (flash[0] if flash else 0) / tokens[0]
