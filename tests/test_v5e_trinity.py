"""The whole scanned step of ``afmoe_conf()`` at its defaults (PR 42: one
rank's share of a Trinity-Mini stage — five gated attention layers of 32
query heads on 4 key/value heads of 128, four of them under a window of
2048, sandwich norms, a dense layer and four expert layers at 8 held
experts, rows of 16384 tokens; 504M parameters under adam), compiled for
a DESCRIBED v5e chip (``tests/v5e.py``), fits a chip: 12.35 GB at its
fullest, held to the 14.4 GB that decided between 16 held experts (14.97
GB: over) and 8 (ISSUE 42's memory rule); lowered for a TPU a windowed
layer IS the flash kernels, under its ``core_window`` scope, on grids of
the window's 45 (query block, key block) steps and not the diagonal's
136.
"""

import re

import pytest

import v5e


def test_the_trinity_step_fits_a_chip_at_eight_held_experts(one_chip):
    """``tools/compile_for_v5e.py``'s compile of the conf the builder
    writes, from shapes alone: 6.05 GB of weights and adam's moments
    aliased to the outputs, the rest temporaries of one 16384-token row
    (12.35 GB live at the peak when this was written; with 16 held
    experts it read 14.97, over the line: the configuration's
    ``memory_analysis_v5e``)."""
    from cxxnet_tpu.models import afmoe_conf

    text = v5e.step_that_fits(afmoe_conf(), 504_147_712, 14.4e9)
    for scope in ("l1_attn0)/core_window/", "l9_attn4)/core_full/",
                  "l1_attn0)/qk_norm/", "l1_attn0)/rotary/",
                  "l4_moe1)/route/", "l4_moe1)/dispatch/",
                  "l4_moe1)/experts/", "l4_moe1)/combine/",
                  "l4_moe1)/shared/"):
        assert scope in text, scope
    # the full layer rotates nothing
    assert "l9_attn4)/rotary/" not in text
    # the held experts row-major through the scan like the accepted cells'
    assert re.search(r"f32\[8,2048,2048\]\{2,1,0", text)
    assert not re.search(r"f32\[8,(?:2048,2048|1024,2048)\]\{1,2,0", text)
    # all five attention layers are the flash kernels, two calls each
    # (one forward: PR 44; one backward, ``flash_bwd``: PR 48), each
    # under its layer's own core scope
    calls = v5e.mosaic_calls(text)
    assert len(calls) == 10, [c[-60:] for c in calls]
    assert sum("/core_window/" in c for c in calls) == 8
    assert sum("/core_full/" in c and "l9_attn4" in c for c in calls) == 2
    assert sorted({c.split("/")[-2] for c in calls}) == [
        "flash_bwd", "flash_fwd"]


@pytest.mark.parametrize("window, steps", [(2048, 45), (0, 136)],
                         ids=["sliding", "full"])
def test_a_trinity_attention_layer_lowered_for_a_tpu_is_the_flash_kernels(
        one_chip, window, steps):
    """One sandwiched ``attention`` layer of the afmoe family on a packed
    row of 16384 tokens, bfloat16, under the net's ``remat``: the
    kernels' grids walk the window's steps on a sliding layer."""
    from cxxnet_tpu.ops.flash import BLOCK

    cfg = dict(nhead=32, nkvhead=4, head_dim=128, qk_norm=1, out_gate=1,
               causal=1, no_bias=1, prenorm=1, postnorm=1,
               residual_scale=1.0)
    if window:
        cfg.update(window=window, rotary_dim=128)
    compiled = v5e.compile_layer(one_chip, "attention", cfg,
                                 [(1, 16384, 2048), (1, 16384)], "l3_attn1")
    text = compiled.as_text()
    calls = v5e.mosaic_calls(text)
    assert sorted(c.split("/")[-2] for c in calls) == [
        "flash_bwd", "flash_fwd"], calls
    scope = "core_window" if window else "core_full"
    assert all("l3_attn1" in c and f"/{scope}/" in c for c in calls), calls
    assert not v5e.SCORE_BLOCK.search(text)
    assert "bf16[4,16384,128]" in text
    # the step tables are operands of the calls: their length is the grid's
    assert BLOCK == 1024
    assert f"s32[{steps}]" in text and f"s32[{8 * steps}]" in text
    # PR 43: the mask reaches the kernels as two bounds a query, columns
    # beside the query block and rows beside the transposed one
    assert "s32[1,16384,1]" in text and "s32[1,1,16384]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2.4e9
