"""The whole scanned step of ``nemotron_h_conf()`` at its defaults (PR
40: one rank's share of a Nemotron-H stage — five mixers at 16 heads in
one group, an attention at 4 query heads on 1 key/value head of 128,
five latent expert layers at 8 held ``relu2`` experts, top-22 of 512 and
the shared expert whole at 5376; 701M parameters under adam), compiled
for a DESCRIBED v5e chip (``tests/v5e.py``), fits a chip: 11.81 GB at
its fullest, held to the JoyAI step's 14.4 (with the prediction module
too it read 15.37 and the cell leaves the module out); its attention is
the flash kernels, its grouped products the compiler's, the held
experts' matrices row-major.  Lowered for a TPU, a ``mamba2`` layer's
scan IS the fused kernels of ``ops/ssd_fused.py`` (PR 41) at one rank's
16 heads at chunks of 128.
"""

import re

import pytest

import v5e


def test_the_nemotron_step_fits_a_chip_at_one_rank_s_share(one_chip):
    """``tools/compile_for_v5e.py``'s compile of the conf the builder
    writes, from shapes alone: 8.41 GB of weights and adam's moments
    aliased to the outputs, the rest temporaries of one 8192-token row
    (11.81 GB live at the peak when this was written)."""
    from cxxnet_tpu.models import nemotron_h_conf

    text = v5e.step_that_fits(nemotron_h_conf(), 700_865_520, 14.4e9)
    # the mixer's five scopes with one group as with many, the expert
    # layer's two new ones beside the five it had
    for scope in ("l1_mixer0)/in_proj/", "l1_mixer0)/conv/",
                  "l1_mixer0)/scan/", "l1_mixer0)/gate_norm/",
                  "l1_mixer0)/out_proj/", "l2_moe1)/route/",
                  "l2_moe1)/dispatch/", "l2_moe1)/experts/",
                  "l2_moe1)/combine/", "l2_moe1)/shared/",
                  "l2_moe1)/latent_in/", "l2_moe1)/latent_out/",
                  "l11_moe10)/latent_in/"):
        assert scope in text, scope
    assert "mtp_" not in text
    # the held experts live in the latent: (8, 1024, 2688) up, no fused
    # half, row-major through the scan like the accepted cells'
    assert re.search(r"f32\[8,1024,2688\]\{2,1,0", text)
    assert not re.search(r"f32\[8,(?:1024,2688|2688,1024)\]\{1,2,0", text)
    assert "f32[8,1024,5376]" not in text
    # a slab of 5632 of the 180 224 (token, pick) pairs, in the latent
    assert "bf16[5632,1024]" in text and "bf16[5632,2688]" in text
    assert not re.search(r"bf16\[180224,(?:1024|2688|4096)\]", text)
    # the shared expert is whole: (5376, 4096) up, no 672-column share
    assert "f32[5376,4096]" in text and "f32[672,4096]" not in text
    # the attention layer (4 query heads on 1 key/value head of 128) is
    # the flash kernels: two Mosaic calls (PR 44: one forward; PR 48: one
    # backward, ``flash_bwd``); since PR 41 the five mixers' scans are the
    # kernels of ops/ssd_fused.py (forward, recompute, backward), billed
    # to their scan scopes
    calls = v5e.mosaic_calls(text)
    ssd = [c for c in calls if "/ssd_scan" in c]
    assert len(ssd) == 15 and len(calls) == 17, [c[-60:] for c in calls]
    assert sorted(c.split("/")[-2] for c in calls if c not in ssd) == [
        "flash_bwd", "flash_fwd"]
    assert all("mixer" in c and "/scan/" in c for c in ssd), ssd
    assert all("attn" in c for c in calls if c not in ssd), calls


@pytest.mark.parametrize("cfg, d", [
    (dict(nhead=16, head_dim=64, nstate=128, chunk=128), 4096),
], ids=["nemotron_h_share"])
def test_a_mamba2_layer_lowered_for_a_tpu_is_the_fused_kernels(one_chip, cfg,
                                                               d):
    v5e.mamba2_layer_is_the_fused_kernels(one_chip, cfg, d)


@pytest.mark.parametrize("cfg", [
    # one rank's share of a Nemotron-H attention: 4 over 1 of width 128
    dict(nhead=4, nkvhead=1, head_dim=128),
], ids=["nemotron_h_share"])
def test_an_attention_layer_lowered_for_a_tpu_is_the_flash_kernels(
        one_chip, cfg):
    v5e.attention_layer_is_the_flash_kernels(one_chip, cfg)
