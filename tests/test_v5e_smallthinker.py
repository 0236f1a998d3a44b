"""The whole scanned step of ``smallthinker_conf()`` at its defaults (PR
46: one rank's share of a SmallThinker-21BA3B stage — four attention
layers of 28 query heads on 4 key/value heads of 128, the first over the
whole document without positions, three under a window of 4096 with
rotary positions; every layer 16 held ReGLU experts behind a router that
reads the ATTENTION's input; rows of 16384 tokens; 559M parameters under
adam), compiled for a DESCRIBED v5e chip (``tests/v5e.py``), fits a
chip: 12.09 GB at its fullest, under the 14.4 GB that decides between 16
held experts and 8 (ISSUE 46's memory rule, ISSUE 42's kept); lowered
for a TPU a layer's attention IS the flash kernels at group 7, the
forward once a layer under the net's ``remat`` policy, on grids of the
window's 70 (query block, key block) steps and not the diagonal's 136.
"""

import re

import v5e


def test_the_smallthinker_step_fits_a_chip_at_sixteen_held_experts(one_chip):
    """``tools/compile_for_v5e.py``'s compile of the conf the builder
    writes, from shapes alone: 6.71 GB of weights and adam's moments
    aliased to the outputs, the rest temporaries of one 16384-token row
    (12.09 GB live at the peak when this was written; with 8 held, the
    guide's floor, 9.83: the configuration's ``memory_analysis_v5e``)."""
    from cxxnet_tpu.models import smallthinker_conf

    text = v5e.step_that_fits(smallthinker_conf(), 559_290_880, 14.4e9)
    for scope in ("l1_attn0)/core_full/", "l3_attn1)/core_window/",
                  "l7_attn3)/core_window/", "l3_attn1)/rotary/",
                  "l2_moe0)/route/", "l2_moe0)/dispatch/",
                  "l2_moe0)/experts/", "l2_moe0)/combine/"):
        assert scope in text, scope
    # the full layer rotates nothing; no layer has a shared expert
    assert "l1_attn0)/rotary/" not in text and ")/shared/" not in text
    # the router's product, softmax and top-k run under the expert
    # layer's route scope; the norm of its input, traced under that scope
    # too (tests/test_smallthinker_layers.py), is the attention's own
    # norm of the same node under the same weight, and the compiler
    # computes the two once, under the attention's scope: the borrowed
    # norm costs the compiled step no pass of its own
    for op in ("dot_general", "top_k", "reduce_max"):
        assert f"l2_moe0)/route/{op}" in text, op
    assert not re.search(r"l[2468]_moe[0-3]\)/route/rsqrt", text)
    assert re.search(r"l1_attn0\)/rsqrt", text)
    # the held experts row-major through the scan like the accepted cells'
    assert re.search(r"f32\[16,2560,1536\]\{2,1,0", text)
    assert not re.search(r"f32\[16,(?:2560,1536|768,2560)\]\{1,2,0", text)
    # all four attention layers are the flash kernels, two calls each:
    # ONE forward a layer under the net's remat policy (PR 44) and ONE
    # backward (PR 48: ``flash_bwd``, no ``flash_dq`` + ``flash_dkv``)
    calls = v5e.mosaic_calls(text)
    assert len(calls) == 8, [c[-60:] for c in calls]
    assert sum("/core_window/" in c for c in calls) == 6
    assert sum("/core_full/" in c and "l1_attn0" in c for c in calls) == 2
    fwd = [c for c in calls if c.endswith("flash_fwd/pallas_call")]
    assert len(fwd) == 4 == sum(
        c.endswith("flash_bwd/pallas_call") for c in calls)
    assert not any("rematted_computation" in c or "transpose(" in c
                   for c in fwd)
    # the kept lse is its numbers, (heads, T)
    assert "f32[28,16384]" in text


def test_a_smallthinker_window_layer_lowered_for_a_tpu_is_the_flash_kernels(
        one_chip):
    """One pre-normed ``attention`` layer of the family under its window
    of 4096 on a packed row of 16384 tokens, bfloat16, under the net's
    ``remat``: 28 query heads read 4 key/value heads by the index map
    (group 7), and the kernels' grids walk the window's 70 steps, not the
    diagonal's 136 (the whole step above holds the full layer's
    scopes)."""
    from cxxnet_tpu.ops.flash import BLOCK

    steps = 70
    cfg = dict(nhead=28, nkvhead=4, head_dim=128, causal=1, no_bias=1,
               prenorm=1, eps=1e-6, residual_scale=1.0, window=4096,
               rotary_dim=128, rope_theta=1500000.0)
    compiled = v5e.compile_layer(one_chip, "attention", cfg,
                                 [(1, 16384, 2560), (1, 16384)], "l3_attn1")
    text = compiled.as_text()
    calls = v5e.mosaic_calls(text)
    assert sorted(c.split("/")[-2] for c in calls) == [
        "flash_bwd", "flash_fwd"], calls
    assert all("l3_attn1" in c and "/core_window/" in c for c in calls), calls
    assert not v5e.SCORE_BLOCK.search(text)
    # no key or value repeated to the query heads' count in HBM
    assert "bf16[4,16384,128]" in text
    assert BLOCK == 1024
    # the step tables are operands of the calls: the forward's a head
    # group, the backward's seven query heads a key/value head
    assert f"s32[{steps}]" in text and f"s32[{7 * steps}]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2.4e9
