"""The whole scanned step of ``bailing_hybrid_conf()`` at its defaults (PR
49: one rank's share of a Ling-3.0-flash stage — five Kimi Delta
Attention mixers of 32 heads of 128 x 128 with one decay a key channel,
one latent attention without a query latent and with a head-wise output
gate, a dense MLP, then 8 held experts of 512 behind the group-limited
sigmoid router; rows of 8192 tokens; 767M parameters under adam),
compiled for a DESCRIBED v5e chip (``tests/v5e.py``), fits a chip: 14.35
GB at its fullest, under the 14.4 GB the token cells are held to — with
NOTHING of the delta rule's forward kernels kept across the backward
pass (ISSUE 49's memory rule; ``ops/kda_fused.py`` has the two compiles
that kept something); lowered for a TPU the rule IS the kernels of
``ops/kda_fused.py``, under the layer's ``scan`` scope.
"""

import v5e


def test_the_ling_step_fits_a_chip_with_thirty_two_heads(one_chip):
    """``tools/compile_for_v5e.py``'s compile of the conf the builder
    writes, from shapes alone: 9.20 GB of weights and adam's moments
    aliased to the outputs, the rest temporaries of one 8192-token row
    (the configuration's ``memory_analysis_v5e``)."""
    from cxxnet_tpu.models import bailing_hybrid_conf

    text = v5e.step_that_fits(bailing_hybrid_conf(), 767_009_056, 14.4e9)
    for scope in ("l1_kda0)/scan/", "l1_kda0)/in_proj/", "l1_kda0)/conv/",
                  "l9_kda4)/gate_norm/", "l9_kda4)/out_proj/",
                  "l11_mla5)/core/", "l11_mla5)/gate/", "l11_mla5)/q_proj/",
                  "l4_moe1)/route/group_limit/", "l4_moe1)/dispatch/",
                  "l4_moe1)/experts/", "l12_moe5)/shared/"):
        assert scope in text, scope
    calls = v5e.mosaic_calls(text)
    names = sorted(c.split("/")[-2] for c in calls)
    # five mixers: forward, recompute and backward; one latent attention:
    # ONE forward under the net's remat policy and ONE backward
    assert names == (["flash_bwd", "flash_fwd"] + ["kda_scan"] * 10
                     + ["kda_scan_bwd"] * 5 + ["kda_solve"] * 10), names
    kda = [c for c in calls if "/kda_" in c]
    assert all("/scan/" in c and "_kda" in c for c in kda), kda
    assert sum("rematted_computation" in c for c in kda) == 10
    # the held experts row-major through the scan like the accepted cells'
    assert "f32[8,2560,1536]{2,1,0" in text


def test_a_kimi_delta_layer_lowered_for_a_tpu_is_the_fused_kernels(one_chip):
    """One pre-normed ``kimi_delta`` layer at the published widths on a
    packed row of 8192 tokens, bfloat16, under the net's ``remat`` as the
    step programs run it: three kernels, the two forward ones twice, all
    billed to the layer's ``scan`` scope; none of the ``jax.numpy`` form's
    whole-row chunk matrices is left."""
    import re

    cfg = dict(nhead=32, key_dim=128, value_dim=128, conv_width=4,
               lower_bound=-5.0, prenorm=1, eps=1e-6, residual_scale=1.0)
    compiled = v5e.compile_layer(one_chip, "kimi_delta", cfg,
                                 [(1, 8192, 2560), (1, 8192)], "l1_kda0")
    text = compiled.as_text()
    calls = v5e.mosaic_calls(text)
    assert sorted(c.split("/")[-2] for c in calls) == [
        "kda_scan", "kda_scan", "kda_scan_bwd", "kda_solve", "kda_solve"]
    assert all("l1_kda0" in c and "/scan/" in c for c in calls), calls
    (bwd,) = [c for c in calls if "kda_scan_bwd" in c]
    assert "transpose(" in bwd and "rematted_computation" not in bwd
    # what the kernels hand each other: [T | Pq] float32, a chunk's two
    # matrices on a tile's 128 lanes, and the states on the activations'
    # dtype; no (chunks, heads, 64, 64) tensor of the jax.numpy form
    assert "f32[1,32,8192,128]" in text and "bf16[1,32,128,128,128]" in text
    assert not re.search(r"f32\[1,128,32,64,64\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 2.6e9
