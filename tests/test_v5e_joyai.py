"""The whole scanned step of ``joyai_llm_flash_conf()`` at its defaults
(PR 36: six latent-attention layers, five expert layers at 16 held
experts, the prediction module; 680M parameters under adam), compiled
for a DESCRIBED v5e chip (``tests/v5e.py``), holds at most 14.4 GB at
its fullest — the number that decided between 16 held experts and the
fallback of 8 (ISSUE 36).  The held experts' float32 matrices keep their
row-major layout through the scan (``moe._as_kept``, PR 39).  Lowered
for a TPU, the latent layers' masked attention IS the flash kernels of
``ops/flash.py`` (PR 37) under their ``core`` scope; the step is compiled
as the CLI compiles it and held to the 14.4 GB that fit a chip: it reads
14.21 GB with the kernels for 13.60 with the row blocks (ISSUE 37's "no
higher than 13.60" is NOT met: PERF.md section 6, PR 37).
"""

import re

import v5e


def test_the_joyai_step_fits_a_chip_with_sixteen_held_experts(one_chip):
    """``tools/compile_for_v5e.py``'s compile of the conf the builder
    writes, from shapes alone: 8.17 GB of weights and adam's moments
    aliased to the outputs, the rest temporaries of one 8192-token row."""
    from cxxnet_tpu.models import joyai_llm_flash_conf

    # 680.44M parameters, all of them updated in place
    text = v5e.step_that_fits(joyai_llm_flash_conf(), 680_441_088, 14.4e9)
    # the new layer's scopes reach the operations' metadata, the
    # module's too, and the grouped products are the compiler's kernels
    for scope in ("l1_mla0)/core/", "l1_mla0)/q_proj/", "l1_mla0)/kv_proj/",
                  "l1_mla0)/rotary/", "l1_mla0)/out_proj/",
                  "l20_mtp_mla)/core/", "l19_mtp_eh_proj", "l21_mtp_moe)/route/"):
        assert scope in text, scope
    # PR 39: the slabs after the first are loops inside the scanned step,
    # and every held expert's float32 matrices (weight and both moments)
    # stay in the layout they are kept in: turned ({1,2,0}), with a copy
    # of each at the scan's edges, the step read 17.4 GB
    assert len(re.findall(r" while\(", text)) > 1
    assert re.search(r"f32\[16,2048,1536\]\{2,1,0", text)
    assert not re.search(r"f32\[16,(?:2048,1536|768,2048)\]\{1,2,0", text)
    # PR 37: every latent layer's core is Mosaic calls, all billed to its
    # core scope — since PR 44 one forward a layer (the remat recompute
    # reads the kept o and lse and runs none), since PR 48 one backward
    # (``flash_bwd`` at 192 / 128: no ``flash_dq`` + ``flash_dkv``);
    # mha's float32 score blocks (1, 32, 512, <= 8192) are gone
    calls = v5e.mosaic_calls(text)
    assert len(calls) == 6 * 2, [c[-60:] for c in calls]
    assert all("/core/" in c and ("mla" in c) for c in calls), calls
    for kern, n in (("flash_fwd", 6), ("flash_bwd", 6)):
        assert sum(f"/{kern}/pallas_call" in c for c in calls) == n, kern
