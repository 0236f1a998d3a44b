"""What the TPU's compiler makes of the two new mechanisms of PR 33 at
their published widths, compiled here for a DESCRIBED v5e chip (the
on-chip-measurement guide, section 2: nothing runs, no chip is needed).

* ``jax.lax.ragged_dot`` inside ``layers/moe.held_experts`` becomes
  kernels named ``ragged-dot-*`` whose layer scope is dropped — the
  name ``benchmarks/lib/stage_scopes.py`` reads the grouped products'
  time by; since PR 39 they run on slabs of 10 240 rows at qwen3_next's
  shapes, under one ``while`` for the slabs after the first, and no
  array with a feature axis has room for all 81 920 (token, pick) pairs;
  in the whole JoyAI step the held experts' float32 matrices keep their
  row-major layout through the scan (``moe._as_kept``);
* the chunked gated delta rule of ``ops/gdn.py`` in its ``jax.numpy``
  form, walked in checkpointed segments, keeps its backward's
  temporaries under the room a 16 GB chip has beside 10 GB of state;
* lowered for a TPU, ``gated_delta_scan`` IS the fused kernels of
  ``ops/gdn_fused.py`` (PR 34): three Mosaic custom calls under the
  caller's ``scan`` scope, the backward's too, no whole-row chunk
  matrices, and less scratch than the segmented form with no segments.

* the whole scanned step of ``joyai_llm_flash_conf()`` at its defaults
  (PR 36: six latent-attention layers, five expert layers at 16 held
  experts, the prediction module; 680M parameters under adam) holds at
  most 14.4 GB at its fullest — the number that decided between 16 held
  experts and the fallback of 8 (ISSUE 36).

* lowered for a TPU, masked attention IS the flash kernels of
  ``ops/flash.py`` (PR 37): ``flash_fwd``, ``flash_dq`` and ``flash_dkv``
  under the latent layers' ``core`` scope and under an ``attention``
  layer's own at granite's and qwen3_next's head shapes, ONE call of each
  a layer (since PR 44 the net's ``remat`` policy keeps the forward's
  ``o`` and ``lse``, so the recompute runs no second ``flash_fwd``), and
  no float32 ``(…, 512, <= 8192)`` score block of ``mha``'s is left.  The JoyAI step is compiled as the CLI
  compiles it and held to the 14.4 GB that fit a chip: it reads 14.21
  GB with the kernels for 13.60 with the row blocks (ISSUE 37's "no
  higher than 13.60" is NOT met: PERF.md section 6, PR 37).

* the whole scanned step of ``nemotron_h_conf()`` at its defaults (PR
  40: one rank's share of a Nemotron-H stage — five mixers at 16 heads
  in one group, an attention at 4 query heads on 1 key/value head of
  128, five latent expert layers at 8 held ``relu2`` experts, top-22 of
  512 and the shared expert whole at 5376; 701M parameters under adam)
  fits a chip: 11.81 GB at its fullest, held to the JoyAI step's 14.4
  (with the prediction module too it read 15.37 and the cell leaves the
  module out); its attention is the flash kernels, its grouped products
  the compiler's, the held experts' matrices row-major.

* lowered for a TPU, a ``mamba2`` layer's scan IS the fused kernels of
  ``ops/ssd_fused.py`` (PR 41) at both cells' shapes — granite's 64
  heads at chunks of 256, one Nemotron-H rank's 16 at chunks of 128:
  ``ssd_scan`` (forward and the ``remat`` recompute) and ``ssd_scan_bwd``
  under the layer's ``scan`` scope, and no ``(…, Q, Q)`` float32 decay or
  score tensor of the ``jax.numpy`` form is left; the whole granite step
  (ten layers under adam, 772M parameters) holds no more at its fullest
  than the parent's 15.06 GB: 14.65.

* the whole scanned step of ``afmoe_conf()`` at its defaults (PR 42:
  one rank's share of a Trinity-Mini stage — five gated attention layers
  of 32 query heads on 4 key/value heads of 128, four of them under a
  window of 2048, sandwich norms, a dense layer and four expert layers
  at 8 held experts, rows of 16384 tokens; 504M parameters under adam)
  fits a chip: 12.35 GB at its fullest, held to the 14.4 GB that decided
  between 16 held experts (14.97 GB: over) and 8 (ISSUE 42's memory
  rule); lowered for a TPU a windowed layer IS the flash kernels, under
  its ``core_window`` scope, on grids of the window's 45 (query block,
  key block) steps and not the diagonal's 136.

The topology is described inside a fixture, in this one file: only one
process at a time may load the TPU's library.
"""

import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps it away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _shaped(one_chip, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def test_grouped_products_become_ragged_dot_kernels(one_chip):
    """qwen3_next's share: 8192 tokens pick 10 of 512, 32 held.  Since PR
    39 every array between the sort and a token's sum has a slab's 10 240
    rows, not the 81 920 of all (token, pick) pairs."""
    from cxxnet_tpu.layers.moe import held_experts, slab_rows

    m, k, d, f, g, e = 8192, 10, 2048, 512, 32, 512
    assert slab_rows(m * k, g, e) == 10240

    def loss(x, w, idx, wmat, wproj):
        with jax.named_scope("l2_moe0"):
            y, counts = held_experts(x, w, idx, wmat, wproj, 0, e)
        return jnp.sum(y.astype(jnp.float32)), counts

    args = (_shaped(one_chip, (m, d)), _shaped(one_chip, (m, k), jnp.float32),
            _shaped(one_chip, (m, k), jnp.int32),
            _shaped(one_chip, (g, d, 2 * f)), _shaped(one_chip, (g, f, d)))
    compiled = jax.jit(jax.grad(loss, argnums=(0, 3, 4), has_aux=True)
                       ).lower(*args).compile()
    text = compiled.as_text()
    # two products forward and their gradients, every one a kernel, in
    # the first slab and in the loop's body alike
    assert text.count('op_name="ragged-dot-none"') >= 4
    assert 'ragged_dot_tiling="512,' in text       # moe.ROW_TILE
    assert 'op_name="ragged-dot-metadata"' in text
    assert "experts/ragged_dot" not in text        # their scope is gone
    for scope in ("dispatch", "experts", "combine"):
        assert f"l2_moe0))/{scope}/" in text       # the others keep theirs
        # and inside the loop over further slabs too
        assert re.search(rf"l2_moe0\)\)/while/body/[^\"]*{scope}/", text)
    # ONE run-time construct, and no conditional
    assert len(re.findall(r" while\(", text)) == 1
    assert " conditional(" not in text
    # no array with tokens x topk rows and a feature axis is left, in or
    # out of the loop: what has that many rows is the int32 plan
    assert not re.search(rf"(?:bf16|f32)\[{m * k},\d+\]", text)
    assert re.search(rf"(?:bf16|f32)\[10240,{d}\]", text)
    # 0.46 GB of temporaries where buffers for all pairs took 0.97
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9


def test_the_segmented_delta_rule_fits_beside_the_state(one_chip):
    from cxxnet_tpu.ops.gdn import gated_delta_xla as gated_delta_scan

    t, h, dk = 8192, 32, 128

    def loss(q, k, v, g, beta):
        return jnp.sum(gated_delta_scan(q, k, v, g, beta, None, 64, 2048)
                       .astype(jnp.float32))

    head = _shaped(one_chip, (1, t, h, dk))
    gate = _shaped(one_chip, (1, t, h), jnp.float32)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        head, head, head, gate, gate).compile()
    whole = jax.jit(jax.grad(
        lambda *a: jnp.sum(gated_delta_scan(*a, None, 64).astype(
            jnp.float32)), argnums=(0, 1, 2, 3, 4))).lower(
        head, head, head, gate, gate).compile()
    seg = compiled.memory_analysis().temp_size_in_bytes
    assert seg < 1.6e9 < whole.memory_analysis().temp_size_in_bytes


def test_the_delta_rule_lowered_for_a_tpu_is_the_fused_kernels(one_chip):
    """The published widths: a row of 8192 tokens, 16 key and 32 value
    heads of 128 x 128, bfloat16."""
    from cxxnet_tpu.ops.gdn import gated_delta_scan_counted

    t, hk, hv, d = 8192, 16, 32, 128

    def loss(q, k, v, g, beta):
        with jax.named_scope("l1_gdn0"), jax.named_scope("scan"):
            o, fused = gated_delta_scan_counted(q, k, v, g, beta, None, 64,
                                                2048)
        return jnp.sum(o.astype(jnp.float32)), fused

    key = _shaped(one_chip, (1, t, hk, d))
    val = _shaped(one_chip, (1, t, hv, d))
    gate = _shaped(one_chip, (1, t, hv), jnp.float32)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)
                       ).lower(key, key, val, gate, gate).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    names = {k: [c for c in calls if f"/{k}/pallas_call" in c]
             for k in ("gdn_solve", "gdn_scan", "gdn_scan_bwd")}
    assert [len(v) for v in names.values()] == [1, 1, 1], names
    # forward and backward alike are billed to the layer's scan scope
    for call in calls:
        op = re.search(r'op_name="([^"]*)"', call).group(1)
        assert "l1_gdn0" in op and "/scan/" in op, op
    assert "transpose(jvp(l1_gdn0))" in names["gdn_scan_bwd"][0]
    # none of the jax.numpy form's whole-row chunk matrices is left
    # ((1, 128 chunks, 32 heads, 64, 64) float32: decay, A, the
    # doubling's operands, q k^T); what the kernels keep for the backward
    # is the inverse a chunk, (1, 32, 8192, 64), and the entering states
    assert not re.search(r"f32\[[0-9,]*,64,64\]", text)
    assert "f32[1,32,8192,64]" in text and "f32[1,32,128,128,128]" in text
    # and the whole backward needs less scratch than the segmented form
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9 < 1.6e9


def test_the_joyai_step_fits_a_chip_with_sixteen_held_experts(one_chip):
    """``tools/compile_for_v5e.py``'s compile of the conf the builder
    writes, from shapes alone: 8.17 GB of weights and adam's moments
    aliased to the outputs, the rest temporaries of one 8192-token row."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from tools.compile_for_v5e import compile_step, live_at_peak_bytes

    from cxxnet_tpu.models import joyai_llm_flash_conf

    compiled = compile_step(joyai_llm_flash_conf())
    m = compiled.memory_analysis()
    # 680.44M parameters x 12 B (weight and two moments; the gradients
    # are temporaries), all of it updated in place
    assert abs(m.argument_size_in_bytes - 680_441_088 * 12) < 2e6
    assert m.alias_size_in_bytes > 0.999 * m.output_size_in_bytes
    assert live_at_peak_bytes(compiled) <= 14.4e9
    text = compiled.as_text()
    # the new layer's scopes reach the operations' metadata, the
    # module's too, and the grouped products are the compiler's kernels
    for scope in ("l1_mla0)/core/", "l1_mla0)/q_proj/", "l1_mla0)/kv_proj/",
                  "l1_mla0)/rotary/", "l1_mla0)/out_proj/",
                  "l20_mtp_mla)/core/", "l19_mtp_eh_proj", "l21_mtp_moe)/route/"):
        assert scope in text, scope
    assert 'op_name="ragged-dot-none"' in text
    # PR 39: the slabs after the first are loops inside the scanned step,
    # and every held expert's float32 matrices (weight and both moments)
    # stay in the layout they are kept in: turned ({1,2,0}), with a copy
    # of each at the scan's edges, the step read 17.4 GB
    assert len(re.findall(r" while\(", text)) > 1
    assert re.search(r"f32\[16,2048,1536\]\{2,1,0", text)
    assert not re.search(r"f32\[16,(?:2048,1536|768,2048)\]\{1,2,0", text)
    # PR 37: every latent layer's core is Mosaic calls, all billed to its
    # core scope — since PR 44 three of them (forward, dq, dk/dv: the
    # remat recompute reads the kept o and lse and runs no forward);
    # mha's float32 score blocks (1, 32, 512, <= 8192) are gone
    calls = _mosaic_calls(text)
    assert len(calls) == 6 * 3, [c[-60:] for c in calls]
    assert all("/core/" in c and ("mla" in c) for c in calls), calls
    for kern, n in (("flash_fwd", 6), ("flash_dq", 6), ("flash_dkv", 6)):
        assert sum(f"/{kern}/pallas_call" in c for c in calls) == n, kern
    assert not _SCORE_BLOCK.search(text)


def test_the_nemotron_step_fits_a_chip_at_one_rank_s_share(one_chip):
    """``tools/compile_for_v5e.py``'s compile of the conf the builder
    writes, from shapes alone: 8.41 GB of weights and adam's moments
    aliased to the outputs, the rest temporaries of one 8192-token row
    (11.81 GB live at the peak when this was written)."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from tools.compile_for_v5e import compile_step, live_at_peak_bytes

    from cxxnet_tpu.models import nemotron_h_conf

    compiled = compile_step(nemotron_h_conf())
    m = compiled.memory_analysis()
    assert abs(m.argument_size_in_bytes - 700_865_520 * 12) < 2e6
    assert m.alias_size_in_bytes > 0.999 * m.output_size_in_bytes
    assert live_at_peak_bytes(compiled) <= 14.4e9
    text = compiled.as_text()
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
    assert 'op_name="ragged-dot-none"' in text
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
    # the flash kernels: three Mosaic calls (PR 44: one forward); since
    # PR 41 the five mixers' scans are the kernels of ops/ssd_fused.py
    # (forward, recompute, backward), billed to their scan scopes
    calls = _mosaic_calls(text)
    ssd = [c for c in calls if "/ssd_scan" in c]
    assert len(ssd) == 15 and len(calls) == 18, [c[-60:] for c in calls]
    assert all("mixer" in c and "/scan/" in c for c in ssd), ssd
    assert all("attn" in c for c in calls if c not in ssd), calls
    assert not _SCORE_BLOCK.search(text)


@pytest.mark.parametrize("cfg, d", [
    (dict(nhead=64, head_dim=64, nstate=128, chunk=256), 2048),
    (dict(nhead=16, head_dim=64, nstate=128, chunk=128), 4096),
], ids=["granite", "nemotron_h_share"])
def test_a_mamba2_layer_lowered_for_a_tpu_is_the_fused_kernels(one_chip, cfg,
                                                               d):
    """One ``mamba2`` layer on a packed row of 8192 tokens, bfloat16,
    under ``remat`` as the step programs run it (the scan names nothing
    the net's policy keeps: forward, recompute, backward)."""
    from cxxnet_tpu.layers import create_layer
    from cxxnet_tpu.nnet.net import REMAT_POLICY

    lay = create_layer("mamba2")
    for k, v in dict(cfg, prenorm=1, residual_scale=0.22).items():
        lay.set_param(k, str(v))
    shapes = [(1, 8192, d), (1, 8192)]
    lay.infer_shape(shapes)
    params = jax.eval_shape(lambda k: lay.init_params(k, shapes),
                            jax.random.PRNGKey(0))
    aux = jax.eval_shape(lambda: lay.init_aux(shapes))

    def loss(p, aux, x, ids):
        def run(p, x):
            with jax.named_scope("l1_mixer0"):
                (y,), new = lay.apply_stateful(p, aux, [x, ids])
            return jnp.sum(y.astype(jnp.float32)), new
        return jax.checkpoint(run, policy=REMAT_POLICY)(p, x)

    shaped = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda v: _shaped(one_chip, v.shape, v.dtype), t)
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 2), has_aux=True)
                       ).lower(
        shaped(params), shaped(aux), _shaped(one_chip, shapes[0]),
        _shaped(one_chip, shapes[1], jnp.float32)).compile()
    text = compiled.as_text()
    calls = _mosaic_calls(text)
    assert sorted(c.split("/")[-2] for c in calls) == [
        "ssd_scan", "ssd_scan", "ssd_scan_bwd"], calls
    # forward, recompute and backward alike are billed to the layer's
    # scan scope
    assert all("l1_mixer0" in c and "/scan/" in c for c in calls), calls
    (bwd,) = [c for c in calls if "ssd_scan_bwd" in c]
    assert "transpose(" in bwd and "rematted_computation" not in bwd
    # none of the jax.numpy form's whole-row chunk tensors is left
    # ((chunks, heads, Q, Q) float32: diff, exp(diff), m — the parent's
    # compile holds 22 fusions that write or read one); what the
    # kernels keep for the backward is the state that entered each chunk,
    # a unit of two heads side by side
    q, h = cfg["chunk"], cfg["nhead"]
    nc = 8192 // q
    assert not re.search(
        rf"(?:f32|bf16)\[(?:1,)?(?:{nc},{h}|{h},{nc}),{q},{q}\]", text)
    assert f"f32[1,{h // 2},{nc},128,128]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9


def test_the_granite_step_holds_no_more_than_the_parent_s(one_chip):
    """The builder's defaults are the cell's conf, compiled as the CLI
    compiles it: the parent's step (the ``jax.numpy`` scan) read 15.054
    GB live at its fullest, the kernels' 14.650 (PR 41) — the float32
    chunk tensors of one layer's backward are gone."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from tools.compile_for_v5e import compile_step, live_at_peak_bytes

    from cxxnet_tpu.models import granite_h_conf

    compiled = compile_step(granite_h_conf())
    assert live_at_peak_bytes(compiled) <= 15.06e9
    calls = _mosaic_calls(compiled.as_text())
    # nine mixers x (forward, recompute, backward) and the attention
    # layer's three flash kernels
    assert sum("/ssd_scan/" in c for c in calls) == 18
    assert sum("/ssd_scan_bwd/" in c for c in calls) == 9
    assert len(calls) == 30
    assert all("/scan/" in c for c in calls if "ssd_scan" in c)


_SCORE_BLOCK = re.compile(r"f32\[[0-9,]*,512,(?:512|1024|[1-8][0-9]{3})\]")


def _mosaic_calls(text):
    """The ``op_name`` of every Pallas kernel's Mosaic custom call of a
    compiled text (the compiler's own ``ragged-dot-*`` are not ours)."""
    names = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    return [n for n in names if n.endswith("/pallas_call")]


def test_the_trinity_step_fits_a_chip_at_eight_held_experts(one_chip):
    """``tools/compile_for_v5e.py``'s compile of the conf the builder
    writes, from shapes alone: 6.05 GB of weights and adam's moments
    aliased to the outputs, the rest temporaries of one 16384-token row
    (12.35 GB live at the peak when this was written; with 16 held
    experts it read 14.97, over the line: the configuration's
    ``memory_analysis_v5e``)."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from tools.compile_for_v5e import compile_step, live_at_peak_bytes

    from cxxnet_tpu.models import afmoe_conf

    compiled = compile_step(afmoe_conf())
    m = compiled.memory_analysis()
    assert abs(m.argument_size_in_bytes - 504_147_712 * 12) < 2e6
    assert m.alias_size_in_bytes > 0.999 * m.output_size_in_bytes
    assert live_at_peak_bytes(compiled) <= 14.4e9
    text = compiled.as_text()
    for scope in ("l1_attn0)/core_window/", "l9_attn4)/core_full/",
                  "l1_attn0)/qk_norm/", "l1_attn0)/rotary/",
                  "l4_moe1)/route/", "l4_moe1)/dispatch/",
                  "l4_moe1)/experts/", "l4_moe1)/combine/",
                  "l4_moe1)/shared/"):
        assert scope in text, scope
    # the full layer rotates nothing
    assert "l9_attn4)/rotary/" not in text
    assert 'op_name="ragged-dot-none"' in text
    # the held experts row-major through the scan like the accepted cells'
    assert re.search(r"f32\[8,2048,2048\]\{2,1,0", text)
    assert not re.search(r"f32\[8,(?:2048,2048|1024,2048)\]\{1,2,0", text)
    # all five attention layers are the flash kernels, three calls each
    # (one forward: PR 44), each under its layer's own core scope
    calls = _mosaic_calls(text)
    assert len(calls) == 15, [c[-60:] for c in calls]
    assert sum("/core_window/" in c for c in calls) == 12
    assert sum("/core_full/" in c and "l9_attn4" in c for c in calls) == 3
    assert not _SCORE_BLOCK.search(text)


@pytest.mark.parametrize("window, steps", [(2048, 45), (0, 136)],
                         ids=["sliding", "full"])
def test_a_trinity_attention_layer_lowered_for_a_tpu_is_the_flash_kernels(
        one_chip, window, steps):
    """One sandwiched ``attention`` layer of the afmoe family on a packed
    row of 16384 tokens, bfloat16, under the net's ``remat``: the
    kernels' grids walk the window's steps on a sliding layer."""
    from cxxnet_tpu.layers import create_layer
    from cxxnet_tpu.nnet.net import REMAT_POLICY
    from cxxnet_tpu.ops.flash import BLOCK

    cfg = dict(nhead=32, nkvhead=4, head_dim=128, qk_norm=1, out_gate=1,
               causal=1, no_bias=1, prenorm=1, postnorm=1,
               residual_scale=1.0)
    if window:
        cfg.update(window=window, rotary_dim=128)
    lay = create_layer("attention")
    for k, v in cfg.items():
        lay.set_param(k, str(v))
    shapes = [(1, 16384, 2048), (1, 16384)]
    lay.infer_shape(shapes)
    params = jax.eval_shape(lambda k: lay.init_params(k, shapes),
                            jax.random.PRNGKey(0))
    aux = jax.eval_shape(lambda: lay.init_aux(shapes))

    def loss(p, aux, x, ids):
        def run(p, x):
            with jax.named_scope("l3_attn1"):
                (y,), new = lay.apply_stateful(p, aux, [x, ids])
            return jnp.sum(y.astype(jnp.float32)), new
        return jax.checkpoint(run, policy=REMAT_POLICY)(p, x)

    shaped = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda v: _shaped(one_chip, v.shape, v.dtype), t)
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 2), has_aux=True)
                       ).lower(
        shaped(params), shaped(aux), _shaped(one_chip, shapes[0]),
        _shaped(one_chip, shapes[1], jnp.float32)).compile()
    text = compiled.as_text()
    calls = _mosaic_calls(text)
    assert sorted(c.split("/")[-2] for c in calls) == [
        "flash_dkv", "flash_dq", "flash_fwd"], calls
    scope = "core_window" if window else "core_full"
    assert all("l3_attn1" in c and f"/{scope}/" in c for c in calls), calls
    assert not _SCORE_BLOCK.search(text)
    assert "bf16[4,16384,128]" in text
    # the step tables are operands of the calls: their length is the grid's
    assert BLOCK == 1024
    assert f"s32[{steps}]" in text and f"s32[{8 * steps}]" in text
    # PR 43: the mask reaches the kernels as two bounds a query, columns
    # beside the query block and rows beside the transposed one
    assert "s32[1,16384,1]" in text and "s32[1,1,16384]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2.4e9


@pytest.mark.parametrize("cfg", [
    # granite 4.0-H micro: 32 query heads over 8 of width 64, scale 1/64
    dict(nhead=32, nkvhead=8, score_scale=0.015625),
    # qwen3_next: 16 over 2 of width 256, partial rotary, an output gate
    dict(nhead=16, nkvhead=2, head_dim=256, qk_norm=1, rotary_dim=64,
         rope_theta=10000000.0, out_gate=1),
    # one rank's share of a Nemotron-H attention: 4 over 1 of width 128
    dict(nhead=4, nkvhead=1, head_dim=128),
], ids=["granite", "qwen3_next", "nemotron_h_share"])
def test_an_attention_layer_lowered_for_a_tpu_is_the_flash_kernels(
        one_chip, cfg):
    """One ``attention`` layer on a packed row of 8192 tokens, bfloat16,
    under ``remat`` as the step programs run it (the net's policy: the
    forward kernel's two outputs are kept, so it runs once)."""
    from cxxnet_tpu.layers import create_layer
    from cxxnet_tpu.nnet.net import REMAT_POLICY

    lay = create_layer("attention")
    for k, v in dict(cfg, causal=1, no_bias=1, prenorm=1).items():
        lay.set_param(k, str(v))
    shapes = [(1, 8192, 2048), (1, 8192)]
    lay.infer_shape(shapes)
    params = jax.eval_shape(lambda k: lay.init_params(k, shapes),
                            jax.random.PRNGKey(0))
    aux = jax.eval_shape(lambda: lay.init_aux(shapes))

    def loss(p, aux, x, ids):
        def run(p, x):
            with jax.named_scope("l3_attn1"):
                (y,), new = lay.apply_stateful(p, aux, [x, ids])
            return jnp.sum(y.astype(jnp.float32)), new
        return jax.checkpoint(run, policy=REMAT_POLICY)(p, x)

    shaped = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda v: _shaped(one_chip, v.shape, v.dtype), t)
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 2), has_aux=True)
                       ).lower(
        shaped(params), shaped(aux), _shaped(one_chip, shapes[0]),
        _shaped(one_chip, shapes[1], jnp.float32)).compile()
    text = compiled.as_text()
    calls = _mosaic_calls(text)
    assert sorted(c.split("/")[-2] for c in calls) == [
        "flash_dkv", "flash_dq", "flash_fwd"], calls
    assert all("l3_attn1" in c for c in calls), calls
    assert not _SCORE_BLOCK.search(text)
    # grouped heads are read by the index map: no key or value repeated
    # to the query heads' count in HBM
    h, hk = cfg["nhead"], cfg["nkvhead"]
    dh = cfg.get("head_dim", 2048 // h)
    assert f"bf16[{hk},8192,{dh}]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1.2e9


@pytest.mark.parametrize("cell", ["granite", "qwen3_next"])
def test_the_net_s_remat_runs_an_attention_layer_s_forward_kernel_once(
        one_chip, cell):
    """Through ``FunctionalNet.forward`` itself (``remat = 1``, the four
    ``jax.checkpoint`` sites under ``REMAT_POLICY``): the cell's builder
    with its stack cut to the ONE attention layer at its published head
    shapes on a row of 8192 tokens, over a small vocabulary and small
    feed-forward parts.  The compiled step holds one ``flash_fwd`` for
    the layer's ``flash_dq`` and ``flash_dkv`` (PR 44: the forward's
    ``o`` and ``lse`` are kept across the backward pass), and the kept
    ``lse`` is its numbers, ``(heads, T)``, not 128 lanes a row."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from tools.compile_for_v5e import compile_step

    from cxxnet_tpu.models import granite_h_conf, qwen3_next_conf

    if cell == "granite":
        conf, heads = granite_h_conf(layer_types="a", vocab=512,
                                     mlp_hidden=512, scan_steps=1), 32
    else:
        conf, heads = qwen3_next_conf(
            layer_types="f", vocab=512, num_experts=8, experts_per_tok=2,
            experts_held=8, expert_hidden=128, shared_hidden=128,
            scan_steps=1), 16
    text = compile_step(conf).as_text()
    calls = _mosaic_calls(text)
    assert sorted(c.split("/")[-2] for c in calls) == [
        "flash_dkv", "flash_dq", "flash_fwd"], calls
    assert all("l1_attn0" in c for c in calls), calls
    (fwd,) = [c for c in calls if "flash_fwd" in c]
    assert "rematted_computation" not in fwd and "transpose(" not in fwd
    assert f"f32[{heads},8192]" in text
    assert not _SCORE_BLOCK.search(text)


def test_a_layer_that_names_nothing_lowers_as_it_did_under_no_policy(
        one_chip):
    """``save_only_these_names`` with no such name in the layer saves
    nothing, which is ``policy=None``: a ``gated_mlp`` branch at
    granite's widths lowers for the chip to the same text under the
    net's policy and under a plain ``jax.checkpoint``."""
    from cxxnet_tpu.layers import create_layer
    from cxxnet_tpu.nnet.net import REMAT_POLICY

    lay = create_layer("gated_mlp")
    for k, v in dict(nhidden=8192, prenorm=1, residual_scale=0.22).items():
        lay.set_param(k, str(v))
    shapes = [(1, 8192, 2048)]
    lay.infer_shape(shapes)
    params = jax.eval_shape(lambda k: lay.init_params(k, shapes),
                            jax.random.PRNGKey(0))

    def lowered(policy):
        def loss(p, x):
            def run(p, x):
                with jax.named_scope("l2_mlp0"):
                    (y,) = lay.apply(p, [x], train=True)
                return jnp.sum(y.astype(jnp.float32))
            return jax.checkpoint(run, policy=policy)(p, x)
        shaped = jax.tree_util.tree_map(
            lambda v: _shaped(one_chip, v.shape, v.dtype), params)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
            shaped, _shaped(one_chip, shapes[0])).as_text()

    text = lowered(REMAT_POLICY)
    assert "stablehlo.dot_general" in text
    assert text == lowered(None)
