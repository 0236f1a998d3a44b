"""What the TPU's compiler makes of the two mechanisms of PR 33 at
qwen3_next's published widths, compiled for a DESCRIBED v5e chip
(``tests/v5e.py``).

* ``jax.lax.ragged_dot`` inside ``layers/moe.held_experts`` becomes
  kernels named ``ragged-dot-*`` whose layer scope is dropped — the
  name ``benchmarks/lib/stage_scopes.py`` reads the grouped products'
  time by; since PR 39 they run on slabs of 10 240 rows at qwen3_next's
  shapes, under one ``while`` for the slabs after the first, and no
  array with a feature axis has room for all 81 920 (token, pick) pairs;
* the chunked gated delta rule of ``ops/gdn.py`` in its ``jax.numpy``
  form, walked in checkpointed segments, keeps its backward's
  temporaries under the room a 16 GB chip has beside 10 GB of state;
* lowered for a TPU, ``gated_delta_scan`` IS the fused kernels of
  ``ops/gdn_fused.py`` (PR 34): three Mosaic custom calls under the
  caller's ``scan`` scope, the backward's too, no whole-row chunk
  matrices, and less scratch than the segmented form with no segments;
  under the net's ``remat`` policy a layer runs each of the three ONCE
  (PR 47: what ``gdn_solve`` and ``gdn_scan`` wrote is kept across the
  backward pass);
* lowered for a TPU, masked attention IS the flash kernels of
  ``ops/flash.py`` (PR 37) at qwen3_next's head shapes, ONE ``flash_fwd``
  and ONE ``flash_bwd`` a layer (PR 44: the net's ``remat`` policy keeps
  ``o`` and ``lse``; PR 48: the backward is one kernel).
"""

import re

import jax
import jax.numpy as jnp
import pytest

import v5e


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

    args = (v5e.shaped(one_chip, (m, d)),
            v5e.shaped(one_chip, (m, k), jnp.float32),
            v5e.shaped(one_chip, (m, k), jnp.int32),
            v5e.shaped(one_chip, (g, d, 2 * f)),
            v5e.shaped(one_chip, (g, f, d)))
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

    head = v5e.shaped(one_chip, (1, t, h, dk))
    gate = v5e.shaped(one_chip, (1, t, h), jnp.float32)
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

    key = v5e.shaped(one_chip, (1, t, hk, d))
    val = v5e.shaped(one_chip, (1, t, hv, d))
    gate = v5e.shaped(one_chip, (1, t, hv), jnp.float32)
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


@pytest.mark.parametrize("checkpoint, want", [
    # the net's policy keeps what ``gdn_solve`` and ``gdn_scan`` wrote:
    # the recompute runs neither
    ({}, {"gdn_solve": 1, "gdn_scan": 1, "gdn_scan_bwd": 1}),
    # the control, a ``jax.checkpoint`` that keeps nothing: the parent's
    # program, every chunk solved twice
    ({"policy": None}, {"gdn_solve": 2, "gdn_scan": 2, "gdn_scan_bwd": 1}),
], ids=["the_net_s_policy", "no_policy"])
def test_a_layer_s_remat_runs_the_forward_kernels_once(one_chip, checkpoint,
                                                       want):
    """One ``gated_deltanet`` layer at the published widths (a row of
    8192 tokens, 16 key and 32 value heads of 128, bfloat16) under
    ``remat`` as the step programs run it."""
    compiled = v5e.compile_layer(
        one_chip, "gated_deltanet",
        dict(nkhead=16, nvhead=32, key_dim=128, value_dim=128, conv_width=4,
             chunk=64, prenorm=1, residual_scale=1.0),
        [(1, 8192, 2048), (1, 8192)], "l1_gdn0", **checkpoint)
    calls = v5e.mosaic_calls(compiled.as_text())
    runs = {k: [c for c in calls if c.endswith(f"/{k}/pallas_call")]
            for k in want}
    assert {k: len(v) for k, v in runs.items()} == want, calls
    assert all("l1_gdn0" in c and "/scan/" in c for c in calls), calls
    again = [c.split("/")[-2] for c in calls if "rematted_computation" in c]
    assert again == [k for k in ("gdn_solve", "gdn_scan") if want[k] == 2]
    (bwd,) = runs["gdn_scan_bwd"]
    assert "transpose(" in bwd and "rematted_computation" not in bwd
    # one layer's kept values are alive between its forward and its
    # backward either way: 1.60 GB of temporaries under both policies
    assert compiled.memory_analysis().temp_size_in_bytes < 1.7e9


@pytest.mark.parametrize("cfg", [
    # qwen3_next: 16 over 2 of width 256, partial rotary, an output gate
    dict(nhead=16, nkvhead=2, head_dim=256, qk_norm=1, rotary_dim=64,
         rope_theta=10000000.0, out_gate=1),
], ids=["qwen3_next"])
def test_an_attention_layer_lowered_for_a_tpu_is_the_flash_kernels(
        one_chip, cfg):
    v5e.attention_layer_is_the_flash_kernels(one_chip, cfg)


@pytest.mark.parametrize("cell", ["qwen3_next"])
def test_the_net_s_remat_runs_an_attention_layer_s_forward_kernel_once(
        one_chip, cell):
    from cxxnet_tpu.models import qwen3_next_conf

    v5e.net_s_remat_runs_the_forward_kernel_once(
        qwen3_next_conf(layer_types="f", vocab=512, num_experts=8,
                        experts_per_tok=2, experts_held=8, expert_hidden=128,
                        shared_hidden=128, scan_steps=1), heads=16)
