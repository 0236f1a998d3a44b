"""What the TPU's compiler makes of the Granite-4.0-H cell's layers and
of its whole step at their published widths, compiled for a DESCRIBED
v5e chip (``tests/v5e.py``).

* lowered for a TPU, a ``mamba2`` layer's scan IS the fused kernels of
  ``ops/ssd_fused.py`` (PR 41) at granite's 64 heads at chunks of 256:
  ``ssd_scan`` (forward and the ``remat`` recompute) and ``ssd_scan_bwd``
  under the layer's ``scan`` scope, and no ``(…, Q, Q)`` float32 decay or
  score tensor of the ``jax.numpy`` form is left; the whole granite step
  (ten layers under adam, 772M parameters) holds no more at its fullest
  than the parent's 15.06 GB: 14.65.
* lowered for a TPU, masked attention IS the flash kernels of
  ``ops/flash.py`` (PR 37) under an ``attention`` layer's own scope at
  granite's head shapes, ONE ``flash_fwd`` and ONE ``flash_bwd`` a layer
  (since PR 44 the net's ``remat`` policy keeps the forward's ``o`` and
  ``lse``, so the recompute runs no second ``flash_fwd``; since PR 48
  the backward is one kernel), and no float32
  ``(…, 512, <= 8192)`` score block of ``mha``'s is left.
"""

import jax
import jax.numpy as jnp
import pytest

import v5e


@pytest.mark.parametrize("cfg, d", [
    (dict(nhead=64, head_dim=64, nstate=128, chunk=256), 2048),
], ids=["granite"])
def test_a_mamba2_layer_lowered_for_a_tpu_is_the_fused_kernels(one_chip, cfg,
                                                               d):
    v5e.mamba2_layer_is_the_fused_kernels(one_chip, cfg, d)


def test_the_granite_step_holds_no_more_than_the_parent_s(one_chip):
    """The builder's defaults are the cell's conf, compiled as the CLI
    compiles it: the parent's step (the ``jax.numpy`` scan) read 15.054
    GB live at its fullest, the kernels' 14.650 (PR 41) — the float32
    chunk tensors of one layer's backward are gone."""
    from cxxnet_tpu.models import granite_h_conf

    compiled = v5e.compile_step(granite_h_conf())
    assert v5e.live_at_peak_bytes(compiled) <= 15.06e9
    calls = v5e.mosaic_calls(compiled.as_text())
    # nine mixers x (forward, recompute, backward) and the attention
    # layer's two flash kernels (``flash_fwd``, ``flash_bwd``: PR 48)
    assert sum("/ssd_scan/" in c for c in calls) == 18
    assert sum("/ssd_scan_bwd/" in c for c in calls) == 9
    assert len(calls) == 29
    assert sorted(c.split("/")[-2] for c in calls if "flash" in c) == [
        "flash_bwd", "flash_fwd"]
    assert all("/scan/" in c for c in calls if "ssd_scan" in c)


@pytest.mark.parametrize("cfg", [
    # granite 4.0-H micro: 32 query heads over 8 of width 64, scale 1/64
    dict(nhead=32, nkvhead=8, score_scale=0.015625),
], ids=["granite"])
def test_an_attention_layer_lowered_for_a_tpu_is_the_flash_kernels(
        one_chip, cfg):
    v5e.attention_layer_is_the_flash_kernels(one_chip, cfg)


@pytest.mark.parametrize("cell", ["granite"])
def test_the_net_s_remat_runs_an_attention_layer_s_forward_kernel_once(
        one_chip, cell):
    from cxxnet_tpu.models import granite_h_conf

    v5e.net_s_remat_runs_the_forward_kernel_once(
        granite_h_conf(layer_types="a", vocab=512, mlp_hidden=512,
                       scan_steps=1), heads=32)


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
        on_chip = jax.tree_util.tree_map(
            lambda v: v5e.shaped(one_chip, v.shape, v.dtype), params)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
            on_chip, v5e.shaped(one_chip, shapes[0])).as_text()

    text = lowered(REMAT_POLICY)
    assert "stablehlo.dot_general" in text
    assert text == lowered(None)
