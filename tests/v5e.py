"""What the ``test_v5e_<family>.py`` files share: a DESCRIBED v5e chip
(the on-chip-measurement guide, section 2: nothing runs, no chip is
needed), shapes placed on it, one layer lowered for it under the net's
``remat`` as the step programs run it, and the readers of a compiled
text.  Not collected; ``one_chip`` itself is ``tests/conftest.py``'s.

One file a family, because ``--dist loadfile`` gives a file to one
worker and a family's whole-step compile is one to three minutes.  Every
process that describes the topology loads the TPU's library; more than
one at a time may only with ``ALLOW_MULTIPLE_LIBTPU_LOAD`` set, which
the fixture does before it describes the chip.
"""

import os
import re
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.compile_for_v5e import compile_step, live_at_peak_bytes  # noqa: E402

#: a float32 score block of ``mha``'s: (…, 512, <= 8192)
SCORE_BLOCK = re.compile(r"f32\[[0-9,]*,512,(?:512|1024|[1-8][0-9]{3})\]")


def shaped(one_chip, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def mosaic_calls(text):
    """The ``op_name`` of every Pallas kernel's Mosaic custom call of a
    compiled text (the compiler's own ``ragged-dot-*`` are not ours)."""
    names = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    return [n for n in names if n.endswith("/pallas_call")]


def step_that_fits(text, parameters, limit):
    """The compiled text of the conf's whole scanned step, once it has
    shown what every token cell's step shows: weights and adam's two
    moments (12 B a parameter; the gradients are temporaries) updated in
    place, no more than ``limit`` bytes live at its fullest, the grouped
    products the compiler's kernels, no float32 score block of
    ``mha``'s."""
    compiled = compile_step(text)
    m = compiled.memory_analysis()
    assert abs(m.argument_size_in_bytes - parameters * 12) < 2e6
    assert m.alias_size_in_bytes > 0.999 * m.output_size_in_bytes
    assert live_at_peak_bytes(compiled) <= limit
    text = compiled.as_text()
    assert 'op_name="ragged-dot-none"' in text
    assert not SCORE_BLOCK.search(text)
    return text


def compile_layer(one_chip, kind, cfg, shapes, scope, **checkpoint):
    """Value and gradient (weights and input) of one layer of ``kind``
    under the named ``scope`` and the net's ``remat`` policy (or the
    ``policy`` given: ``None`` keeps nothing), bfloat16, compiled for the
    chip from shapes alone; ``shapes[1]``, where there is one, is the
    row's ids."""
    from cxxnet_tpu.layers import create_layer
    from cxxnet_tpu.nnet.net import REMAT_POLICY

    checkpoint.setdefault("policy", REMAT_POLICY)

    lay = create_layer(kind)
    for k, v in cfg.items():
        lay.set_param(k, str(v))
    lay.infer_shape(shapes)
    params = jax.eval_shape(lambda k: lay.init_params(k, shapes),
                            jax.random.PRNGKey(0))
    aux = jax.eval_shape(lambda: lay.init_aux(shapes))

    def loss(p, aux, x, ids):
        def run(p, x):
            with jax.named_scope(scope):
                (y,), new = lay.apply_stateful(p, aux, [x, ids])
            return jnp.sum(y.astype(jnp.float32)), new
        return jax.checkpoint(run, **checkpoint)(p, x)

    on_chip = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda v: shaped(one_chip, v.shape, v.dtype), t)
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 2), has_aux=True)
                   ).lower(
        on_chip(params), on_chip(aux), shaped(one_chip, shapes[0]),
        shaped(one_chip, shapes[1], jnp.float32)).compile()


def attention_layer_is_the_flash_kernels(one_chip, cfg):
    """One ``attention`` layer on a packed row of 8192 tokens, bfloat16,
    under ``remat`` as the step programs run it (the net's policy: the
    forward kernel's two outputs are kept, so it runs once; PR 48: the
    backward is the ONE kernel — that it compiles here is the proof it
    fits a v5e's VMEM at these head shapes)."""
    compiled = compile_layer(one_chip, "attention",
                             dict(cfg, causal=1, no_bias=1, prenorm=1),
                             [(1, 8192, 2048), (1, 8192)], "l3_attn1")
    text = compiled.as_text()
    calls = mosaic_calls(text)
    assert sorted(c.split("/")[-2] for c in calls) == [
        "flash_bwd", "flash_fwd"], calls
    assert all("l3_attn1" in c for c in calls), calls
    assert not SCORE_BLOCK.search(text)
    # grouped heads are read by the index map: no key or value repeated
    # to the query heads' count in HBM
    h, hk = cfg["nhead"], cfg["nkvhead"]
    dh = cfg.get("head_dim", 2048 // h)
    assert f"bf16[{hk},8192,{dh}]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1.2e9


def mamba2_layer_is_the_fused_kernels(one_chip, cfg, d):
    """One ``mamba2`` layer on a packed row of 8192 tokens, bfloat16,
    under ``remat`` as the step programs run it (the scan names nothing
    the net's policy keeps: forward, recompute, backward)."""
    compiled = compile_layer(one_chip, "mamba2",
                             dict(cfg, prenorm=1, residual_scale=0.22),
                             [(1, 8192, d), (1, 8192)], "l1_mixer0")
    text = compiled.as_text()
    calls = mosaic_calls(text)
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


def net_s_remat_runs_the_forward_kernel_once(conf, heads):
    """Through ``FunctionalNet.forward`` itself (``remat = 1``, the four
    ``jax.checkpoint`` sites under ``REMAT_POLICY``): a cell's builder
    with its stack cut to the ONE attention layer at its published head
    shapes on a row of 8192 tokens, over a small vocabulary and small
    feed-forward parts.  The compiled step holds one ``flash_fwd`` for
    the layer's one ``flash_bwd`` (PR 44: the forward's ``o`` and
    ``lse`` are kept across the backward pass; PR 48: one backward
    kernel), and the kept ``lse`` is its numbers, ``(heads, T)``, not 128
    lanes a row."""
    text = compile_step(conf).as_text()
    calls = mosaic_calls(text)
    assert sorted(c.split("/")[-2] for c in calls) == [
        "flash_bwd", "flash_fwd"], calls
    assert all("l1_attn0" in c for c in calls), calls
    (fwd,) = [c for c in calls if "flash_fwd" in c]
    assert "rematted_computation" not in fwd and "transpose(" not in fwd
    assert f"f32[{heads},8192]" in text
    assert not SCORE_BLOCK.search(text)
