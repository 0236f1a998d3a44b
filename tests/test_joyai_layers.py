"""The layers a JoyAI-LLM-Flash (DeepSeek-V3 layout) model forced (ISSUE
36), each against a plain statement of the same function at a small
size, float32, seeded weights: ``latent_attention`` against the
reference's; the interleaved rotary pairing against complex numbers;
``routed_experts`` with sigmoid scores, a selection bias and an ungated
shared expert, and the sum of its shares against the uncut layer;
``softmax`` with ``target_shift``; ``token_shift``; the two-loss net
with a shared embedding and head; the ``attn_pairs`` counter.  What
every family's tests share (the builder's conf through the trainer, the
published defaults, the whole net against the reference) is a row of
``tests/families.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import families
from cxxnet_tpu.io.tokens import attn_pairs
from cxxnet_tpu.layers.moe import route
from cxxnet_tpu.models import joyai_llm_flash_conf
from cxxnet_tpu.ops.attention import rotary
from families import (expert_shares, held_against, make,
                      rows_with_documents, strs, with_bias)

FAMILY = "joyai_llm_flash"


# ----------------------------------------------------------------------
MLA = dict(nhead=4, q_rank=24, kv_rank=16, nope_dim=8, rope_dim=4, v_dim=6,
           rope_theta=32000000.0, causal=1, init_sigma=0.3)


@pytest.mark.parametrize("interleave", [1, 0])
def test_latent_attention_is_the_reference_s(ref, interleave):
    """Forward and gradient, two or three documents a row: the mask and
    the positions' restarts are read from the ids."""
    cfg = dict(MLA, rope_interleave=interleave)
    lay, p, out = make("latent_attention", [(2, 24, 20), (2, 24)], **cfg)
    assert out == [(2, 24, 20)]
    assert {t: v.shape for t, v in p.items()} == {
        "wqa": (24, 20), "q_norm": (24,), "wqb": (48, 24),
        "wkva": (20, 20), "kv_norm": (16,), "wkvb": (56, 16),
        "wproj": (20, 24)}
    r = np.random.RandomState(1)
    p = dict(p, q_norm=jnp.asarray(1 + 0.1 * r.randn(24), jnp.float32),
             kv_norm=jnp.asarray(1 + 0.1 * r.randn(16), jnp.float32))
    x = jnp.asarray(r.randn(2, 24, 20), jnp.float32)
    ids = jnp.asarray(rows_with_documents(2, 2, 24))
    scfg = strs(cfg)
    int_ids = ids.astype(jnp.int32)

    def prog(q, a):
        return lay.apply(q, [a, ids])[0]

    def plain(q, a):
        return ref.latent_attention(q, a, int_ids, scfg)

    y, _, _ = held_against(prog, plain, p, x, list(p), y_atol=2e-5)
    with jax.default_matmul_precision("highest"):
        # a token of the second document does not see the first
        cut = x.at[:, :8].set(0.0)
        np.testing.assert_allclose(jax.jit(prog)(p, cut)[:, 9:], y[:, 9:],
                                   atol=1e-6)


def test_latent_attention_as_a_branch_and_without_ids(ref):
    lay, p, _ = make("latent_attention", [(1, 12, 20)], prenorm=1,
                     residual_scale=1.0, eps=1e-6, **MLA)
    assert p["norm"].shape == (20,)
    x = jnp.asarray(np.random.RandomState(3).randn(1, 12, 20), jnp.float32)
    scfg = strs(dict(MLA, eps=1e-6))
    with jax.default_matmul_precision("highest"):
        got = lay.apply(p, [x])[0]
        want = x + ref.latent_attention(
            p, ref.rms_norm(x, p["norm"], 1e-6), None, scfg)
    np.testing.assert_allclose(got, want, atol=2e-5)
    with pytest.raises(ValueError, match="rope_dim=3 must be even"):
        make("latent_attention", [(1, 12, 20)], **dict(MLA, rope_dim=3))
    with pytest.raises(ValueError, match="set nhead"):
        make("latent_attention", [(1, 12, 20)], nhead=4)


# ----------------------------------------------------------------------
def test_interleaved_rotary_is_a_complex_rotation_and_rotate_half_stays():
    r = np.random.RandomState(4)
    x = r.randn(2, 9, 3, 8).astype(np.float32)
    pos = r.randint(0, 5000, (2, 9)).astype(np.int32)
    theta = 3.2e7
    freq = theta ** (-np.arange(4) * 2.0 / 8)
    ang = pos[..., None].astype(np.float64) * freq            # (2, 9, 4)
    turn = np.exp(1j * ang)[:, :, None]
    z = x[..., 0::2].astype(np.float64) + 1j * x[..., 1::2]
    want = np.empty(x.shape)
    want[..., 0::2], want[..., 1::2] = (z * turn).real, (z * turn).imag
    got = rotary(jnp.asarray(x), jnp.asarray(pos), 8, theta, True)
    np.testing.assert_allclose(got, want, atol=2e-4)
    # rotate-half, the default: the pairs are (i, i + dim/2)
    z = x[..., :4].astype(np.float64) + 1j * x[..., 4:]
    half = np.concatenate([(z * turn).real, (z * turn).imag], axis=-1)
    np.testing.assert_allclose(
        rotary(jnp.asarray(x), jnp.asarray(pos), 8, theta), half, atol=2e-4)
    # a score depends on the distance alone, in either pairing
    for pairs in (True, False):
        a = rotary(jnp.asarray(x), jnp.asarray(pos), 8, theta, pairs)
        b = rotary(jnp.asarray(x), jnp.asarray(pos + 77), 8, theta, pairs)
        np.testing.assert_allclose(
            jnp.einsum("nthd,nshd->nhts", a, a),
            jnp.einsum("nthd,nshd->nhts", b, b), atol=2e-3)
    # only the first dim of a head turns
    part = rotary(jnp.asarray(x), jnp.asarray(pos), 4, theta, True)
    np.testing.assert_array_equal(part[..., 4:], x[..., 4:])


# ----------------------------------------------------------------------
def test_sigmoid_routing_chooses_by_the_bias_and_weighs_without_it():
    r = np.random.RandomState(5)
    logits = jnp.asarray(r.randn(40, 32), jnp.float32)
    bias = jnp.asarray(0.3 * r.randn(32), jnp.float32)
    w, idx = route(logits, 8, True, score_func="sigmoid", bias=bias,
                   scale=2.5)
    s = 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))
    chosen = np.argsort(-(s + np.asarray(bias)), axis=1, kind="stable")[:, :8]
    assert np.array_equal(np.sort(np.asarray(idx), axis=1),
                          np.sort(chosen, axis=1))
    plain = np.argsort(-s, axis=1, kind="stable")[:, :8]
    # the bias changed the chosen eight of most tokens here
    assert (np.sort(plain, axis=1) != np.sort(chosen, axis=1)).any(
        axis=1).mean() > 0.5
    picked = np.take_along_axis(s, np.asarray(idx), axis=1)
    np.testing.assert_allclose(
        w, 2.5 * picked / picked.sum(axis=1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(axis=1), 2.5, rtol=1e-5)
    # the bias gets no gradient, the logits do (a whole layer's weights)
    g = jax.grad(lambda lg, b: jnp.sum(jnp.sin(route(
        lg, 8, True, score_func="sigmoid", bias=b, scale=2.5)[0])),
        argnums=(0, 1))(logits, bias)
    assert np.abs(np.asarray(g[1])).max() == 0
    assert np.abs(np.asarray(g[0])).max() > 0
    # softmax scores, no bias, no scale: the path that was there
    w0, idx0 = route(logits, 8)
    p = np.asarray(jax.nn.softmax(logits, axis=-1))
    top = np.sort(p, axis=1)[:, ::-1][:, :8]
    np.testing.assert_allclose(w0, top / top.sum(1, keepdims=True),
                               rtol=1e-5)
    with pytest.raises(ValueError, match="softmax or sigmoid"):
        make("routed_experts", [(4, 8)], nexpert=4, topk=2, nhidden=4,
             score_func="tanh")


MOE = dict(nexpert=32, topk=4, nhidden=10, shared_hidden=6, shared_gate=0,
           score_func="sigmoid", select_bias=1, routed_scale=2.5,
           init_sigma=0.5)


def test_routed_experts_with_a_bias_is_the_reference_s(ref):
    lay, p, _ = make("routed_experts", [(2, 12, 8)], first_expert=8,
                     nheld=8, **MOE)
    assert {t: v.shape for t, v in p.items()} == {
        "wgate": (32, 8), "wmat": (8, 8, 20), "wproj": (8, 10, 8),
        "shared_wmat": (12, 8), "shared_wproj": (8, 6),
        "score_bias": (32,)}
    assert float(jnp.abs(p["score_bias"]).max()) == 0.0
    p = with_bias(p, 6)
    x = jnp.asarray(np.random.RandomState(7).randn(2, 12, 8), jnp.float32)
    scfg = strs(dict(MOE, first_expert=8, nheld=8))
    with jax.default_matmul_precision("highest"):
        (y,), state = jax.jit(lay.apply_stateful)(
            p, lay.init_aux([(2, 12, 8)]), [x])
        _, idx = ref.router(p, x.reshape(-1, 8), scfg)
    _, want, ga = held_against(
        lambda q, a: lay.apply(q, [a])[0],
        lambda q, a: ref.routed_experts(q, a, scfg), p, x,
        ("wmat", "wproj", "shared_wmat", "shared_wproj"), y_atol=3e-5)
    np.testing.assert_allclose(y, want, atol=3e-5)
    held = (np.asarray(idx) >= 8) & (np.asarray(idx) < 16)
    assert int(state["pairs"]) == held.sum() > 0
    # a share's router and the bias anywhere: no gradient
    assert np.abs(np.asarray(ga[0]["wgate"])).max() == 0
    assert np.abs(np.asarray(ga[0]["score_bias"])).max() == 0


def test_the_sixteen_shares_add_up_to_the_uncut_reference_layer(ref):
    """model-configs section 4: 32 experts over 16 ranks of 2; every
    rank routes over all 32 (sigmoid, bias, top-4, times 2.5) and adds
    its own experts' terms and the shared expert; the parts, the shared
    expert counted once, are what the uncut reference gives."""
    _, p, _ = make("routed_experts", [(2, 12, 8)], **MOE)
    p = with_bias(p, 6)
    x = jnp.asarray(np.random.RandomState(8).randn(2, 12, 8), jnp.float32)
    whole = strs(MOE)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.routed_experts(p, x, whole), np.float64)
        none = dict(p, wmat=p["wmat"][:1] * 0, wproj=p["wproj"][:1] * 0)
        shared = np.asarray(ref.routed_experts(
            none, x, dict(whole, nheld="1")), np.float64)
        parts, pairs = expert_shares(MOE, p, x, 16, 2)
    assert pairs == 24 * 4               # every pair on exactly one rank
    np.testing.assert_allclose(sum(parts) - 15 * shared, want, atol=5e-5)
    assert np.abs(shared).max() > 0.01 and np.abs(want - shared).max() > 0.01


# ----------------------------------------------------------------------
def test_softmax_with_a_target_moved_on_and_the_token_shift():
    r = np.random.RandomState(9)
    x = jnp.asarray(r.randn(2, 7, 11), jnp.float32)
    lab = jnp.asarray(r.randint(0, 11, (2, 7)), jnp.float32)
    plain, _, _ = make("softmax", [(2, 7, 11)])
    moved, _, _ = make("softmax", [(2, 7, 11)], target_shift=1)
    logp = np.asarray(jax.nn.log_softmax(x, axis=-1))
    li = np.asarray(lab, np.int64)
    by_hand = -sum(logp[n, t, li[n, t + 1]] for n in range(2)
                   for t in range(6))
    np.testing.assert_allclose(moved.loss(x, lab), by_hand, rtol=1e-6)
    np.testing.assert_allclose(
        plain.loss(x, lab),
        -sum(logp[n, t, li[n, t]] for n in range(2) for t in range(7)),
        rtol=1e-6)
    # the row's last position is weightless: no gradient reaches it
    g = np.asarray(jax.grad(lambda a: moved.loss(a, lab))(x))
    assert np.abs(g[:, -1]).max() == 0 and np.abs(g[:, :-1]).min() > 0
    # and a row's weight still applies (a padded row of a short batch)
    np.testing.assert_allclose(
        moved.loss_masked(x, lab, jnp.asarray([1.0, 0.0])),
        -sum(logp[0, t, li[0, t + 1]] for t in range(6)), rtol=1e-6)
    with pytest.raises(ValueError, match="target_shift=1 needs a sequence"):
        moved.loss(x[:, 0], lab[:, 0])

    shift, p, out = make("token_shift", [(2, 7)])
    assert out == [(2, 7)] and p == {} and shift.integer_input
    ids = jnp.asarray(r.randint(1, 50, (2, 7)), jnp.float32)
    got = np.asarray(shift.apply({}, [ids])[0])
    np.testing.assert_array_equal(got[:, :-1], np.asarray(ids)[:, 1:])
    np.testing.assert_array_equal(got[:, -1], 0)
    with pytest.raises(ValueError, match="T > 1"):
        make("token_shift", [(2, 1)])


# ----------------------------------------------------------------------
def test_two_losses_share_the_embedding_and_the_head(ref):
    """One leaf each, and its gradient the sum of the main path's and
    the module's (the whole net's gradient against the reference's:
    ``tests/test_families.py``)."""
    text = joyai_llm_flash_conf(**families.JOYAI)
    assert text.count("= shared[embed]") == text.count("= shared[head]") == 1
    assert text.index("= softmax") < text.index("token_shift:mtp_shift")
    assert "target_shift = 1" in text and text.rstrip().count("mtp_") > 10
    tr = families.trainer(text)
    assert [k for k in tr.params if "embed" in k or "head" in k] == [
        "l0_embed", "l6_head"]
    net = ref.describe(text, 1)
    params = families.in_program_s_keys(tr, ref.make_weights(net, 5))
    ids = rows_with_documents(10, 1, 64, vocab=64)
    lab = np.roll(ids, -1, axis=1)

    def grads(t):
        """Of the net of trainer ``t``: the three confs differ in two
        loss weights and share the parameters' tree."""
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(lambda q: t.net.loss_fn(
                q, jnp.asarray(ids), jnp.asarray(lab))))(params)

    whole_l, whole = grads(tr)
    main_l, main = grads(families.trainer(joyai_llm_flash_conf(
        **dict(families.JOYAI, mtp_loss_weight=0.0)), init=False))
    scale = f"grad_scale = {1.0 / 64!r}"
    assert text.count(scale) == 1
    mtp_l, mtp = grads(families.trainer(
        text.replace(scale, "grad_scale = 0.0"), init=False))
    np.testing.assert_allclose(whole_l, main_l + mtp_l, rtol=1e-6)
    assert 0.2 * main_l < mtp_l < 0.4 * main_l      # 0.3 x a like loss
    for key in ("l0_embed", "l6_head"):
        a, b = (np.asarray(g[key]["wmat"]) for g in (main, mtp))
        assert np.abs(a).max() > 0 and np.abs(b).max() > 0
        np.testing.assert_allclose(whole[key]["wmat"], a + b, atol=1e-7)
    # the module's own layers get nothing from the main loss
    assert np.abs(np.asarray(main["l13_mtp_eh_proj"]["wmat"])).max() == 0


# ----------------------------------------------------------------------
def test_attn_pairs_counts_what_a_causal_query_of_its_document_sees():
    rows = np.array([[5, 6, 0, 7, 8, 9, 0, 3],      # 3, 4 and a cut 1
                     [0, 4, 4, 4, 4, 4, 4, 4],      # 1 and a cut 7
                     [2, 2, 2, 2, 2, 2, 2, 0]], np.uint16)   # one of 8
    assert attn_pairs(rows) == (6 + 10 + 1) + (1 + 28) + 36
    assert attn_pairs(np.ones((2, 8192), np.uint16)) == 2 * 8192 * 8193 // 2
    r = np.random.RandomState(11)
    rows = r.randint(0, 9, (5, 200)).astype(np.uint16)
    slow = 0
    for row in rows:
        run = 0
        for tok in row:
            run += 1
            slow += run
            if tok == 0:
                run = 0
    assert attn_pairs(rows) == slow


# ----------------------------------------------------------------------
# the masked attention layers' counters (PR 37)
@pytest.mark.parametrize("kind, shapes, cfg", [
    ("latent_attention", [(2, 16, 32), (2, 16)],
     dict(nhead=2, q_rank=16, kv_rank=8, nope_dim=8, rope_dim=4, v_dim=8,
          causal=1)),
    ("attention", [(2, 16, 32), (2, 16)],
     dict(nhead=4, nkvhead=2, causal=1, rotary_dim=8, out_gate=1,
          no_bias=1)),
    ("attention", [(2, 16, 32)], dict(nhead=4, score_scale=0.125, causal=1)),
], ids=["latent", "masked_with_ids", "masked_by_a_scale"])
def test_masked_attention_counts_its_tokens_and_none_flash_on_a_cpu(
        kind, shapes, cfg):
    lay, p, _ = make(kind, shapes, **cfg)
    aux = lay.init_aux(shapes)
    assert set(aux) == set(lay.aux_counters) == {
        "attn_tokens", "attn_tokens_flash", "attn_blocks",
        "attn_blocks_unmasked", "attn_tokens_bwd_fused"}
    assert all(v.dtype == jnp.uint32 and v.shape == () for v in aux.values())
    r = np.random.RandomState(0)
    ins = [jnp.asarray(r.randn(*shapes[0]), jnp.float32)]
    if len(shapes) > 1:
        ids = np.ones(shapes[1], np.float32)
        ids[0, 5] = ids[1, 11] = 0
        ins.append(jnp.asarray(ids))
    (want,) = lay.apply(p, ins)
    step = jax.jit(lambda p, aux, ins: lay.apply_stateful(p, aux, ins))
    for n in (1, 2):
        (got,), aux = step(p, aux, ins)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
        assert int(aux["attn_tokens"]) == n * 2 * 16
        assert int(aux["attn_tokens_flash"]) == 0
        assert int(aux["attn_blocks"]) == int(
            aux["attn_blocks_unmasked"]) == int(
                aux["attn_tokens_bwd_fused"]) == 0


def test_the_plain_attention_layer_keeps_no_counter():
    lay, _, _ = make("attention", [(2, 16, 32)], nhead=4)
    assert lay._plain() and lay.init_aux([(2, 16, 32)]) == {}
