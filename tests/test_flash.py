"""Golden tests: Pallas flash attention vs the XLA ``mha`` reference.

Interpret mode runs the identical kernel code on CPU (the PairTest
discipline, SURVEY §4.1); the on-TPU compile is covered by the layer's
probe machinery.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.ops.attention import mha
from cxxnet_tpu.ops.flash import _pick_block, flash_mha


def _qkv(b=2, t=64, h=2, d=16, dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(
        rng.randn(b, t, h, d).astype(np.float32), dtype=dtype
    )
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_mha(causal):
    q, k, v = _qkv()
    ref = mha(q, k, v, causal=causal)
    out = flash_mha(q, k, v, causal, 32, 16, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("split", [False, True], indirect=True)
@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_mha(causal, split):
    q, k, v = _qkv(t=32, d=8)

    def loss_ref(q, k, v):
        return (mha(q, k, v, causal=causal) ** 2).sum()

    def loss_fl(q, k, v):
        return (flash_mha(q, k, v, causal, 16, 16, True) ** 2).sum()

    gr = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    gf = jax.jit(jax.grad(loss_fl, argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(gr, gf, "qkv"):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=2e-4, atol=2e-4,
            err_msg=f"d{name} mismatch",
        )


def test_flash_bf16_close_to_f32_reference():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    ref = mha(q.astype(jnp.float32), k.astype(jnp.float32),
              v.astype(jnp.float32))
    out = flash_mha(q, k, v, False, 32, 32, True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), rtol=0.05, atol=0.05
    )


def test_flash_uneven_blocks_and_single_block():
    # T smaller than the requested block, and T that only divides by a
    # shrunken power-of-two block
    q, k, v = _qkv(t=24, d=8)
    ref = mha(q, k, v, causal=True)
    out = flash_mha(q, k, v, True, 128, 128, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_pick_block():
    assert _pick_block(256, 128) == 128
    assert _pick_block(24, 128) == 24  # whole T fits one block
    assert _pick_block(48, 32) == 16
    assert _pick_block(7, 128) == 7


@pytest.mark.parametrize("split", [False, True], indirect=True)
def test_flash_cross_attention_lengths(split):
    # Tq != Tk (e.g. decoder cross-attention)
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(2, 16, 2, 8).astype(np.float32))
    k = jnp.asarray(rng.randn(2, 64, 2, 8).astype(np.float32))
    v = jnp.asarray(rng.randn(2, 64, 2, 8).astype(np.float32))
    ref = mha(q, k, v)
    out = flash_mha(q, k, v, False, 16, 32, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------- layer-level attn_impl wiring
def test_attention_layer_attn_impl_pallas_matches_xla():
    """attn_impl = pallas routes the layer through the flash kernel (in
    interpret mode off-TPU) and must match the XLA path bit-for-bit in
    f32 within tolerance."""
    from cxxnet_tpu.layers import create_layer

    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(2, 32, 16).astype(np.float32))
    outs = {}
    for impl in ("xla", "pallas"):
        lay = create_layer("attention")
        lay.set_param("nhead", "2")
        lay.set_param("causal", "1")
        lay.set_param("init_sigma", "0.1")
        lay.set_param("attn_impl", impl)
        lay.infer_shape([(2, 32, 16)])
        params = lay.init_params(jax.random.PRNGKey(0), [(2, 32, 16)])
        (outs[impl],) = lay.apply(params, [x])
    np.testing.assert_allclose(
        np.asarray(outs["pallas"]), np.asarray(outs["xla"]),
        rtol=1e-5, atol=1e-5,
    )
    with pytest.raises(ValueError, match="attn_impl"):
        create_layer("attention").set_param("attn_impl", "cuda")


def test_a2a_with_flash_local_attention():
    """Ulysses SP composed with the flash kernel as the per-device
    full-sequence attention (attn_fn hook)."""
    from cxxnet_tpu.ops.attention import a2a_self_attention
    from cxxnet_tpu.parallel import make_mesh

    rng = np.random.RandomState(11)
    mk = lambda: jnp.asarray(rng.randn(2, 32, 4, 8).astype(np.float32))
    q, k, v = mk(), mk(), mk()
    plan = make_mesh("cpu:0-7", model_parallel=4)
    want = mha(q, k, v, causal=True)

    def attn_fn(q_, k_, v_, causal=True):
        return flash_mha(q_, k_, v_, causal, 16, 16, True)

    got = a2a_self_attention(
        q, k, v, plan.mesh, "model", causal=True, attn_fn=attn_fn
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


# ---------------------------------------------------- flash ring attention
@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_matches_mha(causal):
    from cxxnet_tpu.ops.attention import ring_self_attention_flash
    from cxxnet_tpu.parallel import make_mesh

    rng = np.random.RandomState(0)
    mk = lambda: jnp.asarray(rng.randn(2, 32, 4, 16).astype(np.float32))
    q, k, v = mk(), mk(), mk()
    plan = make_mesh("cpu:0-7", model_parallel=4)
    want = mha(q, k, v, causal=causal)
    got = ring_self_attention_flash(q, k, v, plan.mesh, "model",
                                    causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_flash_gradients_match():
    """The lse-cotangent VJP: gradients through the log-space hop merge
    must equal full-attention gradients."""
    from cxxnet_tpu.ops.attention import ring_self_attention_flash
    from cxxnet_tpu.parallel import make_mesh

    rng = np.random.RandomState(1)
    mk = lambda: jnp.asarray(rng.randn(2, 16, 2, 8).astype(np.float32))
    q, k, v = mk(), mk(), mk()
    plan = make_mesh("cpu:0-7", model_parallel=4)

    def loss_ring(q, k, v):
        return jnp.sum(ring_self_attention_flash(
            q, k, v, plan.mesh, "model", causal=True, interpret=True) ** 2)

    def loss_full(q, k, v):
        return jnp.sum(mha(q, k, v, causal=True) ** 2)

    gr = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    gf = jax.jit(jax.grad(loss_full, argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(gr, gf, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5,
            err_msg=f"ring-flash d{name} mismatch",
        )


def test_attention_layer_ring_pallas_matches_xla_ring():
    """seq_parallel=ring + attn_impl=pallas routes the layer through the
    flash ring and matches the XLA ring output."""
    from cxxnet_tpu.layers import create_layer
    from cxxnet_tpu.parallel import make_mesh

    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(2, 32, 16).astype(np.float32))
    plan = make_mesh("cpu:0-7", model_parallel=4)
    outs = {}
    for impl in ("xla", "pallas"):
        lay = create_layer("attention")
        lay.set_param("nhead", "2")
        lay.set_param("causal", "1")
        lay.set_param("init_sigma", "0.1")
        lay.set_param("seq_parallel", "ring")
        lay.set_param("attn_impl", impl)
        lay.bind_mesh(plan)
        lay.infer_shape([(2, 32, 16)])
        params = lay.init_params(jax.random.PRNGKey(0), [(2, 32, 16)])
        (outs[impl],) = lay.apply(params, [x])
    np.testing.assert_allclose(
        np.asarray(outs["pallas"]), np.asarray(outs["xla"]),
        rtol=2e-5, atol=2e-5,
    )


@pytest.mark.parametrize("k_off, dead", [(8, 8), (40, 32)],
                         ids=["some_rows", "every_key_after_every_query"])
@pytest.mark.parametrize("split", [False, True], indirect=True)
def test_flash_lse_fully_masked_rows_are_zero(k_off, dead, split):
    """Misaligned offsets can fully mask a query row inside a live block
    (causal, keys strictly in the row's future): `out` must be zeros for
    that row — not a mean of v (the exp(s - NEG_INF)=1 failure) — so
    `out` is valid standalone, not only jointly with lse.  A hop whose
    keys ALL lie after its queries is such rows only, and its gradients
    are finite (and zero)."""
    from cxxnet_tpu.ops.flash import flash_mha_lse

    b, t, h, d = 1, 32, 2, 16
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))

    # keys start k_off positions after the queries: the first `dead`
    # query rows see no key at all under the causal mask
    def hop(q, k, v):
        return flash_mha_lse(q, k, v, q_off=0, k_off=k_off, causal=True,
                             block_q=16, block_k=16, interpret=True)

    out, lse = hop(q, k, v)
    out = np.asarray(out)
    np.testing.assert_array_equal(out[:, :dead], np.zeros_like(out[:, :dead]))
    # the masked rows' lse stays ~NEG_INF so a ring merge washes them out
    assert np.all(np.asarray(lse)[:, :dead] < -1e29)
    # live rows are real attention outputs
    assert dead == t or np.abs(out[:, dead:]).max() > 0
    grads = jax.jit(jax.grad(lambda *a: hop(*a)[0].sum() + jnp.where(
        hop(*a)[1] > -1e29, hop(*a)[1], 0.0).sum(), (0, 1, 2)))(q, k, v)
    for g in grads:
        assert np.isfinite(np.asarray(g)).all()
        assert dead < t or not np.asarray(g).any()


# ------------------------------------------------ the masked kernels (PR 37)
def _docs(cuts, t):
    """``(B, T)`` document index from a row's cut positions."""
    d = np.zeros((len(cuts), t), np.int32)
    for r, cs in enumerate(cuts):
        for c in cs:
            d[r, c:] += 1
    return jnp.asarray(d)


def _masked(h, hk, dqk, dv, t=64, dtype=jnp.float32, seed=0, b=2):
    rng = np.random.RandomState(seed)
    mk = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32), dtype)
    return mk(b, t, h, dqk), mk(b, t, hk, dqk), mk(b, t, hk, dv)


def _hold_against_mha(q, k, v, doc, causal, scale, bq, bk, tol):
    """Forward and the gradients of q, k and v, the kernels against
    ``mha`` with the same mask, each within ``tol`` of the reference
    tensor's own scale."""
    from cxxnet_tpu.ops.flash import flash_attention

    def kern(q, k, v):
        return flash_attention(q, k, v, causal=causal, scale=scale, doc=doc,
                               block_q=bq, block_k=bk, interpret=True)[0]

    def ref(q, k, v):
        return mha(q, k, v, causal=causal, scale=scale, doc=doc)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v).astype(jnp.float32) ** 2).sum()

    got, want = kern(q, k, v), ref(q, k, v)
    assert got.shape == want.shape == q.shape[:3] + v.shape[3:]
    assert got.dtype == want.dtype
    pairs = [("o", got, want)] + [
        ("d" + n, a, r) for n, a, r in zip(
            "qkv", jax.jit(jax.grad(loss(kern), (0, 1, 2)))(q, k, v),
            jax.jit(jax.grad(loss(ref), (0, 1, 2)))(q, k, v))]
    for name, a, r in pairs:
        assert a.shape == r.shape and a.dtype == r.dtype, name
        a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
        assert np.isfinite(a).all(), name
        assert np.abs(a - r).max() <= tol * np.abs(r).max(), name


#: rows of 64 tokens under blocks of 16: where the documents end
DOC_CASES = {
    "inside_a_block": [[5, 40], [27]],
    "at_a_block_s_edge": [[16, 48], [32]],
    "longer_than_several_blocks": [[56], [3, 60]],
    "one_document_a_row": [[], []],
    "a_last_document_of_one_token": [[63], [20, 63]],
    "a_document_a_token_then_one_long": [[1, 2, 3], [62, 63]],
}


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("case", sorted(DOC_CASES))
def test_document_mask_matches_mha(case, causal):
    q, k, v = _masked(2, 2, 16, 16)
    _hold_against_mha(q, k, v, _docs(DOC_CASES[case], 64), causal, None,
                      16, 16, 2e-5)


@pytest.mark.parametrize("split", [True], indirect=True)
@pytest.mark.parametrize("case", sorted(DOC_CASES))
def test_document_mask_in_parts_matches_mha(case, split):
    """The diagonal's blocks in parts of 8, the dead ones left out."""
    q, k, v = _masked(2, 2, 16, 16)
    _hold_against_mha(q, k, v, _docs(DOC_CASES[case], 64), True, None,
                      16, 16, 2e-5)


@pytest.mark.parametrize("h, hk, dqk, dv, scale, bq, bk", [
    (2, 2, 192, 128, None, 16, 16),        # latent attention's two widths
    (2, 1, 24, 16, 0.3, 16, 32),           # ... grouped, unequal blocks
    (32, 8, 16, 16, 0.015625, 32, 16),     # granite: 32 / 8, a stated scale
    (16, 2, 32, 32, None, 16, 16),         # qwen3_next: 16 / 2
    (4, 4, 16, 16, 0.5, 64, 64),           # one block a row
], ids=["192x128", "grouped_24x16", "32over8_scale", "16over2", "one_block"])
@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 3e-2)],
                         ids=["f32", "bf16"])
def test_widths_groups_and_scale_match_mha(h, hk, dqk, dv, scale, bq, bk,
                                           dtype, tol):
    q, k, v = _masked(h, hk, dqk, dv, dtype=dtype, seed=1)
    _hold_against_mha(q, k, v, _docs([[5, 16, 40], [63]], 64), True, scale,
                      bq, bk, tol)


def test_grouped_heads_without_documents_and_a_cotangent_of_lse():
    """The plain callers' contract, on grouped heads: ``(o, lse)`` with
    a cotangent into both."""
    from cxxnet_tpu.ops.flash import flash_attention

    q, k, v = _masked(4, 2, 16, 16, t=32)

    def ref(q, k, v):
        kk, vv = (jnp.repeat(x, 2, axis=2) for x in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * 0.2
        s = jnp.where(jnp.tril(jnp.ones((32, 32), bool)), s, -1e30)
        lse = jax.nn.logsumexp(s, axis=-1)
        return (jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(s - lse[..., None]),
                           vv), lse.transpose(0, 2, 1))

    def kern(q, k, v):
        return flash_attention(q, k, v, causal=True, scale=0.2, block_q=16,
                               block_k=8, interpret=True)

    loss = lambda fn: lambda *a: sum((x ** 2).sum() for x in fn(*a))
    grads = lambda fn: jax.jit(jax.grad(loss(fn), (0, 1, 2)))  # noqa: E731
    for a, r in zip(kern(q, k, v) + grads(kern)(q, k, v),
                    ref(q, k, v) + grads(ref)(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=2e-4, atol=2e-4)


def test_a_document_mask_refuses_offsets_and_two_lengths():
    from cxxnet_tpu.ops.flash import flash_attention

    q, k, v = _masked(2, 2, 16, 16, t=32)
    doc = _docs([[5], [9]], 32)
    with pytest.raises(ValueError, match="one length"):
        flash_attention(q, k, v, causal=True, doc=doc, q_off=jnp.int32(4),
                        interpret=True)
    with pytest.raises(ValueError, match="one length"):
        flash_attention(q[:, :16], k, v, doc=doc[:, :16], interpret=True)
    with pytest.raises(ValueError, match="flash: q"):
        flash_attention(q, k[:, :, :1], v, interpret=True)


# ------------------------------------- which blocks are live, and visited
def _brute_live(doc, t, bq, bk, causal):
    """``(B, nq, nk)`` bool: the block holds a pair that may attend."""
    pos = np.arange(t)
    ok = np.ones((doc.shape[0], t, t), bool)
    if causal:
        ok &= (pos[:, None] >= pos[None, :])[None]
    ok &= doc[:, :, None] == doc[:, None, :]
    b = doc.shape[0]
    return ok.reshape(b, t // bq, bq, t // bk, bk).any(axis=(2, 4))


@pytest.mark.parametrize("bq, bk", [(16, 16), (32, 16), (16, 32), (8, 64)])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_live_ranges_are_the_brute_force_mask_s_blocks(bq, bk, causal):
    """Every block with a pair that may attend lies inside ``[lo, hi]``
    (and ``[qlo, qhi]`` from the keys' side), and the ranges hold no
    other block: documents are runs, so the live blocks of a row of
    blocks ARE one range."""
    from cxxnet_tpu.ops.flash import _offs, _ranges

    t = 128
    rng = np.random.RandomState(bq + bk + causal)
    cuts = [sorted(rng.choice(np.arange(1, t), n, replace=False))
            for n in (0, 1, 3, 7, 20)] + [[16, 32, 64], [127], [1]]
    doc = _docs(cuts, t)
    nq, nk = t // bq, t // bk
    lo, hi, qlo, qhi = (np.asarray(x) for x in _ranges(
        doc, _offs(None, None), nq, nk, bq, bk, causal))
    live = _brute_live(np.asarray(doc), t, bq, bk, causal)
    b = len(cuts)
    j = np.arange(nk)[None, None, :]
    got = (j >= lo.reshape(b, nq, 1)) & (j <= hi.reshape(b, nq, 1))
    np.testing.assert_array_equal(got, live)
    i = np.arange(nq)[None, :, None]
    got = (i >= qlo.reshape(b, 1, nk)) & (i <= qhi.reshape(b, 1, nk))
    np.testing.assert_array_equal(got, live)
    # without documents one table row serves every batch row
    lo, hi, qlo, qhi = (np.asarray(x) for x in _ranges(
        None, _offs(None, None), nq, nk, bq, bk, causal))
    assert lo.shape == hi.shape == (nq,) and qlo.shape == qhi.shape == (nk,)
    whole = _brute_live(np.zeros((1, t), np.int32), t, bq, bk, causal)[0]
    np.testing.assert_array_equal(
        (j[0] >= lo[:, None]) & (j[0] <= hi[:, None]), whole)


@pytest.mark.parametrize("nq, nk, bq, bk, group", [
    (4, 4, 16, 16, 1), (2, 4, 32, 16, 4), (4, 2, 16, 32, 2), (1, 1, 64, 64, 8)])
def test_a_causal_grid_visits_the_lower_triangle_once(nq, nk, bq, bk, group):
    from cxxnet_tpu.ops.flash import _FIRST, _LAST, _steps

    (iq, ik, fl, _), (bk_t, bq_t, bfl, bg) = _steps(nq, nk, bq, bk, True,
                                                   group)
    reach = {(i, j) for i in range(nq) for j in range(nk)
             if j * bk <= i * bq + bq - 1}
    assert sorted(zip(iq.tolist(), ik.tolist())) == sorted(reach)
    # a query block's steps are together, first and last flagged once
    assert (np.diff(iq) >= 0).all()
    assert int((fl & _FIRST != 0).sum()) == int((fl & _LAST != 0).sum()) == nq
    # the keys' side: every query head of the group, every pair once
    assert sorted(zip(bq_t.tolist(), bk_t.tolist(), bg.tolist())) == sorted(
        (i, j, g) for (i, j) in reach for g in range(group))
    assert (np.diff(bk_t) >= 0).all()
    assert int((bfl & _FIRST != 0).sum()) == int(
        (bfl & _LAST != 0).sum()) == nk
    # not causal: the whole square
    full = _steps(nq, nk, bq, bk, False, group)[0]
    assert len(full[0]) == nq * nk


def test_a_dead_step_names_the_block_it_holds():
    """Clamped into the live range a dead step's index is the range's
    nearest end, so consecutive steps name one block and nothing moves;
    an empty range names its ``lo``."""
    from cxxnet_tpu.ops.flash import _clamp

    lo, hi = jnp.int32(2), jnp.int32(4)
    assert [int(_clamp(jnp.int32(x), lo, hi)) for x in range(7)] == [
        2, 2, 2, 3, 4, 4, 4]
    assert int(_clamp(jnp.int32(5), jnp.int32(3), jnp.int32(-1))) == 3


# -------------------------------------------- the chooser (ops/attention)
@pytest.mark.parametrize("shape, want", [
    # (T, H, Hkv, Dqk, Dv, dtype) -> block
    ((8192, 32, 32, 192, 128, jnp.bfloat16), 1024),   # JoyAI
    ((8192, 32, 8, 64, 64, jnp.bfloat16), 1024),      # granite
    ((8192, 16, 2, 256, 256, jnp.bfloat16), 1024),    # qwen3_next
    ((1024, 4, 4, 64, 64, jnp.float32), 1024),
    ((1536, 4, 4, 64, 64, jnp.bfloat16), 512),
    ((512, 4, 4, 64, 64, jnp.bfloat16), None),          # short: mha whole
    ((1000, 4, 4, 64, 64, jnp.bfloat16), None),         # no block >= 128
    ((2048, 4, 4, 48, 48, jnp.bfloat16), None),         # a width of 48
    ((2048, 4, 4, 512, 512, jnp.bfloat16), None),       # wider than 256
    ((2048, 6, 4, 64, 64, jnp.bfloat16), None),         # heads do not group
    ((2048, 4, 4, 64, 64, jnp.float16), None),
], ids=lambda v: "x".join(str(getattr(x, "__name__", x)) for x in v)
    if isinstance(v, tuple) and len(v) == 6 else None)
def test_block_for_decides_from_shapes(shape, want):
    from cxxnet_tpu.ops.flash import block_for

    t, h, hk, dqk, dv, dtype = shape
    sds = jax.ShapeDtypeStruct
    got = block_for(sds((1, t, h, dqk), dtype), sds((1, t, hk, dqk), dtype),
                     sds((1, t, hk, dv), dtype))
    assert got == want
    if want is not None:
        assert t % want == 0 and want >= 128


@pytest.mark.parametrize("t, d, kernels", [(1024, 64, True), (256, 64, False),
                                           (1024, 48, False)])
def test_attend_lowers_the_kernels_for_a_tpu_and_mha_elsewhere(t, d, kernels):
    """The platform the program is LOWERED for decides, and the shapes:
    for a TPU a long row is Mosaic calls, on the CPU it is ``mha`` and
    the flag says so."""
    from cxxnet_tpu.ops.attention import attend

    sds = jax.ShapeDtypeStruct
    q = sds((1, t, 4, d), jnp.bfloat16)
    kv = sds((1, t, 2, d), jnp.bfloat16)
    doc = sds((1, t), jnp.int32)

    def f(q, k, v, doc):
        return attend(q, k, v, causal=True, scale=0.1, doc=doc)

    tpu = jax.export.export(jax.jit(f), platforms=["tpu"])(q, kv, kv, doc)
    assert ("tpu_custom_call" in tpu.mlir_module()) == kernels
    cpu = jax.export.export(jax.jit(f), platforms=["cpu"])(q, kv, kv, doc)
    assert "tpu_custom_call" not in cpu.mlir_module()
    assert [a.dtype for a in tpu.out_avals] == [jnp.bfloat16, jnp.uint32]


def test_attend_on_the_cpu_is_mha_and_says_so():
    from cxxnet_tpu.ops.attention import attend

    q, k, v = _masked(4, 2, 64, 64, t=1024, b=1, dtype=jnp.bfloat16)
    doc = _docs([[100, 700]], 1024)
    o, flash = jax.jit(lambda *a: attend(*a, causal=True, doc=doc))(q, k, v)
    assert int(flash) == 0 and flash.dtype == jnp.uint32
    want = mha(q, k, v, causal=True, doc=doc, block_q=512)
    np.testing.assert_array_equal(np.asarray(o, np.float32),
                                  np.asarray(want, np.float32))
    # a short row: the same function, whole
    o, flash = attend(q[:, :64], k[:, :64], v[:, :64], causal=True,
                      doc=doc[:, :64])
    assert int(flash) == 0
    np.testing.assert_array_equal(
        np.asarray(o, np.float32), np.asarray(mha(
            q[:, :64], k[:, :64], v[:, :64], causal=True, doc=doc[:, :64]),
            np.float32))


def _layer_shaped(with_lse):
    """A layer's shape of work around the kernels — projections from one
    ``x``, grouped heads under a document mask and a window, an output
    projection and a residual — as ``loss(run)(params, x)`` for a ``run``
    that wraps the layer (``jax.checkpoint`` or nothing); ``with_lse``
    puts a cotangent on ``lse`` too (the ring path's case)."""
    from cxxnet_tpu.ops.flash import flash_attention

    b, t, h, hk, d, width = 2, 64, 4, 2, 16, 32
    rng = np.random.RandomState(3)
    mk = lambda *s: jnp.asarray(0.2 * rng.randn(*s), jnp.float32)
    params = dict(wq=mk(width, h * d), wk=mk(width, hk * d),
                  wv=mk(width, hk * d), wo=mk(h * d, width))
    x = 5 * mk(b, t, width)
    doc = _docs([[5, 16, 40], [63]], t)

    def layer(p, x):
        q = (x @ p["wq"]).reshape(b, t, h, d)
        k = (x @ p["wk"]).reshape(b, t, hk, d)
        v = (x @ p["wv"]).reshape(b, t, hk, d)
        o, lse = flash_attention(q, k, v, causal=True, doc=doc, window=24,
                                 block_q=16, block_k=16, interpret=True)
        return jnp.tanh(o.reshape(b, t, h * d) @ p["wo"]) + x, lse

    def loss(run):
        def f(p, x):
            y, lse = run(layer)(p, x)
            return (y ** 2).sum() + ((lse ** 2).sum() if with_lse else 0.0)
        return jax.grad(f, (0, 1))

    return loss, params, x


@pytest.mark.parametrize("with_lse", [False, True], ids=["o", "o_and_lse"])
def test_a_layer_s_remat_keeps_the_kernels_outputs(with_lse):
    """Under the net's policy a checkpointed layer keeps what the forward
    kernel NAMES (``flash.KEPT_NAMES``): its gradients are those of the
    layer with no ``jax.checkpoint`` at all, and the program of value and
    gradient runs ``flash_fwd`` once where a plain ``jax.checkpoint``
    runs it twice (forward and recompute); the backward kernels once
    either way."""
    from cxxnet_tpu.nnet.net import REMAT_POLICY

    loss, params, x = _layer_shaped(with_lse)
    grads = {
        "plain": loss(lambda f: f),
        "kept": loss(lambda f: jax.checkpoint(f, policy=REMAT_POLICY)),
        "recomputed": loss(jax.checkpoint),
    }
    want = jax.tree_util.tree_leaves(grads["plain"](params, x))
    assert all(np.abs(np.asarray(w)).max() > 0 for w in want)
    for name in ("kept", "recomputed"):
        got = jax.tree_util.tree_leaves(grads[name](params, x))
        for a, r in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=2e-5, atol=2e-5, err_msg=name)
    runs = {name: {kern: len(re.findall(rf"name={kern}\b", str(
        jax.make_jaxpr(g)(params, x)))) for kern in
        ("flash_fwd", "flash_bwd", "flash_dq", "flash_dkv")}
        for name, g in grads.items()}
    once = {"flash_fwd": 1, "flash_bwd": 1, "flash_dq": 0, "flash_dkv": 0}
    assert runs == {"plain": once, "kept": once,
                    "recomputed": dict(once, flash_fwd=2)}, runs


# ------------------------------------- the ONE backward kernel (PR 48)
#: rows of 64 tokens (two a case) under blocks of 16: ``heads`` is (query
#: heads, key-value heads, q.k width, v width); ``cuts`` where each row's
#: documents end (none: no document mask)
ONE_BACKWARD = {
    "causal": dict(heads=(2, 2, 16, 16)),
    "documents_not_causal": dict(heads=(2, 2, 16, 16), causal=False,
                                 cuts=[[16, 48], [32]]),
    "a_window_s_sliding_layer": dict(heads=(4, 2, 16, 16), window=24,
                                     cuts=[[5, 16, 40], [63]]),
    "the_full_layer_beside_it": dict(heads=(4, 2, 16, 16),
                                     cuts=[[5, 16, 40], [63]]),
    "the_diagonal_s_blocks_in_parts": dict(heads=(4, 2, 16, 16), window=24,
                                           cuts=[[5, 16, 40], [63]],
                                           split=True),
    "grouped_32_over_4": dict(heads=(32, 4, 16, 16), cuts=[[20], [3, 60]]),
    "grouped_28_over_4": dict(heads=(28, 4, 16, 16), window=32,
                              cuts=[[20], [3, 60]]),
    "grouped_16_over_2": dict(heads=(16, 2, 32, 32), cuts=[[56], []]),
    "widths_192_and_128": dict(heads=(2, 2, 192, 128), scale=0.11,
                               cuts=[[5, 40], [27]]),
    "a_cotangent_of_lse": dict(heads=(4, 2, 16, 16), lse=True, scale=0.2,
                               cuts=[[5, 16, 40], [63]]),
    "a_boundary_inside_every_block": dict(
        heads=(2, 1, 16, 16), cuts=[[5, 21, 37, 53], [9, 27, 43, 62]]),
    "unequal_blocks": dict(heads=(2, 1, 24, 16), blocks=(16, 32),
                           cuts=[[1, 2, 3], [62, 63]]),
    "bfloat16": dict(heads=(4, 2, 16, 16), dtype=jnp.bfloat16, tol=3e-2,
                     cuts=[[5, 16, 40], [63]]),
    "a_row_past_the_budget": dict(heads=(4, 2, 16, 16), window=24, lse=True,
                                  cuts=[[5, 16, 40], [63]], fits=False),
}


def _o_and_lse(q, k, v, doc, causal, scale, window):
    """``mha``'s output and, from the dense masked scores, the
    log-sum-exp a query row ``(B, T, H)`` — the golden model of
    ``flash_attention``'s two outputs."""
    t, g = q.shape[1], q.shape[2] // k.shape[2]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   jnp.repeat(k, g, axis=2).astype(jnp.float32)) * scale
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    ok = jnp.ones((1, t, t), bool)
    if causal:
        ok = ok & (j <= i)
    if window:
        ok = ok & (i - j < window)
    if doc is not None:
        ok = ok & (doc[:, :, None] == doc[:, None, :])
    lse = jax.nn.logsumexp(jnp.where(ok[:, None], s, -1e30), axis=-1)
    return (mha(q, k, v, causal=causal, scale=scale, doc=doc, window=window),
            lse.transpose(0, 2, 1))


@pytest.mark.parametrize("case, split, two_kernels", [
    (name, c.get("split", False), not c.get("fits", True))
    for name, c in sorted(ONE_BACKWARD.items())],
    indirect=["split", "two_kernels"],
    ids=sorted(ONE_BACKWARD))
def test_the_one_backward_kernel_is_the_two_kernels_and_mha_s(
        case, split, two_kernels):
    """``flash_bwd`` — ``dq``, ``dk`` and ``dv`` from one derivation of a
    tile's scores — gives the gradients ``flash_dq`` + ``flash_dkv`` give
    on the same residuals and the golden model's; ``flash_attention``
    runs it where a key-value head's row fits the kernel's VMEM budget,
    and the two kernels, with the same gradients, where it does not."""
    from cxxnet_tpu.ops import flash

    c = ONE_BACKWARD[case]
    h, hk, dqk, dv = c["heads"]
    dtype, tol = c.get("dtype", jnp.float32), c.get("tol", 2e-5)
    causal, window = c.get("causal", True), c.get("window", 0)
    scale = c.get("scale", dqk ** -0.5)
    bq, bk = c.get("blocks", (16, 16))
    q, k, v = _masked(h, hk, dqk, dv, dtype=dtype, seed=len(case))
    doc = _docs(c["cuts"], 64) if "cuts" in c else None
    rng = np.random.RandomState(1)
    cts = (jnp.asarray(rng.randn(2, 64, h, dv), dtype),
           jnp.asarray(rng.randn(2, 64, h) * bool(c.get("lse")),
                       jnp.float32))

    def kern(q, k, v):
        return flash.flash_attention(
            q, k, v, causal=causal, scale=scale, doc=doc, window=window,
            block_q=bq, block_k=bk, interpret=True)

    def close(got, want, tol, what):
        for name, a, r in zip("qkv", got, want):
            assert a.shape == r.shape and a.dtype == r.dtype, (what, name)
            a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
            assert np.isfinite(a).all() and np.abs(r).max() > 0, (what, name)
            assert np.abs(a - r).max() <= tol * np.abs(r).max(), (what, name)

    # what ``flash_attention`` runs, by the shapes and the budget alone
    out, vjp = jax.vjp(kern, q, k, v)
    ran = set(re.findall(r"name=(flash_(?:bwd|dq|dkv))\b",
                         str(jax.make_jaxpr(vjp)(cts))))
    assert ran == ({"flash_dq", "flash_dkv"} if two_kernels
                   else {"flash_bwd"})
    got = vjp(cts)
    # ... against the golden model's gradients
    _, ref_vjp = jax.vjp(lambda *a: _o_and_lse(
        *a, doc, causal, scale, window), q, k, v)
    close(got, ref_vjp(cts), tol, "mha")
    # ... and the one kernel against the two, on the forward's own
    # residuals (folded as ``_backward`` folds them)
    qf, kf, vf, g = (flash._fold(x) for x in (q, k, v, cts[0]))
    o, lse = flash._forward(qf, kf, vf, doc, None, None, causal=causal,
                            scale=scale, bq=bq, bk=bk, heads=h,
                            interpret=True, window=window)
    dl = (g.astype(jnp.float32) * o.astype(jnp.float32)).sum(
        -1, keepdims=True) - flash._fold(cts[1][..., None])
    geo = flash._Geometry(qf, kf, vf, doc, None, None, causal, scale, bq, bk,
                          h, window)
    assert geo.one != two_kernels
    a = (qf, kf, vf, g, lse, dl)
    two = (flash._bwd_dq(*a, geo, True), *flash._bwd_dkv(*a, geo, True))
    one = flash._bwd_one(*a, geo, True)
    close(one, two, 1e-6 if dtype == jnp.float32 else 1e-2, "two kernels")
    unfold = lambda x, n: flash._unfold(x, 2, n)  # noqa: E731
    close([unfold(one[0], h), unfold(one[1], hk), unfold(one[2], hk)], got,
          1e-6 if dtype == jnp.float32 else 1e-2, "flash_attention")
