"""The window inside the flash kernels (ISSUE 42): the three kernels in
interpret mode against ``ops/attention.mha`` — itself held against a
mask written out by hand — for window x documents x causal, grouped
heads, a row the window does not divide, windows smaller than, equal to
and larger than a block, forward and gradients; the static step table
visits every block that holds a live pair and none wholly outside the
window; the live ranges are the brute-force mask's blocks; ``window =
0`` builds the tables and the program the parent built.

A step's class (ISSUE 43): the forward table's live steps counted as
needing no mask are exactly the blocks whose every pair may attend under
a mask written out pair by pair, the two bounds a query ARE that mask, a
part of a block is left out only where no pair of it may attend, and
rows with wholly live, position-only and document-boundary blocks in one
call give ``mha``'s outputs, log-sum-exp and gradients.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.ops.attention import attend, mha
from cxxnet_tpu.ops.flash import (_FIRST, _LAST, _Geometry, _bounds, _offs,
                                  _ranges, _steps, block_for, count_blocks,
                                  flash_attention, flash_mha_lse)

T = 64


def _docs(cuts, t=T):
    d = np.zeros((len(cuts), t), np.int32)
    for r, cs in enumerate(cuts):
        for c in cs:
            d[r, c:] += 1
    return jnp.asarray(d)


def _qkv(h, hk, d=16, t=T, dtype=jnp.float32, seed=0, b=2):
    rng = np.random.RandomState(seed)
    mk = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32), dtype)
    return mk(b, t, h, d), mk(b, t, hk, d), mk(b, t, hk, d)


def _by_hand(q, k, v, doc, causal, window):
    """Softmax attention under a mask written out entry by entry."""
    h, hk = q.shape[2], k.shape[2]
    k, v = (jnp.repeat(x, h // hk, axis=2) for x in (k, v))
    t = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    ok = np.ones((t, t), bool)
    if causal:
        ok &= j <= i
    if window:
        ok &= i - j < window
    ok = jnp.asarray(ok)[None, None]
    if doc is not None:
        ok = ok & (doc[:, :, None] == doc[:, None, :])[:, None]
    s = jnp.where(ok, s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


def _hold(fn, ref, q, k, v, tol):
    """Forward and the gradients of q, k and v of ``fn`` against ``ref``,
    each within ``tol`` of the reference tensor's own scale (of 1 where
    that is below it: under a window of 1 a query's softmax is over one
    key and the gradients of q and k are exactly 0)."""
    def loss(f):
        return lambda q, k, v: (f(q, k, v).astype(jnp.float32) ** 2).sum()

    got, want = fn(q, k, v), ref(q, k, v)
    assert got.shape == want.shape and got.dtype == want.dtype
    pairs = [("o", got, want)] + [
        ("d" + n, a, r) for n, a, r in zip(
            "qkv", jax.grad(loss(fn), (0, 1, 2))(q, k, v),
            jax.grad(loss(ref), (0, 1, 2))(q, k, v))]
    for name, a, r in pairs:
        a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
        assert np.isfinite(a).all(), name
        assert np.abs(a - r).max() <= tol * max(np.abs(r).max(), 1.0), name


DOCS = {"one_document": None,
        "cuts_inside_blocks": [[5, 40], [27]],
        "cuts_at_edges_and_single_tokens": [[16, 48, 49], [1, 63]]}


# windows smaller than, equal to and larger than the block of 16, one the
# row's length is no multiple of, one wider than the row
@pytest.mark.parametrize("window", [1, 5, 16, 24, 100])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("case", sorted(DOCS))
def test_the_kernels_under_a_window_are_mha_s(case, causal, window):
    _hold_windowed(case, causal, window)


# the same in parts of 8: the diagonal's and the window's edge inside a
# block, at its edge, and a window no wider than a part
@pytest.mark.parametrize("split", [True], indirect=True)
@pytest.mark.parametrize("window", [5, 16, 24])
@pytest.mark.parametrize("case", sorted(DOCS))
def test_the_kernels_in_parts_under_a_window_are_mha_s(case, window, split):
    _hold_windowed(case, True, window)


def _hold_windowed(case, causal, window):
    q, k, v = _qkv(4, 2)
    doc = None if DOCS[case] is None else _docs(DOCS[case])

    def kern(q, k, v):
        return flash_attention(q, k, v, causal=causal, doc=doc, window=window,
                               block_q=16, block_k=16, interpret=True)[0]

    _hold(kern, lambda q, k, v: mha(q, k, v, causal=causal, doc=doc,
                                    window=window), q, k, v, 2e-5)


@pytest.mark.parametrize("window", [3, 16, 40])
@pytest.mark.parametrize("block_q", [0, 16])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_mha_s_window_is_the_mask_written_out(causal, block_q, window):
    """The golden model itself, whole and in row blocks (which slice
    the keys from the block's first query's reach on)."""
    q, k, v = _qkv(4, 2, seed=1)
    doc = _docs(DOCS["cuts_inside_blocks"])
    _hold(lambda q, k, v: mha(q, k, v, causal=causal, doc=doc, window=window,
                              block_q=block_q),
          lambda q, k, v: _by_hand(q, k, v, doc, causal, window),
          q, k, v, 2e-5)


@pytest.mark.parametrize("bq, bk, h, hk, dtype, tol", [
    (32, 16, 8, 2, jnp.float32, 2e-5),
    (16, 32, 4, 4, jnp.float32, 2e-5),
    (16, 16, 4, 1, jnp.bfloat16, 3e-2),
])
@pytest.mark.parametrize("split", [False, True], indirect=True)
def test_unequal_blocks_groups_and_bfloat16(bq, bk, h, hk, dtype, tol,
                                            split):
    q, k, v = _qkv(h, hk, dtype=dtype, seed=2)
    doc = _docs([[20], [7, 50]])

    def kern(q, k, v):
        return flash_attention(q, k, v, causal=True, doc=doc, window=24,
                               scale=0.2, block_q=bq, block_k=bk,
                               interpret=True)[0]

    _hold(kern, lambda q, k, v: mha(q, k, v, causal=True, doc=doc, window=24,
                                    scale=0.2), q, k, v, tol)


def test_a_row_the_window_does_not_divide_and_a_cotangent_of_lse():
    """T = 96 under a window of 40 and blocks of 32; the log-sum-exp
    output is the windowed scores' and its cotangent reaches q and k."""
    q, k, v = _qkv(2, 2, t=96, seed=3, b=1)

    def kern(q, k, v):
        o, lse = flash_attention(q, k, v, causal=True, window=40, block_q=32,
                                 block_k=32, interpret=True)
        return o.astype(jnp.float32).sum() + (lse ** 2).sum()

    def ref(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bqhk", q, k) / 4.0
        i, j = np.arange(96)[:, None], np.arange(96)[None, :]
        ok = jnp.asarray((j <= i) & (i - j < 40))[None, :, None, :]
        lse = jax.nn.logsumexp(jnp.where(ok, s, -jnp.inf), axis=-1)
        return (mha(q, k, v, causal=True, window=40).astype(jnp.float32).sum()
                + (lse ** 2).sum())

    np.testing.assert_allclose(kern(q, k, v), ref(q, k, v), rtol=1e-5)
    for a, r in zip(jax.grad(kern, (0, 1, 2))(q, k, v),
                    jax.grad(ref, (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(a, r, atol=2e-4)


# ------------------------------------- which blocks are live, and visited
def _brute_live(doc, t, bq, bk, causal, window):
    pos = np.arange(t)
    back = pos[:, None] - pos[None, :]
    ok = np.ones((doc.shape[0], t, t), bool)
    if causal:
        ok &= (back >= 0)[None]
    if window:
        ok &= (back < window)[None]
    ok &= doc[:, :, None] == doc[:, None, :]
    return ok.reshape(doc.shape[0], t // bq, bq, t // bk, bk).any(axis=(2, 4))


@pytest.mark.parametrize("window", [1, 7, 16, 33, 64, 500])
@pytest.mark.parametrize("bq, bk", [(16, 16), (32, 16), (16, 32), (8, 64)])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_live_ranges_under_a_window_are_the_brute_force_mask_s_blocks(
        bq, bk, causal, window):
    """Every block with a pair that may attend lies inside ``[lo, hi]``
    (``[qlo, qhi]`` from the keys' side) and the ranges hold no other:
    the window's edge is monotone in the block index like the other
    two, so the reach is still one range."""
    t = 128
    rng = np.random.RandomState(bq + bk + causal + window)
    cuts = [sorted(rng.choice(np.arange(1, t), n, replace=False))
            for n in (0, 1, 3, 7, 20)] + [[16, 32, 64], [127], [1]]
    doc = _docs(cuts, t)
    nq, nk = t // bq, t // bk
    for d in (doc, None):
        lo, hi, qlo, qhi = (np.asarray(x) for x in _ranges(
            d, _offs(None, None), nq, nk, bq, bk, causal, window))
        live = _brute_live(np.asarray(doc) if d is not None
                           else np.zeros((1, t), np.int32), t, bq, bk,
                           causal, window)
        b = live.shape[0]
        j = np.arange(nk)[None, None, :]
        got = (j >= lo.reshape(b, nq, 1)) & (j <= hi.reshape(b, nq, 1))
        np.testing.assert_array_equal(got, live)
        i = np.arange(nq)[None, :, None]
        got = (i >= qlo.reshape(b, 1, nk)) & (i <= qhi.reshape(b, 1, nk))
        np.testing.assert_array_equal(got, live)


@pytest.mark.parametrize("window", [1, 16, 17, 40, 64])
@pytest.mark.parametrize("nq, nk, bq, bk, group", [
    (4, 4, 16, 16, 1), (2, 4, 32, 16, 4), (4, 2, 16, 32, 2), (8, 8, 8, 8, 8)])
@pytest.mark.parametrize("tri", [True, False], ids=["causal", "full"])
def test_the_step_table_visits_the_window_s_blocks_and_no_other(
        nq, nk, bq, bk, group, window, tri):
    """A block wholly outside the window is in no step, forward, ``dq``
    or ``dkv``; every block that holds a live pair is in exactly one (a
    query head of the group on the keys' side)."""
    (iq, ik, fl, _), (bk_t, bq_t, bfl, bg) = _steps(nq, nk, bq, bk, tri,
                                                   group, window)
    live = _brute_live(np.zeros((1, nq * bq), np.int32), nq * bq, bq, bk,
                       tri, window)[0]
    reach = {(i, j) for i in range(nq) for j in range(nk) if live[i, j]}
    assert sorted(zip(iq.tolist(), ik.tolist())) == sorted(reach)
    assert (np.diff(iq) >= 0).all()
    assert int((fl & _FIRST != 0).sum()) == int((fl & _LAST != 0).sum()) == nq
    assert sorted(zip(bq_t.tolist(), bk_t.tolist(), bg.tolist())) == sorted(
        (i, j, g) for (i, j) in reach for g in range(group))
    assert (np.diff(bk_t) >= 0).all()
    assert int((bfl & _FIRST != 0).sum()) == int(
        (bfl & _LAST != 0).sum()) == nk


def test_the_cell_s_table_is_45_steps_for_136():
    """T 16384, W 2048, blocks of 1024: a query block visits at most 3
    key blocks; at 512 at most 5 (ISSUE 42)."""
    fwd, bwd = _steps(16, 16, 1024, 1024, True, 8, 2048)
    assert len(fwd[0]) == 45 and len(bwd[0]) == 8 * 45
    assert max(np.bincount(fwd[0])) == 3
    assert len(_steps(16, 16, 1024, 1024, True, 8)[0][0]) == 136
    fwd = _steps(32, 32, 512, 512, True, 8, 2048)[0]
    assert len(fwd[0]) == 150 and max(np.bincount(fwd[0])) == 5
    # dead pairs inside the visited blocks: a third at 1024, a fifth at 512
    live = 2048 * 2049 / 2 + (16384 - 2048) * 2048
    assert 1 - live / (45 * 1024 ** 2) == pytest.approx(1 / 3, abs=0.02)
    assert 1 - live / (150 * 512 ** 2) == pytest.approx(1 / 5, abs=0.01)


def test_smallthinker_s_table_is_70_steps_for_136():
    """T 16384, W 4096, blocks of 1024, seven query heads a key/value
    head (ISSUE 46): a query block visits at most 5 key blocks, 70 steps
    for the diagonal's 136 (Trinity's window of 2048: 45)."""
    fwd, bwd = _steps(16, 16, 1024, 1024, True, 7, 4096)
    assert len(fwd[0]) == 70 == 1 + 2 + 3 + 4 + 12 * 5
    assert len(bwd[0]) == 7 * 70
    assert max(np.bincount(fwd[0])) == 5
    assert len(_steps(16, 16, 1024, 1024, True, 7)[0][0]) == 136
    # dead pairs inside the visited blocks: a fifth
    live = 4096 * 4097 / 2 + (16384 - 4096) * 4096
    assert 1 - live / (70 * 1024 ** 2) == pytest.approx(1 / 5, abs=0.01)


def test_no_window_is_the_table_the_parent_built():
    """``window = 0`` builds the tables ``_steps`` and ``_ranges`` gave
    before they knew a window, and the program of a layer without one is
    the parent's to the character."""
    for args in ((4, 4, 16, 16, True, 2), (2, 4, 32, 16, False, 4)):
        for a, b in zip(_steps(*args), _steps(*args, 0)):
            for x, y in zip(a, b):
                np.testing.assert_array_equal(x, y)
    doc = _docs(DOCS["cuts_inside_blocks"])
    for d in (doc, None):
        for a, b in zip(_ranges(d, _offs(None, None), 4, 4, 16, 16, True),
                        _ranges(d, _offs(None, None), 4, 4, 16, 16, True, 0)):
            np.testing.assert_array_equal(a, b)
    q, k, v = _qkv(4, 2)

    def kern(window):
        def f(q, k, v):
            return flash_attention(q, k, v, causal=True, doc=doc,
                                   block_q=16, block_k=16, interpret=True,
                                   **window)[0].sum()
        return str(jax.make_jaxpr(jax.grad(f, (0, 1, 2)))(q, k, v))

    plain = kern({})
    assert kern({"window": 0}) == plain
    assert kern({"window": 24}) != plain
    # a window wider than the row masks nothing: the diagonal's table
    np.testing.assert_array_equal(
        _steps(4, 4, 16, 16, True, 2, 1000)[0][0],
        _steps(4, 4, 16, 16, True, 2)[0][0])


def test_a_window_refuses_offsets_and_two_lengths():
    q, k, v = _qkv(2, 2)
    with pytest.raises(ValueError, match="window = 8.*ROADMAP R3"):
        flash_attention(q, k, v, causal=True, window=8, q_off=jnp.int32(0),
                        k_off=jnp.int32(0), interpret=True)
    with pytest.raises(ValueError, match="window = 8"):
        flash_attention(q, k[:, :32], v[:, :32], window=8, interpret=True)
    with pytest.raises(ValueError, match="window = -1"):
        flash_attention(q, k, v, window=-1, interpret=True)
    with pytest.raises(ValueError, match="window = 8"):
        mha(q, k[:, :32], v[:, :32], window=8)
    # flash_mha_lse (the ring hops' kernel) takes no window at all
    with pytest.raises(TypeError):
        flash_mha_lse(q, k, v, jnp.int32(0), jnp.int32(0), window=8)


# ------------------------------------------------------------ the chooser
def test_block_for_reads_the_cell_s_shape_as_any_other():
    """The chip's reading under a window chose the block the full layers
    have (``flash.BLOCK``'s comment): the chooser takes no window."""
    sds = jax.ShapeDtypeStruct
    shapes = (sds((1, 16384, 32, 128), jnp.bfloat16),
              sds((1, 16384, 4, 128), jnp.bfloat16),
              sds((1, 16384, 4, 128), jnp.bfloat16))
    assert block_for(*shapes) == 1024
    with pytest.raises(TypeError):
        block_for(*shapes, window=2048)
    # SmallThinker's 28 query heads on 4 (group 7): the same block
    assert block_for(sds((1, 16384, 28, 128), jnp.bfloat16),
                     *shapes[1:]) == 1024


@pytest.mark.parametrize("window", [0, 256])
def test_attend_lowers_the_windowed_kernels_for_a_tpu(window):
    """Lowered for a TPU a long windowed row is the two kernels, forward
    and backward (the Pallas -> Mosaic lowering runs here); on the CPU it
    is ``mha`` and the flag says so."""
    q, k, v = _qkv(4, 2, d=64, t=1024, dtype=jnp.bfloat16, b=1)
    doc = _docs([[300, 700]], 1024)

    def f(q, k, v, doc):
        o, flash = attend(q, k, v, causal=True, doc=doc, window=window)
        return o.astype(jnp.float32).sum(), flash

    text = jax.export.export(
        jax.jit(jax.grad(f, (0, 1, 2), has_aux=True)),
        platforms=["tpu"])(q, k, v, doc).mlir_module()
    assert all(f"{name}/pallas_call" in text
               for name in ("flash_fwd", "flash_bwd"))
    assert text.count("tpu_custom_call") >= 2
    (_, flash) = jax.jit(f)(q, k, v, doc)
    assert int(flash) == 0
    o, _ = attend(q, k, v, causal=True, doc=doc, window=window)
    want = mha(q, k, v, causal=True, doc=doc, window=window, block_q=512)
    np.testing.assert_array_equal(np.asarray(o, np.float32),
                                  np.asarray(want, np.float32))


# ------------------------------- a step's class, and a query's bounds
def _brute_mask(doc, t, tk, causal, window, q_off=0, k_off=0):
    """``(B, T, Tk)`` bool, written out pair by pair."""
    back = (q_off + np.arange(t))[:, None] - (k_off + np.arange(tk))[None, :]
    ok = np.ones((1 if doc is None else doc.shape[0], t, tk), bool)
    if causal:
        ok &= (back >= 0)[None]
    if window:
        ok &= (back < window)[None]
    if doc is not None:
        ok &= doc[:, :, None] == doc[:, None, :]
    return ok


def _geometry(b, t, tk, h, hk, doc, q_off, k_off, causal, bq, bk, window=0):
    shape = lambda n, length: jax.ShapeDtypeStruct((n, length, 16),
                                                   jnp.float32)
    return _Geometry(shape(b * h, t), None, shape(b * hk, tk), doc, q_off,
                     k_off, causal, 1.0, bq, bk, h, window)


def _hold_classes(geo, ok):
    """``geo.classes()`` and ``_bounds`` against the mask ``ok``."""
    blocks = ok.reshape(ok.shape[0], geo.nq, geo.bq, geo.nk, geo.bk)
    iq, ik = geo.fwd_t[0], geo.fwd_t[1]
    live, full, one = (np.asarray(c) for c in geo.classes())
    # a visited step computes where its block holds a live pair, and is
    # counted as needing no mask where — and ONLY where — every pair of it
    # is live
    np.testing.assert_array_equal(live, blocks.any(axis=(2, 4))[:, iq, ik])
    np.testing.assert_array_equal(full, blocks.all(axis=(2, 4))[:, iq, ik])
    assert not (full & ~one).any() and not (one & ~live).any()
    return live, full, one


#: rows of 64 tokens: documents inside a block of 16, at a block's edge,
#: over several blocks, one a row, a last one of one token
CLASS_DOCS = {"no_documents": None,
              "inside_a_block": [[5, 40], [27]],
              "at_a_block_s_edge": [[16, 48], [32]],
              "over_several_blocks": [[56], [3, 60]],
              "one_a_row": [[], []],
              "a_last_one_of_one_token": [[63], [20, 63]]}


# no window, one smaller than, equal to and larger than a block
@pytest.mark.parametrize("window", [0, 7, 16, 40])
@pytest.mark.parametrize("bq, bk", [(16, 16), (32, 16), (16, 32)])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("case", sorted(CLASS_DOCS))
def test_a_step_is_counted_wholly_live_only_where_every_pair_may_attend(
        case, causal, bq, bk, window):
    doc = None if CLASS_DOCS[case] is None else _docs(CLASS_DOCS[case])
    b, h = 2, 4
    geo = _geometry(b, T, T, h, 2, doc, None, None, causal, bq, bk, window)
    ok = _brute_mask(None if doc is None else np.asarray(doc), T, T, causal,
                     window)
    live, full, one = _hold_classes(geo, ok)
    if doc is not None:
        d = np.asarray(doc)
        iq, ik = geo.fwd_t[0], geo.fwd_t[1]
        qd, kd = d.reshape(b, -1, bq), d.reshape(b, -1, bk)
        same = ((qd.min(2) == qd.max(2))[:, iq] & (kd.min(2) == kd.max(2))[
            :, ik] & (qd[:, iq, 0] == kd[:, ik, 0]))
        np.testing.assert_array_equal(one, live & same)
    if geo.masked:
        # the two bounds a query ARE the mask
        lo, hi = (np.asarray(x)[:, :, None] for x in geo.bounds)
        j = np.arange(T)[None, None, :]
        np.testing.assert_array_equal((j >= lo) & (j <= hi), ok)
    else:
        assert full.all()
    # the layers' counters: the same classes over all heads and rows
    q, k, v = _qkv(h, 2)
    rows = live.shape[0]
    np.testing.assert_array_equal(
        count_blocks(q, k, v, causal=causal, doc=doc, window=window,
                     block_q=bq, block_k=bk),
        [c.sum() * (b * h // rows) for c in (live, full, one)])


@pytest.mark.parametrize("q_off, k_off", [
    (0, 0), (8, 0), (0, 8), (64, 0), (0, 64), (16, 16), (37, 5), (5, 37)])
@pytest.mark.parametrize("t, tk, bq, bk", [(64, 64, 16, 16), (32, 64, 16, 32)])
def test_a_step_s_class_under_traced_offsets(q_off, k_off, t, tk, bq, bk):
    """``flash_mha_lse``'s hops: the class from offsets only the running
    program knows, and bounds below 0 for a query before every key."""
    @jax.jit
    def classes(q_off, k_off):
        geo = _geometry(1, t, tk, 2, 2, None, q_off, k_off, True, bq, bk)
        return geo.classes(), geo.bounds

    got, (lo, hi) = classes(jnp.int32(q_off), jnp.int32(k_off))
    geo = _geometry(1, t, tk, 2, 2, None, jnp.int32(q_off), jnp.int32(k_off),
                    True, bq, bk)
    ok = _brute_mask(None, t, tk, True, 0, q_off, k_off)
    for a, b in zip(got, _hold_classes(geo, ok)):
        np.testing.assert_array_equal(a, b)
    j = np.arange(tk)[None, None, :]
    np.testing.assert_array_equal(
        (j >= np.asarray(lo)[:, :, None]) & (j <= np.asarray(hi)[:, :, None]),
        ok)


def test_group_seven_under_the_real_window_is_the_xla_path_s():
    """SmallThinker's geometry at its REAL window and block: 7 query
    heads on 1 key/value head, rows of 5120 tokens in three documents
    (one longer than the window), blocks of 1024, the kernels
    interpreted, against ``mha``'s row blocks with the document mask:
    outputs and the three gradients."""
    t, window = 5120, 4096
    q, k, v = _qkv(7, 1, d=8, t=t, seed=6, b=1)
    doc = _docs([[300, 4800]], t)
    mask = dict(causal=True, doc=doc, window=window)
    visited, free, one = (int(n) for n in count_blocks(
        q, k, v, block_q=1024, block_k=1024, **mask))
    # the diagonal's 15 steps a head; the middle document (300..4799)
    # is longer than the window, whose edge crosses the last block row
    assert visited == 7 * 15 and 0 < free < one < visited

    def kern(q, k, v):
        return flash_attention(q, k, v, block_q=1024, block_k=1024,
                               interpret=True, **mask)[0]

    _hold(jax.jit(kern), jax.jit(lambda q, k, v: mha(
        q, k, v, block_q=512, **mask)), q, k, v, 2e-5)


def test_the_cells_rows_run_all_three_classes():
    """A row of 16384 tokens in four documents under a window of 2048 and
    blocks of 1024, as ``tools/attn_ab.py`` cuts it: 45 visited steps at
    most, some of every class, and without the window the full layers'."""
    t = 16384
    doc = _docs([[3000, 9000, 11000]], t)
    shape = lambda h, d=128: jax.ShapeDtypeStruct((1, t, h, d), jnp.bfloat16)
    for window, most in ((2048, 45), (0, 136)):
        visited, free, one = (int(n) // 32 for n in count_blocks(
            shape(32), shape(4), shape(4), causal=True, doc=doc,
            window=window))
        assert 0 < free < one < visited <= most


@pytest.mark.parametrize("h, hk, dqk, dv, window", [
    (2, 2, 192, 128, 0),        # latent attention's two widths
    (32, 8, 16, 16, 0),         # granite: 32 / 8
    (16, 2, 32, 32, 0),         # qwen3_next: 16 / 2
    (32, 4, 16, 16, 40),        # the afmoe family's sliding layers: 32 / 4
    (32, 4, 16, 16, 0),         # ... and its full ones
    (28, 4, 16, 16, 40),        # SmallThinker's window layers: group 7
    (28, 4, 16, 16, 0),         # ... and its position-free full ones
], ids=["192x128", "32over8", "16over2", "32over4_window", "32over4",
        "28over4_window", "28over4"])
@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 3e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("split", [True], indirect=True)
def test_all_three_classes_in_one_call_are_mha_s(h, hk, dqk, dv, window,
                                                 dtype, tol, split):
    """Rows with blocks whose every pair may attend, blocks only the
    diagonal or the window's edge crosses (computed in parts, the dead
    ones left out) and blocks with a document boundary in ONE call:
    outputs, log-sum-exp and the three gradients."""
    rng = np.random.RandomState(4)
    mk = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32), dtype)
    q, k, v = mk(2, T, h, dqk), mk(2, T, hk, dqk), mk(2, T, hk, dv)
    doc = _docs([[40], [5, 23]])
    mask = dict(causal=True, doc=doc, window=window)
    visited, free, one = (int(n) for n in count_blocks(
        q, k, v, block_q=16, block_k=16, **mask))
    assert 0 < free < one < visited

    def kern(q, k, v):
        return flash_attention(q, k, v, block_q=16, block_k=16,
                               interpret=True, **mask)[0]

    _hold(kern, lambda q, k, v: mha(q, k, v, **mask), q, k, v, tol)
    lse = flash_attention(q, k, v, block_q=16, block_k=16, interpret=True,
                          **mask)[1]
    kk = jnp.repeat(k, h // hk, axis=2).astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bqhk", q.astype(jnp.float32), kk) / np.sqrt(
        dqk)
    ok = jnp.asarray(_brute_mask(np.asarray(doc), T, T, True, window))
    want = jax.nn.logsumexp(jnp.where(ok[:, :, None, :], s, -jnp.inf), axis=-1)
    assert lse.dtype == jnp.float32
    np.testing.assert_allclose(lse, want, rtol=tol, atol=tol)


# ------------------- the parts of a block an edge crosses (ISSUE 43)
@pytest.mark.parametrize("window", [0, 1, 7, 16, 24, 33, 64, 500])
@pytest.mark.parametrize("bq, bk, n", [(32, 32, 2), (32, 64, 2), (64, 32, 4)])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("q_off, k_off", [(0, 0), (8, 0), (0, 24), (37, 5)])
def test_a_part_is_left_out_only_where_no_pair_of_it_may_attend(
        q_off, k_off, causal, bq, bk, n, window):
    """``_part_seen`` against the diagonal and the window written out
    pair by pair: a part is computed where ANY pair of it may attend, a
    block is computed whole where every part is."""
    from cxxnet_tpu.ops.flash import _part_seen

    t = 128
    hq, hk = bq // n, bk // n
    ok = _brute_mask(None, t, t, causal, window, q_off, k_off)[0]
    any_ = ok.reshape(t // hq, hq, t // hk, hk).any(axis=(1, 3))
    for iq in range(t // bq):
        for ik in range(t // bk):
            q0, k0 = q_off + iq * bq, k_off + ik * bk
            seen = np.array([[bool(_part_seen(q0, k0, a, b, hq, hk, causal,
                                              window))
                              for b in range(n)] for a in range(n)])
            np.testing.assert_array_equal(
                seen, any_[iq * n:(iq + 1) * n, ik * n:(ik + 1) * n])
            # monotone: the two far corners say whether any part is dead
            assert seen.all() == (seen[0, n - 1] and seen[n - 1, 0])


def test_parts_are_whole_lane_tiles_or_none(monkeypatch):
    """A block splits only into parts of whole lane tiles: the cells'
    1024 into 512s, a block of 128 not at all."""
    from cxxnet_tpu.ops import flash

    assert flash._LANES == 128 and flash._PARTS == 2
    assert flash._parts(1024, 1024) == 2 and flash._parts(256, 512) == 2
    assert flash._parts(128, 1024) == 1 and flash._parts(1024, 192) == 1
    monkeypatch.setattr(flash, "_LANES", 8)
    assert flash._parts(16, 32) == 2 and flash._parts(8, 16) == 1
