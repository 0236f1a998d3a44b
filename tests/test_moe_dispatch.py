"""``layers/moe.held_experts`` on slabs (PR 39): every array between
the sort and a token's sum has ``C`` rows, ``C`` from the layer's shapes;
what the router sends beyond ``C`` is computed by the same code on
further slabs.  Held against a dense masked loop (every held expert on
every token, times the router's weight for it or 0) and against the
parent's ``held_experts``, kept here as the oracle: buffers with room
for all ``tokens x topk`` pairs, a gather each way.

The router is put in the test's hands (``moe.route`` replaced) wherever
a case needs an exact number of held pairs; the layer, its state and
its counters are the program's own.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import families
from cxxnet_tpu.layers import moe
from cxxnet_tpu.utils.profiler import pipeline_stats

# 512 tokens pick 4 of 64, 4 held: 2048 pairs, 128 to the share of an
# even router, a slab of one row tile
M, K, D, F, G, E, FIRST = 512, 4, 8, 6, 4, 64, 8
C = 512


def parent_held_experts(x, w, idx, wmat, wproj, first):
    """``held_experts`` as PR 38 left it."""
    m, k = idx.shape
    g, f = wmat.shape[0], wmat.shape[-1] // 2
    local = idx.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < g), local, g)
    pair = lax.iota(jnp.int32, m * k)
    skey, order = lax.sort((key, pair), num_keys=1)
    _, inv = lax.sort((order, pair), num_keys=1)
    counts = (key[:, None] == lax.iota(jnp.int32, g)[None]).sum(
        axis=0, dtype=jnp.int32)
    valid = (skey < g)[:, None]
    xs = jnp.where(valid, x[order // k], 0)
    gu = lax.ragged_dot(xs, wmat, counts, preferred_element_type=x.dtype)
    h = jax.nn.silu(gu[:, :f]) * gu[:, f:]
    ys = lax.ragged_dot(jnp.where(valid, h, 0), wproj, counts,
                        preferred_element_type=x.dtype)
    ys = jnp.where(valid, ys, 0)
    return (ys[inv].reshape(m, k, -1) * w[..., None]).sum(axis=1), counts


def dense_loop(x, w, idx, wmat, wproj, first):
    """Every held expert on every token, times the router's weight for
    it or 0."""
    f = wmat.shape[-1] // 2
    y = jnp.zeros_like(x)
    for j in range(wmat.shape[0]):
        gu = x @ wmat[j]
        mask = jnp.where(idx == first + j, w, 0.0).sum(-1)
        y = y + mask[:, None] * ((jax.nn.silu(gu[:, :f]) * gu[:, f:])
                                 @ wproj[j])
    return y


def make(in_shape, **cfg):
    return families.make("routed_experts", [in_shape], **cfg)[:2]


def picks(rng, held: int, empty=()):
    """``(M, K)`` expert ids with exactly ``held`` pairs on the held
    experts (none on those of ``empty``), the rest on experts held
    elsewhere, and weights."""
    idx = rng.choice([e for e in range(E) if not FIRST <= e < FIRST + G],
                     size=M * K)
    mine = [FIRST + j for j in range(G) if j not in empty]
    idx[rng.permutation(M * K)[:held]] = rng.choice(mine, size=held)
    return (jnp.asarray(rng.rand(M, K), jnp.float32),
            jnp.asarray(idx.reshape(M, K), jnp.int32))


def grads_of(fn, x, p):
    loss = lambda x, wmat, wproj: jnp.sum(jnp.sin(  # noqa: E731
        fn(x, wmat, wproj)))
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(
        x, p["wmat"], p["wproj"])


def assert_same(got, want, atol):
    assert float(got[0]) == pytest.approx(float(want[0]), abs=atol * 50)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, atol=atol, rtol=1e-3)


def test_a_slab_is_sized_from_the_share():
    assert moe.SLAB_FACTOR == 2 and moe.ROW_TILE == 512
    assert moe.slab_rows(M * K, G, E) == C
    # the two cells: an eighth of the pairs in each
    assert moe.slab_rows(8192 * 10, 32, 512) == 10240
    assert moe.slab_rows(8192 * 8, 16, 256) == 8192
    # whole row tiles, rounded up; never more than all pairs
    assert moe.slab_rows(8192 * 6, 8, 128) == 6144
    assert moe.slab_rows(8192 * 6, 9, 128) == 7168
    assert moe.slab_rows(8192 * 8, 128, 256) == 8192 * 8
    assert moe.slab_rows(8192 * 8, 256, 256) == 8192 * 8
    assert moe.slab_rows(72, 6, 16) == 72


@pytest.mark.parametrize("held, empty", [
    (C // 2, ()), (C, ()), (C + 1, ()), (M * K, ()), (0, ()),
    (C + C // 2, (1,)), (3 * C, (0, 3)),
], ids=["half_a_slab", "exactly_a_slab", "a_slab_and_one", "every_pick_held",
        "no_pick_held", "an_expert_nobody_picks", "three_slabs_two_experts"])
def test_a_share_computes_every_held_pair_whatever_the_router_sends(
        monkeypatch, held, empty):
    rng = np.random.RandomState(held + len(empty))
    lay, p = make((M, D), nexpert=E, topk=K, nhidden=F, first_expert=FIRST,
                  nheld=G, init_sigma=0.5)
    w, idx = picks(rng, held, empty)
    monkeypatch.setattr(moe, "route", lambda *a, **kw: (w, idx))
    x = jnp.asarray(rng.randn(M, D), jnp.float32)
    with jax.default_matmul_precision("highest"):
        (y,), state = jax.jit(lay.apply_stateful)(
            p, lay.init_aux([(M, D)]), [x])
        got = grads_of(lambda x, a, b: lay.apply(
            dict(p, wmat=a, wproj=b), [x])[0], x, p)
        dense = grads_of(lambda x, a, b: dense_loop(
            x, w, idx, a, b, FIRST), x, p)
        parent = grads_of(lambda x, a, b: parent_held_experts(
            x, w, idx, a, b, FIRST)[0], x, p)
        want_y, counts = jax.jit(parent_held_experts, static_argnums=5)(
            x, w, idx, p["wmat"], p["wproj"], FIRST)
        dense_y = jax.jit(dense_loop, static_argnums=5)(
            x, w, idx, p["wmat"], p["wproj"], FIRST)
    np.testing.assert_allclose(y, want_y, atol=2e-5)
    np.testing.assert_allclose(y, dense_y, atol=2e-5)
    assert_same(got, dense, 1e-4)
    assert_same(got, parent, 1e-4)
    for j in empty:                      # no token, no gradient
        assert np.abs(np.asarray(got[1][1][j])).max() == 0
    assert int(counts.sum()) == held
    assert int(state["pairs"]) == held
    assert int(state["pairs_max"]) == int(counts.max())
    assert int(state["pairs_dropped"]) == 0
    assert int(state["pairs_overflow"]) == max(held - C, 0)


def test_a_share_s_overflow_is_one_loop_and_its_first_slab_is_outside_it():
    lay, p = make((M, D), nexpert=E, topk=K, nhidden=F, first_expert=FIRST,
                  nheld=G)
    x = jnp.zeros((M, D), jnp.float32)
    fwd = str(jax.make_jaxpr(lambda q, a: lay.apply(q, [a]))(p, x))
    assert fwd.count("while[") == 1 and "cond[" not in fwd
    bwd = str(jax.make_jaxpr(jax.grad(
        lambda q, a: jnp.sum(lay.apply(q, [a])[0]), argnums=(0, 1)))(p, x))
    # the forward's loop and the backward's own
    assert bwd.count("while[") == 2 and "cond[" not in bwd
    # every grouped product runs on a slab's rows
    assert f"f32[{C},{D}]" in bwd and f"f32[{M * K},{D}]" not in bwd
    assert f"f32[{M * K},{2 * F}]" not in bwd


def test_a_whole_layer_is_one_slab_with_no_loop_and_differentiates_w():
    m, k, e = 48, 3, 8
    lay, p = make((m, D), nexpert=e, topk=k, nhidden=F, init_sigma=0.5)
    assert moe.slab_rows(m * k, e, e) == m * k
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(m, D), jnp.float32)
    text = str(jax.make_jaxpr(jax.grad(
        lambda q, a: jnp.sum(lay.apply(q, [a])[0]), argnums=(0, 1)))(p, x))
    assert "while[" not in text and "cond[" not in text
    w = jnp.asarray(rng.rand(m, k), jnp.float32)
    idx = jnp.asarray(np.argsort(rng.rand(m, e), axis=-1)[:, :k], jnp.int32)

    def grads(fn):
        return jax.jit(jax.value_and_grad(
            lambda x, w, a, b: jnp.sum(jnp.sin(fn(x, w, a, b))),
            argnums=(0, 1, 2, 3)))(x, w, p["wmat"], p["wproj"])

    with jax.default_matmul_precision("highest"):
        got = grads(lambda x, w, a, b: moe.held_experts(
            x, w, idx, a, b, 0, e)[0])
        dense = grads(lambda x, w, a, b: dense_loop(x, w, idx, a, b, 0))
        parent = grads(lambda x, w, a, b: parent_held_experts(
            x, w, idx, a, b, 0)[0])
        (_,), state = jax.jit(lay.apply_stateful)(
            p, lay.init_aux([(m, D)]), [x])
    assert np.abs(np.asarray(got[1][1])).max() > 0
    assert_same(got, dense, 1e-4)
    assert_same(got, parent, 1e-4)
    assert int(state["pairs"]) == m * k
    assert int(state["pairs_overflow"]) == 0 == int(state["pairs_dropped"])


@pytest.mark.parametrize("cfg", [
    dict(score_func="softmax"),
    dict(score_func="sigmoid", select_bias=1, routed_scale=2.5),
], ids=["softmax", "sigmoid_and_bias"])
def test_a_router_that_favours_the_held_experts_fills_further_slabs(cfg):
    """The layer's own router, its held experts' rows raised so that
    they get most picks: the layer is the dense loop over its own
    routing, and counts what went beyond the first slab."""
    lay, p = make((M, D), nexpert=E, topk=K, nhidden=F, first_expert=FIRST,
                  nheld=G, init_sigma=0.5, **cfg)
    rng = np.random.RandomState(11)
    x = jnp.asarray(np.abs(rng.randn(M, D)), jnp.float32)
    wg = np.asarray(p["wgate"]).copy()
    wg[FIRST:FIRST + G] = np.abs(wg[FIRST:FIRST + G]) + 0.5
    wg[FIRST + 1] = wg[FIRST]            # two held experts always tie
    p = dict(p, wgate=jnp.asarray(wg))
    if "score_bias" in p:
        p["score_bias"] = jnp.asarray(rng.randn(E) * 0.05, jnp.float32)
    with jax.default_matmul_precision("highest"):
        logits = jnp.dot(x, p["wgate"].T)
        w, idx = moe.route(logits, K, True, score_func=cfg["score_func"],
                           bias=p.get("score_bias"),
                           scale=cfg.get("routed_scale", 1.0))
        (y,), state = jax.jit(lay.apply_stateful)(
            p, lay.init_aux([(M, D)]), [x])
        got = grads_of(lambda x, a, b: lay.apply(
            dict(p, wmat=a, wproj=b), [x])[0], x, p)
        # a share's routing weights are constants of the backward pass
        dense = grads_of(lambda x, a, b: dense_loop(
            x, w, idx, a, b, FIRST), x, p)
    held = int(((idx >= FIRST) & (idx < FIRST + G)).sum())
    assert held > C + C // 2             # at least two slabs
    with jax.default_matmul_precision("highest"):
        dense_y = jax.jit(dense_loop, static_argnums=5)(
            x, w, idx, p["wmat"], p["wproj"], FIRST)
    np.testing.assert_allclose(y, dense_y, atol=5e-5)
    assert_same(got, dense, 2e-4)
    assert int(state["pairs"]) == held
    assert int(state["pairs_overflow"]) == held - C
    assert int(state["pairs_dropped"]) == 0


@pytest.mark.parametrize("family", ["joyai_llm_flash", "qwen3_next"])
def test_the_builders_confs_train_a_round_and_count_no_overflow(family):
    f = families.FAMILIES[family]
    text = f.builder(**f.tiny)
    assert text.count("= routed_experts:") == 2
    tr = families.trainer(text)
    ids = np.random.RandomState(0).randint(0, 64, (4, 1, 64)).astype(
        np.float32)
    stats = pipeline_stats()
    before = dict(stats.counters())
    loss = tr.update_scan(ids, np.roll(ids, -1, axis=2))
    assert np.isfinite(loss).all()
    tr.count_layer_state()
    got = stats.counters()
    assert got["expert_pairs"] - before.get("expert_pairs", 0) > 0
    # the counter is in the round's record, at 0
    assert "expert_pairs_overflow" in got
    assert got["expert_pairs_overflow"] == before.get(
        "expert_pairs_overflow", 0)
    assert got.get("expert_pairs_dropped", 0) == before.get(
        "expert_pairs_dropped", 0)
