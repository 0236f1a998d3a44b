"""The layers a SmallThinker-21BA3B model forced (ISSUE 46), each
against the configuration's plain reference at a small size, float32,
seeded weights: ``routed_experts`` with a SECOND input — the router
reads the attention's input under the attention's norm weight, the
experts the attention's output — whole (the router's gradient reaches
that norm weight and the second input) and as a share (it does not);
``expert_act = reglu``, forward and both gradients, the shared fork too;
the four ranks' shares of a layer adding up to the uncut reference; a
router fed the post-attention stream choosing other experts; the
net handing the borrowed leaf over; the layer's refusals.  What every
family's tests share (the builder's conf through the trainer, the
published defaults, the whole small net's loss, gradients and adam
chunks, whole and as a share, against the reference) is a row of
``tests/families.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import families
from families import held_against, make, strs

FAMILY = "smallthinker"

MOE = dict(nexpert=16, topk=3, nhidden=10, expert_act="reglu", norm_topk=1,
           eps=1e-6, init_sigma=0.5)
BRANCH = dict(prenorm=1, residual_scale=1.0)
SHAPE = (2, 12, 8)


def two_inputs(seed):
    """(experts' input, router's input) stacked: ``held_against`` takes
    one array and checks its gradient."""
    return jnp.asarray(np.random.RandomState(seed).randn(2, *SHAPE),
                       jnp.float32)


def with_norms(p, seed):
    """The layer's leaves with its own norm off 1 and the borrowed one
    (the attention layer's) beside them, as the net hands it over."""
    r = np.random.RandomState(seed)
    return dict(p, norm=jnp.asarray(1 + 0.2 * r.randn(8), jnp.float32),
                route_norm=jnp.asarray(1 + 0.2 * r.randn(8), jnp.float32))


def plain_layer(ref, cfg):
    """``x' + experts(rms(x', n2))`` routed on ``rms(h, n1)``."""
    def plain(q, a):
        v = ref.rms_norm(a[0], q["norm"], 1e-6)
        seen = ref.rms_norm(a[1], q["route_norm"], 1e-6)
        return a[0] + ref.routed_experts(q, v, seen, strs(cfg))
    return plain


@pytest.mark.parametrize("held", [16, 4], ids=["whole", "share"])
def test_the_router_reads_the_second_input_under_the_borrowed_norm(ref, held):
    """Whole: the router's gradient flows into ``n1`` and the router's
    input; in a share both are exactly zero, like the router's own."""
    cfg = dict(MOE, first_expert=4 if held == 4 else 0, nheld=held)
    lay, p, out = make("routed_experts", [SHAPE, SHAPE],
                       **dict(cfg, route_norm="attn0", **BRANCH))
    assert out == [SHAPE] and "route_norm" not in p
    assert lay.borrows() == {"route_norm": ("attn0", "norm")}
    p = with_norms(p, 3)
    x = two_inputs(4)
    share = held < 16
    _, _, g = held_against(
        lambda q, a: lay.apply(q, [a[0], a[1]])[0], plain_layer(ref, cfg),
        p, x, ["wmat", "wproj", "norm"] + ([] if share else
                                          ["wgate", "route_norm"]),
        zero=("wgate", "route_norm") if share else ())
    # held_against held the input's gradient; its router half
    with jax.default_matmul_precision("highest"):
        dx = jax.jit(jax.grad(lambda a: jnp.sum(jnp.sin(
            lay.apply(p, [a[0], a[1]])[0]))))(x)
    assert (np.abs(np.asarray(dx[1])).max() == 0) == share
    # the counters see the same pairs whatever the router reads
    (_,), st = lay.apply_stateful(p, lay.init_aux([SHAPE, SHAPE]),
                                  [x[0], x[1]])
    assert int(st["pairs_dropped"]) == 0
    assert (int(st["pairs"]) == 24 * 3) == (not share)


def test_the_reference_s_expert_loop_in_token_blocks_is_the_loop(ref,
                                                                monkeypatch):
    """The reference runs its dense expert loop 2048 tokens at a time
    (the loop's running sums would not fit a chip beside the state at
    16384 tokens): blocks of 8 of these 24 tokens give the one block's
    output and gradients."""
    cfg = dict(MOE, first_expert=4, nheld=4)
    _, p, _ = make("routed_experts", [SHAPE, SHAPE],
                   **dict(cfg, route_norm="attn0", **BRANCH))
    p, x = with_norms(p, 3), two_inputs(4)

    def both():
        fn = plain_layer(ref, cfg)
        return jax.jit(lambda q, a: (fn(q, a), jax.grad(
            lambda q, a: jnp.sum(jnp.sin(fn(q, a))), argnums=(0, 1))(q, a)))

    with jax.default_matmul_precision("highest"):
        whole = both()(p, x)                    # traced now: one block
        monkeypatch.setattr(ref, "EXPERT_BLOCK", 8)
        blocked = both()(p, x)                  # three blocks of 8
    for a, b in zip(jax.tree_util.tree_leaves(blocked),
                    jax.tree_util.tree_leaves(whole)):
        np.testing.assert_allclose(a, b, atol=2e-5)
    assert np.abs(np.asarray(whole[1][0]["wmat"])).max() > 0


def test_without_route_norm_the_second_input_is_read_as_it_is(ref):
    lay, p, _ = make("routed_experts", [SHAPE, SHAPE], **dict(MOE, **BRANCH))
    assert lay.borrows() == {}
    x = two_inputs(5)

    def plain(q, a):
        v = ref.rms_norm(a[0], q["norm"], 1e-6)
        return a[0] + ref.routed_experts(q, v, a[1], strs(MOE))

    held_against(lambda q, a: lay.apply(q, [a[0], a[1]])[0], plain, p, x,
                 ["wmat", "wproj", "norm", "wgate"])
    # and with ONE input the layer is what it was: routed on its own
    one, _, _ = make("routed_experts", [SHAPE], **dict(MOE, **BRANCH))
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            one.apply(p, [x[0]])[0],
            lay.apply(p, [x[0], ref.rms_norm(x[0], p["norm"], 1e-6)])[0],
            atol=1e-6)


def test_reglu_is_relu_of_the_gate_times_up_with_both_gradients(ref):
    """``expert_act = reglu`` on the fused gate | up, against plain
    ``jax.numpy``: the held experts (forward, the input's gradient and
    the weights') and the shared fork."""
    cfg = dict(MOE, shared_hidden=6, shared_gate=0)
    lay, p, _ = make("routed_experts", [SHAPE], **cfg)
    assert p["wmat"].shape == (16, 8, 20) and p["shared_wmat"].shape == (12, 8)
    x = jnp.asarray(np.random.RandomState(6).randn(*SHAPE), jnp.float32)

    def plain(q, a):
        gu = a @ q["shared_wmat"].T
        shared = (jax.nn.relu(gu[..., :6]) * gu[..., 6:]) @ q["shared_wproj"].T
        return ref.routed_experts(q, a, a, strs(MOE)) + shared

    ya, _, _ = held_against(lambda q, a: lay.apply(q, [a])[0], plain, p, x,
                            ["wmat", "wproj", "wgate", "shared_wmat",
                             "shared_wproj"])
    # silu in relu's place is another function
    swi, _, _ = make("routed_experts", [SHAPE],
                     **dict(cfg, expert_act="swiglu"))
    with jax.default_matmul_precision("highest"):
        assert np.abs(np.asarray(swi.apply(p, [x])[0] - ya)).max() > 0.05
    # one expert by hand: W_d (relu(W_g x) * W_u x)
    q1 = {k: p[k] for k in ("wgate", "wmat", "wproj")}
    one, _, _ = make("routed_experts", [(1, 8)], nexpert=16, topk=16,
                     nhidden=10, expert_act="reglu", norm_topk=0)
    row = x[0, :1]
    with jax.default_matmul_precision("highest"):
        w = jax.nn.softmax(row @ p["wgate"].T, axis=-1)[0]
        by_hand = sum(
            w[e] * ((jax.nn.relu(row @ p["wmat"][e][:, :10])
                     * (row @ p["wmat"][e][:, 10:])) @ p["wproj"][e])
            for e in range(16))
        np.testing.assert_allclose(one.apply(q1, [row])[0], by_hand,
                                   atol=2e-5)


def test_the_four_ranks_shares_add_up_to_the_uncut_layer(ref):
    """model-configs section 4: 64 experts over 4 ranks of 16
    (``first_expert`` 0, 16, 32, 48); every rank norms both inputs
    alike and routes over all 64 on the attention's input; its layer
    adds its own experts' terms to ``x'``.  The ranks' parts — ``x'``,
    the norms and the router counted once — sum to the uncut reference's
    whole layer, and every pair is computed on exactly one rank."""
    cfg = dict(MOE, nexpert=64, topk=6)
    _, p, _ = make("routed_experts", [SHAPE, SHAPE],
                   **dict(cfg, route_norm="attn0", **BRANCH))
    p = with_norms(p, 7)
    x = two_inputs(8)
    with jax.default_matmul_precision("highest"):
        uncut = np.asarray(plain_layer(ref, cfg)(p, x), np.float64)
        parts, pairs = [], 0
        for first in (0, 16, 32, 48):
            share = dict(first_expert=first, nheld=16)
            lay, _, _ = make("routed_experts", [SHAPE, SHAPE],
                             **dict(cfg, route_norm="attn0", **BRANCH,
                                    **share))
            mine = dict(p, wmat=p["wmat"][first:first + 16],
                        wproj=p["wproj"][first:first + 16])
            (y,), st = lay.apply_stateful(
                mine, lay.init_aux([SHAPE, SHAPE]), [x[0], x[1]])
            parts.append(np.asarray(y, np.float64))
            pairs += int(st["pairs"])
            # a lone rank's layer is the reference's share
            np.testing.assert_allclose(y, plain_layer(
                ref, dict(cfg, **share))(mine, x), atol=3e-5)
    assert pairs == 24 * 6
    total = sum(parts) - 3 * np.asarray(x[0], np.float64)
    np.testing.assert_allclose(total, uncut, atol=5e-5)
    # no rank alone is the layer
    assert min(np.abs(q - uncut).max() for q in parts) > 0.05


def test_a_router_fed_the_attention_s_output_chooses_other_experts(ref):
    """At the published widths (hidden 2560, 28 query heads on 4
    key/value heads of 128, 64 experts, top-6) and the seed's weights —
    the stream the embedding's normal(0, 1) rows, the matrices at 0.02 —
    the attention moves the stream enough that a router fed ``rms(x +
    a)`` picks another six for more than a tenth of the tokens: such a
    program is not ``correct``.  The program's layer follows the input
    it is given."""
    key = jax.random.PRNGKey(46)
    t, d = 192, 2560
    mat = lambda i, *s: jax.random.normal(  # noqa: E731
        jax.random.fold_in(key, i), s, jnp.float32) * 0.02
    x = jax.random.normal(key, (1, t, d), jnp.float32)
    attn = strs(dict(nhead=28, nkvhead=4, head_dim=128, causal=1, no_bias=1))
    pa = {"wmat": mat(1, (28 + 8) * 128, d), "wproj": mat(2, d, 28 * 128)}
    cfg = strs(dict(nexpert=64, topk=6, nhidden=768))
    pr = {"wgate": mat(3, 64, d)}
    ones = jnp.ones((d,), jnp.float32)
    with jax.default_matmul_precision("highest"):
        u = ref.rms_norm(x, ones, 1e-6)
        after = ref.rms_norm(x + ref.attention(pa, u, None, attn), ones, 1e-6)
        w, before = ref.router(pr, u.reshape(t, d), cfg)
        _, wrong = ref.router(pr, after.reshape(t, d), cfg)
    changed = (np.sort(np.asarray(before), axis=1)
               != np.sort(np.asarray(wrong), axis=1)).any(axis=1).mean()
    assert changed > 0.1, changed
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-5)
    # the softmax over all 64, the top 6 renormalised IS the softmax over
    # the six chosen logits: the order of the two is no assumption
    logits = np.asarray(u.reshape(t, d) @ pr["wgate"].T)
    six = np.take_along_axis(logits, np.asarray(before), axis=1)
    np.testing.assert_allclose(w, jax.nn.softmax(six, axis=-1), rtol=2e-5)
    # the program's layer routes on the node it is handed
    lay, p, _ = make("routed_experts", [SHAPE, SHAPE], **MOE)
    xs = two_inputs(9)
    a = lay.apply(p, [xs[0], xs[1]])[0]
    b = lay.apply(p, [xs[0], xs[0]])[0]
    assert np.abs(np.asarray(a - b)).max() > 1e-3


def test_the_net_hands_the_attention_s_norm_to_the_router():
    """Through ``FunctionalNet``: the expert layer owns no ``route_norm``
    leaf (no parameter, no updater state, no checkpoint entry more); the
    attention layer's ``norm`` moves the router's choice; a conf that
    names a layer without a ``norm`` is refused."""
    f = families.FAMILIES[FAMILY]
    text = f.builder(**dict(f.tiny, experts_held=16))
    tr = families.trainer(text)
    assert "route_norm" not in tr.params["l2_moe0"]
    assert tr.net.borrowed == {2: {"route_norm": ("l1_attn0", "norm")},
                               4: {"route_norm": ("l3_attn1", "norm")}}
    assert set(tr.ustates["l2_moe0"]) == {"wgate", "wmat", "wproj", "norm"}
    ids = jnp.asarray(families.rows_with_documents(1, 1, 64, vocab=64))
    node = tr.net.graph.node_index_of("h1")
    fwd = jax.jit(lambda q: tr.net.forward(q, ids, train=False)[0][node])
    base = fwd(tr.params)
    tilt = jnp.asarray(1 + 0.5 * np.random.RandomState(2).randn(32),
                       jnp.float32)
    # with the attention's output projection at 0 the attention adds
    # nothing whatever its norm: the router is n1's only reader left
    off = dict(tr.params, l1_attn0=dict(
        tr.params["l1_attn0"], wproj=0 * tr.params["l1_attn0"]["wproj"]))
    moved = dict(off, l1_attn0=dict(off["l1_attn0"], norm=tilt))
    assert np.abs(np.asarray(fwd(off) - base)).max() > 0
    assert np.abs(np.asarray(fwd(moved) - fwd(off))).max() > 1e-4
    bad = text.replace("route_norm = attn0", "route_norm = embed")
    with pytest.raises(ValueError, match="no 'norm' weight"):
        families.trainer(bad)
    with pytest.raises(ValueError, match="unknown layer name"):
        families.trainer(text.replace("route_norm = attn0",
                                      "route_norm = nowhere"))


def test_the_layer_says_what_a_second_input_does_not_go_with():
    with pytest.raises(ValueError, match="the layer's second"):
        make("routed_experts", [SHAPE], **dict(MOE, route_norm="attn0"))
    with pytest.raises(ValueError, match="is not shaped like"):
        make("routed_experts", [SHAPE, (2, 12, 4)], **MOE)
    with pytest.raises(ValueError, match="expected 1 input, or 2"):
        make("routed_experts", [SHAPE, SHAPE, SHAPE], **MOE)
    with pytest.raises(ValueError, match="swiglu, reglu or relu2"):
        make("routed_experts", [SHAPE], **dict(MOE, expert_act="geglu"))
    # the route scope holds the second input's norm: the trace's readers
    # bill it to routing
    lay, p, _ = make("routed_experts", [SHAPE, SHAPE],
                     **dict(MOE, route_norm="attn0", **BRANCH))
    p = with_norms(p, 1)
    x = two_inputs(2)
    hlo = jax.jit(lambda a: lay.apply(p, [a[0], a[1]])[0]).lower(x).as_text(
        debug_info=True)
    assert "route/rsqrt" in hlo.replace('"', "")
