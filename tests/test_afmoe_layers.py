"""The layers a Trinity-Mini (``model_type: afmoe``) model forced (ISSUE
42), each against the configuration's plain reference at a small size,
float32, seeded weights: ``attention`` with a ``window`` (sliding, with
rotary) and without (full, no positions), q/k norms and the output
gate; the sandwich (``postnorm``) on ``attention``, ``gated_mlp`` and
``routed_experts``; the ranks' shares of an expert layer adding up to
the uncut reference BEFORE the post norm; a dropped bias changing the
chosen experts; the ``attn_window_pairs`` counter; the layer's refusals.
What every family's tests share (the builder's conf through the trainer,
the published defaults, the whole small net's loss, gradients and an adam
chunk against the reference) is a row of ``tests/families.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.io.tokens import attn_pairs
from cxxnet_tpu.utils.profiler import pipeline_stats
from families import (held_against, make, rows_with_documents, strs,
                      with_bias)

FAMILY = "afmoe"


def jiggled(p, seed, *tags):
    """Norm weights off their start of 1, so that a norm left out or
    applied in the wrong place shows."""
    r = np.random.RandomState(seed)
    return dict(p, **{t: jnp.asarray(1 + 0.2 * r.randn(*p[t].shape),
                                     jnp.float32) for t in tags})


# ----------------------------------------------------------------------
ATTN = dict(nhead=4, nkvhead=2, head_dim=8, qk_norm=1, out_gate=1, causal=1,
            no_bias=1, eps=1e-5, init_sigma=0.3)
SLIDING = dict(ATTN, window=5, rotary_dim=8, rope_theta=10000.0)
SANDWICH = dict(prenorm=1, postnorm=1, residual_scale=1.0)


@pytest.mark.parametrize("kind", ["sliding", "full"])
def test_each_kind_of_attention_layer_is_the_reference_s(ref, kind):
    """The mixer alone: a sliding layer (window 5 in documents of 8 and
    more, rotate-half rotary over the whole head, positions restarting
    at each document) and a full one (no window, no positions); q/k
    norms and the sigmoid gate in both."""
    cfg = SLIDING if kind == "sliding" else ATTN
    lay, p, out = make("attention", [(2, 24, 20), (2, 24)], **cfg)
    assert out == [(2, 24, 20)]
    assert {t: v.shape for t, v in p.items()} == {
        "wmat": ((2 * 4 + 2 * 2) * 8, 20), "wproj": (20, 32),
        "q_norm": (8,), "k_norm": (8,)}
    p = jiggled(p, 1, "q_norm", "k_norm")
    x = jnp.asarray(np.random.RandomState(2).randn(2, 24, 20), jnp.float32)
    ids = jnp.asarray(rows_with_documents(3, 2, 24))
    int_ids = ids.astype(jnp.int32)
    held_against(lambda q, a: lay.apply(q, [a, ids])[0],
                 lambda q, a: ref.attention(q, a, int_ids, strs(cfg)),
                 p, x, list(p))
    with jax.default_matmul_precision("highest"):
        y = lay.apply(p, [x, ids])[0]
        # a token of the second document does not see the first
        cut = x.at[:, :8].set(0.0)
        np.testing.assert_allclose(lay.apply(p, [cut, ids])[0][:, 9:],
                                   y[:, 9:], atol=1e-6)
        # row 1's last document runs from 9 to 23: under the window its
        # last token sees 19..23 and nothing of 9..18; a full layer does
        far = x.at[1, 9:19].set(0.0)
        moved = np.abs(np.asarray(
            lay.apply(p, [far, ids])[0] - y))[1, 23].max()
    assert (moved < 1e-6) == (kind == "sliding")


def test_the_window_s_edge_is_itself_and_the_w_minus_one_before(ref):
    """``assumed.window_edge``: with W = 5, query 10 sees keys 6..10 —
    the reference and the program agree on which key falls out."""
    lay, p, _ = make("attention", [(1, 16, 20)], **SLIDING)
    x = jnp.asarray(np.random.RandomState(4).randn(1, 16, 20), jnp.float32)
    with jax.default_matmul_precision("highest"):
        for fn in (lambda a: lay.apply(p, [a])[0],
                   lambda a: ref.attention(p, a, None, strs(SLIDING))):
            base = np.asarray(fn(x))[0, 10]
            at6 = np.asarray(fn(x.at[0, 6].add(1.0)))[0, 10]
            at5 = np.asarray(fn(x.at[0, 5].add(1.0)))[0, 10]
            assert np.abs(at6 - base).max() > 1e-4
            assert np.abs(at5 - base).max() < 1e-6


@pytest.mark.parametrize("kind, shapes, cfg, tags", [
    ("attention", [(2, 12, 20), (2, 12)], SLIDING,
     ["wmat", "wproj", "q_norm", "k_norm", "norm", "postnorm"]),
    ("gated_mlp", [(2, 12, 20)], dict(nhidden=14, eps=1e-5, init_sigma=0.3),
     ["wmat", "wproj", "norm", "postnorm"]),
    ("routed_experts", [(2, 12, 20)],
     dict(nexpert=8, topk=2, nhidden=6, shared_hidden=5, shared_gate=0,
          score_func="sigmoid", select_bias=1, routed_scale=2.826,
          eps=1e-5, init_sigma=0.5),
     ["wmat", "wproj", "shared_wmat", "shared_wproj", "norm", "postnorm"]),
])
def test_the_sandwich_is_x_plus_the_normed_branch(ref, kind, shapes, cfg,
                                                  tags):
    """``postnorm = 1``: ``x + rms(f(rms(x, norm)), postnorm)``, both
    norms inside the residual add, for every layer type the family's
    stack is made of; forward and gradient against the reference."""
    lay, p, _ = make(kind, shapes, **dict(cfg, **SANDWICH))
    d = shapes[0][-1]
    assert p["norm"].shape == p["postnorm"].shape == (d,)
    assert float(p["postnorm"].min()) == float(p["postnorm"].max()) == 1.0
    assert "postnorm" in lay.f32_tags
    p = jiggled(p, 5, "norm", "postnorm")
    x = jnp.asarray(np.random.RandomState(6).randn(*shapes[0]), jnp.float32)
    more = [jnp.asarray(rows_with_documents(7, 2, 12))] if len(
        shapes) > 1 else []
    scfg = strs(cfg)

    def plain(q, a):
        u = ref.rms_norm(a, q["norm"], 1e-5)
        if kind == "attention":
            y = ref.attention(q, u, more[0].astype(jnp.int32), scfg)
        elif kind == "gated_mlp":
            y = ref._swiglu(u, q["wmat"], q["wproj"], None)
        else:
            y = ref.routed_experts(q, u, scfg)
        return a + ref.rms_norm(y, q["postnorm"], 1e-5)

    held_against(lambda q, a: lay.apply(q, [a] + more)[0], plain, p, x, tags)
    # without the key the layer is what it was: no leaf, no norm
    bare, pb, _ = make(kind, shapes, **dict(cfg, prenorm=1,
                                           residual_scale=1.0))
    assert "postnorm" not in pb and set(pb) == set(p) - {"postnorm"}


# ----------------------------------------------------------------------
MOE = dict(nexpert=32, topk=4, nhidden=10, shared_hidden=6, shared_gate=0,
           score_func="sigmoid", select_bias=1, routed_scale=2.826,
           eps=1e-5, init_sigma=0.5)


def test_the_eight_shares_add_up_before_the_post_norm(ref):
    """model-configs section 4, with the sandwich: 32 experts over 8
    ranks of 4; every rank norms its input alike, routes over all 32
    (sigmoid, bias, top-4, times 2.826) and adds its own experts' terms
    and the shared expert.  The ranks' ``FF`` parts — the shared expert,
    the router and the norms counted once — add up to the uncut
    reference's ``FF``; the post norm is NOT linear, so the sum is taken
    before it and the uncut layer is ``x + rms(sum, postnorm)``; a lone
    rank's layer is ``x + rms(its part, postnorm)``: the partial sum
    goes through the norm as it is (the configuration's
    ``deployment``)."""
    _, p, _ = make("routed_experts", [(2, 12, 8)], **dict(MOE, **SANDWICH))
    p = jiggled(with_bias(p, 8), 9, "norm", "postnorm")
    x = jnp.asarray(np.random.RandomState(10).randn(2, 12, 8), jnp.float32)
    whole = strs(MOE)
    with jax.default_matmul_precision("highest"):
        u = ref.rms_norm(x, p["norm"], 1e-5)
        ff = np.asarray(ref.routed_experts(p, u, whole), np.float64)
        none = dict(p, wmat=p["wmat"][:1] * 0, wproj=p["wproj"][:1] * 0)
        shared = np.asarray(ref.routed_experts(
            none, u, dict(whole, nheld="1")), np.float64)
        parts, pairs = [], 0
        for rank in range(8):
            share = dict(first_expert=4 * rank, nheld=4)
            # the FF part: the layer without its post norm and residual
            lay, _, _ = make("routed_experts", [(2, 12, 8)],
                             **dict(MOE, prenorm=1, **share))
            mine = dict(p, wmat=p["wmat"][4 * rank:4 * rank + 4],
                        wproj=p["wproj"][4 * rank:4 * rank + 4])
            (y,), st = lay.apply_stateful(
                mine, lay.init_aux([(2, 12, 8)]), [x])
            parts.append(np.asarray(y, np.float64))
            pairs += int(st["pairs"])
            # and the rank's whole layer: its part through the norm
            full, _, _ = make("routed_experts", [(2, 12, 8)],
                              **dict(MOE, **SANDWICH, **share))
            np.testing.assert_allclose(
                full.apply(mine, [x])[0],
                x + ref.rms_norm(y, p["postnorm"], 1e-5), atol=2e-5)
            np.testing.assert_allclose(
                full.apply(mine, [x])[0], x + ref.rms_norm(
                    ref.routed_experts(mine, u, dict(whole, **strs(share))),
                    p["postnorm"], 1e-5), atol=3e-5)
        total = sum(parts) - 7 * shared
        uncut = x + ref.rms_norm(jnp.asarray(ff, jnp.float32),
                                 p["postnorm"], 1e-5)
        summed = x + ref.rms_norm(jnp.asarray(total, jnp.float32),
                                  p["postnorm"], 1e-5)
        after = sum(np.asarray(ref.rms_norm(
            jnp.asarray(q, jnp.float32), p["postnorm"], 1e-5)) for q in parts)
    assert pairs == 24 * 4               # every pair on exactly one rank
    np.testing.assert_allclose(total, ff, atol=5e-5)
    np.testing.assert_allclose(summed, uncut, atol=5e-5)
    assert np.abs(shared).max() > 0.01 and np.abs(ff - shared).max() > 0.01
    # summing AFTER the norm is another function altogether
    assert np.abs(after - np.asarray(uncut - x)).max() > 0.5


def test_a_dropped_bias_changes_the_chosen_eight(ref):
    """At the published router (128 experts, top-8, hidden 2048) and
    the seed's weights, the bias drawn normal(0, 0.01) from the seed
    changes the chosen eight of more than a tenth of the tokens, and a
    layer that drops it computes another output: such a program is not
    ``correct``."""
    key = jax.random.PRNGKey(5)
    wgate = jax.random.normal(key, (128, 2048), jnp.float32) * 0.02
    bias = jax.random.normal(jax.random.fold_in(key, 1), (128,),
                             jnp.float32) * ref.BIAS_SIGMA
    x = jax.random.normal(jax.random.fold_in(key, 2), (512, 2048),
                          jnp.float32)               # a normed input
    cfg = strs(dict(nexpert=128, topk=8, nhidden=1024, score_func="sigmoid",
                    select_bias=1, routed_scale=2.826))
    p = {"wgate": wgate, "score_bias": bias}
    w, with_b = ref.router(p, x, cfg)
    _, without = ref.router(dict(p, score_bias=0 * bias), x, cfg)
    changed = (np.sort(np.asarray(with_b), axis=1)
               != np.sort(np.asarray(without), axis=1)).any(axis=1).mean()
    assert changed > 0.1, changed
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.826, rtol=1e-5)
    assert ref.BIAS_SIGMA == 0.01
    # the program's layer follows the bias it is given
    lay, q, _ = make("routed_experts", [(2, 12, 8)], **MOE)
    xs = jnp.asarray(np.random.RandomState(11).randn(2, 12, 8), jnp.float32)
    a = lay.apply(with_bias(q, 8), [xs])[0]
    b = lay.apply(q, [xs])[0]
    assert np.abs(np.asarray(a - b)).max() > 1e-3


# ----------------------------------------------------------------------
def test_attn_window_pairs_counts_what_a_windowed_query_sees():
    rows = np.array([[5, 6, 0, 7, 8, 9, 0, 3],      # 3, 4 and a cut 1
                     [0, 4, 4, 4, 4, 4, 4, 4],      # 1 and a cut 7
                     [2, 2, 2, 2, 2, 2, 2, 0]], np.uint16)   # one of 8
    # W = 3: L <= W gives L (L + 1) / 2, beyond it 6 + (L - 3) 3
    assert attn_pairs(rows, 3) == (6 + 9 + 1) + (1 + 18) + 21
    assert attn_pairs(rows, 8) == attn_pairs(rows) == 17 + 29 + 36
    assert attn_pairs(rows, 1) == rows.size
    one = np.ones((2, 16384), np.uint16)
    assert attn_pairs(one, 2048) == 2 * (2048 * 2049 // 2 + 14336 * 2048)
    r = np.random.RandomState(11)
    rows = r.randint(0, 9, (5, 200)).astype(np.uint16)
    slow = 0
    for row in rows:
        run = 0
        for tok in row:
            run += 1
            slow += min(run, 4)
            if tok == 0:
                run = 0
    assert attn_pairs(rows, 4) == slow


def test_the_feed_counts_the_windowed_pairs_only_where_it_is_told(tmp_path):
    from cxxnet_tpu.io.tokens import TokenIterator

    r = np.random.RandomState(1)
    raw = r.randint(1, 50, 4 * 32 + 1).astype("<u2")
    raw[r.rand(raw.size) < 0.1] = 0
    path = str(tmp_path / "tokens.bin")
    raw.tofile(path)

    def counters(**keys):
        it = TokenIterator()
        for k, v in dict(filename=path, seq_len=32, batch_size=2, silent=1,
                         **keys).items():
            it.set_param(k, str(v))
        it.init()
        it.before_first()
        before = dict(pipeline_stats().counters())
        while it.next():
            it.value()
        after = pipeline_stats().counters()
        return {k: after[k] - before.get(k, 0) for k in after
                if after[k] != before.get(k, 0)}

    plain = counters()
    assert "attn_window_pairs" not in plain and plain["attn_pairs"] > 0
    told = counters(attn_window=6)
    rows = raw[:128].reshape(4, 32)
    assert told["attn_pairs"] == attn_pairs(rows) == plain["attn_pairs"]
    assert told["attn_window_pairs"] == attn_pairs(rows, 6)
    assert told["attn_window_pairs"] < told["attn_pairs"]


# ----------------------------------------------------------------------
def test_the_layer_says_what_a_window_does_not_go_with():
    with pytest.raises(ValueError, match=r"window = 8 with decode = 1.*"
                       r"ROADMAP R3"):
        make("attention", [(1, 16, 32)], nhead=4, causal=1, window=8,
             decode=1, decode_window=16)
    for mode in ("ring", "alltoall"):
        with pytest.raises(ValueError, match=r"window = 8 with seq_parallel"
                           r".*ROADMAP R3"):
            make("attention", [(1, 16, 32)], nhead=4, causal=1, window=8,
                 seq_parallel=mode)
    with pytest.raises(ValueError, match="window=-2"):
        make("attention", [(1, 16, 32)], nhead=4, window=-2)
    with pytest.raises(ValueError, match="window"):
        make("attention", [(1, 16, 32)], nhead=4, window=8,
             attn_impl="pallas")
    # a window alone takes the masked path and its counters
    lay, p, _ = make("attention", [(1, 16, 32)], nhead=4, causal=1, window=8)
    assert not lay._plain(1) and set(lay.init_aux([(1, 16, 32)])) == {
        "attn_tokens", "attn_tokens_flash", "attn_blocks",
        "attn_blocks_unmasked", "attn_tokens_bwd_fused"}
    x = jnp.asarray(np.random.RandomState(2).randn(1, 16, 32), jnp.float32)
    (y,), aux = lay.apply_stateful(p, lay.init_aux([(1, 16, 32)]), [x])
    assert int(aux["attn_tokens"]) == 16 and int(
        aux["attn_tokens_flash"]) == 0 and int(aux["attn_blocks"]) == int(
            aux["attn_blocks_unmasked"]) == int(
                aux["attn_tokens_bwd_fused"]) == 0
    # and names its scope for the trace's readers
    hlo = jax.jit(lambda a: lay.apply(p, [a])[0]).lower(x).as_text(
        debug_info=True)
    assert "core_window" in hlo and "core_full" not in hlo
    full, pf, _ = make("attention", [(1, 16, 32)], nhead=4, nkvhead=2,
                       causal=1)
    hlo = jax.jit(lambda a: full.apply(pf, [a])[0]).lower(x).as_text(
        debug_info=True)
    assert "core_full" in hlo and "core_window" not in hlo
