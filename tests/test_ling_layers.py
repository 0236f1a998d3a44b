"""The layers a Ling-3.0-flash (``bailing_hybrid``) model forced (ISSUE
49), each against a plain statement of the same function at a small
size, float32, seeded weights: the delta rule with a decay a key channel
(``ops/kda.py``: the chunked ``jax.numpy`` form and the interpreted
kernels of ``ops/kda_fused.py`` against the token-by-token recurrence,
forward and gradients, with documents and at the gate's floor; with one
decay a head it is ``gated_delta_scan``); ``kimi_delta`` and
``latent_attention`` with ``q_rank = 0`` and ``out_gate = head`` against
the reference's; ``route`` with groups against a plain loop, its
defaults bit for bit the ungrouped path; the shares' sum against the
uncut reference layer; the builder's refusal of a swiglu clamp.  What
every family's tests share is a row of ``tests/families.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import families
from cxxnet_tpu.layers.moe import route
from cxxnet_tpu.models import bailing_hybrid_conf
from cxxnet_tpu.nnet.net import REMAT_POLICY
from cxxnet_tpu.ops.gdn import gated_delta_scan, unit_rows
from cxxnet_tpu.ops.kda import (BLOCK, kimi_delta_recurrence,
                                kimi_delta_scan, kimi_delta_scan_counted)
from cxxnet_tpu.ops.kda_fused import kimi_delta_fused, supported
from families import (expert_shares, held_against, make,
                      rows_with_documents, strs, through_cos, with_bias)

FAMILY = "bailing_hybrid"
FLOOR = -80.0 / BLOCK


# ----------------------------------------------------------------------
def delta_inputs(seed=0, n=2, t=150, h=3, dk=8, dv=6, floor=False,
                 dtype=jnp.float32):
    """Unit ``q`` and ``k``, a gate over its whole range ``(-5, 0)`` (or
    every entry AT the floor), two or more documents a row."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = unit_rows(jax.random.normal(ks[0], (n, t, h, dk))).astype(dtype)
    k = unit_rows(jax.random.normal(ks[1], (n, t, h, dk))).astype(dtype)
    v = jax.random.normal(ks[2], (n, t, h, dv)).astype(dtype)
    g = FLOOR * jax.nn.sigmoid(2 * jax.random.normal(ks[3], (n, t, h, dk)))
    if floor:
        g = jnp.full_like(g, FLOOR)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (n, t, h)))
    doc = jnp.cumsum(jax.random.bernoulli(ks[5], 0.02, (n, t)), axis=1
                     ).astype(jnp.int32)
    return (q, k, v, g, beta), doc


def fused(*a, **kw):
    return kimi_delta_fused(*a, interpret=True, **kw)


@pytest.mark.parametrize("form", ["chunked", "segments", "kernels"])
def test_the_delta_rule_with_a_decay_a_channel_is_the_recurrence(form):
    """Forward and the five gradients; 150 tokens are two chunks and a
    ragged end, the documents' edges fall inside chunks and blocks."""
    wide = form == "kernels"
    xs, doc = delta_inputs(t=150, **(dict(n=1, h=2, dk=128, dv=128)
                                     if wide else {}))
    scan = {"chunked": lambda *a: kimi_delta_scan(*a, doc),
            "segments": lambda *a: kimi_delta_scan(*a, doc, 64, 64),
            "kernels": lambda *a: fused(*a, doc)}[form]
    want = through_cos(lambda *a: kimi_delta_recurrence(*a, doc), xs)
    got = through_cos(scan, xs)
    for a, b, name in zip(got, want, ("o", "q", "k", "v", "g", "beta")):
        np.testing.assert_allclose(a, b, atol=5e-5 * max(
            1.0, float(jnp.abs(b).max())), err_msg=name)
        assert float(jnp.abs(b).max()) > 0, name


@pytest.mark.parametrize("form", ["chunked", "kernels"])
def test_at_the_gate_s_floor_every_factor_stays_inside_float32(form):
    """Every ``g`` at -5 for a whole chunk and more: ``e^{-G_s}`` alone
    would be ``e^{320}``; the blocks' references keep it to ``e^{80}``."""
    wide = form == "kernels"
    xs, doc = delta_inputs(1, t=128, floor=True,
                           **(dict(n=1, h=2, dk=128, dv=128) if wide else {}))
    doc = jnp.zeros_like(doc)                 # one document: nothing resets
    scan = ((lambda *a: fused(*a, doc)) if wide
            else (lambda *a: kimi_delta_scan(*a, doc)))
    want = through_cos(lambda *a: kimi_delta_recurrence(*a, doc), xs)
    got = through_cos(scan, xs)
    for a, b in zip(got, want):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, atol=2e-5 * max(
            1.0, float(jnp.abs(b).max())))


@pytest.mark.parametrize("form", ["chunked", "kernels"])
def test_one_decay_a_head_is_the_gated_delta_rule(form):
    wide = form == "kernels"
    (q, k, v, g, beta), doc = delta_inputs(
        2, **(dict(n=1, t=130, h=2, dk=128, dv=128) if wide else {}))
    one = g[..., 0]
    same = jnp.broadcast_to(one[..., None], g.shape)
    scan = fused if wide else kimi_delta_scan
    np.testing.assert_allclose(
        scan(q, k, v, same, beta, doc),
        gated_delta_scan(q, k, v, one, beta, doc), atol=2e-5)


def test_the_kernels_bring_q_and_k_to_unit_length_themselves():
    (_, _, v, g, beta), doc = delta_inputs(3, n=1, t=128, h=2, dk=128,
                                           dv=128, dtype=jnp.bfloat16)
    r = jax.random.split(jax.random.PRNGKey(4), 2)
    q, k = (jax.random.normal(a, v.shape).astype(jnp.bfloat16) for a in r)
    kw = dict(unit=1e-6, q_scale=128 ** -0.5)

    def plain(q, k, v, g, beta):
        q = (unit_rows(q, 1e-6) * kw["q_scale"]).astype(q.dtype)
        return kimi_delta_scan(q, unit_rows(k, 1e-6).astype(k.dtype), v, g,
                               beta, doc)

    want = through_cos(plain, (q, k, v, g, beta))
    got = through_cos(lambda *a: fused(*a, doc, **kw), (q, k, v, g, beta))
    for a, b, name in zip(got, want, ("o", "q", "k", "v", "g", "beta")):
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        assert np.abs(a - b).max() <= 0.03 * np.abs(b).max(), name


def test_the_platform_and_the_shapes_choose_the_path_and_say_so():
    (q, k, v, g, beta), doc = delta_inputs(5, n=1, t=64, h=2, dk=128, dv=128)
    assert supported(q, k, v, 64) and not supported(q, k, v, 32)
    assert not supported(q[..., :8], k[..., :8], v, 64)
    assert not supported(q[:, :, :1], k[:, :, :1], v, 64)   # heads one to one
    # on the CPU the jax.numpy form runs and says so
    o, ran = jax.jit(lambda *a: kimi_delta_scan_counted(*a, doc))(
        q, k, v, g, beta)
    assert int(ran) == 0
    np.testing.assert_allclose(o, kimi_delta_recurrence(q, k, v, g, beta,
                                                        doc), atol=2e-5)
    # lowered for a TPU the same call holds the three kernels
    text = jax.jit(jax.grad(lambda *a: jnp.sum(
        kimi_delta_scan_counted(*a, doc)[0]))).trace(
        q, k, v, g, beta).lower(lowering_platforms=("tpu",)).as_text()
    for name in ("kda_solve", "kda_scan", "kda_scan_bwd"):
        assert name in text, name
    with pytest.raises(ValueError, match="power of two"):
        kimi_delta_scan(q, k, v, g, beta, doc, chunk=48)


def test_a_layer_s_remat_runs_both_forward_kernels_again():
    """The kernels name nothing for the net's policy (memory:
    ``ops/kda_fused.py``): the recompute of a checkpointed scan runs
    ``kda_solve`` and ``kda_scan`` a second time, under the policy as
    without one — what ``kda_fwd_runs_per_bwd`` reads as 2."""
    (q, k, v, g, beta), doc = delta_inputs(6, n=1, t=64, h=2, dk=128, dv=128)

    def calls(**checkpoint):
        loss = lambda *a: jnp.sum(jax.checkpoint(  # noqa: E731
            lambda *b: fused(*b, doc), **checkpoint)(*a))
        text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(
            q, k, v, g, beta))
        return [text.count(f"name={n}\n") + text.count(f"name={n} ")
                for n in ("kda_solve", "kda_scan", "kda_scan_bwd")]

    assert calls(policy=REMAT_POLICY) == calls() == [2, 2, 1]


# ----------------------------------------------------------------------
KDA = dict(nhead=3, key_dim=8, value_dim=6, conv_width=4, lower_bound=-5.0,
           init_sigma=0.3)


def test_kimi_delta_is_the_reference_s(ref):
    """Forward and gradient with documents: no state, no convolution tap
    crosses from one to the next."""
    lay, p, out = make("kimi_delta", [(2, 40, 20), (2, 40)], **KDA)
    assert out == [(2, 40, 20)]
    assert {t: v.shape for t, v in p.items()} == {
        "wmat": (3 * 24 + 2 * 18, 20), "wbeta": (3, 20), "conv": (66, 4),
        "a_log": (3,), "dt_bias": (24,), "gate_norm": (6,),
        "wproj": (20, 18)}
    r = np.random.RandomState(1)
    # a gate that spans its range: the draws' own steps are near 0
    p = dict(p, dt_bias=jnp.asarray(r.randn(24), jnp.float32),
             gate_norm=jnp.asarray(1 + 0.1 * r.randn(6), jnp.float32))
    x = jnp.asarray(r.randn(2, 40, 20), jnp.float32)
    ids = jnp.asarray(rows_with_documents(2, 2, 40))
    scfg, int_ids = strs(KDA), ids.astype(jnp.int32)
    y, _, _ = held_against(
        lambda q, a: lay.apply(q, [a, ids])[0],
        lambda q, a: ref.kimi_delta(q, a, int_ids, scfg), p, x, list(p),
        atol=5e-4, y_atol=5e-5)        # gradients up to 11: 5e-5 of them
    with jax.default_matmul_precision("highest"):
        # a token of the second document does not see the first
        cut = x.at[:, :13].set(0.0)
        np.testing.assert_allclose(
            jax.jit(lambda q, a: lay.apply(q, [a, ids])[0])(p, cut)[:, 14:],
            y[:, 14:], atol=2e-5)    # (the chunk's running sum rounds)
    # the layer counts its tokens, none by the kernels on the CPU
    _, state = lay.apply_stateful(p, lay.init_aux([]), [x, ids])
    assert (int(state["scan_tokens"]), int(state["scan_tokens_fused"])) == (
        80, 0)
    assert lay.aux_counters == {"scan_tokens": "kda_scan_tokens",
                                "scan_tokens_fused": "kda_scan_tokens_fused"}


@pytest.mark.parametrize("cfg, match", [
    (dict(KDA, lower_bound=-6.0), "must lie in"),
    (dict(KDA, lower_bound=0.0), "must lie in"),
    (dict(KDA, chunk=8), "at least 16"),
    (dict(KDA, nkhead=2), "set nhead"),
    (dict(key_dim=8, value_dim=8), "set nhead, key_dim"),
])
def test_kimi_delta_refuses(cfg, match):
    with pytest.raises(ValueError, match=match):
        make("kimi_delta", [(1, 32, 20), (1, 32)], **cfg)


# ----------------------------------------------------------------------
MLA = dict(nhead=4, q_rank=0, kv_rank=16, nope_dim=8, rope_dim=4, v_dim=6,
           rope_theta=6000000.0, causal=1, out_gate="head", init_sigma=0.3)


def test_latent_attention_without_a_query_latent_and_gated_a_head(ref):
    lay, p, out = make("latent_attention", [(2, 24, 20), (2, 24)], **MLA)
    assert out == [(2, 24, 20)]
    assert {t: v.shape for t, v in p.items()} == {
        "wq": (48, 20), "wkva": (20, 20), "kv_norm": (16,),
        "wkvb": (56, 16), "wgate": (4, 20), "wproj": (20, 24)}
    r = np.random.RandomState(1)
    x = jnp.asarray(r.randn(2, 24, 20), jnp.float32)
    ids = jnp.asarray(rows_with_documents(2, 2, 24))
    scfg, int_ids = strs(MLA), ids.astype(jnp.int32)
    y, _, _ = held_against(
        lambda q, a: lay.apply(q, [a, ids])[0],
        lambda q, a: ref.latent_attention(q, a, int_ids, scfg), p, x,
        list(p), y_atol=2e-5)
    # the gate is a head's: closing one head's takes that head's part out
    shut = dict(p, wgate=p["wgate"].at[1].set(0.0))
    ungated, _, _ = make("latent_attention", [(2, 24, 20), (2, 24)],
                         **dict(MLA, out_gate="none"))
    free = {k: v for k, v in p.items() if k != "wgate"}
    with jax.default_matmul_precision("highest"):
        half = lay.apply(dict(shut, wgate=shut["wgate"] * 0), [x, ids])[0]
        np.testing.assert_allclose(
            half, 0.5 * ungated.apply(free, [x, ids])[0], atol=2e-5)
    # a tree that still brings the latent's leaves is not read in silence
    with pytest.raises(ValueError, match="the parameters bring q_norm"):
        lay.apply(dict(p, q_norm=jnp.ones((24,))), [x, ids])
    with pytest.raises(ValueError, match="none or head"):
        make("latent_attention", [(1, 12, 20)], **dict(MLA, out_gate="all"))
    with pytest.raises(ValueError, match="and q_rank"):
        make("latent_attention", [(1, 12, 20)],
             **{k: v for k, v in MLA.items() if k != "q_rank"})


# ----------------------------------------------------------------------
def plain_group_route(logits, bias, topk, n_group, topk_group, scale):
    """A token at a time, in float64 numpy."""
    s = 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))
    c = s + np.asarray(bias, np.float64)
    per = c.shape[1] // n_group
    ids, ws = [], []
    for srow, crow in zip(s, c):
        score = [np.sort(crow[j * per:(j + 1) * per])[-2:].sum()
                 for j in range(n_group)]
        kept = np.argsort(-np.asarray(score), kind="stable")[:topk_group]
        allowed = np.full(crow.shape, -np.inf)
        for j in kept:
            allowed[j * per:(j + 1) * per] = crow[j * per:(j + 1) * per]
        pick = np.argsort(-allowed, kind="stable")[:topk]
        ids.append(pick)
        ws.append(scale * srow[pick] / srow[pick].sum())
    return np.asarray(ws), np.asarray(ids)


def test_the_group_limited_choice_is_the_plain_loop_and_changes_the_eight():
    r = np.random.RandomState(5)
    logits = jnp.asarray(r.randn(96, 64), jnp.float32)
    bias = jnp.asarray(0.01 * r.randn(64), jnp.float32)
    kw = dict(score_func="sigmoid", bias=bias, scale=2.5)
    w, idx = route(logits, 8, True, n_group=8, topk_group=4, **kw)
    want_w, want_idx = plain_group_route(logits, bias, 8, 8, 4, 2.5)
    assert np.array_equal(np.asarray(idx), want_idx)
    np.testing.assert_allclose(w, want_w, rtol=1e-5)
    # every pick lies in one of at most 4 groups
    assert max(len(set(row // 8)) for row in np.asarray(idx)) <= 4
    # and the limit is no formality: it changes the chosen eight of a good
    # part of the tokens, so a program that dropped it is not correct
    _, free = route(logits, 8, True, **kw)
    changed = (np.sort(np.asarray(idx), 1) != np.sort(np.asarray(free), 1)
               ).any(axis=1).sum()
    assert changed > 10, changed
    # the weights' gradient reaches the logits, never the bias
    g = jax.grad(lambda lg, b: jnp.sum(jnp.sin(route(
        lg, 8, True, score_func="sigmoid", bias=b, scale=2.5, n_group=8,
        topk_group=4)[0])), argnums=(0, 1))(logits, bias)
    assert np.abs(np.asarray(g[1])).max() == 0 < np.abs(np.asarray(g[0])).max()


@pytest.mark.parametrize("kw", [
    dict(),                                            # softmax, qwen3_next's
    dict(score_func="sigmoid", scale=2.5, bias=True),  # JoyAI's: 256, top-8
])
def test_the_defaults_are_the_ungrouped_path_bit_for_bit(kw):
    r = np.random.RandomState(6)
    logits = jnp.asarray(r.randn(64, 256), jnp.float32)
    if kw.get("bias"):
        kw = dict(kw, bias=jnp.asarray(0.01 * r.randn(256), jnp.float32))
    w, idx = jax.jit(lambda lg: route(lg, 8, True, **kw))(logits)
    w1, idx1 = jax.jit(lambda lg: route(lg, 8, True, n_group=1, topk_group=1,
                                        **kw))(logits)
    assert np.array_equal(np.asarray(idx), np.asarray(idx1))
    assert np.array_equal(np.asarray(w), np.asarray(w1))
    # and the program of the default call names no group_limit
    text = str(jax.make_jaxpr(lambda lg: route(lg, 8, True, **kw))(logits))
    assert text == str(jax.make_jaxpr(lambda lg: route(
        lg, 8, True, n_group=1, topk_group=1, **kw))(logits))


MOE = dict(nexpert=32, topk=4, n_group=4, topk_group=2, nhidden=10,
           shared_hidden=6, shared_gate=0, score_func="sigmoid",
           select_bias=1, routed_scale=2.5, init_sigma=0.5)


def test_routed_experts_with_groups_is_the_reference_s(ref):
    lay, p, _ = make("routed_experts", [(2, 12, 8)], first_expert=0,
                     nheld=8, **MOE)
    p = with_bias(p, 6, 0.05)
    x = jnp.asarray(np.random.RandomState(7).randn(2, 12, 8), jnp.float32)
    scfg = strs(dict(MOE, first_expert=0, nheld=8))
    with jax.default_matmul_precision("highest"):
        (y,), state = jax.jit(lay.apply_stateful)(
            p, lay.init_aux([(2, 12, 8)]), [x])
        _, idx = ref.router(p, x.reshape(-1, 8), scfg)
        _, free = ref.router(p, x.reshape(-1, 8), dict(scfg, n_group="1"))
    _, want, _ = held_against(
        lambda q, a: lay.apply(q, [a])[0],
        lambda q, a: ref.routed_experts(q, a, scfg), p, x,
        ("wmat", "wproj", "shared_wmat", "shared_wproj"), y_atol=3e-5,
        zero=("wgate", "score_bias"))
    np.testing.assert_allclose(y, want, atol=3e-5)
    assert int(state["pairs"]) == (np.asarray(idx) < 8).sum() > 0
    # the reference's own limit changes some token's chosen four
    assert (np.sort(np.asarray(idx), 1) != np.sort(np.asarray(free), 1)).any()


def test_the_four_shares_add_up_to_the_uncut_reference_layer(ref):
    """model-configs section 4: 32 experts over 4 ranks of 8 (a group
    each, as the cell's rank holds group 0); every rank routes over all
    32 with the group limit and adds its own experts' terms and the
    shared expert; the parts, the shared expert counted once, are what
    the uncut reference gives."""
    _, p, _ = make("routed_experts", [(2, 12, 8)], **MOE)
    p = with_bias(p, 6, 0.05)
    x = jnp.asarray(np.random.RandomState(8).randn(2, 12, 8), jnp.float32)
    whole = strs(MOE)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.routed_experts(p, x, whole), np.float64)
        none = dict(p, wmat=p["wmat"][:1] * 0, wproj=p["wproj"][:1] * 0)
        shared = np.asarray(ref.routed_experts(
            none, x, dict(whole, nheld="1")), np.float64)
        parts, pairs = expert_shares(MOE, p, x, 4, 8)
    assert pairs == 24 * 4               # every pair on exactly one rank
    np.testing.assert_allclose(sum(parts) - 3 * shared, want, atol=5e-5)


@pytest.mark.parametrize("cfg", [
    dict(n_group=3), dict(n_group=4, topk_group=5),
    dict(n_group=8, topk_group=1, topk=8), dict(n_group=0)])
def test_routed_experts_refuses_groups_that_cannot_hold_the_choice(cfg):
    with pytest.raises(ValueError, match="n_group=.* must divide"):
        make("routed_experts", [(4, 8)], **dict(MOE, **cfg))


def test_a_swiglu_clamp_is_refused_not_dropped():
    tiny = families.FAMILIES[FAMILY].tiny
    zeros = bailing_hybrid_conf(**dict(tiny, expert_swiglu_limits=(0, 0, 0),
                                       shared_swiglu_limits=(0, 0, 0)))
    assert zeros == bailing_hybrid_conf(**tiny)
    for key in ("expert_swiglu_limits", "shared_swiglu_limits"):
        with pytest.raises(ValueError, match="a non-zero swiglu limit"):
            bailing_hybrid_conf(**dict(tiny, **{key: (0, 0, 4)}))
    with pytest.raises(ValueError, match="layer_group_size"):
        bailing_hybrid_conf(**dict(tiny, layer_group_size=0))
