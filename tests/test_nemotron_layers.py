"""The layers a Nemotron-H model forced (ISSUE 40), each against a plain
statement of the same function at a small size, float32, seeded
weights: the scan and ``mamba2`` with groups of ``B`` / ``C`` and a gated
norm a group; ``routed_experts`` with ungated ``relu(.)^2`` experts in a
latent behind two projections; for each of the three kinds of layer,
the sum of the shares a tensor- and expert-parallel deployment's ranks
hold against the uncut reference layer; the programs of the accepted
cells' layers (one group, gated experts in the stream's width) held to
what they were before; the shipped example and the CLI.  What every
family's tests share (the builder's conf through the trainer, the
published defaults) is a row of ``tests/families.py``.
"""

import hashlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import families
from cxxnet_tpu.layers import create_layer
from cxxnet_tpu.models import nemotron_h_conf
from cxxnet_tpu.ops.ssd import doc_index, ssd_recurrence, ssd_scan
from families import (held_against, make, rows_with_documents, strs,
                      with_bias)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


FAMILY = "nemotron_h"


# ----------------------------------------------------------------------
MIX = dict(nhead=4, head_dim=6, nstate=5, ngroup=2, conv_width=4, chunk=8,
           init_sigma=0.3)


@pytest.mark.parametrize("chunk", [8, 7])
def test_the_grouped_scan_is_the_recurrence_a_head_reading_its_group(chunk):
    r = np.random.RandomState(1)
    n, t, h, p, g, s = 2, 21, 6, 4, 3, 5
    x = jnp.asarray(r.randn(n, t, h, p), jnp.float32)
    dt = jnp.asarray(np.abs(r.randn(n, t, h)) * 0.3 + 0.01, jnp.float32)
    a = -jnp.asarray(np.abs(r.randn(h)) + 0.2, jnp.float32)
    b = jnp.asarray(r.randn(n, t, g, s), jnp.float32)
    c = jnp.asarray(r.randn(n, t, g, s), jnp.float32)
    doc = doc_index(jnp.asarray(rows_with_documents(2, n, t)))

    def both(fn):
        """Value and all five gradients, compiled once."""
        return jax.jit(lambda *v: (fn(*v), jax.grad(
            lambda *v: jnp.sum(jnp.sin(fn(*v))), argnums=range(5))(*v)))

    chunked = lambda *v: ssd_scan(*v, doc, chunk)  # noqa: E731
    stepwise = lambda *v: ssd_recurrence(*v, doc)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        y, ga = both(chunked)(x, dt, a, b, c)
        want, gb = both(stepwise)(x, dt, a, b, c)
        # head 2 of 6 in 3 groups reads group 1, and no other
        moved, _ = both(chunked)(x, dt, a, b.at[:, :, 1].add(1.0), c)
    np.testing.assert_allclose(y, want, atol=2e-5)
    for got, want in zip(ga, gb):
        np.testing.assert_allclose(got, want, atol=1e-4)
    same = np.isclose(np.asarray(moved), np.asarray(y),
                      atol=1e-6).all(axis=(0, 1, 3))
    assert list(same) == [True, True, False, False, True, True]


def test_mamba2_with_groups_is_the_reference_s(ref):
    shapes = [(2, 24, 10), (2, 24)]
    lay, p, out = make("mamba2", shapes, **MIX)
    e, gs = 4 * 6, 2 * 5
    assert out == [(2, 24, 10)]
    assert {t: v.shape for t, v in p.items()} == {
        "wmat": (2 * e + 2 * gs + 4, 10), "conv": (e + 2 * gs, 4),
        "conv_bias": (e + 2 * gs,), "dt_bias": (4,), "a_log": (4,),
        "d": (4,), "gate_norm": (e,), "wproj": (10, e)}
    r = np.random.RandomState(3)
    p = dict(p, gate_norm=jnp.asarray(1 + 0.3 * r.randn(e), jnp.float32),
             conv_bias=jnp.asarray(0.1 * r.randn(e + 2 * gs), jnp.float32))
    x = jnp.asarray(r.randn(2, 24, 10), jnp.float32)
    ids = jnp.asarray(rows_with_documents(4, 2, 24))
    cfg = strs(MIX)
    held_against(lambda q, a: lay.apply(q, [a, ids])[0],
                 lambda q, a: ref.mamba2(q, a, ids.astype(jnp.int32), cfg),
                 p, x, p, y_atol=3e-5)
    with pytest.raises(ValueError, match="ngroup"):
        make("mamba2", shapes, **dict(MIX, ngroup=3))


# ----------------------------------------------------------------------
LAT = dict(nexpert=16, topk=3, nhidden=10, latent_hidden=6, shared_hidden=12,
           expert_act="relu2", shared_gate=0, score_func="sigmoid",
           select_bias=1, routed_scale=5.0, init_sigma=0.3)


@pytest.mark.parametrize("first,held", [(0, 16), (4, 4)])
def test_latent_relu2_experts_are_the_reference_s(ref, first, held):
    shapes = [(2, 12, 8)]
    cfg = dict(LAT, first_expert=first, nheld=held)
    lay, p, _ = make("routed_experts", shapes, **cfg)
    assert {t: v.shape for t, v in p.items()} == {
        "wgate": (16, 8), "wmat": (held, 6, 10), "wproj": (held, 10, 6),
        "shared_wmat": (12, 8), "shared_wproj": (8, 12),
        "score_bias": (16,), "latent_in": (6, 8), "latent_out": (8, 6)}
    p = with_bias(p, 5, 0.05)
    x = jnp.asarray(np.random.RandomState(7).randn(2, 12, 8), jnp.float32)
    with jax.default_matmul_precision("highest"):
        (y,), state = jax.jit(lay.apply_stateful)(p, lay.init_aux(shapes),
                                                  [x])
        _, idx = ref.router(p, x.reshape(-1, 8), strs(cfg))
    # a share's router gets no gradient; a whole layer's does
    tags = ["wmat", "wproj", "shared_wmat", "shared_wproj", "latent_in",
            "latent_out"]
    still = ["score_bias"]
    (tags if held == 16 else still).append("wgate")
    _, want, _ = held_against(
        lambda q, a: lay.apply(q, [a])[0],
        lambda q, a: ref.routed_experts(q, a, strs(cfg)), p, x, tags,
        y_atol=3e-5, zero=still)
    np.testing.assert_allclose(y, want, atol=3e-5)
    idx = np.asarray(idx)
    assert int(state["pairs"]) == (
        (idx >= first) & (idx < first + held)).sum() > 0


def test_an_expert_s_activation_and_its_latent_are_two_keys():
    """Each alone: ``relu2`` in the stream's width, gated experts in a
    latent; and what neither key may be."""
    shapes = [(2, 12, 8)]
    _, p, _ = make("routed_experts", shapes, nexpert=8, topk=2, nhidden=10,
                   expert_act="relu2", shared_hidden=4)
    assert p["wmat"].shape == (8, 8, 10) and "latent_in" not in p
    assert p["shared_wmat"].shape == (4, 8) and "shared_gate" in p
    _, p, _ = make("routed_experts", shapes, nexpert=8, topk=2, nhidden=10,
                   latent_hidden=6)
    assert p["wmat"].shape == (8, 6, 20) and p["wproj"].shape == (8, 10, 6)
    with pytest.raises(ValueError, match="swiglu, reglu or relu2"):
        make("routed_experts", shapes, nexpert=8, topk=2, nhidden=10,
             expert_act="gelu")
    with pytest.raises(ValueError, match="latent_hidden"):
        make("routed_experts", shapes, nexpert=8, topk=2, nhidden=10,
             latent_hidden=-1)


# ----------------------------------------------------------------------
def mixer_share(p, rank, h, hp, g, s):
    """Rank ``rank`` of ``g`` of a mixer's parameters: group ``rank``,
    its ``h / g`` heads' columns of z, x and dt, their rows of the out
    projection, their part of the gated norm; the pre-norm whole."""
    e, eg, hg = h * hp, h * hp // g, h // g
    cols = np.r_[rank * eg:(rank + 1) * eg]
    grp = np.r_[rank * s:(rank + 1) * s]
    heads = np.r_[rank * hg:(rank + 1) * hg]
    xbc = np.concatenate([cols, e + grp, e + g * s + grp])
    rows = np.concatenate([cols, e + xbc, 2 * e + 2 * g * s + heads])
    out = dict(p, wmat=p["wmat"][rows], conv=p["conv"][xbc],
               conv_bias=p["conv_bias"][xbc], gate_norm=p["gate_norm"][cols],
               wproj=p["wproj"][:, cols])
    out.update({t: p[t][heads] for t in ("dt_bias", "a_log", "d")})
    return out


def attention_share(p, rank, ranks, h, hk, dh):
    """Rank ``rank`` of ``ranks``: its ``h / ranks`` query heads, the
    key/value head they read (held by ``ranks / hk`` ranks alike), and
    its heads' columns of the out projection."""
    hq = h // ranks
    q = np.r_[rank * hq * dh:(rank + 1) * hq * dh]
    kv = rank * hq // (h // hk)
    one = np.r_[kv * dh:(kv + 1) * dh]
    rows = np.concatenate([q, h * dh + one, (h + hk) * dh + one])
    return dict(p, wmat=p["wmat"][rows], wproj=p["wproj"][:, q])


def experts_share(p, rank, held, cols=None):
    """Rank ``rank``: ``held`` experts; router, bias, norm and latent
    projections whole; the shared expert whole too (what the cell's
    deployment does: a sum over ranks counts it once), or ``cols`` of
    its columns (how Megatron would divide it)."""
    ex = np.r_[rank * held:(rank + 1) * held]
    out = dict(p, wmat=p["wmat"][ex], wproj=p["wproj"][ex])
    if cols is not None:
        sc = np.r_[rank * cols:(rank + 1) * cols]
        out.update(shared_wmat=p["shared_wmat"][sc],
                   shared_wproj=p["shared_wproj"][:, sc])
    return out


BRANCH = dict(prenorm=1, residual_scale=1.0, eps=1e-5)


@pytest.mark.parametrize("kind", ["M", "*", "E", "E_columns"])
def test_the_ranks_shares_add_up_to_the_uncut_reference_layer(ref, kind):
    """model-configs section 4, for each kind of layer of the pattern:
    the branches that the ranks of a tensor- (and expert-) parallel
    group compute from the heads, columns and experts they hold — THE
    PROGRAM'S layer at the share's own keys, as the cell's conf spells
    it — add up to the branch the uncut reference layer gives; the
    norm, the router and the latent projections are every rank's alike
    and counted once, being inside each branch's own linear map.  ``E``
    is the cell's deployment: every rank holds the shared expert WHOLE
    and the sum counts it once; ``E_columns`` divides its columns over
    the ranks, which the layer can do and no cell does."""
    r = np.random.RandomState(11)
    x = jnp.asarray(r.randn(2, 24, 16), jnp.float32)
    ids = jnp.asarray(rows_with_documents(12, 2, 24))
    shapes = [(2, 24, 16), (2, 24)]
    if kind == "M":    # 2 groups x 2 heads over 2 ranks
        whole = dict(MIX, **BRANCH)
        share = dict(whole, nhead=2, ngroup=1)
        name, ranks, ins = "mamba2", 2, [x, ids]
        cut = lambda p, k: mixer_share(p, k, 4, 6, 2, 5)  # noqa: E731
        fn = lambda p: ref.mamba2(p, u(p), ids.astype(jnp.int32),  # noqa
                                  strs(whole))
    elif kind == "*":  # 8 query heads on 2 key/value heads over 4 ranks
        whole = dict(nhead=8, nkvhead=2, head_dim=6, causal=1, no_bias=1,
                     init_sigma=0.3, **BRANCH)
        share = dict(whole, nhead=2, nkvhead=1)
        name, ranks, ins = "attention", 4, [x, ids]
        cut = lambda p, k: attention_share(p, k, 4, 8, 2, 6)  # noqa: E731
        fn = lambda p: ref.attention(p, u(p), ids.astype(jnp.int32),  # noqa
                                     strs(whole))
    else:              # 16 experts (and 12 shared columns) over 4 ranks
        cols = 3 if kind == "E_columns" else None
        whole = dict(LAT, **BRANCH)
        share = dict(whole, nheld=4, shared_hidden=cols or 12)
        name, ranks, ins, shapes = "routed_experts", 4, [x], shapes[:1]
        cut = lambda p, k: experts_share(p, k, 4, cols)  # noqa: E731
        fn = lambda p: ref.routed_experts(p, u(p), strs(whole))  # noqa

    def u(p):
        return ref.rms_norm(x, p["norm"], 1e-5)

    _, p, _ = make(name, shapes, seed=13, **whole)
    p = dict(p, norm=jnp.asarray(1 + 0.2 * r.randn(16), jnp.float32))
    if kind[0] == "E":
        p = with_bias(p, 5, 0.05)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(fn(p), np.float64)
        parts = []
        for rank in range(ranks):
            keys = dict(share, first_expert=4 * rank) if kind[0] == "E" \
                else share
            lay, mine, _ = make(name, shapes, **keys)
            got = cut(p, rank)
            assert {t: v.shape for t, v in got.items()} == {
                t: v.shape for t, v in mine.items()}
            (y,) = lay.apply(got, ins)
            parts.append(np.asarray(y - x, np.float64))  # the branch
        once = 0.0
        if kind == "E":    # what every rank computes alike, counted once
            shared = ref.relu2(u(p) @ p["shared_wmat"].T) \
                @ p["shared_wproj"].T
            once = (ranks - 1) * np.asarray(shared, np.float64)
            assert np.abs(once).max() > 1e-3
    np.testing.assert_allclose(sum(parts) - once, want, atol=5e-5)
    # every rank's part is needed: none is zero, none is the whole
    assert all(np.abs(part).max() > 1e-3 for part in parts)
    assert all(np.abs(part - want).max() > 1e-3 for part in parts)


# ----------------------------------------------------------------------
#: The layers the accepted cells run — granite's mixer, qwen3_next's and
#: JoyAI's expert shares, a whole expert layer — against the commit
#: BEFORE groups, the latent and relu2 came (2b18f56, PR 39), two ways.
#: Both columns were taken from a ``git archive`` of THAT commit (``python
#: -c`` with ``PYTHONPATH`` on the archive, jax 0.9.0 on the CPU), never
#: from the tree under test; a PR that changes one of these programs on
#: purpose records on ITS parent's archive and says so.
#:
#: * the numbers: value and gradients on seeded inputs — per leaf the
#:   norm and one seeded projection (``layer_numbers``); on the machine
#:   that recorded them this tree gives the parent's bytes, and the test
#:   holds any machine to a few float32 roundings;
#: * the program: sha256 of the jaxpr text (addresses struck out) of
#:   value and gradient, which says "the same program to the character"
#:   and is only comparable under the jax that printed it.
UNCHANGED = {
    "mamba2_one_group": (
        "mamba2", [(2, 48, 32), (2, 48)],
        dict(nhead=4, head_dim=8, nstate=16, chunk=16, prenorm=1,
             residual_scale=0.22),
        "2b7f527dbe65d8c48ba9298609d635df689873bdda7a36508033ac4772997f97"),
    "routed_experts_softmax_share": (
        "routed_experts", [(2, 64, 32)],
        dict(nexpert=16, topk=3, nhidden=24, first_expert=4, nheld=4,
             shared_hidden=24, prenorm=1, residual_scale=1.0),
        "11dabe512f2fdd574fc1c1b0cee949fb21bc88497b0fa359a227a2ecce47bf36"),
    "routed_experts_sigmoid_bias_share": (
        "routed_experts", [(2, 64, 32)],
        dict(nexpert=16, topk=3, nhidden=24, first_expert=0, nheld=4,
             shared_hidden=24, shared_gate=0, score_func="sigmoid",
             select_bias=1, routed_scale=2.5, prenorm=1,
             residual_scale=1.0),
        "5949ac647f734f6386dd3e27e45e493dd31a00e9ae5b3bcca7e4d0fe32f80a7f"),
    "routed_experts_whole": (
        "routed_experts", [(2, 64, 32)],
        dict(nexpert=8, topk=2, nhidden=24),
        "c6f1d38d0fa8ef2e2dfef610ac29512f580981ded7a2f491d102ea3501c3a9e4"),
}
JAX_OF_THE_DIGESTS = "0.9.0"


def _accepted_layer(case):
    kind, shapes, cfg, digest = UNCHANGED[case]
    lay = create_layer(kind)
    for k, v in cfg.items():
        lay.set_param(k, str(v))
    lay.infer_shape(shapes)
    return lay, shapes, digest


@pytest.mark.skipif(
    jax.__version__ != JAX_OF_THE_DIGESTS,
    reason="a jaxpr's text is comparable only under the jax that printed "
           "it; the numbers test beside this one holds under any")
@pytest.mark.parametrize("case", sorted(UNCHANGED))
def test_the_accepted_cells_layers_are_the_programs_they_were(case):
    lay, shapes, want = _accepted_layer(case)
    p = jax.eval_shape(lambda k: lay.init_params(k, shapes),
                       jax.random.PRNGKey(0))
    xs = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    text = str(jax.make_jaxpr(jax.value_and_grad(
        lambda q, *a: jnp.sum(lay.apply(q, list(a))[0]),
        argnums=(0, 1)))(p, *xs))
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    assert hashlib.sha256(text.encode()).hexdigest() == want


# -- layer_numbers: copied out and run on the parent's archive as it is
def layer_numbers(lay, shapes):
    """Value and gradients of ``sum(layer(x) * w)`` at the layer's own
    seeded start and seeded ``x``, ``w`` (documents inside every row):
    ``{"value": v, leaf or "dx": [norm, projection on a seeded
    direction of unit norm]}``."""
    p = lay.init_params(jax.random.PRNGKey(0), shapes)
    r = np.random.RandomState(7)
    x = jnp.asarray(r.randn(*shapes[0]), jnp.float32)
    more = []
    if len(shapes) > 1:
        ids = r.randint(1, 50, shapes[1])
        ids[:, shapes[1][1] // 3] = 0
        more = [jnp.asarray(ids, jnp.float32)]
    w = jnp.asarray(r.randn(*shapes[0]), jnp.float32)
    v, (gp, gx) = jax.jit(jax.value_and_grad(
        lambda q, a: jnp.sum(lay.apply(q, [a] + more)[0] * w),
        argnums=(0, 1)))(p, x)
    out = {"value": float(v)}
    for t, g in sorted(dict(gp, dx=gx).items()):
        g = np.asarray(g, np.float64)
        d = r.randn(*g.shape)
        out[t] = [float(np.linalg.norm(g)),
                  float(np.sum(g * d) / np.linalg.norm(d))]
    return out
# -- end of layer_numbers


#: ``layer_numbers`` of each case on the archive of 2b18f56
PARENT_NUMBERS = {'mamba2_one_group': {'a_log': [5.21218468711711e-06,
                                6.803700151033642e-07],
                      'conv': [0.3624754702618451, -0.008744700521581363],
                      'conv_bias': [2.765161913852966, 0.20947553986957368],
                      'd': [0.0794346075263529, -0.018409429317659744],
                      'dt_bias': [4.188454506943848e-06,
                                  2.232381065361198e-06],
                      'dx': [54.52862738985375, 0.048936326372837124],
                      'gate_norm': [0.11097237573544813,
                                    0.01886244207172666],
                      'norm': [0.15110353675668303, 0.008154084946847339],
                      'value': -1.099144697189331,
                      'wmat': [14.315507557791097, 0.0734215788133954],
                      'wproj': [9.845915906337726, -0.11554880552526828]},
 'routed_experts_sigmoid_bias_share': {'dx': [63.76081747674471,
                                              -0.43872187798226425],
                                       'norm': [0.00824276339931436,
                                                0.0006751115990970104],
                                       'score_bias': [0.0, 0.0],
                                       'shared_wmat': [0.7009466832962479,
                                                       0.016804953737547316],
                                       'shared_wproj': [0.4715239565946366,
                                                        0.012509550051154048],
                                       'value': 128.49005126953125,
                                       'wgate': [0.0, 0.0],
                                       'wmat': [0.5515568421047979,
                                                0.007620976678754551],
                                       'wproj': [0.40245399193649534,
                                                 0.004483306186534956]},
 'routed_experts_softmax_share': {'dx': [63.761098889935006,
                                         -0.4389202061002374],
                                  'norm': [0.003954695254735315,
                                           0.0005698640321067231],
                                  'shared_gate': [0.0075574750162549255,
                                                  0.001917979876161955],
                                  'shared_wmat': [0.3503841232776463,
                                                  0.009077774292975183],
                                  'shared_wproj': [0.23603817836875896,
                                                   -0.008538502385148961],
                                  'value': 128.4859161376953,
                                  'wgate': [0.0, 0.0],
                                  'wmat': [0.20783815332114308,
                                           -0.0022286990626770174],
                                  'wproj': [0.14657692291048335,
                                            0.0006583893101578254]},
 'routed_experts_whole': {'dx': [0.005085546724390816,
                                 3.966857282937288e-05],
                          'value': 0.003455840051174164,
                          'wgate': [0.015366554208465775,
                                    -0.0008831614124196279],
                          'wmat': [0.5090091656791987,
                                   -0.004748050038113576],
                          'wproj': [0.3619901704540733,
                                    -0.001518143698266815]}}


@pytest.mark.parametrize("case", sorted(UNCHANGED))
def test_the_accepted_cells_layers_give_the_numbers_they_gave(case):
    """Same start, same inputs, the parent's value and gradients: every
    leaf's norm to 1e-4 of itself and its projection to 1e-4 of the
    norm (float32 sums in another order on another machine, no more;
    on the machine that recorded them the bytes are the parent's)."""
    lay, shapes, _ = _accepted_layer(case)
    got, want = layer_numbers(lay, shapes), PARENT_NUMBERS[case]
    assert sorted(got) == sorted(want)
    assert got["value"] == pytest.approx(want["value"], rel=1e-4)
    for t in sorted(set(want) - {"value"}):
        norm, proj = want[t]
        assert got[t][0] == pytest.approx(norm, rel=1e-4, abs=1e-12), t
        assert abs(got[t][1] - proj) <= 1e-4 * norm + 1e-12, t


# ----------------------------------------------------------------------
EXAMPLE = dict(vocab=512, seq_len=256, hidden=128, pattern="MEM*E",
               mamba_heads=8, mamba_head_dim=32, mamba_groups=2,
               mamba_state=32, mamba_chunk=64, attn_heads=4, attn_kv_heads=2,
               head_dim=32, num_experts=16, experts_per_tok=4,
               expert_hidden=96, latent_hidden=64, shared_hidden=192,
               experts_held=16, num_nextn_predict_layers=1, batch_size=8,
               token_file="tokens.bin", eta=0.001)


def test_the_shipped_example_is_the_builder_s_and_the_cli_trains_it(
        tmp_path):
    """``example/nemotron_h/nemotron_h_small.conf`` is what the builder
    writes at the arguments its header names, and ``python -m
    cxxnet_tpu`` trains what the builder writes on the CPU from a seeded
    token file — at the small widths of ``tests/families.py``, a round
    of three chunks: the same CLI, iterator, round loop, ``update_scan``
    and adam as the benchmark's cell."""
    from conftest import run_cli

    path = os.path.join(ROOT, "example", "nemotron_h",
                        "nemotron_h_small.conf")
    with open(path) as f:
        shipped = f.read()
    body = "".join(line for line in shipped.splitlines(True)
                   if not line.startswith("#"))
    assert body == nemotron_h_conf(**EXAMPLE)
    small = dict(families.NEMOTRON_H, token_file="tokens.bin", eta=0.001)
    (tmp_path / "small.conf").write_text(nemotron_h_conf(**small))
    r = np.random.RandomState(0)
    # three chunks of 2 steps x 2 rows x 32 tokens, and the last label
    ids = r.randint(1, 64, 3 * 2 * 2 * 32 + 1).astype("<u2")
    ids[r.rand(ids.size) < 1 / 20] = 0
    ids.tofile(str(tmp_path / "tokens.bin"))
    out = run_cli(["small.conf", "num_round=1", "max_round=1",
                   "save_model=0", f"model_dir={tmp_path}/models"],
                  str(tmp_path))
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    losses = [float(v) for v in re.findall(r"train-logloss:([0-9.]+)",
                                           out.stdout + out.stderr)]
    assert "update round 0" in out.stdout + out.stderr
    assert all(np.isfinite(v) for v in losses)
