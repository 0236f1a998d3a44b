"""The layers a Qwen3-Next style model forced (ISSUE 33), each against
a plain statement of the same function at a small size: the chunked
gated delta rule (``ops/gdn.py``) and its gradient against the
token-by-token recurrence; ``attention``'s rotary positions, q/k norms,
output gate and free head width against a plain attention;
``routed_experts`` against a dense masked loop and against the sum of
its shares; the untied ``lm_head``; the scan's counters.  What every
family's tests share (the builder's conf through the trainer, the
published defaults) is a row of ``tests/families.py``.
"""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import families
from cxxnet_tpu.layers.moe import held_experts, route
from cxxnet_tpu.models import qwen3_next_conf
from families import expert_shares, make, through_cos
from cxxnet_tpu.ops.attention import doc_positions, rotary
from cxxnet_tpu.ops.gdn import (gated_delta_recurrence, gated_delta_scan,
                                gated_delta_scan_counted, gated_delta_xla,
                                unit_rows)
from cxxnet_tpu.ops.gdn_fused import KEPT_NAMES, gated_delta_fused, supported
from cxxnet_tpu.ops.ssd import doc_index
from cxxnet_tpu.utils.profiler import pipeline_stats


# ----------------------------------------------------------------------
# the gated delta rule
def delta_inputs(seed=0, n=2, t=50, h=3, dk=8, dv=6):
    r = np.random.RandomState(seed)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)  # noqa
    q = unit(r.randn(n, t, h, dk)).astype(np.float32)
    k = unit(r.randn(n, t, h, dk)).astype(np.float32)
    v = r.randn(n, t, h, dv).astype(np.float32)
    g = (-0.3 * np.abs(r.randn(n, t, h))).astype(np.float32)
    beta = (1 / (1 + np.exp(-r.randn(n, t, h)))).astype(np.float32)
    # documents that start inside a chunk, whatever the chunk
    doc = np.cumsum(r.rand(n, t) < 0.12, axis=1).astype(np.int32)
    return (q, k, v, g, beta), doc


@pytest.mark.parametrize("chunk", [8, 16])
def test_chunked_delta_rule_is_the_recurrence(chunk):
    xs, doc = delta_inputs()
    assert (np.diff(doc, axis=1)[:, : 3 * chunk] > 0).any()
    with jax.default_matmul_precision("highest"):
        got = gated_delta_scan(*xs, doc, chunk)
        want = gated_delta_recurrence(*xs, doc)
    assert got.shape == want.shape == (2, 50, 3, 6)
    np.testing.assert_allclose(got, want, atol=2e-6)
    # and the first token of a document sees no state: its output is
    # beta (k . q) v alone
    q, k, v, _, beta = xs
    n, t = np.nonzero(np.diff(doc, axis=1, prepend=-1))
    alone = (beta[n, t] * (q[n, t] * k[n, t]).sum(-1))[..., None] * v[n, t]
    np.testing.assert_allclose(np.asarray(got)[n, t], alone, atol=2e-6)


@pytest.mark.parametrize("chunk", [8, 16])
def test_chunked_delta_rule_gradient_is_the_recurrence_s(chunk):
    xs, doc = delta_inputs(seed=1)

    def through(fn):
        return jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                                argnums=(0, 1, 2, 3, 4)))(*xs)

    with jax.default_matmul_precision("highest"):
        got = through(lambda *a: gated_delta_scan(*a, doc, chunk))
        want = through(lambda *a: gated_delta_recurrence(*a, doc))
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-6)


def test_one_row_is_one_document_and_a_ragged_end_is_padded():
    xs, _ = delta_inputs(seed=2, t=37)
    with jax.default_matmul_precision("highest"):
        got = gated_delta_scan(*xs, None, 16)
        want = gated_delta_recurrence(*xs, None)
    np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("chunk,segment", [(8, 16), (8, 24), (16, 32)])
def test_checkpointed_segments_carry_the_state_between_them(chunk, segment):
    """Documents that cross a segment's edge, and one that starts on it."""
    xs, doc = delta_inputs(seed=3)
    doc = np.asarray(doc).copy()
    doc[0, segment:] += 1                # row 0: a document starts there
    with jax.default_matmul_precision("highest"):
        got = gated_delta_scan(*xs, doc, chunk, segment)
        want = gated_delta_recurrence(*xs, doc)
        ga = jax.jit(jax.grad(lambda v: jnp.sum(jnp.sin(gated_delta_scan(
            xs[0], xs[1], v, xs[3], xs[4], doc, chunk, segment)))))(xs[2])
        gb = jax.jit(jax.grad(lambda v: jnp.sum(jnp.sin(
            gated_delta_recurrence(xs[0], xs[1], v, xs[3], xs[4], doc)))))(
            xs[2])
    np.testing.assert_allclose(got, want, atol=2e-6)
    np.testing.assert_allclose(ga, gb, atol=5e-6)
    with pytest.raises(ValueError, match="multiple of chunk"):
        gated_delta_scan(*xs, doc, chunk, chunk + 1)


# ----------------------------------------------------------------------
# the fused kernels (ops/gdn_fused.py), on the CPU's interpreter, against
# the token-by-token recurrence and the jax.numpy chunked form
def fused_inputs(case, dtype):
    """Heads of 128 x 128, the width the kernels are written for."""
    r = np.random.RandomState(5)
    n, t, hk, hv = {"documents": (1, 640, 1, 2), "ragged": (2, 150, 2, 2)}[
        case]
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)  # noqa
    q = unit(r.randn(n, t, hk, 128)) / math.sqrt(128)
    k = unit(r.randn(n, t, hk, 128))
    v = r.randn(n, t, hv, 128)
    g = -0.3 * np.abs(r.randn(n, t, hv))
    beta = 1 / (1 + np.exp(-r.randn(n, t, hv)))
    doc = None
    if case == "documents":
        # a boundary inside a chunk, two at chunks' edges, a document
        # longer than a stretch of 8 chunks (it crosses the stretch's
        # edge at 512), one more inside the last chunk
        doc = np.zeros((n, t), np.int32)
        for start in (30, 64, 128, 128 + 530 - 40, 630):
            doc[:, start:] += 1
    xs = tuple(jnp.asarray(a, jnp.float32).astype(dtype) for a in (q, k, v))
    return xs + (jnp.asarray(g, jnp.float32),
                 jnp.asarray(beta, jnp.float32)), doc


def assert_near_the_recurrence(got, form, want, dtype):
    """The kernels' ``got`` against the recurrence's ``want``: to
    float32 rounding, or, in bfloat16, as near as the jax.numpy
    ``form`` on the same operands is, within a half."""
    for name, a, b, c in zip(("o", "dq", "dk", "dv", "dg", "dbeta"),
                             got, form, want):
        a, b, c = (np.asarray(x, np.float32) for x in (a, b, c))
        assert np.isfinite(a).all(), name
        if dtype == jnp.float32:
            np.testing.assert_allclose(a, c, atol=3e-6 * np.abs(c).max(),
                                       err_msg=name)
            np.testing.assert_allclose(a, b, atol=3e-6 * np.abs(c).max(),
                                       err_msg=name)
        else:
            err = np.linalg.norm(a - c) / np.linalg.norm(c)
            ref = np.linalg.norm(b - c) / np.linalg.norm(c)
            assert err < 1.5 * ref + 1e-3, (name, err, ref)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["documents", "ragged"])
def test_fused_kernels_are_the_recurrence_and_the_chunked_form(case, dtype):
    xs, doc = fused_inputs(case, dtype)
    assert supported(*xs[:3], 64)
    with jax.default_matmul_precision("highest"):
        got = through_cos(
            lambda *a: gated_delta_fused(*a, doc, interpret=True), xs)
        form = through_cos(lambda *a: gated_delta_xla(*a, doc, 64), xs)
        want = through_cos(lambda *a: gated_delta_recurrence(*a, doc), xs)
    assert got[0].shape == want[0].shape
    assert_near_the_recurrence(got, form, want, dtype)
    if dtype == jnp.bfloat16:
        # the forward rounds where the jax.numpy form rounds
        np.testing.assert_allclose(
            np.asarray(got[0]), np.asarray(form[0]),
            atol=2e-2 * np.abs(np.asarray(want[0])).max())


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_fused_kernels_bring_q_and_k_to_unit_length_themselves(dtype):
    """``unit``: rows of any length go in, the kernels normalise a tile
    at a time and take the gradient back through it; the jax.numpy
    branch does the same before it starts."""
    (q, k, v, g, beta), doc = fused_inputs("ragged", dtype)
    xs = ((3.0 * q).astype(dtype), (0.4 * k).astype(dtype), v, g, beta)
    scale = 1.0 / math.sqrt(128)

    def plain(scan):
        return lambda q, k, *a: scan(
            (unit_rows(q, 1e-6) * jnp.float32(scale)).astype(dtype),
            unit_rows(k, 1e-6).astype(dtype), *a)

    with jax.default_matmul_precision("highest"):
        got = through_cos(lambda *a: gated_delta_fused(
            *a, doc, unit=1e-6, q_scale=scale, interpret=True), xs)
        form = through_cos(lambda *a: gated_delta_scan_counted(
            *a, doc, unit=1e-6, q_scale=scale)[0], xs)
        want = through_cos(
            plain(lambda *a: gated_delta_recurrence(*a, doc)), xs)
    assert_near_the_recurrence(got, form, want, dtype)


def test_the_platform_and_the_shapes_choose_the_path_and_say_so():
    """Off the TPU the jax.numpy form runs and counts 0 fused, whatever
    the widths; widths the kernels are not written for never reach
    them."""
    xs, doc = fused_inputs("ragged", jnp.float32)
    o, fused = jax.jit(lambda *a: gated_delta_scan_counted(*a, doc))(*xs)
    assert int(fused) == 0 and fused.dtype == jnp.uint32
    np.testing.assert_array_equal(
        o, jax.jit(lambda *a: gated_delta_xla(*a, doc))(*xs))
    narrow, _ = delta_inputs()
    assert not supported(*narrow[:3], 8)
    assert not supported(*xs[:3], 32)
    assert not supported(xs[0], xs[1].astype(jnp.bfloat16), xs[2], 64)
    # and what a TPU would be handed holds the kernels: Mosaic lowers here
    exported = jax.export.export(
        jax.jit(lambda *a: gated_delta_scan_counted(*a, doc)),
        platforms=["tpu"])(*xs)
    assert "tpu_custom_call" in exported.mlir_module()


def _mixer_shaped():
    """A mixer's shape of work around the kernels — ``q``, ``k``, ``v``,
    the decay and ``beta`` projected from one ``x``, two value heads on
    one key head under three documents, something non-linear on ``o``,
    an output projection and a residual — as ``layer(p, x)`` and
    ``loss(run)(p, x)`` for a ``run`` that wraps the layer
    (``jax.checkpoint`` or nothing)."""
    n, t, hk, hv, d, width = 1, 128, 1, 2, 128, 32
    rng = np.random.RandomState(7)
    mk = lambda *s: jnp.asarray(0.2 * rng.randn(*s), jnp.float32)  # noqa
    params = dict(wq=mk(width, hk * d), wk=mk(width, hk * d),
                  wv=mk(width, hv * d), wg=mk(width, hv), wb=mk(width, hv),
                  wo=mk(hv * d, width))
    x = 3 * mk(n, t, width)
    doc = jnp.asarray(np.repeat([0, 1, 2], [40, 50, 38])[None], jnp.int32)

    def layer(p, x):
        q = (x @ p["wq"]).reshape(n, t, hk, d)
        k = (x @ p["wk"]).reshape(n, t, hk, d)
        v = (x @ p["wv"]).reshape(n, t, hv, d)
        o = gated_delta_fused(
            q, k, v, -jax.nn.softplus(x @ p["wg"]),
            jax.nn.sigmoid(x @ p["wb"]), doc, unit=1e-6,
            q_scale=1 / math.sqrt(d), interpret=True)
        return jnp.tanh(o.reshape(n, t, hv * d)) @ p["wo"] + x

    def loss(run):
        return jax.jit(jax.grad(
            lambda p, x: (run(layer)(p, x) ** 2).sum(), (0, 1)))

    return layer, loss, params, x


def test_a_layer_s_remat_keeps_what_the_forward_kernels_made():
    """Under the net's policy a checkpointed layer keeps what the forward
    rule NAMES (``gdn_fused.KEPT_NAMES``): its gradients are those of a
    plain ``jax.checkpoint`` and of no checkpoint at all BIT FOR BIT (a
    kept value is the value the recompute would have made), and the
    program of the gradient runs ``gdn_solve`` and ``gdn_scan`` once
    where a plain ``jax.checkpoint`` runs each twice (forward and
    recompute); the backward kernel once either way."""
    from cxxnet_tpu.nnet.net import REMAT_POLICY

    _, loss, params, x = _mixer_shaped()
    grads = {
        "plain": loss(lambda f: f),
        "kept": loss(lambda f: jax.checkpoint(f, policy=REMAT_POLICY)),
        "recomputed": loss(jax.checkpoint),
    }
    want = jax.tree_util.tree_leaves(grads["recomputed"](params, x))
    assert all(np.abs(np.asarray(w)).max() > 0 for w in want)
    for name in ("kept", "plain"):
        got = jax.tree_util.tree_leaves(grads[name](params, x))
        for a, r in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(r),
                                          err_msg=name)
    runs = {name: {kern: len(re.findall(rf"name={kern}\b", str(
        jax.make_jaxpr(g)(params, x)))) for kern in
        ("gdn_solve", "gdn_scan", "gdn_scan_bwd")}
        for name, g in grads.items()}
    once = {"gdn_solve": 1, "gdn_scan": 1, "gdn_scan_bwd": 1}
    assert runs == {"plain": once, "kept": once,
                    "recomputed": dict(once, gdn_solve=2, gdn_scan=2)}, runs


def test_the_names_a_checkpointed_mixer_offers_are_the_kept_names():
    """Read from the jaxpr of the checkpointed function, not from a copy
    of the tuple: what ``_rule_fwd`` names is what ``KEPT_NAMES`` lists,
    each once (that the net's policy saves them is the test above)."""
    from cxxnet_tpu.nnet.net import REMAT_POLICY

    layer, _, params, x = _mixer_shaped()
    text = str(jax.make_jaxpr(jax.checkpoint(layer, policy=REMAT_POLICY))(
        params, x))
    assert tuple(re.findall(r"name\[name=(\w+)\]", text)) == KEPT_NAMES
    assert len(set(KEPT_NAMES)) == len(KEPT_NAMES)


def test_gated_deltanet_layer_shapes_and_document_reset():
    lay, p, out = make("gated_deltanet", [(2, 24, 16), (2, 24)], nkhead=2,
                       nvhead=4, key_dim=8, value_dim=4, chunk=8, prenorm=1,
                       residual_scale=1.0, init_sigma=0.3)
    assert out == [(2, 24, 16)]
    assert {t: v.shape for t, v in p.items()} == {
        "wmat": (64, 16), "wba": (8, 16), "conv": (48, 4), "dt_bias": (4,),
        "a_log": (4,), "gate_norm": (4,), "wproj": (16, 16), "norm": (16,)}
    r = np.random.RandomState(0)
    x = r.randn(2, 24, 16).astype(np.float32)
    ids = r.randint(1, 9, (2, 24)).astype(np.float32)
    ids[:, 10] = 0                       # a document ends at 10
    run = jax.jit(lambda a: lay.apply(p, [a, jnp.asarray(ids)])[0])
    y = run(jnp.asarray(x))
    # what follows the separator depends on nothing before it
    x2 = x.copy()
    x2[:, :11] = r.randn(2, 11, 16)
    y2 = run(jnp.asarray(x2))
    np.testing.assert_allclose(y[:, 11:] - x[:, 11:], y2[:, 11:] - x2[:, 11:],
                               atol=1e-5)
    assert np.abs(np.asarray(y[:, :11] - y2[:, :11])).max() > 1e-3
    with pytest.raises(ValueError, match="must divide"):
        make("gated_deltanet", [(2, 24, 16)], nkhead=3, nvhead=4, key_dim=8,
             value_dim=4)
    # the solve inside a chunk doubles its blocks
    with pytest.raises(ValueError, match="power of two"):
        make("gated_deltanet", [(2, 24, 16)], nkhead=2, nvhead=4, key_dim=8,
             value_dim=4, chunk=12)
    with pytest.raises(ValueError, match="power of two"):
        gated_delta_scan(*(jnp.zeros(s) for s in (
            (1, 24, 2, 4), (1, 24, 2, 4), (1, 24, 2, 4), (1, 24, 2),
            (1, 24, 2))), chunk=12)


# ----------------------------------------------------------------------
# attention: rotary, q/k norm, output gate, a head width of its own
def plain_attention(p, x, ids, h, hk, dh, rot, theta, eps):
    """What the layer should compute, head by head, in float64."""
    x = np.asarray(x, np.float64)
    n, t, d = x.shape
    w = np.asarray(p["wmat"], np.float64)
    qkv = x @ w.T
    nq = h * dh
    qg = qkv[..., :2 * nq].reshape(n, t, h, 2 * dh)
    q, gate = qg[..., :dh], qg[..., dh:]
    k = qkv[..., 2 * nq:2 * nq + hk * dh].reshape(n, t, hk, dh)
    v = qkv[..., 2 * nq + hk * dh:].reshape(n, t, hk, dh)
    norm = lambda a, g: a / np.sqrt((a * a).mean(-1, keepdims=True)  # noqa
                                    + eps) * np.asarray(g, np.float64)
    q, k = norm(q, p["q_norm"]), norm(k, p["k_norm"])
    doc = np.cumsum(np.concatenate(
        [np.zeros((n, 1)), ids[:, :-1] == 0], axis=1), axis=1)
    out = np.zeros((n, t, h, dh))
    freq = theta ** (-np.arange(rot // 2) * 2.0 / rot)
    for b in range(n):
        pos = np.zeros(t)
        for i in range(1, t):
            pos[i] = 0 if doc[b, i] != doc[b, i - 1] else pos[i - 1] + 1

        def turn(a):
            ang = pos[:, None] * freq
            a1, a2 = a[:, :rot // 2], a[:, rot // 2:rot]
            return np.concatenate([a1 * np.cos(ang) - a2 * np.sin(ang),
                                   a2 * np.cos(ang) + a1 * np.sin(ang),
                                   a[:, rot:]], axis=1)

        for j in range(h):
            qj, kj, vj = turn(q[b, :, j]), turn(k[b, :, j // (h // hk)]), \
                v[b, :, j // (h // hk)]
            s = qj @ kj.T / math.sqrt(dh)
            seen = (doc[b][:, None] == doc[b][None]) & (
                np.arange(t)[:, None] >= np.arange(t)[None])
            s = np.where(seen, s, -np.inf)
            e = np.exp(s - s.max(-1, keepdims=True))
            out[b, :, j] = (e / e.sum(-1, keepdims=True)) @ vj
    out = out * (1 / (1 + np.exp(-gate)))
    return out.reshape(n, t, nq) @ np.asarray(p["wproj"], np.float64).T


def test_attention_with_rotary_norms_gate_and_its_own_head_width():
    h, hk, dh, rot, theta, eps = 4, 2, 12, 6, 1e4, 1e-6
    lay, p, out = make("attention", [(2, 20, 16), (2, 20)], seed=3, nhead=h,
                       nkvhead=hk, head_dim=dh, qk_norm=1, rotary_dim=rot,
                       rope_theta=theta, out_gate=1, causal=1, no_bias=1,
                       eps=eps, init_sigma=0.4)
    assert out == [(2, 20, 16)]          # 4 heads of 12 over a hidden of 16
    assert p["wmat"].shape == (2 * 48 + 2 * 24, 16)
    assert p["wproj"].shape == (16, 48) and p["q_norm"].shape == (12,)
    r = np.random.RandomState(4)
    p = dict(p, q_norm=jnp.asarray(1 + 0.3 * r.randn(12), jnp.float32),
             k_norm=jnp.asarray(1 + 0.3 * r.randn(12), jnp.float32))
    x = r.randn(2, 20, 16).astype(np.float32)
    ids = r.randint(1, 9, (2, 20)).astype(np.float32)
    ids[0, 6] = ids[1, 13] = 0
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda q, a, i: lay.apply(q, [a, i])[0])(
            p, jnp.asarray(x), jnp.asarray(ids))
    want = plain_attention(p, x, ids, h, hk, dh, rot, theta, eps)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_positions_restart_at_each_document():
    ids = jnp.asarray([[5, 3, 0, 7, 7, 0, 0, 2]], jnp.float32)
    pos = doc_positions(doc_index(ids), 1, 8)
    assert pos.tolist() == [[0, 1, 2, 0, 1, 2, 0, 0]]
    assert doc_positions(None, 2, 3).tolist() == [[0, 1, 2]] * 2
    # a rotation keeps a pair's length and leaves the rest of the head
    x = jnp.asarray(np.random.RandomState(0).randn(1, 8, 2, 10), jnp.float32)
    y = rotary(x, pos, 4, 100.0)
    np.testing.assert_allclose(y[..., 4:], x[..., 4:])
    np.testing.assert_allclose(
        y[..., 0] ** 2 + y[..., 2] ** 2, x[..., 0] ** 2 + x[..., 2] ** 2,
        rtol=1e-5)
    np.testing.assert_allclose(y[:, [0, 3, 6, 7]], x[:, [0, 3, 6, 7]],
                               atol=1e-6)   # position 0 turns nothing


def test_attention_refuses_what_the_masked_path_cannot_do():
    with pytest.raises(ValueError, match="rotary_dim"):
        make("attention", [(2, 8, 16)], nhead=4, rotary_dim=3)
    # the masked path chooses its own form (ops/attention.attend):
    # attn_impl = pallas forces the plain layer's kernel only
    with pytest.raises(ValueError, match="chooses between the flash kernels"):
        make("attention", [(2, 8, 16)], nhead=4, head_dim=8,
             attn_impl="pallas")
    # and a layer that sets none of the new keys is the layer it was
    lay, p, _ = make("attention", [(2, 8, 16)], nhead=4)
    assert lay._plain() and set(p) == {"wmat", "wproj", "bias", "bproj"}
    assert p["wmat"].shape == (48, 16) and p["wproj"].shape == (16, 16)


# ----------------------------------------------------------------------
# routed experts
def dense_moe(p, x, nexpert, topk, first, nheld, shared=True):
    """Every held expert on every token, times the router's weight for
    it or 0 (float64)."""
    x = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    logits = x @ np.asarray(p["wgate"], np.float64).T
    e = np.exp(logits - logits.max(-1, keepdims=True))
    prob = e / e.sum(-1, keepdims=True)
    # the topk largest, the lower id first where two are equal
    idx = np.argsort(-prob, axis=-1, kind="stable")[:, :topk]
    w = np.take_along_axis(prob, idx, -1)
    w = w / w.sum(-1, keepdims=True)
    silu = lambda a: a / (1 + np.exp(-a))  # noqa: E731

    def expert(wmat, wproj):
        gu = x @ np.asarray(wmat, np.float64).T
        f = gu.shape[-1] // 2
        return (silu(gu[:, :f]) * gu[:, f:]) @ np.asarray(
            wproj, np.float64).T

    y = np.zeros_like(x)
    load = np.zeros(nheld, int)
    for j in range(nheld):
        mask = np.where(idx == first + j, w, 0.0).sum(-1)
        load[j] = (idx == first + j).sum()
        y += mask[:, None] * expert(p["wmat"][j].T, p["wproj"][j].T)
    if shared:
        gate = 1 / (1 + np.exp(-(x @ np.asarray(p["shared_gate"],
                                                np.float64).T)))
        y += gate * expert(p["shared_wmat"], p["shared_wproj"])
    return y, load


MOE = dict(nexpert=16, topk=3, nhidden=10, shared_hidden=6, init_sigma=0.5)


def test_routed_experts_is_the_dense_masked_loop():
    lay, p, out = make("routed_experts", [(2, 12, 8)], first_expert=4,
                       nheld=6, **MOE)
    assert out == [(2, 12, 8)]
    assert {t: v.shape for t, v in p.items()} == {
        "wgate": (16, 8), "wmat": (6, 8, 20), "wproj": (6, 10, 8),
        "shared_wmat": (12, 8), "shared_wproj": (8, 6),
        "shared_gate": (1, 8)}
    x = np.random.RandomState(5).randn(2, 12, 8).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        (y,), state = jax.jit(lay.apply_stateful)(
            p, lay.init_aux([(2, 12, 8)]), [jnp.asarray(x)])
    want, load = dense_moe(p, x, 16, 3, 4, 6)
    np.testing.assert_allclose(np.asarray(y).reshape(-1, 8), want, atol=2e-5)
    assert int(state["pairs"]) == load.sum() > 0
    assert int(state["pairs_max"]) == load.max()
    assert int(state["pairs_dropped"]) == 0


def test_routed_experts_with_ties_and_an_expert_nobody_picks():
    lay, p, _ = make("routed_experts", [(24, 8)], **MOE)
    wg = np.asarray(p["wgate"]).copy()
    wg[5] = wg[2]                        # experts 2 and 5 always tie
    wg[9] = -50.0                        # never picked by a positive token
    x = np.abs(np.random.RandomState(6).randn(24, 8)).astype(np.float32)
    p = dict(p, wgate=jnp.asarray(wg))
    with jax.default_matmul_precision("highest"):
        (y,), state = jax.jit(lay.apply_stateful)(
            p, lay.init_aux([(24, 8)]), [jnp.asarray(x)])
    want, load = dense_moe(p, x, 16, 3, 0, 16)
    assert load[9] == 0 and load[2] > 0
    # a tie admits no extra expert: every token has exactly three pairs
    assert int(state["pairs"]) == 24 * 3 == load.sum()
    np.testing.assert_allclose(y, want, atol=2e-5)
    # the gradient goes through the permutations both ways
    g = jax.jit(jax.grad(
        lambda q, a: jnp.sum(jnp.sin(lay.apply(q, [a])[0])),
        argnums=(0, 1)))(p, jnp.asarray(x))
    # no token, no gradient
    assert np.abs(np.asarray(g[0]["wmat"][9])).max() == 0
    assert np.abs(np.asarray(g[0]["wmat"][2])).max() > 0
    assert np.isfinite(np.asarray(g[1])).all()


def test_the_shares_of_all_ranks_add_up_to_the_whole_layer():
    """model-configs section 4: 16 experts over 4 ranks of 4; every rank
    routes over all 16 and adds its own experts' terms and the shared
    expert; the parts, the shared expert counted once, are the layer."""
    whole, p, _ = make("routed_experts", [(2, 12, 8)], **MOE)
    x = jnp.asarray(np.random.RandomState(7).randn(2, 12, 8), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = whole.apply(p, [x])[0]
        only_shared = dense_moe(p, np.asarray(x), 16, 3, 0, 0)[0]
        parts, pairs = expert_shares(MOE, p, x, 4, 4)
    assert pairs == 24 * 3               # every pair on exactly one rank
    total = sum(q.reshape(-1, 8) for q in parts) - 3 * only_shared
    np.testing.assert_allclose(total, np.asarray(want).reshape(-1, 8),
                               atol=3e-5)
    with pytest.raises(ValueError, match="not among"):
        make("routed_experts", [(2, 12, 8)], first_expert=14, nheld=4, **MOE)


def test_a_share_takes_its_routing_weights_as_constants():
    """A lone rank has only its own experts' terms of the weights'
    cotangent: a share's router gets no gradient and none reaches the
    input through it; a whole layer differentiates the weights."""
    x = jnp.asarray(np.random.RandomState(8).randn(2, 12, 8), jnp.float32)
    whole, p, _ = make("routed_experts", [(2, 12, 8)], **MOE)
    share, _, _ = make("routed_experts", [(2, 12, 8)], first_expert=4,
                       nheld=6, **MOE)
    mine = dict(p, wmat=p["wmat"][4:10], wproj=p["wproj"][4:10])
    loss = lambda lay: lambda q, a: jnp.sum(  # noqa: E731
        jnp.sin(lay.apply(q, [a])[0]))
    with jax.default_matmul_precision("highest"):
        gw = jax.jit(jax.grad(loss(whole), argnums=(0, 1)))(p, x)
        gs = jax.jit(jax.grad(loss(share), argnums=(0, 1)))(mine, x)
        # the same layer with its router detached from the input
        logits = x.reshape(-1, 8) @ p["wgate"].T

        def detached(q, a):
            w, idx = route(logits, 3)
            u = a.reshape(-1, 8)
            y, _ = held_experts(u, w, idx, q["wmat"], q["wproj"], 4, 16)
            gu = u @ q["shared_wmat"].T
            sh = (jax.nn.silu(gu[:, :6]) * gu[:, 6:]) @ q["shared_wproj"].T
            y = y + jax.nn.sigmoid(u @ q["shared_gate"].T) * sh
            return jnp.sum(jnp.sin(y))

        gd = jax.jit(jax.grad(detached, argnums=(0, 1)))(mine, x)
    assert np.abs(np.asarray(gw[0]["wgate"])).max() > 0
    assert np.abs(np.asarray(gs[0]["wgate"])).max() == 0
    np.testing.assert_allclose(gs[1], gd[1], atol=1e-5)
    np.testing.assert_allclose(gs[0]["wmat"], gd[0]["wmat"], atol=1e-5)


# ----------------------------------------------------------------------
def test_an_untied_head_owns_its_matrix():
    lay, p, out = make("lm_head", [(2, 5, 8)], nhidden=11, init_sigma=0.1)
    assert out == [(2, 5, 11)] and p["wmat"].shape == (11, 8)
    x = jnp.ones((2, 5, 8))
    np.testing.assert_allclose(lay.apply(p, [x])[0], x @ p["wmat"].T,
                               rtol=1e-6)
    tied, p2, _ = make("lm_head", [(2, 5, 8)], nhidden=11, tied="embed")
    assert p2 == {}


def test_a_cpu_run_counts_the_scan_s_tokens_and_none_fused():
    """The gated_deltanet layer's counters: tokens x layers through the
    scan, and those the kernels computed - none off the TPU."""
    tr = families.trainer(qwen3_next_conf(**families.QWEN3_NEXT))
    assert set(tr.aux["l1_gdn0"]) == {"scan_tokens", "scan_tokens_fused"}
    stats = pipeline_stats()
    before = dict(stats.counters())
    ids = np.random.RandomState(1).randint(0, 64, (4, 1, 64)).astype(
        np.float32)
    tr.update_scan(ids, np.roll(ids, -1, axis=2))
    tr.count_layer_state()
    got = stats.counters()
    # 4 steps of one row of 64 tokens, one delta-rule layer
    assert got["gdn_scan_tokens"] - before.get("gdn_scan_tokens", 0) == 256
    assert got.get("gdn_scan_tokens_fused", 0) == before.get(
        "gdn_scan_tokens_fused", 0)
    # and the gated attention layer's (its masked path), likewise
    assert set(tr.aux["l3_attn1"]) == {"attn_tokens", "attn_tokens_flash",
                                       "attn_blocks", "attn_blocks_unmasked",
                                       "attn_tokens_bwd_fused"}
    assert got["attn_tokens"] - before.get("attn_tokens", 0) == 256
    for name in ("attn_tokens_flash", "attn_blocks", "attn_blocks_unmasked",
                 "attn_tokens_bwd_fused"):
        assert got.get(name, 0) == before.get(name, 0)
    tr.count_layer_state()
    assert stats.counters()["gdn_scan_tokens"] == got["gdn_scan_tokens"]
