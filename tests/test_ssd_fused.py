"""The fused kernels of the Mamba-2 scan (``ops/ssd_fused.py``) on the
CPU's interpreter, against the token-by-token recurrence and the
``jax.numpy`` chunked form; and how ``ops/ssd.ssd_scan`` chooses between
the two forms."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.ops.ssd import (ssd_recurrence, ssd_scan, ssd_scan_counted,
                                ssd_xla)
from cxxnet_tpu.ops.ssd_fused import heads_per_step, ssd_fused, supported
from families import through_cos

#: case -> (N, T, H, P, chunk, where documents begin or None)
CASES = {
    # a boundary inside the first chunk, one at a chunk's edge, a
    # document that spans six chunks and two stretches' edges (3 chunks
    # a grid step: 384, 768), one more inside the last chunk
    "documents_128": (1, 1152, 2, 64, 128, (40, 256, 1140)),
    # five chunks of 256 walked one a grid step (the state crosses every
    # step's edge), documents inside and across
    "documents_256": (1, 1280, 2, 64, 256, (100, 300, 301, 1200)),
    "one_document_256": (1, 512, 2, 64, 256, None),
    # a head as wide as a lane tile is a unit of its own
    "wide_heads_128": (1, 256, 1, 128, 128, (130,)),
    # sixteen heads are two steps of eight: B's and C's cotangents are
    # two partial sums; a row of one document (doc=None)
    "sixteen_heads_128": (1, 128, 16, 64, 128, None),
}


def fused_inputs(case, dtype):
    n, t, h, p, chunk, starts = CASES[case]
    r = np.random.RandomState(11)
    x = r.randn(n, t, h, p)
    dt = np.log1p(np.exp(r.randn(n, t, h) - 2.0))
    a = -r.uniform(1.0, 8.0, (h,))
    b = r.randn(n, t, 128) / np.sqrt(128)
    c = r.randn(n, t, 128)
    doc = None
    if starts is not None:
        doc = np.zeros((n, t), np.int32)
        for start in starts:
            doc[:, start:] += 1
    f32 = jnp.float32
    return ((jnp.asarray(x, f32).astype(dtype), jnp.asarray(dt, f32),
             jnp.asarray(a, f32), jnp.asarray(b, f32).astype(dtype),
             jnp.asarray(c, f32).astype(dtype)), doc, chunk)


@pytest.mark.parametrize("case, dtype", [
    ("documents_128", jnp.float32), ("documents_128", jnp.bfloat16),
    ("documents_256", jnp.float32), ("one_document_256", jnp.bfloat16),
    ("wide_heads_128", jnp.bfloat16), ("sixteen_heads_128", jnp.bfloat16),
], ids=lambda v: v if isinstance(v, str) else v.__name__)
def test_fused_kernels_are_the_recurrence_and_the_chunked_form(case, dtype):
    """``y`` and the gradients of ``x``, ``dt``, ``a``, ``B``, ``C``: to
    float32 rounding, or, in bfloat16, as near the recurrence as the
    jax.numpy form on the same operands is, within a half."""
    xs, doc, chunk = fused_inputs(case, dtype)
    assert supported(xs[0], xs[3], xs[4], chunk)
    with jax.default_matmul_precision("highest"):
        got = through_cos(
            lambda *v: ssd_fused(*v, doc, chunk, interpret=True), xs)
        form = through_cos(lambda *v: ssd_xla(*v, doc, chunk), xs)
        want = through_cos(lambda *v: ssd_recurrence(*v, doc), xs)
    assert got[0].shape == want[0].shape
    for name, g, f, w in zip(("y", "dx", "ddt", "da", "dB", "dC"),
                             got, form, want):
        g, f, w = (np.asarray(v, np.float32) for v in (g, f, w))
        assert np.isfinite(g).all(), name
        if dtype == jnp.float32:
            # da sums every token's share, of both signs, into one
            # number a head: the jax.numpy form is as far from the
            # recurrence there (2e-4) as the kernels are
            atol = (1e-3 if name == "da" else 5e-5) * np.abs(w).max()
            np.testing.assert_allclose(g, w, atol=atol, err_msg=name)
            np.testing.assert_allclose(g, f, atol=atol, err_msg=name)
        else:
            err = np.linalg.norm(g - w) / np.linalg.norm(w)
            ref = np.linalg.norm(f - w) / np.linalg.norm(w)
            # (da: two numbers, each what is left of sums that cancel)
            room = (2.0, 1e-2) if name == "da" else (1.5, 1e-3)
            assert err < room[0] * ref + room[1], (name, err, ref)
    if dtype == jnp.bfloat16:
        # the forward rounds where the jax.numpy form rounds
        np.testing.assert_allclose(got[0], form[0],
                                   atol=2e-2 * np.abs(want[0]).max())


def test_no_state_and_nothing_in_a_chunk_crosses_a_document():
    """What comes before a document's first token changes nothing after
    it: not through a chunk's matrices, not through the carried state."""
    (x, dt, a, b, c), doc, chunk = fused_inputs("documents_256",
                                                jnp.float32)
    run = lambda *v: ssd_fused(*v, doc, chunk, interpret=True)  # noqa: E731
    y = run(x, dt, a, b, c)
    y2 = run(x.at[:, :300].mul(3.0), dt, a, b.at[:, :300].add(1.0),
             c.at[:, :300].mul(-2.0))
    np.testing.assert_array_equal(y[:, 301:], y2[:, 301:])
    assert float(jnp.abs(y[:, :300] - y2[:, :300]).max()) > 1e-2


NARROW = dict(n=1, t=256, h=2, p=64, s=128, chunk=128)


@pytest.mark.parametrize("change,why", [
    (dict(p=8), "a head of 8 columns"),
    (dict(p=256), "a head wider than a lane tile"),
    (dict(s=16), "a state of 16"),
    (dict(s=192), "a state that is no multiple of 128"),
    (dict(t=200), "a row that is no whole number of chunks"),
    (dict(chunk=64), "chunks of 64"),
    (dict(h=3), "three heads of 64: no whole lane tiles"),
    (dict(groups=2), "(N,T,G,S) operands"),
    (dict(mixed=True), "operands of two dtypes"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_supported_refuses_what_the_kernels_are_not_written_for(change, why):
    """... and the refused call is the jax.numpy program, to the
    character, with no platform switch in it."""
    cfg = dict(NARROW, **{k: v for k, v in change.items()
                          if k in NARROW})
    n, t, h, p, s, chunk = (cfg[k] for k in ("n", "t", "h", "p", "s",
                                              "chunk"))
    groups = change.get("groups", 0)
    f32 = jnp.float32
    x = jax.ShapeDtypeStruct((n, t, h, p), f32)
    dt = jax.ShapeDtypeStruct((n, t, h), f32)
    a = jax.ShapeDtypeStruct((h,), f32)
    bc = jax.ShapeDtypeStruct((n, t, groups, s) if groups else (n, t, s),
                              jnp.bfloat16 if change.get("mixed") else f32)
    assert not supported(x, bc, bc, chunk), why
    counted = jax.make_jaxpr(
        lambda *v: ssd_scan_counted(*v, None, chunk)[0])(x, dt, a, bc, bc)
    plain = jax.make_jaxpr(
        lambda *v: ssd_xla(*v, None, chunk))(x, dt, a, bc, bc)
    assert str(counted) == str(plain), why
    assert "platform_index" not in str(counted)


def test_the_shapes_of_both_cells_are_supported():
    bf16 = jnp.bfloat16
    for h, chunk, hb in ((64, 256, 8), (16, 128, 8)):
        x = jax.ShapeDtypeStruct((1, 8192, h, 64), bf16)
        bc = jax.ShapeDtypeStruct((1, 8192, 128), bf16)
        assert supported(x, bc, bc, chunk)
        assert heads_per_step(h, 64) == hb
    assert heads_per_step(6, 64) == 2 and heads_per_step(3, 128) == 1


@pytest.mark.parametrize("skip", [False, True], ids=["scan", "with_d_skip"])
def test_the_platform_and_the_shapes_choose_the_path_and_say_so(skip):
    """Off the TPU the jax.numpy form runs and counts 0 fused, whatever
    the widths; lowered for a TPU the same call holds the kernels and
    counts 1 — each branch says for itself which it was."""
    xs, doc, chunk = fused_inputs("documents_128", jnp.float32)
    d = jnp.linspace(0.5, 1.5, xs[0].shape[2]) if skip else None

    def counted(*v):
        return ssd_scan_counted(*v, doc, chunk, skip=d)

    y, fused = jax.jit(counted)(*xs)
    assert int(fused) == 0 and fused.dtype == jnp.uint32
    want = jax.jit(lambda *v: ssd_xla(*v, doc, chunk))(*xs)
    if skip:
        want = want + d[:, None] * xs[0]
    else:
        np.testing.assert_array_equal(
            y, jax.jit(lambda *v: ssd_scan(*v, doc, chunk))(*xs))
    np.testing.assert_allclose(y, want, rtol=2e-6, atol=2e-6)
    # what a TPU would be handed holds the kernels: Mosaic lowers here,
    # forward and backward, and the branch that holds them counts 1
    exported = jax.export.export(
        jax.jit(jax.value_and_grad(
            lambda *v: jnp.sum(counted(*v)[0]), argnums=(0, 1, 2, 3, 4))),
        platforms=["tpu"])(*xs)
    text = exported.mlir_module()
    assert text.count("tpu_custom_call") >= 2
    assert "ssd_scan_bwd" in text
    tpu_only = jax.make_jaxpr(counted)(*xs)
    assert "platform_index" in str(tpu_only)


def test_the_d_skip_on_rows_is_the_d_skip_a_head():
    """The kernels' branch adds ``d x`` on the ``(N, T, H P)`` rows: the
    same numbers as the per-head broadcast of the jax.numpy branch."""
    (x, dt, a, b, c), doc, chunk = fused_inputs("one_document_256",
                                                jnp.bfloat16)
    n, t, h, p = x.shape
    d = jnp.linspace(0.5, 1.5, h)
    rows = (jnp.repeat(d.astype(x.dtype), p) * x.reshape(n, t, h * p))
    np.testing.assert_array_equal(
        rows.reshape(x.shape), d.astype(x.dtype)[:, None] * x)
