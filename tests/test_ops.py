"""Pallas kernels vs their XLA golden models (the PairTest discipline,
SURVEY §4.1): identical inputs, compare outputs and input-gradients.

Kernels run in ``interpret=True`` mode on the CPU harness; on TPU the
same code compiles natively.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.ops.lrn import lrn, lrn_matmul, lrn_xla


@pytest.mark.parametrize("shape", [(2, 5, 5, 64), (16, 192), (2, 7, 7, 96)])
@pytest.mark.parametrize("nsize", [3, 5])
def test_lrn_pallas_matches_xla_forward(rng, shape, nsize):
    x = jnp.asarray(rng.randn(*shape).astype(np.float32))
    got = lrn(x, nsize, 0.0001, 0.75, 1.0, True)
    want = lrn_xla(x, nsize, 0.0001, 0.75, 1.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("nsize", [3, 5])
def test_lrn_pallas_matches_xla_grad(rng, nsize):
    x = jnp.asarray(rng.randn(2, 4, 4, 32).astype(np.float32))

    def loss_pallas(x):
        return jnp.sum(lrn(x, nsize, 0.001, 0.75, 1.0, True) ** 2)

    def loss_xla(x):
        return jnp.sum(lrn_xla(x, nsize, 0.001, 0.75, 1.0) ** 2)

    g1 = jax.grad(loss_pallas)(x)
    g2 = jax.grad(loss_xla)(x)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("c,nsize", [(8, 3), (8, 4), (16, 5), (16, 2)])
def test_lrn_matmul_band_exact(rng, c, nsize):
    """The banded-matmul window (lrn_matmul) must select EXACTLY the
    reduce_window channels, including even-nsize asymmetric windows and
    clipped edges: integer-valued x with beta=1, knorm=0, alpha=n makes
    any band mistake an integer-sized discrepancy."""
    x = jnp.asarray(rng.randint(1, 5, (2, 3, 3, c)).astype(np.float32))
    a = lrn_xla(x, nsize, float(nsize), 1.0, 0.0)
    b = lrn_matmul(x, nsize, float(nsize), 1.0, 0.0)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("shape", [(2, 5, 5, 64), (16, 192)])
@pytest.mark.parametrize("nsize", [3, 5])
def test_lrn_matmul_matches_xla(rng, shape, nsize):
    x = jnp.asarray(rng.randn(*shape).astype(np.float32))
    got = lrn_matmul(x, nsize, 0.001, 0.75, 1.0)
    want = lrn_xla(x, nsize, 0.001, 0.75, 1.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    g1 = jax.grad(lambda v: jnp.sum(lrn_matmul(v, nsize, 0.001, 0.75,
                                               1.0) ** 2))(x)
    g2 = jax.grad(lambda v: jnp.sum(lrn_xla(v, nsize, 0.001, 0.75,
                                            1.0) ** 2))(x)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=2e-4, atol=2e-4)


def test_lrn_layer_matmul_dispatch(rng, monkeypatch):
    """`lrn_impl = matmul` on the LAYER really routes through lrn_matmul
    (call-counted via monkeypatch) and matches the default XLA path."""
    import importlib

    from cxxnet_tpu.layers.base import create_layer

    # NB: the package re-exports the `lrn` FUNCTION as an attribute of
    # cxxnet_tpu.ops, shadowing the module name — go via importlib
    lrn_mod = importlib.import_module("cxxnet_tpu.ops.lrn")

    calls = []
    real = lrn_mod.lrn_matmul
    monkeypatch.setattr(
        lrn_mod, "lrn_matmul",
        lambda *a, **k: (calls.append(1), real(*a, **k))[1],
    )
    x = jnp.asarray(rng.randn(2, 4, 4, 32).astype(np.float32))
    outs = []
    for impl in ("auto", "matmul"):
        lay = create_layer("lrn")
        lay.set_param("local_size", "5")
        lay.set_param("lrn_impl", impl)
        outs.append(lay.apply({}, [x])[0])
    assert len(calls) == 1  # only the matmul-configured layer dispatched
    np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(outs[1]),
                               rtol=2e-5, atol=2e-5)


def test_lrn_pallas_bf16(rng):
    x = jnp.asarray(rng.randn(4, 3, 3, 128).astype(np.float32)).astype(
        jnp.bfloat16
    )
    got = lrn(x, 5, 0.0001, 0.75, 1.0, True)
    assert got.dtype == jnp.bfloat16
    want = lrn_xla(x.astype(jnp.float32), 5, 0.0001, 0.75, 1.0)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), rtol=2e-2, atol=2e-2
    )


def test_lrn_layer_impl_selection(rng, monkeypatch):
    """lrn_impl=auto is the stock XLA path; the pallas opt-in really
    routes through the kernel (interpreted off-TPU) and agrees."""
    import importlib

    from cxxnet_tpu.layers import create_layer

    lrn_mod = importlib.import_module("cxxnet_tpu.ops.lrn")
    calls = []
    real = lrn_mod.lrn
    monkeypatch.setattr(
        lrn_mod, "lrn", lambda *a: calls.append(a[-1]) or real(*a))
    lay = create_layer("lrn")
    lay.set_param("local_size", "5")
    x = jnp.asarray(rng.randn(2, 4, 4, 16).astype(np.float32))
    (y_xla,) = lay.apply({}, [x])
    assert calls == []  # auto never picks the kernel
    lay.set_param("lrn_impl", "pallas")
    (y_pl,) = lay.apply({}, [x])
    assert calls == [True]  # the kernel, under the interpreter
    np.testing.assert_allclose(np.asarray(y_pl), np.asarray(y_xla),
                               rtol=1e-5, atol=1e-6)
    with pytest.raises(Exception):
        lay.set_param("lrn_impl", "bogus")


# ---------------------------------------------------------------- maxpool
from cxxnet_tpu.layers.conv import _maxpool_eq
from cxxnet_tpu.ops.maxpool import maxpool_fused


@pytest.mark.parametrize("hw,k,s,p", [
    (12, 3, 2, 0), (8, 2, 2, 0), (9, 3, 3, 0), (8, 3, 1, 1), (7, 3, 2, 1),
])
def test_maxpool_pallas_matches_xla(rng, hw, k, s, p):
    """Pallas kernel (interpret mode on CPU) == the XLA unpool-VJP
    expression, forward and gradient, incl. tied maxima."""
    x = rng.randn(3, hw, hw, 8).astype(np.float32)
    x[:, : hw // 2] = np.maximum(x[:, : hw // 2], 0.0)  # force ties
    xj = jnp.asarray(x)
    want = _maxpool_eq(xj, k, k, s, p, p)
    got = maxpool_fused(xj, k, k, s, p, p, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    g = jnp.asarray(rng.randn(*want.shape).astype(np.float32))
    gw = jax.grad(lambda v: (_maxpool_eq(v, k, k, s, p, p) * g).sum())(xj)
    gg = jax.grad(
        lambda v: (maxpool_fused(v, k, k, s, p, p, True) * g).sum()
    )(xj)
    np.testing.assert_allclose(np.asarray(gg), np.asarray(gw),
                               rtol=1e-5, atol=1e-6)


def test_maxpool_pallas_bf16(rng):
    x = jnp.asarray(rng.randn(2, 8, 8, 16), jnp.bfloat16)
    want = _maxpool_eq(x, 3, 3, 2, 0, 0)
    got = maxpool_fused(x, 3, 3, 2, 0, 0, True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32)
    )


def test_pool_layer_impl_selection(rng, monkeypatch):
    from cxxnet_tpu.layers import create_layer

    import importlib

    mp_mod = importlib.import_module("cxxnet_tpu.ops.maxpool")
    calls = []
    real = mp_mod.maxpool_fused
    monkeypatch.setattr(
        mp_mod, "maxpool_fused", lambda *a: calls.append(a[-1]) or real(*a))
    lay = create_layer("max_pooling")
    lay.set_param("kernel_size", "2")
    lay.set_param("stride", "2")
    x = jnp.asarray(rng.randn(2, 6, 6, 8).astype(np.float32))
    (y_xla,) = lay.apply({}, [x])
    assert calls == []  # auto never picks pallas
    lay.set_param("pool_impl", "pallas")
    (y_pl,) = lay.apply({}, [x])
    assert calls == [True]  # the kernel, under the interpreter
    np.testing.assert_array_equal(np.asarray(y_pl), np.asarray(y_xla))
    with pytest.raises(ValueError):
        lay.set_param("pool_impl", "bogus")


def test_maxpool_pallas_bwd_matches_xla():
    """pool_impl=pallas_bwd: one-pass stride-1 backward kernel equals
    the XLA unpool-equality path, values and gradients."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cxxnet_tpu.layers.conv import _maxpool_eq, _maxpool_eq_pb

    rng = np.random.RandomState(0)
    # ties included: quantized values make equality duplication real
    x = jnp.asarray(
        np.round(rng.randn(2, 9, 9, 8) * 2) / 2, jnp.float32
    )
    for k, pad in ((3, 1), (5, 2)):  # same-size pools
        ref = _maxpool_eq(x, k, k, 1, pad, pad)
        got = _maxpool_eq_pb(x, k, pad, True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   err_msg=f"fwd k={k} pad={pad}")
        gr = jax.grad(lambda v: (_maxpool_eq(v, k, k, 1, pad, pad)
                                 ** 2).sum())(x)
        gp = jax.grad(lambda v: (_maxpool_eq_pb(v, k, pad, True)
                                 ** 2).sum())(x)
        np.testing.assert_allclose(
            np.asarray(gp), np.asarray(gr), rtol=1e-5, atol=1e-5,
            err_msg=f"bwd k={k} pad={pad}",
        )
