"""Ring attention vs plain attention (golden), on the 8-device CPU mesh."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from cxxnet_tpu.ops.attention import mha, ring_attention, ring_self_attention
from cxxnet_tpu.parallel import make_mesh


def _qkv(rng, b=2, t=32, h=4, d=16):
    mk = lambda: jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_full_attention(rng, causal):
    q, k, v = _qkv(rng)
    plan = make_mesh("cpu:0-7", model_parallel=4)  # seq over 'model' (4-way)
    want = mha(q, k, v, causal=causal)
    got = jax.jit(lambda q, k, v: ring_self_attention(
        q, k, v, plan.mesh, "model", causal=causal))(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_ring_full_eight_way(rng):
    q, k, v = _qkv(rng, b=8, t=64)
    plan = make_mesh("cpu:0-7", model_parallel=8)  # pure SP ring
    want = mha(q, k, v, causal=True)
    got = jax.jit(lambda q, k, v: ring_self_attention(
        q, k, v, plan.mesh, "model", causal=True))(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_ring_gradients_match(rng):
    q, k, v = _qkv(rng, b=2, t=16, h=2, d=8)
    plan = make_mesh("cpu:0-7", model_parallel=4)

    def loss_ring(q, k, v):
        return jnp.sum(
            ring_self_attention(q, k, v, plan.mesh, "model", causal=True) ** 2
        )

    def loss_full(q, k, v):
        return jnp.sum(mha(q, k, v, causal=True) ** 2)

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_full = jax.jit(jax.grad(loss_full, argnums=(0, 1, 2)))(q, k, v)
    for gr, gf in zip(g_ring, g_full):
        np.testing.assert_allclose(
            np.asarray(gr), np.asarray(gf), rtol=5e-4, atol=5e-5
        )


def test_mha_causal_is_lower_triangular(rng):
    """Causal output at position t must not depend on inputs after t."""
    q, k, v = _qkv(rng, b=1, t=8, h=1, d=4)
    base = np.asarray(mha(q, k, v, causal=True))
    v2 = v.at[:, -1].set(999.0)  # poison the last position
    out2 = np.asarray(mha(q, k, v2, causal=True))
    np.testing.assert_allclose(base[:, :-1], out2[:, :-1], rtol=1e-5)
    assert not np.allclose(base[:, -1], out2[:, -1])


# ------------------------------------------------- Ulysses all-to-all SP
from cxxnet_tpu.ops.attention import a2a_self_attention


@pytest.mark.parametrize("causal", [False, True])
def test_a2a_matches_full_attention(rng, causal):
    q, k, v = _qkv(rng)  # h=4 divides the 4-way axis
    plan = make_mesh("cpu:0-7", model_parallel=4)
    want = mha(q, k, v, causal=causal)
    got = jax.jit(lambda q, k, v: a2a_self_attention(
        q, k, v, plan.mesh, "model", causal=causal))(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_a2a_eight_way(rng):
    q, k, v = _qkv(rng, b=8, t=64, h=8)
    plan = make_mesh("cpu:0-7", model_parallel=8)
    want = mha(q, k, v, causal=True)
    got = jax.jit(lambda q, k, v: a2a_self_attention(
        q, k, v, plan.mesh, "model", causal=True))(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_a2a_gradients_match(rng):
    q, k, v = _qkv(rng, b=2, t=16, h=4, d=8)
    plan = make_mesh("cpu:0-7", model_parallel=4)

    def loss_a2a(q_, k_, v_):
        return jnp.sum(
            a2a_self_attention(q_, k_, v_, plan.mesh, "model") ** 2
        )

    def loss_full(q_, k_, v_):
        return jnp.sum(mha(q_, k_, v_) ** 2)

    ga = jax.jit(jax.grad(loss_a2a, argnums=(0, 1, 2)))(q, k, v)
    gf = jax.jit(jax.grad(loss_full, argnums=(0, 1, 2)))(q, k, v)
    for a, f in zip(ga, gf):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(f), rtol=1e-4, atol=1e-5
        )


def test_attention_layer_seq_parallel_modes(rng):
    """Config grammar: seq_parallel = ring|alltoall|0|1|2 select the SP
    schedule; both produce mha-identical output through the layer."""
    from cxxnet_tpu.layers import create_layer

    x = jnp.asarray(rng.randn(4, 16, 32).astype(np.float32))
    plan = make_mesh("cpu:0-7", model_parallel=4)
    outs = {}
    for mode in ("0", "ring", "alltoall"):
        lay = create_layer("attention")
        lay.set_param("nhead", "4")
        lay.set_param("init_sigma", "0.1")
        lay.set_param("seq_parallel", mode)
        lay.bind_mesh(plan)
        lay.infer_shape([(4, 16, 32)])
        params = lay.init_params(jax.random.PRNGKey(0), [(4, 16, 32)])
        (outs[mode],) = jax.jit(lambda p, a, lay=lay: lay.apply(p, [a]))(
            params, x)
    np.testing.assert_allclose(
        np.asarray(outs["ring"]), np.asarray(outs["0"]), rtol=2e-5,
        atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(outs["alltoall"]), np.asarray(outs["0"]), rtol=2e-5,
        atol=2e-5)
    import pytest as _pytest

    lay = create_layer("attention")
    lay.set_param("nhead", "3")  # 3 % 4 != 0
    lay.set_param("seq_parallel", "alltoall")
    lay.bind_mesh(plan)
    with _pytest.raises(ValueError, match="alltoall"):
        lay.infer_shape([(4, 16, 33)])
