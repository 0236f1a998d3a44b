"""What the TPU's compiler makes of the two new mechanisms of PR 33 at
their published widths, compiled here for a DESCRIBED v5e chip (the
on-chip-measurement guide, section 2: nothing runs, no chip is needed).

* ``jax.lax.ragged_dot`` inside ``layers/moe.held_experts`` becomes
  kernels named ``ragged-dot-*`` whose layer scope is dropped — the
  name ``benchmarks/lib/stage_scopes.py`` reads the grouped products'
  time by;
* the chunked gated delta rule of ``ops/gdn.py``, walked in
  checkpointed segments, keeps its backward's temporaries under the
  room a 16 GB chip has beside 10 GB of state.

The topology is described inside a fixture, in this one file: only one
process at a time may load the TPU's library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps it away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _shaped(one_chip, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def test_grouped_products_become_ragged_dot_kernels(one_chip):
    from cxxnet_tpu.layers.moe import held_experts

    m, k, d, f, g = 8192, 10, 2048, 512, 32

    def loss(x, w, idx, wmat, wproj):
        with jax.named_scope("l2_moe0"):
            y, counts = held_experts(x, w, idx, wmat, wproj, 0)
        return jnp.sum(y.astype(jnp.float32)), counts

    args = (_shaped(one_chip, (m, d)), _shaped(one_chip, (m, k), jnp.float32),
            _shaped(one_chip, (m, k), jnp.int32),
            _shaped(one_chip, (g, d, 2 * f)), _shaped(one_chip, (g, f, d)))
    compiled = jax.jit(jax.grad(loss, argnums=(0, 3, 4), has_aux=True)
                       ).lower(*args).compile()
    text = compiled.as_text()
    # two products forward and their gradients, every one a kernel
    assert text.count('op_name="ragged-dot-none"') >= 4
    assert "ragged_dot_tiling" in text
    assert 'op_name="ragged-dot-metadata"' in text
    assert "experts/ragged_dot" not in text        # their scope is gone
    assert "l2_moe0)/dispatch/" in text            # the others keep theirs
    assert "l2_moe0)/experts/" in text             # silu, gate x up
    assert compiled.memory_analysis().temp_size_in_bytes < 3.0e9


def test_the_segmented_delta_rule_fits_beside_the_state(one_chip):
    from cxxnet_tpu.ops.gdn import gated_delta_scan

    t, h, dk = 8192, 32, 128

    def loss(q, k, v, g, beta):
        return jnp.sum(gated_delta_scan(q, k, v, g, beta, None, 64, 2048)
                       .astype(jnp.float32))

    head = _shaped(one_chip, (1, t, h, dk))
    gate = _shaped(one_chip, (1, t, h), jnp.float32)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        head, head, head, gate, gate).compile()
    whole = jax.jit(jax.grad(
        lambda *a: jnp.sum(gated_delta_scan(*a, None, 64).astype(
            jnp.float32)), argnums=(0, 1, 2, 3, 4))).lower(
        head, head, head, gate, gate).compile()
    seg = compiled.memory_analysis().temp_size_in_bytes
    assert seg < 1.6e9 < whole.memory_analysis().temp_size_in_bytes
