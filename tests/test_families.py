"""What every token-model family's tests share, once, over the table of
``tests/families.py`` with the family as the id: the builder's small conf
through the trainer (it trains, the routers of a share stay put, the
layers count what they saw), the published defaults' parameter counts,
and — for the families whose reference is held to here as well as under
``benchmarks/tests/`` — the whole small net's loss and every gradient
leaf, and an adam chunk through ``update_scan``, against the plain
reference.  A family's own mechanisms are in ``test_<family>_layers.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import families
from cxxnet_tpu.utils.profiler import pipeline_stats
from families import FAMILIES


def having(field):
    return [name for name, f in FAMILIES.items()
            if getattr(f, field) is not None]


@pytest.mark.parametrize("family", having("counts"))
def test_the_builder_s_conf_trains_and_counts_its_pairs(family):
    """Two scanned chunks through the trainer move every leaf but a
    share's routers and their bias, the loss falls, and the expert and
    attention layers' counters reach the round's once
    ``count_layer_state`` reads the layers' state."""
    f = FAMILIES[family]
    text = f.builder(**f.tiny)
    for piece, n in f.conf_has.items():
        assert text.count(piece) == n, piece
    for piece in f.conf_lacks:
        assert piece not in text, piece
    tr = families.trainer(text)
    if f.aux is not None:
        assert set(tr.aux) == f.aux
    r = np.random.RandomState(0)
    shape = (f.tiny["scan_steps"], f.tiny.get("batch_size", 1),
             f.tiny["seq_len"])
    ids = r.randint(0, f.tiny["vocab"], shape).astype(np.float32)
    if f.biased:
        tr.params[f.biased]["score_bias"] = jnp.asarray(
            0.05 * r.randn(f.tiny["num_experts"]), jnp.float32)
    start = jax.device_get(tr.params)
    stats = pipeline_stats()
    before = dict(stats.counters())
    first = np.asarray(tr.update_scan(ids, np.roll(ids, -1, axis=2)))
    again = np.asarray(tr.update_scan(ids, np.roll(ids, -1, axis=2)))
    assert np.isfinite(first).all() and again.mean() < first.mean()
    if f.first_loss:
        lo, hi = f.first_loss
        assert lo < first.reshape(-1)[0] / np.log(f.tiny["vocab"]) < hi
    # in a share neither the router nor its bias moves under adam (an
    # expert layer's ``wgate``: a latent attention's is its output gate)
    for key, tags in jax.device_get(tr.params).items():
        for tag, w in tags.items():
            still = np.array_equal(w, start[key][tag])
            assert still == (tag in ("wgate", "score_bias")
                             and "_moe" in key), (key, tag)
    tr.count_layer_state()
    moved = lambda name: (stats.counters().get(name, 0)  # noqa: E731
                          - before.get(name, 0))
    for name, (lo, hi) in f.counts.items():
        assert lo <= moved(name) <= hi, (name, moved(name))
    for name in f.unmoved:
        assert moved(name) == 0, name
    if "expert_pairs" in f.counts:
        # a step's fullest expert times the held ones covers its pairs
        assert moved("expert_pairs_max") * f.tiny["experts_held"] >= moved(
            "expert_pairs")
    got = dict(stats.counters())
    tr.count_layer_state()               # nothing new: nothing added
    assert stats.counters() == got
    if f.also:
        f.also(text)


@pytest.mark.parametrize("family", having("layers"))
def test_the_published_defaults_are_what_the_issue_reckoned(family):
    f = FAMILIES[family]
    text = f.builder(**dict(f.defaults, dev="cpu"))
    counts = families.parameter_counts(text)
    for key, n in f.layers.items():
        assert counts[key] == n, key
    total = sum(counts.values())
    if isinstance(f.total, int):
        assert total == f.total
    else:
        assert round(total / 1e6, 1) == f.total
    if f.defaults_also:
        f.defaults_also(text, counts)


@pytest.mark.parametrize("family", having("whole_net"))
def test_the_whole_small_net_s_loss_and_gradients_are_the_reference_s(family):
    """Seeded reference weights under the program's keys, the norms off
    their start of 1 so that each of a layer's is in its place, a row
    with documents: the loss and every gradient leaf."""
    f, ref = FAMILIES[family], families.reference(family)
    kw = dict(f.tiny, **f.whole_net)
    text = f.builder(**kw)
    batch = kw.get("batch_size", 1)
    tr = families.trainer(text)
    net = ref.describe(text, batch)
    made = ref.make_weights(net, 5)
    r = np.random.RandomState(12)
    for leaves in made.values():
        for t in leaves:
            if t in getattr(ref, "ONES", ()):
                leaves[t] = jnp.asarray(1 + 0.2 * r.randn(*leaves[t].shape),
                                        jnp.float32)
    params = families.in_program_s_keys(tr, made, net)
    ids = families.rows_with_documents(13, batch, kw["seq_len"],
                                       vocab=kw["vocab"])
    lab = np.roll(ids, -1, axis=1)
    with jax.default_matmul_precision("highest"):
        got_l, got = jax.jit(jax.value_and_grad(lambda q: tr.net.loss_fn(
            q, jnp.asarray(ids), jnp.asarray(lab))))(params)
        ref_l, ref_g = jax.jit(jax.value_and_grad(ref.loss_fn(net)))(
            made, jnp.asarray(ids, jnp.int32), jnp.asarray(lab, jnp.int32))
    np.testing.assert_allclose(got_l, ref_l, rtol=1e-6)
    for key, tags in got.items():
        for tag, g in tags.items():
            want = np.asarray(ref_g[families.layer_index(key)][tag])
            bound = f.grad_tol.get("atol", 0.0) + f.grad_tol.get(
                "rtol", 0.0) * float(np.abs(want).max())
            assert float(np.abs(np.asarray(g) - want).max()) <= bound, (
                key, tag)
    if f.whole_also:
        f.whole_also(tr, params, ids, lab, ref_l, got)


@pytest.mark.parametrize("family, case", [
    (name, case) for name in having("chunks")
    for case in FAMILIES[name].chunks],
    ids=lambda v: v or None)
def test_an_adam_chunk_through_update_scan_is_the_reference_s(family, case):
    """The scanned step under adam, 4 steps: the losses, the parameters
    after and adam's first moment against ``train_chunk``."""
    f = FAMILIES[family]
    more, bounds = f.chunks[case]
    kw = dict(f.tiny, **more)
    text = f.builder(**kw)
    tr, net = families.with_reference_weights(text, family, 5,
                                              kw.get("batch_size", 1))
    data, labels = families.seeded_rows(family, net, 3, kw["scan_steps"])
    gaps = families.chunk_gaps(tr, family, net, 5, data, labels)
    for name, bound in bounds.items():
        assert gaps[name] < bound, gaps
