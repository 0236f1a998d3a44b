"""The Granite-4.0-H style hybrid (ISSUE 29) on the CPU at small sizes:
the chunked state-space scan and its gradient against the recurrence,
the layers' counters, and the packed-token iterator.  The whole 10-layer
pattern against the benchmark's plain reference is a row of
``tests/families.py``, run by ``tests/test_families.py``."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import families
from cxxnet_tpu.io.data import create_iterator
from cxxnet_tpu.models import granite_h_conf
from cxxnet_tpu.ops.attention import mha
from cxxnet_tpu.ops.ssd import doc_index, ssd_recurrence, ssd_scan
from cxxnet_tpu.utils.profiler import pipeline_stats

@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


# ----------------------------------------------------------------------
# the scan
def _scan_inputs(t, seed=0, n=2, h=3, p=4, s=5):
    rng = np.random.RandomState(seed)
    f = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)  # noqa: E731
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.5),
                                        (n, t, h))), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, (h,)), jnp.float32)
    return f(n, t, h, p), dt, a, f(n, t, s), f(n, t, s)


# documents that start on a chunk's edge (16, 32), just before one (15,
# 31), just after one (17, 33), back to back (16, 17), and none at all
DOCS = {"on": [16, 32], "before": [15, 31], "after": [17, 33],
        "back_to_back": [16, 17], "mixed": [7, 15, 16, 31, 40],
        "one_document": []}


def _doc(t, starts, n=2):
    ids = np.ones((n, t), np.float32)
    for s in starts:
        ids[0, s - 1] = 0          # a document begins AFTER a separator
        ids[1, (s * 5) % (t - 1)] = 0
    return doc_index(jnp.asarray(ids))


@functools.lru_cache(maxsize=None)
def _jitted(chunk):
    """(value, gradient) of the chunked scan at ``chunk``, or of the
    recurrence at ``None``; compiled once, whatever the documents."""
    def f(x, dt, a, b, c, doc):
        return (ssd_recurrence(x, dt, a, b, c, doc) if chunk is None
                else ssd_scan(x, dt, a, b, c, doc, chunk))

    return jax.jit(f), jax.jit(jax.grad(
        lambda x, dt, a, b, c, doc, w: (f(x, dt, a, b, c, doc) * w).sum(),
        argnums=(0, 1, 2, 3, 4)))


@pytest.mark.parametrize("chunk", [8, 16, 7, 64])
@pytest.mark.parametrize("docs", sorted(DOCS))
def test_chunked_scan_and_its_gradient_match_the_recurrence(chunk, docs):
    t = 50  # 8 and 16 do not divide it; 64 is one padded chunk
    args = _scan_inputs(t)
    doc = _doc(t, DOCS[docs])
    (f, g), (fr, gr) = _jitted(chunk), _jitted(None)
    y, yr = f(*args, doc), fr(*args, doc)
    np.testing.assert_allclose(y, yr, atol=2e-5, rtol=2e-5)
    w = jnp.asarray(np.random.RandomState(1).randn(*y.shape), jnp.float32)
    for got, want in zip(g(*args, doc, w), gr(*args, doc, w)):
        assert float(jnp.abs(got - want).max()) <= 2e-5 * max(
            1.0, float(jnp.abs(want).max()))


def test_no_state_crosses_a_document_start():
    """What follows a separator does not depend on what came before."""
    t = 40
    x, dt, a, b, c = _scan_inputs(t, n=1)
    ids = np.ones((1, t), np.float32)
    ids[0, 19] = 0
    doc = doc_index(jnp.asarray(ids))
    scan = _jitted(16)[0]
    y = scan(x, dt, a, b, c, doc)
    y2 = scan(x.at[:, :20].mul(3.0), dt, a, b.at[:, :20].add(1.0), c, doc)
    np.testing.assert_array_equal(np.asarray(y[:, 20:]),
                                  np.asarray(y2[:, 20:]))
    assert float(jnp.abs(y[:, :20] - y2[:, :20]).max()) > 1e-3


def test_doc_index_starts_a_document_after_every_separator():
    ids = jnp.asarray([[5, 0, 3, 3, 0, 0, 2], [0, 1, 1, 1, 1, 1, 0]],
                      jnp.float32)
    assert doc_index(ids).tolist() == [[0, 0, 1, 1, 1, 2, 3],
                                       [0, 1, 1, 1, 1, 1, 1]]


# ----------------------------------------------------------------------
# the layers in the trainer: their counters, the precision the reference
# is held at, the tied head (the whole pattern against the plain
# reference: tests/test_families.py)
def _mam(**more):
    """The trainer of a mixer, the attention layer and a mixer with the
    reference's weights from the seed in its place, and a 4-step chunk
    with separators around a chunk's edge."""
    text = granite_h_conf(**dict(families.GRANITE, layer_types="mam",
                                 **more))
    tr, net = families.with_reference_weights(text, "granite_h", 5, 2)
    return tr, net, families.seeded_rows("granite_h", net, 3, 4)


@pytest.fixture(scope="module")
def counted():
    """``(trainer, counters a chunk added)`` once ``count_layer_state``
    has read the layers' state."""
    tr, net, (data, labels) = _mam()
    stats = pipeline_stats()
    before = dict(stats.counters())
    tr.update_scan(data, labels, sync=True)
    tr.count_layer_state()
    return tr, {k: v - before.get(k, 0)
                for k, v in stats.counters().items()}


def test_the_attention_layer_counts_its_tokens_into_the_round(counted):
    """PR 37: the masked path's counters, summed into the round's
    once ``count_layer_state`` reads the layers' state — every token
    counted, none by the flash kernels off the TPU, and so none of their
    blocks (PR 43)."""
    tr, got = counted
    (key,) = [k for k, v in tr.aux.items() if "attn_tokens" in v]
    assert set(tr.aux[key]) == {"attn_tokens", "attn_tokens_flash",
                                "attn_blocks", "attn_blocks_unmasked",
                                "attn_tokens_bwd_fused"}
    # 4 steps x 2 rows x 48 tokens, one attention layer
    assert got["attn_tokens"] == 4 * 2 * 48
    for name in ("attn_tokens_flash", "attn_blocks", "attn_blocks_unmasked",
                 "attn_tokens_bwd_fused"):
        assert got.get(name, 0) == 0


def test_the_mixers_count_their_tokens_into_the_round(counted):
    """PR 41: the scan's two counters, summed over the mixers into the
    round's once ``count_layer_state`` reads the layers' state — every
    token counted, none by the fused kernels off the TPU (and at these
    widths on none)."""
    tr, got = counted
    keys = [k for k, v in tr.aux.items() if "scan_tokens" in v]
    assert len(keys) == 2
    for key in keys:
        assert set(tr.aux[key]) == {"scan_tokens", "scan_tokens_fused"}
    # 4 steps x 2 rows x 48 tokens, two mixers
    assert got["ssd_scan_tokens"] == 2 * 4 * 2 * 48
    assert got.get("ssd_scan_tokens_fused", 0) == 0


def test_a_bfloat16_run_fails_the_float32_tolerances():
    """So the tolerances of the adam chunk against the reference
    (``tests/test_families.py``) would catch a lower precision."""
    tr, net, (data, labels) = _mam(compute_dtype="bfloat16")
    gaps = families.chunk_gaps(tr, "granite_h", net, 5, data, labels)
    assert gaps["dw"] > 2e-2 and gaps["dm"] > 2e-3, gaps


def test_the_tied_head_has_one_parameter_and_one_gradient():
    text = granite_h_conf(**dict(families.GRANITE, seq_len=32,
                                 layer_types="m"))
    tr, net = families.with_reference_weights(text, "granite_h", 2, 2)
    heads = [k for k in tr.params if k.endswith("_head")]
    assert heads == [] and "l0_embed" in tr.params
    assert set(tr.params["l0_embed"]) == {"wmat"}
    data, labels = families.seeded_rows("granite_h", net, 1, 1)
    g = jax.jit(jax.grad(lambda p: tr.net.loss_fn(
        p, jnp.asarray(data[0]), jnp.asarray(labels[0]))))(tr.params)
    # one leaf, the sum of the head's and the lookup's gradients: a row
    # of an id that no position holds is reached by the head alone
    ids = data[0].astype(np.int32)
    emb = np.asarray(g["l0_embed"]["wmat"])
    unused = np.setdiff1d(np.arange(64), ids)
    assert np.abs(emb[unused]).max() > 0  # rows only the head reaches


def test_attention_takes_grouped_heads_a_scale_and_documents():
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(2, 24, 4, 8), jnp.float32)
    k = jnp.asarray(rng.randn(2, 24, 2, 8), jnp.float32)
    v = jnp.asarray(rng.randn(2, 24, 2, 8), jnp.float32)
    ids = np.ones((2, 24), np.float32)
    ids[0, 7] = ids[1, 12] = 0
    doc = doc_index(jnp.asarray(ids))
    got, blocked = (jax.jit(lambda q, k, v, block=block: mha(
        q, k, v, causal=True, scale=0.25, doc=doc, block_q=block))(q, k, v)
        for block in (0, 8))
    np.testing.assert_allclose(got, blocked, atol=1e-6)
    # by hand, a head at a time
    kk, vv = np.repeat(k, 2, axis=2), np.repeat(v, 2, axis=2)
    for b in range(2):
        for h in range(4):
            s = np.asarray(q[b, :, h] @ kk[b, :, h].T) * 0.25
            see = (np.arange(24)[:, None] >= np.arange(24)[None]) & (
                np.asarray(doc[b])[:, None] == np.asarray(doc[b])[None])
            s = np.where(see, s, -np.inf)
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            np.testing.assert_allclose(got[b, :, h], p @ vv[b, :, h],
                                       atol=1e-5)


# ----------------------------------------------------------------------
# the packed-token iterator
def _token_file(tmp_path, n=4 * 16 + 1, seed=0):
    rng = np.random.RandomState(seed)
    raw = rng.randint(1, 500, n).astype("<u2")
    raw[[5, 16, 17, 40]] = 0
    path = str(tmp_path / "tokens.bin")
    raw.tofile(path)
    return path, raw


def _iter(path, extra=()):
    it = create_iterator([("iter", "tokens"), ("filename", path),
                          ("seq_len", "16"), ("batch_size", "2"),
                          ("silent", "1"), *extra, ("iter", "end")])
    it.init()
    return it


def test_token_rows_are_the_stream_and_labels_the_next_token(tmp_path):
    path, raw = _token_file(tmp_path)
    pipeline_stats().reset()
    batches = [(b.data.copy(), b.label.copy(), b.num_batch_padd)
               for b in _iter(path)]
    assert len(batches) == 2 and all(p == 0 for *_, p in batches)
    data = np.concatenate([d for d, *_ in batches])
    label = np.concatenate([l for _, l, _ in batches])
    assert data.dtype == np.float32 and data.shape == (4, 16)
    np.testing.assert_array_equal(data, raw[:64].reshape(4, 16))   # no pad
    np.testing.assert_array_equal(label, raw[1:65].reshape(4, 16))
    # separators stay where the file has them
    assert sorted(np.flatnonzero(data.ravel() == 0)) == [5, 16, 17, 40]
    counts = pipeline_stats().counters()
    # the pairs a causal query of its own document sees: documents of
    # 6 and a cut 10; 1, 1 and a cut 14; 9 and a cut 7; one cut 16
    assert counts == {"tokens": 64, "docs": 4, "docs_cut": 4,
                      "attn_pairs": (21 + 55) + (1 + 1 + 105) + (45 + 28)
                      + 136}
    assert pipeline_stats().snapshot()["batch"]["rows"] == 4


def test_token_iterator_is_deterministic_and_shuffles_by_the_seed(tmp_path):
    path, _ = _token_file(tmp_path)
    rows = lambda *extra: np.concatenate(  # noqa: E731
        [b.data.copy() for b in _iter(path, extra)])
    plain = rows()
    np.testing.assert_array_equal(plain, rows())
    a = rows(("shuffle", "1"), ("seed_data", "3"))
    np.testing.assert_array_equal(a, rows(("shuffle", "1"),
                                          ("seed_data", "3")))
    assert sorted(map(tuple, a)) == sorted(map(tuple, plain))
    assert not np.array_equal(a, rows(("shuffle", "1"), ("seed_data", "4")))


def test_token_iterator_keeps_the_shard_contract(tmp_path):
    """Equal, disjoint shares: every worker the same number of rows."""
    path, raw = _token_file(tmp_path, n=6 * 16 + 1)
    assert _iter(path).supports_dist_shard()
    shares = [np.concatenate([b.data.copy() for b in _iter(
        path, (("dist_num_worker", "2"), ("dist_worker_rank", str(r)),
               ("round_batch", "0")))]) for r in range(2)]
    assert shares[0].shape == shares[1].shape == (2, 16)
    both = {tuple(r) for s in shares for r in s}
    assert len(both) == 4
    assert both <= {tuple(r) for r in raw[:96].reshape(6, 16)}


def test_ids_reach_the_embedding_unrounded():
    """12543 is no bfloat16: the net keeps the ids' node in float32."""
    tr = families.trainer(granite_h_conf(**dict(
        families.GRANITE, seq_len=16, batch_size=1, layer_types="m",
        compute_dtype="bfloat16", vocab=12544)))
    ids = np.full((1, 16), 12543.0, np.float32)
    h0 = tr.net.graph.node_index_of("h0")
    first = jax.jit(lambda p: tr.net.forward(
        p, jnp.asarray(ids), train=False)[0][h0])(tr.params)
    want = np.asarray(tr.params["l0_embed"]["wmat"][12543], np.float32) * 12
    got = np.asarray(first[0, 0], np.float32)
    np.testing.assert_allclose(got, want, rtol=1e-2)
