"""Branch-embedding fusion (``conv_branch_embed = 1``).

The inception 3x3/5x5 branch convs run as ONE block-kernel conv
(doc/performance.md "Conv efficiency"; the cuDNN algorithmic-rewrite
analog, ``/root/reference/src/layer/cudnn_convolution_layer-inl.hpp``).
Exactness at the op level, end-to-end pair equality on GoogLeNet (which
also exercises the deferred-consumer rescheduling — the 5x5 reduce sits
between the 3x3 conv and the 5x5 conv in declaration order), training
parity, SPMD composition, and the off-domain no-op.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu import config as C
from cxxnet_tpu.nnet.net import FunctionalNet
from cxxnet_tpu.nnet.trainer import NetTrainer


def _int_valued(rng, *shape):
    # integer-valued f32: conv sums stay < 2^24, so equality is exact
    return jnp.asarray(
        rng.randint(-3, 4, shape).astype(np.float32))


def test_apply_branch_embed_bit_exact():
    """The block-kernel conv equals the separate member convs bit-for-
    bit on integer-valued inputs (no float-tolerance hiding)."""
    from jax import lax

    rng = np.random.RandomState(0)
    x3 = _int_valued(rng, 2, 9, 9, 6)
    x5 = _int_valued(rng, 2, 9, 9, 4)
    w3 = _int_valued(rng, 3, 3, 6, 8)
    w5 = _int_valued(rng, 5, 5, 4, 3)
    b3 = _int_valued(rng, 8)
    b5 = _int_valued(rng, 3)
    y3 = lax.conv_general_dilated(
        x3, w3, (1, 1), ((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC")) + b3
    y5 = lax.conv_general_dilated(
        x5, w5, (1, 1), ((2, 2), (2, 2)),
        dimension_numbers=("NHWC", "HWIO", "NHWC")) + b5
    o3, o5 = FunctionalNet._apply_branch_embed(
        [{"wmat": w3, "bias": b3}, {"wmat": w5, "bias": b5}], [x3, x5])
    np.testing.assert_array_equal(np.asarray(o3), np.asarray(y3))
    np.testing.assert_array_equal(np.asarray(o5), np.asarray(y5))


INCEPTION_CFG = [
    ("dev", "tpu:0-{n}"),
    ("batch_size", "16"),
    ("input_shape", "8,12,12"),
    ("eta", "0.1"),
    ("momentum", "0.9"),
    ("netconfig", "start"),
    # branch A: 1x1 reduce -> relu -> 3x3
    ("layer[0->1]", "conv:r3"),
    ("kernel_size", "1"), ("pad", "0"), ("nchannel", "6"),
    ("random_type", "xavier"),
    ("layer[1->2]", "relu"),
    ("layer[2->3]", "conv:c3"),
    ("kernel_size", "3"), ("pad", "1"), ("nchannel", "8"),
    ("random_type", "xavier"),
    ("layer[3->4]", "relu"),
    # branch B: 1x1 reduce -> relu -> 5x5 (declared AFTER c3: the
    # rescheduling path — c5's input does not exist at c3's position)
    ("layer[0->5]", "conv:r5"),
    ("kernel_size", "1"), ("pad", "0"), ("nchannel", "4"),
    ("random_type", "xavier"),
    ("layer[5->6]", "relu"),
    ("layer[6->7]", "conv:c5"),
    ("kernel_size", "5"), ("pad", "2"), ("nchannel", "4"),
    ("random_type", "xavier"),
    ("layer[7->8]", "relu"),
    ("layer[4,8->9]", "ch_concat"),
    ("layer[9->10]", "flatten"),
    ("layer[10->11]", "fullc:fc"),
    ("nhidden", "4"), ("random_type", "xavier"),
    ("layer[11->11]", "softmax"),
    ("netconfig", "end"),
]


def _build(bembed, ndev=1, extra=()):
    cfg = [(k, v.format(n=ndev - 1) if k == "dev" else v)
           for k, v in INCEPTION_CFG]
    tr = NetTrainer()
    tr.set_params(cfg + [("conv_branch_embed", str(bembed)),
                         ("seed", "11")] + list(extra))
    tr.init_model()
    return tr


def test_inception_group_forms_and_reschedules():
    tr = _build(1)
    items, gmap = tr.net._branch_embed_plan()
    assert items is not None
    # one group: the c3 (idx 2 in layer list terms) + c5 convs
    (leader, idxs), = gmap.items()
    assert len(idxs) == 2
    names = [tr.net.graph.layers[j].name for j in idxs]
    assert names == ["c3", "c5"]
    # the plan runs every layer exactly once, members only via the group
    ran = [i for kind, i in items if kind == "L"]
    assert sorted(ran + list(idxs)) == list(range(len(tr.net.graph.layers)))
    # c5's reduce chain (r5, relu) must execute before the group
    pos = {("E" if k == "E" else i): n for n, (k, i) in enumerate(items)}
    r5_idx = next(j for j, s in enumerate(tr.net.graph.layers)
                  if s.name == "r5")
    assert pos[r5_idx] < pos["E"]


def test_inception_pair_forward_and_grads():
    """conv_branch_embed=1 equals the plain path: loss and every
    gradient (same seed -> same init), wino-test tolerances (the f32
    delta is XLA conv-lowering reassociation; f64 is bit-exact)."""
    a, b = _build(0), _build(1)
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(16, 12, 12, 8).astype(np.float32))
    y = jnp.asarray(rng.randint(0, 4, (16, 1)).astype(np.float32))
    (la, ga), (lb, gb) = (jax.jit(jax.value_and_grad(
        lambda p, t=t: t.net.loss_fn(p, x, y, train=False)))(t.params)
        for t in (a, b))
    np.testing.assert_allclose(float(la), float(lb), rtol=2e-4)
    for pa, pb in zip(jax.tree_util.tree_leaves(ga),
                      jax.tree_util.tree_leaves(gb)):
        np.testing.assert_allclose(np.asarray(pa), np.asarray(pb),
                                   rtol=2e-3, atol=2e-3)


def test_googlenet_all_nine_modules_group():
    """The real GoogLeNet builder conf: all 9 inception modules form a
    (3x3, 5x5) group, and the fused net's loss matches the plain one."""
    from cxxnet_tpu.models import googlenet_conf

    def build(bembed):
        tr = NetTrainer()
        tr.set_params(C.parse_pairs(googlenet_conf(
            batch_size=4, num_class=10, synthetic=False, dev="cpu",
            input_size=64)))
        tr.set_param("conv_branch_embed", str(bembed))
        tr.set_param("seed", "7")
        tr._build_net()
        return tr

    a, b = build(0), build(1)
    _items, gmap = b.net._branch_embed_plan()
    assert len(gmap) == 9
    assert all(len(v) == 2 for v in gmap.values())
    # parameters stay per-layer under the fusion: one seeded start for
    # both nets (drawn in one program, not 130 leaves one small program
    # each), and each forward compiled once
    params = jax.jit(lambda k: a.net.init_params(k, 4))(jax.random.PRNGKey(7))
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(4, 64, 64, 3).astype(np.float32))
    y = jnp.asarray(rng.randint(0, 10, (4, 1)).astype(np.float32))
    la, lb = (float(jax.jit(lambda p, t=t: t.net.loss_fn(
        p, x, y, train=False))(params)) for t in (a, b))
    np.testing.assert_allclose(la, lb, rtol=1e-3)


def test_branch_embed_training_parity():
    """3 sgd+momentum steps with the fusion on vs off stay within the
    SPMD-parity tolerance — the gradient path through the block kernel
    is the same optimization trajectory."""
    ta, tb = _build(0), _build(1)
    rng = np.random.RandomState(5)
    for _ in range(3):
        x = rng.randn(16, 12, 12, 8).astype(np.float32)
        y = rng.randint(0, 4, (16, 1)).astype(np.float32)
        ta.update_all(x, y)
    rng = np.random.RandomState(5)
    for _ in range(3):
        x = rng.randn(16, 12, 12, 8).astype(np.float32)
        y = rng.randint(0, 4, (16, 1)).astype(np.float32)
        tb.update_all(x, y)
    for key in ta.params:
        for tag in ta.params[key]:
            np.testing.assert_allclose(
                np.asarray(ta.params[key][tag]),
                np.asarray(tb.params[key][tag]),
                rtol=2e-3, atol=2e-4,
                err_msg=f"{key}/{tag} diverged (branch-embed on vs off)",
            )


@pytest.mark.parametrize("mp", [1, 2])
def test_branch_embed_matches_single_under_mesh(mp):
    """Composes with DP (and DP x TP) sharding over the 8-device mesh,
    the same discipline as the wino/s2d SPMD parity tests."""
    def train(ndev):
        tr = _build(1, ndev=ndev,
                    extra=([("model_parallel", str(mp))]
                           if ndev > 1 else []))
        rng = np.random.RandomState(5)
        for _ in range(3):
            tr.update_all(rng.randn(16, 12, 12, 8).astype(np.float32),
                          rng.randint(0, 4, (16, 1)).astype(np.float32))
        return tr

    t1, t8 = train(1), train(8)
    assert t8.net._branch_embed_plan()[1]
    for key in t1.params:
        for tag in t1.params[key]:
            np.testing.assert_allclose(
                np.asarray(t1.params[key][tag]),
                np.asarray(t8.params[key][tag]),
                rtol=2e-4, atol=2e-5,
                err_msg=f"{key}/{tag} diverged (1- vs 8-device)",
            )


def test_branch_embed_off_domain_no_group():
    """Strided / non-SAME / lone convs never group: ResNet-50 and
    AlexNet plans stay empty (the knob is inception-shaped by
    construction)."""
    from cxxnet_tpu.models import alexnet_conf, resnet50_conf

    for conf in (resnet50_conf(batch_size=4, num_class=10,
                               synthetic=False, dev="cpu", input_size=32),
                 alexnet_conf(batch_size=4, num_class=10,
                              synthetic=False, dev="cpu", input_size=67)):
        tr = NetTrainer()
        tr.set_params(C.parse_pairs(conf))
        tr.set_param("conv_branch_embed", "1")
        tr._build_net()          # the plan reads the graph, no weight
        items, gmap = tr.net._branch_embed_plan()
        assert gmap == {} and items is None


def test_branch_embed_checkpoint_interchange(tmp_path):
    """Parameters stay per-layer under the fusion: a checkpoint saved
    from a bembed-trained net loads into a plain net (and back) with
    identical predictions — the fusion is execution-only state."""
    ta = _build(1)
    rng = np.random.RandomState(9)
    x = rng.randn(16, 12, 12, 8).astype(np.float32)
    y = rng.randint(0, 4, (16, 1)).astype(np.float32)
    ta.update_all(x, y)
    p = str(tmp_path / "be.model")
    ta.save_model(p)
    tb = _build(0)
    tb.load_model(p)
    xa = jnp.asarray(x)
    na, _ = ta.net.forward(ta.params, xa, train=False)
    nb, _ = tb.net.forward(tb.params, xa, train=False)
    np.testing.assert_allclose(
        np.asarray(na[ta.net.out_node_index()]),
        np.asarray(nb[tb.net.out_node_index()]), rtol=2e-4, atol=2e-5)


def test_branch_embed_update_scan():
    """The device-side scanned step (update_scan) runs the same
    forward; a scanned round with the fusion on matches per-step
    updates with it off within the SPMD-parity tolerance."""
    ta, tb = _build(1), _build(0)
    rng = np.random.RandomState(13)
    xs = rng.randn(4, 16, 12, 12, 8).astype(np.float32)
    ys = rng.randint(0, 4, (4, 16, 1)).astype(np.float32)
    ta.update_scan(xs, ys)
    from cxxnet_tpu.io.data import DataBatch

    for k in range(4):
        tb.update(DataBatch(data=xs[k], label=ys[k]))
    for key in ta.params:
        for tag in ta.params[key]:
            np.testing.assert_allclose(
                np.asarray(ta.params[key][tag]),
                np.asarray(tb.params[key][tag]),
                rtol=2e-3, atol=2e-4,
                err_msg=f"{key}/{tag} diverged (scan+embed vs plain)",
            )


def test_branch_embed_with_remat_and_bf16():
    """Smoke: composes with jax.checkpoint and compute_dtype=bfloat16
    (the two knobs most likely to interact with a custom apply path)."""
    tr = _build(1, extra=[("remat", "1"),
                          ("compute_dtype", "bfloat16")])
    rng = np.random.RandomState(2)
    x = rng.randn(16, 12, 12, 8).astype(np.float32)
    y = rng.randint(0, 4, (16, 1)).astype(np.float32)
    tr.update_all(x, y)
    assert np.isfinite(
        np.asarray(tr.params["l2_c3"]["wmat"]).sum())
