"""Python API parity tests: DataIter / Net / train (wrapper/cxxnet.py)."""

import os

import numpy as np
import pytest

from cxxnet_tpu.wrapper import DataIter, Net, train

MLP_CFG = """
netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 32
  init_sigma = 0.1
layer[+1:a1] = relu:a1
layer[a1->out] = fullc:fc2
  nhidden = 4
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end
input_shape = 1,1,8
batch_size = 16
eta = 0.5
momentum = 0.9
metric = error
"""


def toy_xy(n=64, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 8).astype(np.float32)
    w = rng.randn(8, 4).astype(np.float32)
    y = (x @ w).argmax(-1).astype(np.float32)
    return x, y


def csv_iter(tmp_path, x, y, name="train.csv", batch=16):
    path = os.path.join(str(tmp_path), name)
    rows = np.concatenate([y[:, None], x], axis=1)
    np.savetxt(path, rows, delimiter=",")
    return DataIter(
        f"""
        iter = csv
        filename = {path}
        label_width = 1
        input_shape = 1,1,8
        batch_size = {batch}
        """
    )


def test_dataiter_protocol(tmp_path):
    x, y = toy_xy(32)
    it = csv_iter(tmp_path, x, y)
    with pytest.raises(RuntimeError):
        it.get_data()  # head state
    assert it.next()
    d, l = it.get_data(), it.get_label()
    assert d.reshape(16, 8).shape == (16, 8) and l.shape == (16, 1)
    np.testing.assert_allclose(d.reshape(16, 8), x[:16], rtol=1e-5)
    assert it.next()
    assert not it.next()
    with pytest.raises(RuntimeError):
        it.get_data()  # tail state
    it.before_first()
    assert it.next()


def test_dataiter_section_markers_tolerated(tmp_path):
    x, y = toy_xy(16)
    path = os.path.join(str(tmp_path), "t.csv")
    np.savetxt(path, np.concatenate([y[:, None], x], 1), delimiter=",")
    it = DataIter(
        f"""
        data = train
        iter = csv
        filename = {path}
        label_width = 1
        input_shape = 1,1,8
        batch_size = 16
        iter = end
        """
    )
    assert it.next()
    assert it.get_data().shape[0] == 16


def test_net_update_ndarray_and_predict():
    net = Net(dev="cpu", cfg=MLP_CFG)
    net.init_model()
    x, y = toy_xy(64)
    for _ in range(60):
        for i in range(0, 64, 16):
            net.update(x[i : i + 16], y[i : i + 16])
    pred = net.predict(x[:16])
    assert pred.shape == (16,)
    assert (pred == y[:16]).mean() >= 0.9


def test_net_update_label_validation():
    net = Net(dev="cpu", cfg=MLP_CFG)
    net.init_model()
    x, y = toy_xy(16)
    with pytest.raises(ValueError):
        net.update(x)  # no label
    with pytest.raises(ValueError):
        net.update(x, y[:8])  # size mismatch
    with pytest.raises(TypeError):
        net.update([1, 2, 3], y)


def test_net_weight_roundtrip_and_extract():
    net = Net(dev="cpu", cfg=MLP_CFG)
    net.init_model()
    w = net.get_weight("fc1", "wmat")
    assert w is not None and w.size > 0
    net.set_weight(np.zeros_like(w), "fc1", "wmat")
    assert np.all(net.get_weight("fc1", "wmat") == 0)
    assert net.get_weight("a1", "wmat") is None  # no-weight layer
    x, _ = toy_xy(16)
    feat = net.extract(x, "fc1")
    assert feat.shape[0] == 16 and feat.reshape(16, -1).shape[1] == 32
    top = net.extract(x, "top[-1]")
    assert top.reshape(16, -1).shape[1] == 4


def test_net_save_load_model(tmp_path):
    net = Net(dev="cpu", cfg=MLP_CFG)
    net.init_model()
    x, y = toy_xy(32)
    net.update(x[:16], y[:16])
    path = os.path.join(str(tmp_path), "m.model")
    net.save_model(path)
    net2 = Net(dev="cpu", cfg=MLP_CFG)
    net2.load_model(path)
    np.testing.assert_allclose(
        net.get_weight("fc1", "wmat"), net2.get_weight("fc1", "wmat")
    )
    np.testing.assert_allclose(net.predict(x[:16]), net2.predict(x[:16]))


def test_train_loop_with_iterators(tmp_path, capsys):
    x, y = toy_xy(64)
    it = csv_iter(tmp_path, x, y)
    ev = csv_iter(tmp_path, x[:32], y[:32], name="eval.csv")
    net = train(
        MLP_CFG,
        it,
        num_round=40,
        param={"eta": 0.5},
        eval_data=ev,
        dev="cpu",
        print_step=0,
    )
    ev.before_first()
    assert ev.next()
    pred = net.predict(ev)
    assert (pred == y[:16]).mean() >= 0.9
    captured = capsys.readouterr()
    assert "eval-error" in captured.err


def test_train_loop_with_ndarray():
    x, y = toy_xy(16)
    net = train(MLP_CFG, x, num_round=3, param={}, label=y, dev="cpu")
    assert net.trainer.epoch_counter == 3


def test_load_model_without_conf_errors_clearly(tmp_path):
    """Checkpoints are structure-only (reference parity): loading into a
    bare Net must say so instead of failing deep in shape inference."""
    import pytest

    from cxxnet_tpu.wrapper import Net

    conf = """
netconfig = start
layer[0->1] = fullc:fc
  nhidden = 4
layer[1->1] = softmax
netconfig = end
input_shape = 1,1,8
batch_size = 4
eta = 0.1
"""
    net = Net(dev="cpu", cfg=conf)
    net.init_model()
    net.save_model(str(tmp_path / "m.model"))
    bare = Net(dev="cpu")
    with pytest.raises(ValueError, match="netconfig"):
        bare.load_model(str(tmp_path / "m.model"))


def test_predict_ndarray_trims_to_request_rows():
    """Raw-array predict/extract must return exactly the requested rows
    (bucket padding trimmed) and agree with the full-batch rows: the
    predicted labels exactly, the features to float32 rounding.  The
    n-row bucket and the 32-row batch are two XLA programs of different
    batch shape, free to order a dot product's sums differently (one
    ulp in ``fc1`` on this CPU), so they owe no bit equality."""
    net = Net(dev="cpu", cfg=MLP_CFG)
    net.init_model()
    x, _ = toy_xy(32)
    full = net.predict(x)
    full_feat = net.extract(x, "fc1")
    for n in (1, 3, 7, 20):
        pred = net.predict(x[:n])
        assert pred.shape == (n,)
        np.testing.assert_array_equal(pred, full[:n])
        feat = net.extract(x[:n], "fc1")
        assert feat.shape[0] == n
        np.testing.assert_allclose(feat, full_feat[:n],
                                   rtol=1e-5, atol=1e-6)


def test_predict_ndarray_bucket_cache_no_rejit():
    """Repeated odd-sized raw-array calls hit the shape-bucket cache
    instead of re-tracing a fresh XLA program per size (forward runs
    only at trace time, so its call count == compile count)."""
    net = Net(dev="cpu", cfg=MLP_CFG)
    net.init_model()
    x, _ = toy_xy(64)
    calls = []
    orig = net.trainer.net.forward

    def counting(*a, **k):
        calls.append(1)
        return orig(*a, **k)

    net.trainer.net.forward = counting
    sizes = [1, 3, 7, 5, 3, 1, 7, 6, 2, 5]
    for n in sizes:
        assert net.predict(x[:n]).shape == (n,)
    # buckets {1, 2, 4, 8}: at most one trace per bucket, none repeated
    assert len(calls) <= len({1, 2, 4, 8})
    warm = len(calls)
    for n in sizes:
        net.predict(x[:n])
    assert len(calls) == warm, "odd-sized predict re-jitted after warmup"


def test_net_update_scan_trains_like_update():
    # [K, B, ...] stack path: 4 chunks of 16 per epoch as one dispatch
    net = Net(dev="cpu", cfg=MLP_CFG)
    net.init_model()
    x, y = toy_xy(64)
    stack = x.reshape(4, 16, -1)
    lstack = y.reshape(4, 16, -1)
    losses = None
    for _ in range(60):
        losses = net.update_scan(stack, lstack)
    assert losses.shape == (4,)
    pred = net.predict(x[:16])
    assert (pred == y[:16]).mean() >= 0.9
