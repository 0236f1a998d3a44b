"""NetTrainer tests: overfit, accumulation, checkpointing, finetune, weights."""

import jax
import numpy as np
import pytest

from cxxnet_tpu import config as C
from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.nnet.trainer import NetTrainer

MLP_CFG = """
netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 32
  init_sigma = 0.1
layer[+1:a1] = relu
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
metric = logloss
"""


def make_trainer(extra=""):
    tr = NetTrainer()
    tr.set_params(C.parse_pairs(MLP_CFG + extra))
    tr.init_model()
    return tr


def toy_data(n=64, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 8).astype(np.float32)
    w = rng.randn(8, 4).astype(np.float32)
    y = (x @ w).argmax(-1).astype(np.float32)[:, None]
    return x, y


def batches(x, y, bs=16):
    for i in range(0, len(x), bs):
        yield DataBatch(data=x[i : i + bs], label=y[i : i + bs])


def test_overfit_small_dataset():
    tr = make_trainer()
    x, y = toy_data()
    first_err = None
    for epoch in range(60):
        for b in batches(x, y):
            tr.update(b)
    # final train error on the data itself
    errs = []
    for b in batches(x, y):
        pred = tr.predict(b)
        errs.append((pred != b.label[:, 0]).mean())
    err = float(np.mean(errs))
    assert err <= 0.05, f"did not overfit: err={err}"
    assert tr.epoch_counter == 60 * 4


def test_update_period_accumulation():
    tr = make_trainer("update_period = 2\n")
    x, y = toy_data(32)
    for b in batches(x, y):
        tr.update(b)
    # 2 micro-batches per update → epoch_counter advanced half as often
    assert tr.epoch_counter == 1
    assert tr.sample_counter == 0


def test_eval_train_metrics_and_format():
    tr = make_trainer()
    x, y = toy_data(32)
    for b in batches(x, y):
        tr.update(b)
    line = tr.evaluate(None, "train")
    assert "\ttrain-error:" in line and "\ttrain-logloss:" in line


def test_evaluate_iterator_trims_padding():
    from cxxnet_tpu.utils.metric import MetricSet

    tr = make_trainer()
    x, y = toy_data(32)

    class FakeIter:
        def __init__(self):
            self.pos = 0

        def before_first(self):
            self.pos = 0

        def next(self):
            self.pos += 1
            return self.pos <= 2

        def value(self):
            b = DataBatch(data=x[:16], label=y[:16])
            if self.pos == 2:
                b = DataBatch(data=x[16:32], label=y[16:32], num_batch_padd=6)
            return b

    line = tr.evaluate(FakeIter(), "val")
    assert "\tval-error:" in line
    # 16 + 10 = 26 instances counted
    assert tr.metric.metrics[0].cnt_inst == 26


def test_checkpoint_roundtrip(tmp_path):
    tr = make_trainer()
    x, y = toy_data(32)
    for b in batches(x, y):
        tr.update(b)
    path = str(tmp_path / "0001.model")
    tr.save_model(path)

    tr2 = NetTrainer()
    tr2.set_params(C.parse_pairs(MLP_CFG))
    tr2.load_model(path)
    assert tr2.epoch_counter == tr.epoch_counter
    b = DataBatch(data=x[:16], label=y[:16])
    np.testing.assert_allclose(tr.predict(b), tr2.predict(b))
    # loaded model can continue training
    tr2.update(b)


def test_finetune_copies_matched_layers(tmp_path):
    tr = make_trainer()
    path = str(tmp_path / "m.model")
    tr.save_model(path)

    # new net: same fc1 name, different fc2 size → only fc1 copied
    cfg2 = MLP_CFG.replace("nhidden = 4", "nhidden = 3")
    tr2 = NetTrainer()
    tr2.set_params(C.parse_pairs(cfg2))
    tr2.copy_model_from(path)
    np.testing.assert_allclose(
        tr2.get_weight("fc1", "wmat"), tr.get_weight("fc1", "wmat")
    )
    assert tr2.get_weight("fc2", "wmat").shape == (3, 32)
    assert tr2.epoch_counter == 0


def test_get_set_weight_2d():
    tr = make_trainer()
    w = tr.get_weight("fc1", "wmat")
    assert w.shape == (32, 8)
    neww = np.zeros_like(w)
    tr.set_weight(neww, "fc1", "wmat")
    np.testing.assert_allclose(tr.get_weight("fc1", "wmat"), 0.0)
    b = tr.get_weight("fc1", "bias")
    assert b.shape == (1, 32)


def test_conv_weight_2d_roundtrip():
    cfg = """
netconfig=start
layer[0->1] = conv:cv
  kernel_size = 3
  nchannel = 6
netconfig=end
input_shape = 3,8,8
batch_size = 4
"""
    tr = NetTrainer()
    tr.set_params(C.parse_pairs(cfg))
    tr.init_model()
    w2 = tr.get_weight("cv", "wmat")
    assert w2.shape == (6, 3 * 3 * 3)
    tr.set_weight(w2 * 2, "cv", "wmat")
    np.testing.assert_allclose(tr.get_weight("cv", "wmat"), w2 * 2, rtol=1e-6)


def test_predict_raw_single_column():
    cfg = """
netconfig=start
layer[0->1] = fullc:f
  nhidden = 1
layer[+0] = l2_loss
netconfig=end
input_shape = 1,1,4
batch_size = 8
"""
    tr = NetTrainer()
    tr.set_params(C.parse_pairs(cfg))
    tr.init_model()
    x = np.ones((8, 4), np.float32)
    pred = tr.predict(DataBatch(data=x, label=np.zeros((8, 1), np.float32)))
    # 1-column output: raw values, not argmax
    w = tr.get_weight("f", "wmat")
    bias = tr.get_weight("f", "bias")
    np.testing.assert_allclose(pred, (x @ w.T + bias)[:, 0], rtol=1e-4)


def test_extract_feature_by_name_and_top():
    tr = make_trainer()
    x, y = toy_data(16)
    b = DataBatch(data=x[:16], label=y[:16])
    f1 = tr.extract_feature(b, "fc1")
    assert f1.shape == (16, 32)
    # top[-1] = last node (softmax output)
    fo = tr.extract_feature(b, "top[-1]")
    assert fo.shape == (16, 4)
    np.testing.assert_allclose(fo.sum(-1), 1.0, rtol=1e-4)


def test_training_with_extra_data():
    """Side inputs (extra_data_num) must flow through the TRAIN path too."""
    cfg = """
extra_data_num = 1
extra_data_shape[0] = 1,1,3
netconfig=start
layer[0->2] = fullc:f1
  nhidden = 5
layer[in_1->3] = fullc:f2
  nhidden = 5
layer[2,3->4] = concat
layer[4->5] = fullc:f3
  nhidden = 2
layer[+0] = softmax
netconfig=end
input_shape = 1,1,4
batch_size = 8
eta = 0.1
"""
    from cxxnet_tpu.io.data import DataBatch

    tr = NetTrainer()
    tr.set_params(C.parse_pairs(cfg))
    tr.init_model()
    rng = np.random.RandomState(0)
    b = DataBatch(
        data=rng.randn(8, 4).astype(np.float32),
        label=np.zeros((8, 1), np.float32),
        extra_data=[rng.randn(8, 3).astype(np.float32)],
    )
    tr.update(b)  # must not raise
    assert tr.epoch_counter == 1
    out = tr.predict(b)
    assert out.shape == (8,)


def test_bfloat16_mixed_precision_converges():
    """compute_dtype=bfloat16: bf16 layer math, f32 master params + loss."""
    import jax.numpy as jnp

    tr = make_trainer("compute_dtype = bfloat16\n")
    assert tr.net.compute_dtype == jnp.bfloat16
    x, y = toy_data()
    for _ in range(60):
        for b in batches(x, y):
            tr.update(b)
    # master params stay f32
    for leaf in __import__("jax").tree_util.tree_leaves(tr.params):
        assert leaf.dtype == jnp.float32
    errs = []
    for b in batches(x, y):
        pred = tr.predict(b)
        errs.append((pred != b.label[:, 0]).mean())
    assert float(np.mean(errs)) <= 0.1


def test_remat_trains_identically():
    """remat=1 recomputes activations in backprop; numerics unchanged."""
    t_plain = make_trainer()
    t_remat = make_trainer("remat = 1\n")
    assert t_remat.net.remat == 1
    x, y = toy_data(32)
    for tr in (t_plain, t_remat):
        for b in batches(x, y):
            tr.update(b)
    for key in t_plain.params:
        for tag in t_plain.params[key]:
            np.testing.assert_allclose(
                np.asarray(t_plain.params[key][tag]),
                np.asarray(t_remat.params[key][tag]),
                rtol=1e-5, atol=1e-6,
            )


def test_batchnorm_running_stats():
    """bn_eval=running: eval uses EMA statistics carried as aux state and
    checkpointed; default stays reference batch-stats parity."""
    cfg = """
netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 16
  init_sigma = 0.1
layer[+0] = batch_norm:bn1
  bn_eval = running
  bn_momentum = 0.5
layer[+1:a1] = relu:a1
layer[a1->out] = fullc:fc2
  nhidden = 4
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end
input_shape = 1,1,8
batch_size = 16
eta = 0.1
"""
    tr = NetTrainer()
    tr.set_params(C.parse_pairs(cfg))
    tr.init_model()
    key = [k for k in tr.aux if "bn1" in k][0]
    assert np.all(np.asarray(tr.aux[key]["rmean"]) == 0)
    x, y = toy_data(32)
    for b in batches(x, y):
        tr.update(b)
    rmean = np.asarray(tr.aux[key]["rmean"])
    assert np.abs(rmean).max() > 0, "EMA stats did not update"
    # eval path consumes the running stats without error
    pred = tr.predict(DataBatch(data=x[:16], label=y[:16]))
    assert pred.shape == (16,)
    # aux round-trips through checkpoints
    import tempfile, os
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "m.model")
        tr.save_model(path)
        tr2 = NetTrainer()
        tr2.set_params(C.parse_pairs(cfg))
        tr2.load_model(path)
        np.testing.assert_allclose(
            np.asarray(tr2.aux[key]["rmean"]), rmean)
    # default (no bn_eval): no aux state, reference parity
    tr3 = NetTrainer()
    tr3.set_params(C.parse_pairs(cfg.replace("  bn_eval = running\n", "")))
    tr3.init_model()
    assert tr3.aux == {}


def test_remat_with_running_stats():
    """remat=1 + bn_eval=running: stateful layers are checkpointed too
    (state outputs are non-differentiable); numerics match no-remat."""
    cfg = """
netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 16
  init_sigma = 0.1
layer[+0] = batch_norm:bn1
  bn_eval = running
  bn_momentum = 0.5
layer[+1:a1] = relu:a1
layer[a1->out] = fullc:fc2
  nhidden = 4
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end
input_shape = 1,1,8
batch_size = 16
eta = 0.1
"""
    x, y = toy_data(32)
    trainers = []
    for extra in ("", "remat = 1\n"):
        tr = NetTrainer()
        tr.set_params(C.parse_pairs(cfg + extra))
        tr.init_model()
        if extra:
            assert tr.net.remat == 1
        for b in batches(x, y):
            tr.update(b)
        trainers.append(tr)
    t_plain, t_remat = trainers
    key = [k for k in t_plain.aux if "bn1" in k][0]
    np.testing.assert_allclose(
        np.asarray(t_plain.aux[key]["rmean"]),
        np.asarray(t_remat.aux[key]["rmean"]), rtol=1e-5, atol=1e-6)
    for k in t_plain.params:
        for tag in t_plain.params[k]:
            np.testing.assert_allclose(
                np.asarray(t_plain.params[k][tag]),
                np.asarray(t_remat.params[k][tag]),
                rtol=1e-5, atol=1e-6, err_msg=f"{k}/{tag}")


def test_short_final_train_batch_pad_and_mask():
    """A short train batch is zero-padded to the compiled batch size with
    padded rows masked out of the loss (the static-shape AdjustBatchSize,
    neural_net-inl.hpp:266-277): gradient comes from real rows only."""
    x, y = toy_data(10)
    tr_b = make_trainer()  # batch_size = 16
    tr_b.update(DataBatch(data=x, label=y))  # 10-row short batch
    assert tr_b.epoch_counter == 1

    # ground truth: masked loss = sum(real-row losses) / 16, which a
    # batch_size=10 trainer reproduces with grad_scale = 10/16
    cfg = MLP_CFG.replace("batch_size = 16", "batch_size = 10").replace(
        "layer[+0] = softmax",
        "layer[+0] = softmax\n  grad_scale = 0.625",
    )
    tr_a = NetTrainer()
    tr_a.set_params(C.parse_pairs(cfg))
    tr_a.init_model()
    tr_a.update(DataBatch(data=x, label=y))

    for key in tr_a.params:
        for tag in tr_a.params[key]:
            np.testing.assert_allclose(
                np.asarray(tr_a.params[key][tag]),
                np.asarray(tr_b.params[key][tag]),
                rtol=1e-5, atol=1e-6, err_msg=f"{key}/{tag}")

    # an oversize batch is a clear error, not silent truncation
    xb, yb = toy_data(20)
    with pytest.raises(ValueError, match="exceeds batch_size"):
        tr_b.update(DataBatch(data=xb, label=yb))


def test_num_batch_padd_rows_masked_in_training():
    """The IO chain's full-size final batch carries num_batch_padd filler
    rows (round_batch=0); update() must zero their loss contribution."""
    x, y = toy_data(16)
    garbage = DataBatch(
        data=x, label=y, num_batch_padd=6
    )  # rows 10..15 are filler
    tr_b = make_trainer()
    tr_b.update(garbage)

    cfg = MLP_CFG.replace("batch_size = 16", "batch_size = 10").replace(
        "layer[+0] = softmax",
        "layer[+0] = softmax\n  grad_scale = 0.625",
    )
    tr_a = NetTrainer()
    tr_a.set_params(C.parse_pairs(cfg))
    tr_a.init_model()
    tr_a.update(DataBatch(data=x[:10], label=y[:10]))

    for key in tr_a.params:
        for tag in tr_a.params[key]:
            np.testing.assert_allclose(
                np.asarray(tr_a.params[key][tag]),
                np.asarray(tr_b.params[key][tag]),
                rtol=1e-5, atol=1e-6, err_msg=f"{key}/{tag}")


def _train_line_values(line):
    return {k: float(v) for k, v in
            (item.split(":") for item in line.strip().split("\t"))}


def test_update_scan_matches_sequential_updates():
    """update_scan (lax.scan over the fused step, ONE device program)
    must advance params/epoch exactly like K sequential update() calls.
    The scan path is how a TPU training loop amortizes per-dispatch host
    cost (doc/performance.md).  Its train metrics are summed inside the
    program (utils/metric_device.py): on tie-free data the line they
    print is the line the host's metrics print over the K updates."""
    K = 5
    rng = np.random.RandomState(3)
    data = rng.randn(K, 16, 8).astype(np.float32)
    w = rng.randn(8, 4).astype(np.float32)
    labels = (data @ w).argmax(-1).astype(np.float32)[..., None]

    tr_seq = make_trainer("metric = rec@1\n")
    for i in range(K):
        tr_seq.update(DataBatch(data=data[i], label=labels[i]))

    tr_scan = make_trainer("metric = rec@1\n")
    losses = tr_scan.update_scan(data, labels)
    assert losses.shape == (K,)
    assert tr_scan.epoch_counter == K == tr_seq.epoch_counter
    for key in tr_seq.params:
        for tag in tr_seq.params[key]:
            np.testing.assert_allclose(
                np.asarray(tr_seq.params[key][tag]),
                np.asarray(tr_scan.params[key][tag]),
                rtol=1e-5, atol=1e-5, err_msg=f"{key}/{tag}")
    # train metrics were accumulated for all K steps: counts are equal,
    # the float sum to what two programs' float32 outputs allow
    for m_seq, m_scan in zip(tr_seq.train_metric.metrics,
                             tr_scan.train_metric.metrics):
        assert m_scan.cnt_inst == m_seq.cnt_inst == K * 16
    got = _train_line_values(tr_scan.train_metric.print("train"))
    want = _train_line_values(tr_seq.train_metric.print("train"))
    assert list(got) == ["train-error", "train-logloss", "train-rec@1"]
    assert got["train-error"] == want["train-error"]
    assert got["train-rec@1"] == want["train-rec@1"]
    assert got["train-rec@1"] == pytest.approx(1 - got["train-error"])
    assert got["train-logloss"] == pytest.approx(want["train-logloss"],
                                                 rel=1e-5)


DROPOUT_CFG = MLP_CFG.replace(
    "layer[+1:a1] = relu",
    "layer[+1:a1] = relu\nlayer[+0] = dropout\n  threshold = 0.3",
) + "metric = rec@1\nmetric = rec@3\n"


def _scan_chunk(eval_train):
    tr = NetTrainer()
    tr.set_params(C.parse_pairs(DROPOUT_CFG + f"eval_train = {eval_train}\n"))
    tr.init_model()
    rng = np.random.RandomState(5)
    data = rng.randn(4, 16, 8).astype(np.float32)
    labels = rng.randint(0, 4, (4, 16, 1)).astype(np.float32)
    return tr, tr.update_scan(data, labels)


def test_update_scan_train_metrics_leave_the_training_stream_alone():
    """rec@n's tie-break is drawn from the step's key folded with a
    constant: with dropout on, weights, momentum and losses after a
    chunk are bit for bit what eval_train = 0 trains from the seed."""
    tr1, losses1 = _scan_chunk(1)
    tr0, losses0 = _scan_chunk(0)
    np.testing.assert_array_equal(losses1, losses0)
    for a, b in zip(jax.tree_util.tree_leaves((tr1.params, tr1.ustates)),
                    jax.tree_util.tree_leaves((tr0.params, tr0.ustates))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(tr1._rng_key),
                                  np.asarray(tr0._rng_key))
    assert tr1.train_metric.metrics[0].cnt_inst == 64
    assert tr0.train_metric.metrics[0].cnt_inst == 0


def test_update_scan_program_returns_no_batch_axis():
    """The compiled scan hands back losses [K] and sums [K, n_metric]:
    no [K, B, classes] output to fetch."""
    import jax.numpy as jnp

    tr = make_trainer("metric = rec@1\n")
    K = 3
    fn = tr._scan_step_fn(K, True, True)
    # the jitted step under the device-telemetry wrapper
    out = jax.eval_shape(
        getattr(fn, "fn", fn), tr.params, tr.ustates, tr.aux,
        jax.ShapeDtypeStruct((K, 16, 8), jnp.float32),
        jax.ShapeDtypeStruct((K, 16, 1), jnp.float32),
        tr._rng_key, jnp.asarray(0, jnp.int32))
    losses, sums = out[-1]
    assert losses.shape == (K,)
    assert sums.shape == (K, 3) and sums.dtype == jnp.float32
    # and the program is keyed by the metric set it sums
    assert tr._scan_step_fn(K, True, True) is fn
    tr.train_metric.add_metric("rec@2")
    assert tr._scan_step_fn(K, True, True) is not fn


def test_metric_rows_counters_say_where_the_train_metrics_were_scored():
    from cxxnet_tpu.utils.profiler import pipeline_stats

    x, y = toy_data(16)
    tr = make_trainer()
    stats = pipeline_stats()
    stats.reset()
    tr.update_scan(np.stack([x] * 3), np.stack([y] * 3))
    got = stats.counters()
    assert got["metric_rows"] == got["metric_rows_device"] == 48
    stats.reset()
    tr.update(DataBatch(data=x, label=y))
    got = stats.counters()
    assert got["metric_rows"] == 16
    assert got.get("metric_rows_device", 0) == 0
    stats.reset()
    tr.eval_train = 0
    tr.update_scan(np.stack([x] * 3), np.stack([y] * 3))
    assert "metric_rows" not in stats.counters()
    stats.reset()


def test_update_scan_single_batch_mode():
    """[B,...] + n_steps: the same staged batch is reused each step
    (synthetic benchmark mode); loss must strictly decrease."""
    x, y = toy_data(16)
    tr = make_trainer()
    tr.eval_train = 0
    losses = tr.update_scan(x, y, n_steps=6)
    assert losses.shape == (6,)
    assert tr.epoch_counter == 6
    assert losses[-1] < losses[0], losses


def test_update_scan_requires_update_period_1():
    tr = make_trainer(extra="update_period = 2\n")
    x, y = toy_data(16)
    with pytest.raises(ValueError, match="update_period"):
        tr.update_scan(x, y, n_steps=2)


def test_save_ustate_exact_resume(tmp_path):
    """save_ustate=1 checkpoints momentum; load restores it bit-exact,
    so a resumed run continues identically. Default keeps the reference
    quirk (momentum NOT saved, restarts from zero)."""
    # dropout included: exact resume must continue the SAME rng stream
    # (the checkpoint carries the key), not just optimizer state
    cfg = [
        ("dev", "cpu"), ("batch_size", "8"), ("input_shape", "1,1,6"),
        ("eta", "0.1"), ("momentum", "0.9"),
        ("netconfig", "start"),
        ("layer[0->1]", "fullc:fc"), ("nhidden", "4"),
        ("layer[1->1]", "dropout"), ("threshold", "0.3"),
        ("layer[1->1]", "softmax"),
        ("netconfig", "end"),
    ]
    rng = np.random.RandomState(0)
    data = rng.randn(6, 8, 6).astype(np.float32)
    labels = rng.randint(0, 4, (6, 8, 1)).astype(np.float32)

    def train(tr, lo, hi):
        for i in range(lo, hi):
            tr.update_all(data[i], labels[i])

    # continuous run = ground truth
    t_full = NetTrainer(); t_full.set_params(cfg); t_full.init_model()
    train(t_full, 0, 6)

    # save at step 3 WITH ustate, resume, finish
    t_a = NetTrainer(); t_a.set_params(cfg)
    t_a.set_param("save_ustate", "1")
    t_a.init_model()
    train(t_a, 0, 3)
    ck = str(tmp_path / "m.model")
    t_a.save_model(ck)
    t_b = NetTrainer(); t_b.set_params(cfg)
    t_b.load_model(ck)
    st = t_b.ustates["l0_fc"]["wmat"]
    assert float(np.abs(np.asarray(st["m"])).max()) > 0  # momentum restored
    train(t_b, 3, 6)
    for tag in t_full.params["l0_fc"]:
        np.testing.assert_allclose(
            np.asarray(t_b.params["l0_fc"][tag]),
            np.asarray(t_full.params["l0_fc"][tag]),
            rtol=1e-5, atol=1e-6,
            err_msg=f"exact resume diverged on {tag}",
        )

    # default: momentum NOT saved (reference parity)
    t_c = NetTrainer(); t_c.set_params(cfg); t_c.init_model()
    train(t_c, 0, 3)
    ck2 = str(tmp_path / "m2.model")
    t_c.save_model(ck2)
    t_d = NetTrainer(); t_d.set_params(cfg)
    t_d.load_model(ck2)
    st = t_d.ustates["l0_fc"]["wmat"]
    assert float(np.abs(np.asarray(st["m"])).max()) == 0


MIDNODE_CFG = """
netconfig=start
layer[0->hid] = fullc:f1
  nhidden = 4
  init_sigma = 0.3
layer[hid->out] = fullc:f2
  nhidden = 4
  init_sigma = 0.3
layer[out->out] = softmax
netconfig=end
input_shape = 1,1,8
batch_size = 16
eta = 0.1
metric = error
metric[label,hid] = error
"""


def test_metric_node_selection_eval():
    """metric[field,node] scores the named mid-graph node
    (nnet_impl-inl.hpp:57-67, 363-372) — not just the final out."""
    tr = NetTrainer()
    tr.set_params(C.parse_pairs(MIDNODE_CFG))
    tr.init_model()
    assert tr.metric.nodes == [None, "hid"]
    x, y = toy_data(32)

    class OneShot:
        def __init__(self):
            self.done = False

        def before_first(self):
            self.done = False

        def next(self):
            if self.done:
                return False
            self.done = True
            return True

        def value(self):
            return DataBatch(data=x[:16], label=y[:16])

    line = tr.evaluate(OneShot(), "val")
    assert line.count("val-error") == 2
    # the node-bound metric must equal argmax over the hid node's values
    hid = tr.extract_feature(DataBatch(data=x[:16], label=y[:16]), "hid")
    expect = float((hid.argmax(1) != y[:16, 0]).mean())
    assert abs(tr.metric.metrics[1].get() - expect) < 1e-6
    # and differ from the final-out metric in general
    out_err = tr.metric.metrics[0].get()
    assert tr.metric.metrics[1].cnt_inst == 16
    assert isinstance(out_err, float)


def test_metric_node_selection_train():
    """eval_train with a node-bound metric runs the extra node forward."""
    tr = NetTrainer()
    tr.set_params(C.parse_pairs(MIDNODE_CFG + "eval_train = 1\n"))
    tr.init_model()
    x, y = toy_data(16)
    tr.update(DataBatch(data=x, label=y))
    assert tr.train_metric.metrics[1].cnt_inst == 16
    line = tr.evaluate(None, "train")
    assert line.count("train-error") == 2


def test_metric_bad_node_fails_at_init():
    tr = NetTrainer()
    tr.set_params(C.parse_pairs(
        MIDNODE_CFG.replace("metric[label,hid]", "metric[label,hdi]")
    ))
    with pytest.raises(ValueError, match="hdi"):
        tr.init_model()


def test_metric_node_same_weights_as_base():
    """Node-bound and final-out train metrics must score the SAME
    (pre-update) weight version in the fused update_period=1 path."""
    cfg = MIDNODE_CFG.replace("metric[label,hid] = error",
                              "metric[label,out] = error")
    tr = NetTrainer()
    tr.set_params(C.parse_pairs(cfg + "eval_train = 1\n"))
    tr.init_model()
    x, y = toy_data(16)
    tr.update(DataBatch(data=x, label=y))
    # 'out' IS the final node: both metrics see identical predictions,
    # so identical error — any pre/post-update skew would break this
    assert tr.train_metric.metrics[0].get() == tr.train_metric.metrics[1].get()


def test_update_scan_rejects_node_metrics():
    tr = NetTrainer()
    tr.set_params(C.parse_pairs(MIDNODE_CFG + "eval_train = 1\n"))
    tr.init_model()
    x, y = toy_data(16)
    with pytest.raises(ValueError, match="node-bound"):
        tr.update_scan(x, y, n_steps=2)
    # with eval_train off the scan path is allowed again
    tr2 = NetTrainer()
    tr2.set_params(C.parse_pairs(MIDNODE_CFG + "eval_train = 0\n"))
    tr2.init_model()
    tr2.update_scan(x, y, n_steps=2)
    assert tr2.epoch_counter == 2


INCEPTION_CFG = """
netconfig=start
layer[0->stem] = conv:stem
  kernel_size = 3
  pad = 1
  nchannel = 8
  init_sigma = 0.1
layer[stem->stem] = relu
layer[stem->b1] = conv:br1
  kernel_size = 1
  nchannel = 4
  init_sigma = 0.1
layer[stem->b2] = conv:br2
  kernel_size = 1
  nchannel = 6
  init_sigma = 0.1
layer[stem->b3] = conv:br3
  kernel_size = 1
  nchannel = 2
  init_sigma = 0.1
layer[b1,b2,b3->cat] = ch_concat
layer[cat->fl] = flatten
layer[fl->out] = fullc:fc
  nhidden = 4
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end
input_shape = 3,6,6
batch_size = 16
eta = 0.1
momentum = 0.9
metric = error
"""


@pytest.mark.parametrize("remat", ["0", "1"])
def test_fuse_1x1_sibling_convs_parity(remat):
    """fuse_1x1=1 executes the three sibling 1x1 branch convs as one
    concatenated conv; weights after training and predictions must match
    the unfused graph (same seed) to fp tolerance."""
    rng = np.random.RandomState(5)
    x = rng.randn(32, 6, 6, 3).astype(np.float32)
    y = rng.randint(0, 4, (32, 1)).astype(np.float32)

    def run(fuse):
        tr = NetTrainer()
        tr.set_params(C.parse_pairs(
            INCEPTION_CFG + f"fuse_1x1 = {fuse}\nremat = {remat}\n"
        ))
        tr.set_param("seed", "7")
        tr.init_model()
        groups, member = tr.net._sibling_1x1_groups()
        if fuse:
            assert [len(v) for v in groups.values()] == [3]
        for _ in range(3):
            for b in batches(x, y):
                tr.update(b)
        preds = np.concatenate(
            [tr.predict(b) for b in batches(x, y)]
        )
        return preds, jax.tree_util.tree_map(np.asarray, tr.params)

    p0, w0 = run(0)
    p1, w1 = run(1)
    f0 = {jax.tree_util.keystr(k): a
          for k, a in jax.tree_util.tree_leaves_with_path(w0)}
    f1 = {jax.tree_util.keystr(k): a
          for k, a in jax.tree_util.tree_leaves_with_path(w1)}
    assert sorted(f0) == sorted(f1)
    for k in f0:
        np.testing.assert_allclose(f0[k], f1[k], rtol=1e-5, atol=1e-5,
                                   err_msg=k)
    np.testing.assert_allclose(p0, p1, rtol=1e-5, atol=1e-5)


RESNET_BOUNDARY_CFG = """
netconfig=start
layer[0->stem] = conv:stem
  kernel_size = 3
  pad = 1
  nchannel = 8
  init_sigma = 0.1
layer[stem->stem] = relu
layer[stem->a] = conv:reduce
  kernel_size = 1
  stride = 2
  nchannel = 4
  init_sigma = 0.1
layer[a->ar] = relu
layer[ar->b] = conv:mid
  kernel_size = 3
  pad = 1
  nchannel = 4
  init_sigma = 0.1
layer[b->c] = conv:expand
  kernel_size = 1
  nchannel = 6
  init_sigma = 0.1
layer[stem->p] = conv:proj
  kernel_size = 1
  stride = 2
  nchannel = 6
  init_sigma = 0.1
layer[p,c->sum] = eltwise_sum
layer[sum->sum] = relu
layer[sum->fl] = flatten
layer[fl->out] = fullc:fc
  nhidden = 4
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end
input_shape = 3,6,6
batch_size = 16
eta = 0.1
momentum = 0.9
metric = error
"""


def test_fuse_1x1_strided_sibling_pair_parity():
    """Stride-2 1x1 siblings reading one node (ResNet's stage-boundary
    reduce + projection convs) fuse into one strided conv; the stride-1
    expand conv must NOT join their group (different key).  Training +
    prediction parity vs the unfused graph."""
    rng = np.random.RandomState(6)
    x = rng.randn(32, 6, 6, 3).astype(np.float32)
    y = rng.randint(0, 4, (32, 1)).astype(np.float32)

    def run(fuse):
        tr = NetTrainer()
        tr.set_params(C.parse_pairs(
            RESNET_BOUNDARY_CFG + f"fuse_1x1 = {fuse}\n"
        ))
        tr.set_param("seed", "9")
        tr.init_model()
        groups, _ = tr.net._sibling_1x1_groups()
        if fuse:
            # exactly one group: the two s2 convs (reduce + proj)
            assert [len(v) for v in groups.values()] == [2]
            (idxs,) = groups.values()
            names = {tr.net.graph.layers[j].name for j in idxs}
            assert names == {"reduce", "proj"}
        for _ in range(3):
            for b in batches(x, y):
                tr.update(b)
        preds = np.concatenate([tr.predict(b) for b in batches(x, y)])
        return preds, jax.tree_util.tree_map(np.asarray, tr.params)

    p0, w0 = run(0)
    p1, w1 = run(1)
    for k, (a, b) in {
        k: (a, b)
        for (k, a), (_, b) in zip(
            sorted((jax.tree_util.keystr(kp), leaf)
                   for kp, leaf in jax.tree_util.tree_leaves_with_path(w0)),
            sorted((jax.tree_util.keystr(kp), leaf)
                   for kp, leaf in jax.tree_util.tree_leaves_with_path(w1)),
        )
    }.items():
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5, err_msg=k)
    np.testing.assert_allclose(p0, p1, rtol=1e-5, atol=1e-5)


def test_fuse_1x1_respects_selfloop_writes():
    """A self-loop layer (relu writing the shared node) between sibling
    1x1 declarations versions the node: siblings across the write must
    NOT fuse (they read different values)."""
    cfg = """
netconfig=start
layer[0->stem] = conv:stem
  kernel_size = 3
  pad = 1
  nchannel = 8
  init_sigma = 0.1
layer[stem->b1] = conv:br1
  kernel_size = 1
  nchannel = 4
  init_sigma = 0.1
layer[stem->stem] = relu
layer[stem->b2] = conv:br2
  kernel_size = 1
  nchannel = 4
  init_sigma = 0.1
layer[b1,b2->cat] = ch_concat
layer[cat->fl] = flatten
layer[fl->out] = fullc:fc
  nhidden = 4
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end
input_shape = 3,6,6
batch_size = 8
eta = 0.1
metric = error
fuse_1x1 = 1
"""
    tr = NetTrainer()
    tr.set_params(C.parse_pairs(cfg))
    tr.init_model()
    groups, member = tr.net._sibling_1x1_groups()
    assert groups == {} and member == {}  # the relu write splits them

    # and the net still trains correctly through the plain path
    rng = np.random.RandomState(2)
    x = rng.randn(8, 6, 6, 3).astype(np.float32)
    y = rng.randint(0, 4, (8, 1)).astype(np.float32)
    tr.update(DataBatch(data=x, label=y))
    tr.sync()
