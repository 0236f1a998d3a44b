"""Unit tests for utils/metric.py — rec@n semantics incl. the
reference's random tie-break (src/utils/metric.h:150-170)."""

import numpy as np
import pytest

from cxxnet_tpu.utils.metric import create_metric


def _score(name, pred, label):
    m = create_metric(name)
    m.add_eval(pred, label)
    return m.get()


def test_rec_at_1_matches_accuracy_on_distinct_scores():
    pred = np.array(
        [[0.1, 0.7, 0.2], [0.9, 0.05, 0.05], [0.2, 0.3, 0.5]], np.float32
    )
    label = np.array([[1.0], [2.0], [2.0]], np.float32)
    assert _score("rec@1", pred, label) == 2.0 / 3.0


def test_rec_at_n_multi_label_list():
    # label_width 2: fraction of the label list found in the top-n
    pred = np.array([[0.4, 0.3, 0.2, 0.1]], np.float32)
    label = np.array([[0.0, 3.0]], np.float32)  # one in top-2, one not
    assert _score("rec@2", pred, label) == 0.5


def test_rec_at_n_random_tiebreak_spreads_equal_scores():
    # all scores equal: a deterministic argsort would always pick class
    # 0, scoring exactly 1.0 for label 0 and 0.0 for any other label.
    # The reference shuffles before sorting; with 200 instances labelled
    # class 7 of 10, random tie-break recalls ~1/10, never 0 or 1.
    n, c = 200, 10
    pred = np.ones((n, c), np.float32)
    label = np.full((n, 1), 7.0, np.float32)
    got = _score("rec@1", pred, label)
    assert 0.0 < got < 1.0
    assert abs(got - 1.0 / c) < 0.1

    # seeded: two fresh metric instances agree exactly
    assert got == _score("rec@1", pred, label)


def test_rec_at_n_tiebreak_keeps_clear_winners():
    # random tie-break must not disturb strictly ordered scores
    rng = np.random.RandomState(3)
    pred = rng.rand(64, 12).astype(np.float32)
    label = np.argmax(pred, axis=1).astype(np.float32)[:, None]
    assert _score("rec@1", pred, label) == 1.0


def test_logloss_raises_on_nan_in_both_branches():
    """A diverged net must stop the run: np.clip passes NaN through, so
    the multiclass branch used to print ``logloss:nan`` round after
    round where the binary branch raised."""
    from cxxnet_tpu.utils.metric import MetricLogloss

    m = MetricLogloss()
    label = np.array([[1.0], [0.0]], np.float32)
    ok = np.array([[0.2, 0.8], [0.6, 0.4]], np.float32)
    assert np.isclose(m._batch_sum(ok, label), -np.log(0.8) - np.log(0.6))
    bad = ok.copy()
    bad[1, 0] = np.nan  # the target-class probability of row 1
    with pytest.raises(FloatingPointError, match="NaN"):
        m._batch_sum(bad, label)
    with pytest.raises(FloatingPointError, match="NaN"):
        m._batch_sum(np.array([[np.nan], [0.5]], np.float32), label)


# ----------------------------------------------------------------------
# the device twins (utils/metric_device.py): a step program's own row
# sums against Metric._batch_sum
def _probs(rng, n, c):
    p = rng.rand(n, c).astype(np.float32) + 0.05
    return p / p.sum(axis=1, keepdims=True)


def _case(name):
    """(metric, pred, labels, label_ranges, exact) on tie-free rows."""
    rng = np.random.RandomState(11)
    n, c = 96, 20
    pred = _probs(rng, n, c)
    one = {"label": (0, 1)}
    target = rng.randint(0, c, (n, 1)).astype(np.float32)
    if name == "error_argmax":
        return "error", pred, target, one, True
    if name == "error_one_column":
        return ("error", rng.randn(n, 1).astype(np.float32),
                rng.randint(0, 2, (n, 1)).astype(np.float32), one, True)
    if name == "rmse":
        return ("rmse", pred, rng.rand(n, c).astype(np.float32),
                {"label": (0, c)}, False)
    if name == "logloss_multiclass":
        return "logloss", pred, target, one, False
    if name == "logloss_binary":
        return ("logloss", _probs(rng, n, 2)[:, :1],
                rng.randint(0, 2, (n, 1)).astype(np.float32), one, False)
    if name == "perplexity":
        return "perplexity", pred, target, one, False
    if name in ("rec@1", "rec@5"):
        return name, pred, target, one, True
    if name == "rec@3_two_columns":
        # the field is columns [1, 3) of a three-column label
        labels = rng.randint(0, c, (n, 3)).astype(np.float32)
        return "rec@3", pred, labels, {"label": (0, 1), "tags": (1, 3)}, True
    if name == "sequence_error":
        t = 6
        return ("error", _probs(rng, n * t, c).reshape(n, t, c),
                rng.randint(0, c, (n, t)).astype(np.float32),
                {"label": (0, t)}, True)
    raise AssertionError(name)


@pytest.mark.parametrize("name", [
    "error_argmax", "error_one_column", "rmse", "logloss_multiclass",
    "logloss_binary", "perplexity", "rec@1", "rec@5", "rec@3_two_columns",
    "sequence_error"])
def test_device_row_sum_matches_the_host_metric(name):
    import jax

    from cxxnet_tpu.utils import metric_device
    from cxxnet_tpu.utils.metric import MetricSet

    kind, pred, labels, ranges, exact = _case(name)
    field = "tags" if "tags" in ranges else "label"
    host, dev = MetricSet(), MetricSet()
    for m in (host, dev):
        m.add_metric(kind, field)
    host.add_eval(pred, labels, ranges)
    sums = jax.jit(
        lambda p, l, k: metric_device.set_sums(dev, p, l, ranges, k)
    )(pred, labels, jax.random.PRNGKey(5))
    assert sums.shape == (1,) and sums.dtype == np.float32
    rows = int(np.prod(pred.shape[:-1]))
    dev.add_sums(np.asarray(sums)[None], rows)
    h, d = host.metrics[0], dev.metrics[0]
    assert d.cnt_inst == h.cnt_inst == rows
    if exact:
        assert d.sum_metric == h.sum_metric
        assert dev.print("train") == host.print("train")
    else:
        assert d.sum_metric == pytest.approx(h.sum_metric, rel=1e-6)


def test_device_sequence_metric_checks_the_field_width():
    import jax.numpy as jnp

    from cxxnet_tpu.utils import metric_device
    from cxxnet_tpu.utils.metric import MetricSet

    ms = MetricSet()
    ms.add_metric("error")
    with pytest.raises(ValueError, match="label field of width 6"):
        metric_device.set_sums(ms, jnp.zeros((4, 6, 5)), jnp.zeros((4, 5)),
                               {"label": (0, 5)}, None)


def test_device_rec_at_n_tiebreak():
    """The device twins of the two tie-break tests above: a net that
    starts at zero ties all 1000 classes and must read rec@5 = 5/1000,
    not 1 and not 0; strictly ordered scores are not disturbed."""
    import jax

    from cxxnet_tpu.utils import metric_device

    m = create_metric("rec@5")
    n, c = 4096, 1000
    f = jax.jit(lambda p, l, k: metric_device.row_sum(m, p, l, k))
    key = jax.random.PRNGKey(0)
    tied = f(np.ones((n, c), np.float32), np.full((n, 1), 7.0, np.float32),
             key)
    assert abs(float(tied) / n - 0.005) < 0.004
    # seeded: the same key draws the same ties
    assert float(tied) == float(f(np.ones((n, c), np.float32),
                                  np.full((n, 1), 7.0, np.float32), key))

    rng = np.random.RandomState(3)
    pred = rng.rand(64, 12).astype(np.float32)
    label = np.argmax(pred, axis=1).astype(np.float32)[:, None]
    m1 = create_metric("rec@1")
    assert float(metric_device.row_sum(m1, pred, label, key)) == 64.0
    # a label outside the classes is in no top n (np.isin finds none)
    label[:8] = 12.0
    label[8:16] = -1.0
    assert float(metric_device.row_sum(m1, pred, label, key)) == 48.0


def test_update_scan_raises_on_nan_probabilities():
    """A diverged net stops the run through the scanned path too: the
    step's logloss sum is NaN and the host refuses it."""
    from cxxnet_tpu import config as C
    from cxxnet_tpu.nnet.trainer import NetTrainer

    cfg = """
netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 4
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end
input_shape = 1,1,8
batch_size = 16
eta = 0.1
metric = logloss
"""
    tr = NetTrainer()
    tr.set_params(C.parse_pairs(cfg))
    tr.init_model()
    rng = np.random.RandomState(0)
    data = rng.randn(2, 16, 8).astype(np.float32)
    data[1, 3, 2] = np.nan
    labels = rng.randint(0, 4, (2, 16, 1)).astype(np.float32)
    with pytest.raises(FloatingPointError, match="logloss: NaN detected!"):
        tr.update_scan(data, labels)
