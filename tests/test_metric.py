"""Unit tests for utils/metric.py — rec@n semantics incl. the
reference's random tie-break (src/utils/metric.h:150-170)."""

import numpy as np

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
    import pytest

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
