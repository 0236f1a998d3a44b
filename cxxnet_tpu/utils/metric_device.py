"""The metrics of ``utils/metric.py`` as ``jax.numpy`` row sums.

Every metric is a sum over rows, so a step program can reduce its
output node to one number per metric and hand the host ``n_metric``
scalars where it handed it ``[B, classes]`` scores
(``nnet/trainer.py`` ``update_scan``).  Each function here is the twin
of one ``Metric._batch_sum``: the same rows in, the same sum out —
counts (``error``, ``rec@n`` hits) are taken in int32 and are exact,
float sums are float32 on the float32 prediction.

``rec@n`` needs no sort.  A label is in the top n iff fewer than n
classes rank before it: ``#(score > s_label) + #(score == s_label and
jitter < jitter_label) < n`` — the law of the host's
``lexsort((jitter, -pred))``, equal scores entering the top n in
uniformly random order, and the same answer wherever a row has no tie at
the label's score.

``metric.py`` stays importable without jax; this module imports it.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .metric import (Metric, MetricError, MetricLogloss, MetricRecall,
                     MetricRMSE, MetricSet)

_EPS = 1e-15


def _target(label) -> jax.Array:
    return label[:, 0].astype(jnp.int32)


def error_sum(pred, label) -> jax.Array:
    if pred.shape[1] != 1:
        guess = jnp.argmax(pred, axis=1).astype(jnp.int32)
    else:
        guess = (pred[:, 0] > 0).astype(jnp.int32)
    return jnp.sum(guess != _target(label), dtype=jnp.int32)


def rmse_sum(pred, label) -> jax.Array:
    if pred.shape != label.shape:
        raise ValueError("rmse: prediction and label sizes must match")
    return jnp.sum((pred - label.astype(pred.dtype)) ** 2)


def logloss_sum(pred, label) -> jax.Array:
    """NaN where any row's probability is NaN (``jnp.clip`` passes it
    through, and an out-of-range target reads as one): the host raises
    on the sum as ``MetricLogloss._batch_sum`` raises on the row."""
    if pred.shape[1] != 1:
        p = jnp.take_along_axis(pred, _target(label)[:, None], axis=1,
                                mode="fill", fill_value=jnp.nan)[:, 0]
        res = -jnp.log(jnp.clip(p, _EPS, 1 - _EPS))
    else:
        p = jnp.clip(pred[:, 0], _EPS, 1 - _EPS)
        y = label[:, 0].astype(pred.dtype)
        res = -(y * jnp.log(p) + (1 - y) * jnp.log(1 - p))
    return jnp.sum(res)


def recall_sum(pred, label, topn: int, key) -> jax.Array:
    """Hits of the label list in the top ``topn``, over the list's
    length.  ``key`` draws the tie-break: one uint32 per score."""
    n, c = pred.shape
    if c < topn:
        raise ValueError(
            f"rec@{topn} meaningless for prediction list of size {c}")
    lab = label.astype(jnp.int32)                          # (N, L)
    known = (lab >= 0) & (lab < c)
    at = jnp.clip(lab, 0, c - 1)
    jitter = jax.random.bits(key, (n, c), jnp.uint32)
    s_lab = jnp.take_along_axis(pred, at, axis=1)[:, :, None]
    j_lab = jnp.take_along_axis(jitter, at, axis=1)[:, :, None]
    s, j = pred[:, None, :], jitter[:, None, :]
    before = (s > s_lab) | ((s == s_lab) & (j < j_lab))   # (N, L, C)
    rank = jnp.sum(before, axis=2, dtype=jnp.int32)
    hits = jnp.sum(known & (rank < topn), dtype=jnp.int32)
    return hits.astype(jnp.float32) / label.shape[1]


def row_sum(mt: Metric, pred, label, key) -> jax.Array:
    """``mt._batch_sum(pred, label)`` on the device, as a float32
    scalar.  pred: (N, K) float32 scores; label: (N, L) field columns."""
    if isinstance(mt, MetricRecall):
        total = recall_sum(pred, label, mt.topn, key)
    elif isinstance(mt, MetricLogloss):  # perplexity too
        total = logloss_sum(pred, label)
    elif isinstance(mt, MetricRMSE):
        total = rmse_sum(pred, label)
    elif isinstance(mt, MetricError):
        total = error_sum(pred, label)
    else:
        raise ValueError(f"Metric: no device sum for {mt.name!r}")
    return total.astype(jnp.float32)


def set_sums(mset: MetricSet, pred, labels,
             label_ranges: Dict[str, Tuple[int, int]], key) -> jax.Array:
    """One batch's sums of every metric of ``mset`` on the out node's
    prediction, shape ``[n_metric]`` float32: ``MetricSet.add_eval``
    inside a traced program (its field slices, its ``(N, T, V)``
    reshape and width check, at trace time).  Each ``rec@n`` draws its
    tie-break from ``key`` folded with the metric's index."""
    return jnp.stack([
        row_sum(mt, p, lab, jax.random.fold_in(key, i))
        for i, (mt, p, lab) in enumerate(
            mset.views(pred, labels, label_ranges))
    ])
