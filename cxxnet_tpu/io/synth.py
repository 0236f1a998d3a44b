"""Synthetic data iterator (framework extension, not in the reference).

Generates a deterministic random dataset in RAM — the benchmark/test
stand-in for datasets that are not shipped (the reference assumes you
downloaded MNIST/ImageNet).  The labels are drawn from a fixed linear
teacher over the inputs so that models can actually *learn* from it in
overfit tests.

Config keys::

    nsample      number of instances (default 512)
    input_shape  C,H,W (same convention as the net config)
    nclass       number of classes (default 10)
    label_width  label columns (default 1; class id in column 0)
    batch_size   required
    seed_data    RNG seed
"""

from __future__ import annotations

import numpy as np

from .data import DataBatch, DataIter


class SyntheticIterator(DataIter):
    def supports_dist_shard(self) -> bool:
        return True

    def __init__(self) -> None:
        self.nsample = 512
        self.dist_num_worker = 1
        self.dist_worker_rank = 0
        self.input_shape = (1, 1, 16)
        self.nclass = 10
        self.label_width = 1
        self.layout = "auto"  # seq: emit (N, T, D) sequence batches
        self.batch_size = 0
        self.seed = 0
        self._loc = 0
        self._data: np.ndarray | None = None
        self._label: np.ndarray | None = None

    def set_param(self, name, val):
        if name == "nsample":
            self.nsample = int(val)
        elif name == "input_shape":
            z, y, x = (int(t) for t in val.split(","))
            self.input_shape = (z, y, x)
        elif name == "nclass":
            self.nclass = int(val)
        elif name == "label_width":
            self.label_width = int(val)
        elif name == "batch_size":
            self.batch_size = int(val)
        elif name == "seed_data":
            self.seed = int(val)
        elif name == "layout":
            self.layout = val
        elif name == "dist_num_worker":
            self.dist_num_worker = int(val)
        elif name == "dist_worker_rank":
            self.dist_worker_rank = int(val)

    def init(self):
        if self.batch_size <= 0:
            raise ValueError("SyntheticIterator: batch_size must be set")
        rng = np.random.RandomState(1234 + self.seed)
        c, h, w = self.input_shape
        if self.layout == "seq":
            shape = (self.nsample, h, w)
        elif c == 1 and h == 1:
            shape = (self.nsample, w)
        else:
            shape = (self.nsample, h, w, c)
        self._data = self._randn(rng, shape)
        flat = self._data.reshape(self.nsample, -1)
        teacher = self._randn(rng, (flat.shape[1], self.nclass))
        if self.dist_num_worker > 1 and self.dist_worker_rank > 0:
            # each worker draws DISTINCT samples (disjoint rng streams)
            # labelled by the SAME teacher; rank 0 keeps the exact
            # single-process stream so 1-vs-n runs stay comparable
            rng_k = np.random.RandomState(
                1234 + self.seed + 7919 * self.dist_worker_rank
            )
            self._data = self._randn(rng_k, shape)
            flat = self._data.reshape(self.nsample, -1)
        cls = (flat @ teacher).argmax(-1).astype(np.float32)
        lab = np.zeros((self.nsample, self.label_width), np.float32)
        lab[:, 0] = cls
        self._label = lab

    @staticmethod
    def _randn(rng, shape) -> np.ndarray:
        """``rng.randn(*shape).astype(float32)`` drawn a slab of rows at
        a time — the same stream (the legacy generator draws
        sequentially), without the whole-array float64 transient: an
        ImageNet-shaped set of 1024 samples is 0.6 GB in float32 and
        twice that again as float64."""
        out = np.empty(shape, np.float32)
        step = max(1, (1 << 22) // max(1, int(np.prod(shape[1:]))))
        for lo in range(0, shape[0], step):
            out[lo:lo + step] = rng.randn(*out[lo:lo + step].shape)
        return out

    def before_first(self):
        self._loc = 0

    def next(self) -> bool:
        assert self._data is not None, "init() not called"
        if self._loc + self.batch_size <= self.nsample:
            self._loc += self.batch_size
            return True
        return False

    def value(self) -> DataBatch:
        lo, hi = self._loc - self.batch_size, self._loc
        return DataBatch(
            data=self._data[lo:hi],
            label=self._label[lo:hi],
            inst_index=np.arange(lo, hi, dtype=np.uint32),
        )
