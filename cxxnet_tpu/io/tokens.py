"""Packed-token iterator for language-model training on a tokenized
corpus.

New TPU-first scope, beside ``text.py`` (which reads raw bytes, one
window a row): here the file is a flat stream of integer token ids,
little-endian ``uint16`` (``token_bytes = 2``, vocabularies up to
65536) or ``uint32`` (``token_bytes = 4``), in which documents of any
length lie end to end with the separator id 0 after each.  A row is ``seq_len`` consecutive tokens of that stream
and its labels are the same tokens moved on by one: no padding, every
position trains, and a document may begin, end or be cut anywhere in
a row.  The net's layers find the document starts from the ids
themselves (``ops/ssd.doc_index``), so the batch stays two arrays.

``iter = tokens`` config keys:

* ``filename``, ``token_bytes`` (2)
* ``seq_len`` — row length T (``label_width = T``)
* ``batch_size``
* ``shuffle`` / ``seed_data`` — one-shot shuffle of the rows
* ``round_batch`` — 1 wraps a short last batch (flagged as padding)
* ``attn_window`` — W > 0: the net's sliding-window attention layers'
  ``window``; the feed then counts ``attn_window_pairs`` too (default
  0: not counted)
* ``dist_num_worker`` / ``dist_worker_rank`` — equal-truncated row
  sharding (``data.shard_rows``)

Emits ``data (N, T)`` and ``label (N, T)`` as float32 ids (exact to
2**24; the ``embedding`` layer sets ``integer_input``, so they reach
the device unrounded).  Every batch counts into the round's
``PipelineStats`` counters (the telemetry record's ``counters``):
``tokens``, ``docs`` (separators in the rows fed, i.e. documents that
end there), ``docs_cut`` (rows whose last token is no separator: the
document running there is cut by the row's end) and ``attn_pairs`` (the
(query, key) pairs a causal query of its own document may see: the sum
over a row's documents of ``L (L + 1) / 2``, a document beginning at a
row's first token and after every separator as ``ops/ssd.doc_index``
has it — the work of masked attention whatever computes it) and, with
``attn_window = W``, ``attn_window_pairs`` (those of them less than W
positions apart, what a windowed layer's query may see: a document of
``L <= W`` gives ``L (L + 1) / 2``, a longer one ``W (W + 1) / 2 + (L -
W) W``); its own work is billed to the ``batch`` stage.
"""

from __future__ import annotations

import time

import numpy as np

from ..utils import faults
from ..utils.faults import RetryPolicy
from ..utils.profiler import pipeline_stats
from .data import DataBatch, DataIter

SEP_ID = 0  # closes a document; the layers read starts from it (ops/ssd.py)


def doc_lengths(rows: np.ndarray) -> np.ndarray:
    """The lengths of the documents of ``rows (N, T)`` of ids, a row's
    closing separators among them; the row's end cuts the last."""
    # a row's documents end at its separators and at its last token, so
    # in the flat order every document begins where the last one ended
    ends = rows == SEP_ID
    ends[:, -1] = True
    return np.diff(np.flatnonzero(ends), prepend=-1)


def attn_pairs(rows: np.ndarray, window: int = 0, lengths=None) -> int:
    """(query, key) pairs of ``rows (N, T)`` of ids under a causal mask
    inside documents (``lengths``: their ``doc_lengths``, where the
    caller has them): a document of ``L`` tokens has ``L (L + 1) / 2`` —
    under a ``window`` W > 0 (a query sees itself and the W - 1 before)
    the first ``min(L, W)`` queries see that triangle and every later
    one W keys."""
    length = doc_lengths(rows) if lengths is None else lengths
    head = np.minimum(length, window) if window > 0 else length
    return int((head * (head + 1) // 2 + (length - head) * window).sum())


class TokenIterator(DataIter):
    def supports_dist_shard(self) -> bool:
        return True

    def __init__(self) -> None:
        self.filename = ""
        self.token_bytes = 2
        self.seq_len = 0
        self.batch_size = 0
        self.shuffle = 0
        self.seed = 0
        self.silent = 0
        self.dist_num_worker = 1
        self.dist_worker_rank = 0
        self.round_batch = 1
        self.attn_window = 0
        self._retry_cfg: list = []
        self._raw: np.ndarray | None = None
        self._rows: np.ndarray | None = None
        self._loc = 0
        self._padd = 0

    def set_param(self, name, val):
        if name == "filename":
            self.filename = val
        elif name in ("token_bytes", "seq_len", "batch_size",
                      "shuffle", "silent", "dist_num_worker",
                      "dist_worker_rank", "round_batch", "attn_window"):
            setattr(self, name, int(val))
        elif name == "seed_data":
            self.seed = int(val)
        elif name in RetryPolicy.CONFIG_KEYS:
            self._retry_cfg.append((name, val))

    def init(self):
        if self.seq_len <= 0 or self.batch_size <= 0:
            raise ValueError("tokens: set seq_len and batch_size")
        if self.token_bytes not in (2, 4):
            raise ValueError("tokens: token_bytes is 2 or 4")
        dtype = np.dtype("<u2" if self.token_bytes == 2 else "<u4")

        def _read():
            faults.fault_point("tokens.read")
            return np.fromfile(self.filename, dtype)

        raw = RetryPolicy.from_cfg(self._retry_cfg).run(
            _read, what=f"reading {self.filename}",
            silent=bool(self.silent))
        t = self.seq_len
        nrow = (len(raw) - 1) // t  # a row's labels reach one token on
        if nrow <= 0:
            raise ValueError(
                f"tokens: {self.filename} has {len(raw)} tokens, need "
                f"more than seq_len={t}")
        rows = np.arange(nrow, dtype=np.int64)
        if self.shuffle:
            rows = rows[np.random.RandomState(42 + self.seed).permutation(
                nrow)]
        if self.dist_num_worker > 1:
            from .data import shard_rows

            rows = rows[shard_rows(nrow, self.dist_worker_rank,
                                   self.dist_num_worker)]
        self._raw, self._rows = raw, rows
        if not self.silent:
            print(f"TokenIterator: {self.filename}: {len(raw)} tokens -> "
                  f"{len(rows)} rows of T={t}")

    def before_first(self):
        self._loc = 0
        self._padd = 0

    def next(self) -> bool:
        assert self._rows is not None, "init() not called"
        n = len(self._rows)
        if self._loc + self.batch_size <= n:
            self._loc += self.batch_size
            self._padd = 0
            return True
        if self.round_batch and self._loc < n:
            self._padd = self._loc + self.batch_size - n
            self._loc = n
            return True
        return False

    def value(self) -> DataBatch:
        t0 = time.perf_counter()
        lo, hi = self._loc - self.batch_size + self._padd, self._loc
        t = self.seq_len
        take = self._rows[lo:hi]
        if self._padd:
            take = np.concatenate([take, self._rows[: self._padd]])
        win = self._raw[take[:, None] * t + np.arange(t + 1)[None, :]]
        fed = win[: len(win) - self._padd, :-1]
        stats = pipeline_stats()
        stats.count("tokens", fed.size)
        stats.count("docs", int((fed == SEP_ID).sum()))
        stats.count("docs_cut", int((fed[:, -1] != SEP_ID).sum()))
        lengths = doc_lengths(fed)
        stats.count("attn_pairs", attn_pairs(fed, lengths=lengths))
        if self.attn_window > 0:
            stats.count("attn_window_pairs",
                        attn_pairs(fed, self.attn_window, lengths))
        win = win.astype(np.float32)
        batch = DataBatch(
            data=win[:, :-1],
            label=win[:, 1:],
            inst_index=take.astype(np.uint32),
            num_batch_padd=self._padd,
        )
        stats.add("batch", time.perf_counter() - t0, rows=self.batch_size)
        return batch
