"""Data pipeline core: DataBatch, iterator protocol, chain factory.

Parity: ``/root/reference/src/io/data.h`` (``DataInst``/``DataBatch`` with
``num_batch_padd`` for short final batches, ``extra_data`` side inputs) and
``/root/reference/src/io/data.cpp:24-82`` (the ordered ``iter = X`` chain
factory: base iterators at the bottom, ``threadbuffer``/``membuffer``/
``attachtxt`` wrap the iterator below them; params following an ``iter=``
line configure the current top of the chain, which forwards them down).

Layout note: batches are NHWC (or flat ``(N, D)``) numpy float32 — the
TPU-native transposition of the reference's NCHW batches.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import numpy as np

ConfigEntry = Tuple[str, str]


@dataclasses.dataclass
class DataBatch:
    """One mini-batch. ``num_batch_padd`` trailing instances are padding
    (replicated data to keep shapes static) and must be excluded from
    evaluation/prediction output (data.h:86-88).

    The sparse part mirrors the reference's CSR fields
    (``data.h:97-101``: ``sparse_row_ptr`` / ``sparse_data``) with the
    Entry struct-array split into parallel index/value arrays — the
    layout ``scipy.sparse.csr_matrix`` and XLA gather/segment ops
    consume directly, instead of an array-of-structs a TPU can't use."""

    data: np.ndarray                  # (N, H, W, C) or (N, D)
    label: np.ndarray                 # (N, label_width) float32
    inst_index: Optional[np.ndarray] = None
    num_batch_padd: int = 0
    extra_data: List[np.ndarray] = dataclasses.field(default_factory=list)
    #: CSR row pointer, shape (N+1,), int64 — None for dense batches
    sparse_row_ptr: Optional[np.ndarray] = None
    #: CSR column indices (Entry.findex), shape (nnz,), int32
    sparse_index: Optional[np.ndarray] = None
    #: CSR values (Entry.fvalue), shape (nnz,), float32
    sparse_value: Optional[np.ndarray] = None

    @property
    def batch_size(self) -> int:
        return self.data.shape[0]

    def is_sparse(self) -> bool:
        """Parity: ``DataBatch::is_sparse`` (data.h:166-168)."""
        return self.sparse_row_ptr is not None

    def get_row_sparse(self, rid: int):
        """Row ``rid`` as (indices, values) — parity
        ``DataBatch::GetRowSparse`` (data.h:170-175)."""
        if not self.is_sparse():
            raise ValueError("GetRowSparse on a dense batch")
        lo, hi = self.sparse_row_ptr[rid], self.sparse_row_ptr[rid + 1]
        return self.sparse_index[lo:hi], self.sparse_value[lo:hi]


def shard_rows(n_rows: int, rank: int, nworker: int, block: int = 1):
    """Equal-length row shard for distributed data parallelism.

    ``block = 1`` (default): worker ``rank`` takes rows ``rank::nworker``
    truncated to ``n_rows // nworker`` — disjoint AND class-mixed even
    on unshuffled data.  Shards are always the same length: unequal
    shards (plain ``k::n`` slicing) deadlock the SPMD train loop — the
    process with one extra batch issues a collective the others never
    join.

    ``block > 1`` (``dist_shard = block`` with the LOCAL batch size):
    rows are dealt out in contiguous blocks of ``block`` round-robin,
    so worker ``rank``'s k-th local batch is exactly rows
    ``[k*B*nworker + rank*B, ... + B)`` of the global stream — the
    global SPMD batch assembled across workers is the IDENTICAL rows in
    the IDENTICAL order a single-process run of the same mesh feeds.
    That alignment is what makes the multi-process trainer bitwise equal
    to the single-process one (the MESH=1 parity lane): interleaved
    shards permute rows across data-axis shards, which reorders the
    gradient reduction and drifts ~1 ulp/step.  Returns an index array.
    """
    import numpy as _np

    if block <= 1:
        per = n_rows // nworker
        if per == 0:
            raise ValueError(
                f"cannot shard {n_rows} rows over {nworker} workers"
            )
        return _np.arange(rank, n_rows, nworker)[:per]
    nblocks = n_rows // (block * nworker)
    if nblocks == 0:
        raise ValueError(
            f"cannot shard {n_rows} rows over {nworker} workers in "
            f"blocks of {block}"
        )
    starts = (_np.arange(nblocks) * nworker + rank) * block
    return (starts[:, None] + _np.arange(block)[None, :]).reshape(-1)


class DataIter:
    """Iterator protocol (parity: ``IIterator``, data.h:19-39)."""

    #: True for source iterators that honor ``dist_num_worker`` /
    #: ``dist_worker_rank`` (wrappers delegate).  The CLI refuses to
    #: run multi-process with a train iterator that would silently feed
    #: every process identical data.
    def supports_dist_shard(self) -> bool:
        return False

    def set_param(self, name: str, val: str) -> None:  # noqa: D401
        pass

    def init(self) -> None:
        pass

    def before_first(self) -> None:
        raise NotImplementedError

    def next(self) -> bool:
        raise NotImplementedError

    def value(self) -> DataBatch:
        raise NotImplementedError

    def close(self) -> None:
        """Release resources (threads, native readers).  Idempotent;
        wrappers delegate down the chain.  Base iterators holding no
        resources inherit this no-op."""

    # python sugar
    def __iter__(self):
        self.before_first()
        while self.next():
            yield self.value()


def create_iterator(cfg: Sequence[ConfigEntry]) -> DataIter:
    """Build an iterator chain from an ordered config section."""
    # imports here to avoid cycles
    from .augment import AugmentIterator
    from .batch import BatchAdaptIterator
    from .csv import CSVIterator
    from .img import ImageIterator
    from .imgbin import ImageBinIterator
    from .membuffer import MemBufferIterator
    from .mnist import MNISTIterator
    from .pipeline import ParallelAugmentIterator
    from .prefetch import ThreadBufferIterator
    from .synth import SyntheticIterator
    from .attach_txt import AttachTxtIterator
    from .libsvm import LibSVMIterator
    from .text import TextIterator
    from .tokens import TokenIterator

    it: Optional[DataIter] = None
    for name, val in cfg:
        if name == "iter":
            if val == "mnist":
                if it is not None:
                    raise ValueError("mnist cannot chain over another iterator")
                it = MNISTIterator()
            elif val in ("imgbin", "imgbinx"):
                if it is not None:
                    raise ValueError("imgbin cannot chain over another iterator")
                # the decode+augment stage parallelizes when the section
                # sets num_decode_workers > 1 (io/pipeline.py); it is a
                # transparent pass-through otherwise
                it = BatchAdaptIterator(ParallelAugmentIterator(
                    AugmentIterator(ImageBinIterator())))
            elif val == "img":
                if it is not None:
                    raise ValueError("img cannot chain over another iterator")
                it = BatchAdaptIterator(ParallelAugmentIterator(
                    AugmentIterator(ImageIterator())))
            elif val == "csv":
                if it is not None:
                    raise ValueError("csv cannot chain over another iterator")
                it = BatchAdaptIterator(CSVIterator())
            elif val == "synthetic":
                if it is not None:
                    raise ValueError("synthetic cannot chain over another iterator")
                it = SyntheticIterator()
            elif val == "text":
                if it is not None:
                    raise ValueError("text cannot chain over another iterator")
                it = TextIterator()
            elif val == "tokens":
                if it is not None:
                    raise ValueError("tokens cannot chain over another iterator")
                it = TokenIterator()
            elif val == "libsvm":
                if it is not None:
                    raise ValueError("libsvm cannot chain over another iterator")
                it = LibSVMIterator()
            elif val == "service":
                if it is not None:
                    raise ValueError("service cannot chain over another iterator")
                # network base iterator: streams blocks from a shared
                # task=data_service decode fleet (io/dataservice/)
                from .dataservice.client import ServiceIterator

                it = ServiceIterator()
            elif val == "threadbuffer":
                if it is None:
                    raise ValueError("must specify input of threadbuffer")
                it = ThreadBufferIterator(it)
            elif val == "membuffer":
                if it is None:
                    raise ValueError("must specify input of membuffer")
                it = MemBufferIterator(it)
            elif val == "attachtxt":
                if it is None:
                    raise ValueError("must specify input of attachtxt")
                it = AttachTxtIterator(it)
            elif val == "end":
                continue
            else:
                raise ValueError(f"unknown iterator type {val!r}")
            continue
        if it is not None:
            it.set_param(name, val)
    if it is None:
        raise ValueError("must specify iterator by iter=itername")
    return it
