"""Chunk assembly for the scanned train loop: ``scan_steps`` batches
become one ``[K, B, ...]`` host block with ONE copy of each batch, into
memory that is recycled from chunk to chunk.

Why recycled: a block ``malloc`` has just mapped costs a page fault and
a zeroing per page on first touch, and is unmapped again a chunk later;
at 1.2 GB a chunk that was most of a training step's period (PERF.md,
PR 26); the TPU runtime also prepares a host region the first time it
is handed it (97 ms for 1.2 GB, 0.5 ms from then on).  Why by reference and not by position: a handed-out chunk may
be held by anyone — a caller that keeps it, the runtime while its
transfer runs, a CPU-backend device array that aliases the host memory —
and none of them announces itself.  So a chunk's arrays are views of an
``ndarray`` built over a raw buffer: every view's ``.base`` chain ends
at that owner, and a ``weakref.finalize`` on the owner returns the
buffer to the free list only when the last reference is gone.  A held
chunk keeps its block out of the list and the next chunk allocates
another; the number of blocks is whatever the holders force.
"""

from __future__ import annotations

import weakref
from typing import List, Optional, Tuple

import numpy as np

# Data and labels each start on a page.  Numpy's large arrays, the
# batches copied from, sit 16 bytes into a page; a destination 16 to
# ~500 bytes further into its page than the source makes every load of
# the copy alias a store in flight (4K aliasing): 4-5 GB/s where a page
# start takes 20, on the v5e's host (PERF.md, PR 26).  The CPU backend
# aliases a host array from 64 bytes' alignment up, and copies it else.
_ALIGN = 4096


class ChunkAssembler:
    """``add`` up to ``steps`` batches, ``take`` them as ``(data[:n],
    labels[:n])``.  ``allocated`` / ``recycled`` count the blocks newly
    mapped and the blocks taken from the free list since ``reset``."""

    def __init__(self, steps: int) -> None:
        self.steps = int(steps)
        self.allocated = 0
        self.recycled = 0
        self._layout: Optional[Tuple] = None  # batch shapes and dtypes
        self._free: List[bytearray] = []  # blocks of _layout nobody holds
        self._open: Optional[Tuple[np.ndarray, np.ndarray]] = None
        self._n = 0

    def __len__(self) -> int:
        return self._n

    def reset(self) -> None:
        """Start a round: forget a chunk left open (a round that raised)
        and zero the counters.  The free list stays."""
        self._open, self._n = None, 0
        self.allocated = self.recycled = 0

    def _block(self, layout: Tuple):
        """A ``[steps, ...]`` data and label array over one raw buffer."""
        if layout != self._layout:
            # blocks of the old layout still out return to the old list
            self._layout, self._free = layout, []
        k = self.steps
        d_shape, d_type, l_shape, l_type = layout
        d_bytes = k * d_type.itemsize * int(np.prod(d_shape))
        l_bytes = k * l_type.itemsize * int(np.prod(l_shape))
        l_at = -(-d_bytes // _ALIGN) * _ALIGN  # labels: the next page
        if self._free:
            raw = self._free.pop()
            self.recycled += 1
        else:
            raw = bytearray(_ALIGN + l_at + l_bytes)
            self.allocated += 1
        owner = np.frombuffer(raw, np.uint8)
        weakref.finalize(owner, self._free.append, raw).atexit = False
        page = -owner.ctypes.data % _ALIGN

        def view(at, nbytes, dtype, shape):
            return owner[page + at:page + at + nbytes].view(dtype).reshape(
                (k,) + shape)

        return (view(0, d_bytes, d_type, d_shape),
                view(l_at, l_bytes, l_type, l_shape))

    def add(self, data, label) -> None:
        """Copy one batch into the open chunk's next slot (the only copy
        of its bytes; iterator buffers are reused by ``next()``)."""
        data, label = np.asarray(data), np.asarray(label)
        layout = (data.shape, data.dtype, label.shape, label.dtype)
        if self._open is None:
            self._open = self._block(layout)
        elif layout != self._layout:
            raise ValueError(
                f"batch {self._n} of a chunk is {layout}, the chunk's "
                f"first was {self._layout}")
        blk_d, blk_l = self._open
        np.copyto(blk_d[self._n], data)
        np.copyto(blk_l[self._n], label)
        self._n += 1

    def take(self) -> Tuple[np.ndarray, np.ndarray]:
        """Close the chunk: the batches added, as leading slices of the
        block (contiguous, no copy).  The caller owns them now."""
        (blk_d, blk_l), n = self._open, self._n
        self._open, self._n = None, 0
        return blk_d[:n], blk_l[:n]
