"""The one general traffic generator.  A traffic mix is a data file
(``traffic/<name>.json``) of parameters; this module turns it into the
iterator block of the conf and, where the mix asks for files (packed
images), makes them from the seed in the run's output directory.

Keys of a mix: ``dev`` (the conf's device string), ``batch_scale``
(global batch = the configuration's batch x this; 4 on four chips),
``chunks_per_round`` (a round is that many ``scan_steps`` chunks, at
least 3), ``conf`` (the iterator block, a list of lines with
``{placeholders}``), ``settings`` (further global conf lines),
``rehearsal`` (overrides for ``--cpu-rehearsal``).  Placeholders:
``{nsample} {input_shape} {batch_size} {num_class} {seed} {out}`` and,
where the mix has ``images`` (``size``, ``quality``, ``mean``,
``divideby``): ``{image_bin} {image_list} {mean} {divideby}``, a shard of seeded JPEGs packed here in the program's
documented page layout and its list file.
"""

from __future__ import annotations

import io
import os
import struct
from concurrent.futures import ThreadPoolExecutor
from typing import Dict

import numpy as np

PAGE_MAGIC = 0x43584250  # "CXBP": magic u32 | nrec u32 | lens u32 x nrec | blobs
PAGE_BYTES = 64 << 20


def make(mix: dict, fill: dict, out: str) -> Dict[str, str]:
    if int(mix["chunks_per_round"]) < 3:
        raise ValueError(
            "a round holds at least three chunks: with one, nothing that "
            "overlaps chunk k+1's host work with chunk k could ever show")
    if "images" not in mix:
        return {}
    im = mix["images"]
    n, size = int(fill["nsample"]), int(im["size"])
    bin_path = os.path.join(out, "images.bin")
    lst_path = os.path.join(out, "images.lst")
    bank = noise_bank(size, int(fill["seed"]))
    with ThreadPoolExecutor(8) as pool:  # PIL releases the GIL to encode
        blobs = list(pool.map(
            lambda i: jpeg_bytes(i, size, int(fill["seed"]), bank,
                                 int(im["quality"])), range(n)))
    write_pages(bin_path, blobs)
    with open(lst_path, "w", encoding="ascii") as f:
        for i in range(n):
            f.write(f"{i}\t{label_of(i, int(fill['num_class']))}\t{i}.jpg\n")
    return {"image_bin": bin_path, "image_list": lst_path,
            "mean": im["mean"], "divideby": im["divideby"]}


def label_of(i: int, num_class: int) -> int:
    return i % num_class


def image_pixels(i: int, size: int, seed: int, bank: np.ndarray) -> np.ndarray:
    """Image ``i`` of the seed's set as uint8 RGB: smooth gradients plus
    texture, so it decodes at a photograph's cost (noise would not)."""
    yy, xx = np.mgrid[0:size, 0:size].astype(np.float32)
    k = (seed + 7919 * i) % 104729
    base = (128 + 100 * np.sin(xx / (7 + k % 13) + k % 17)
            + 60 * np.cos(yy / (5 + k % 7) + k % 11))
    img = np.stack([base, np.roll(base, k % size, 0), base.T], axis=-1)
    img += np.roll(bank[k % len(bank)], (k % size, (3 * k) % size), (0, 1))
    return np.clip(img, 0, 255).astype(np.uint8)


def noise_bank(size: int, seed: int) -> np.ndarray:
    return (np.random.RandomState(seed).randn(8, size, size, 3) * 8).astype(
        np.float32)


def jpeg_bytes(i: int, size: int, seed: int, bank: np.ndarray,
               quality: int) -> bytes:
    from PIL import Image

    buf = io.BytesIO()
    Image.fromarray(image_pixels(i, size, seed, bank), "RGB").save(
        buf, "JPEG", quality=quality)
    return buf.getvalue()


def write_pages(path: str, blobs) -> None:
    with open(path, "wb") as f:
        page, used = [], 0

        def flush():
            if page:
                f.write(struct.pack("<II", PAGE_MAGIC, len(page)))
                for b in page:
                    f.write(struct.pack("<I", len(b)))
                for b in page:
                    f.write(b)

        for b in blobs:
            if page and used + len(b) + 8 > PAGE_BYTES:
                flush()
                page, used = [], 0
            page.append(b)
            used += len(b) + 4
        flush()


# ----------------------------------------------------------------------
# what the program fed its step, against the images this module made
def check_feed(mix: dict, fill: dict, data, labels, sample: int = 16):
    """For a mix with ``images``: a seeded sample of the rows the program
    handed to its first chunk, each held against this module's own
    decode (PIL) of the image it must have come from, under the crop
    and the mirror that fit best.  Returns the widest gap in pixel
    levels (0-255) and how many rows were looked at; None for a mix
    whose rows the program makes itself."""
    if "images" not in mix:
        return None
    from PIL import Image

    im = mix["images"]
    size, seed = int(im["size"]), int(fill["seed"])
    n, nclass = int(fill["nsample"]), int(fill["num_class"])
    mean, div = float(im["mean"]), float(im["divideby"])
    bank = noise_bank(size, seed)
    rows = data.reshape((-1,) + data.shape[2:])
    labs = np.asarray(labels).reshape(-1)
    rng = np.random.RandomState(seed)
    worst = 0.0
    picks = rng.choice(len(rows), size=min(sample, len(rows)), replace=False)
    for r in picks:
        row = np.asarray(rows[r], np.float32) * div + mean
        best = np.inf
        for i in range(int(labs[r]), n, nclass):
            blob = jpeg_bytes(i, size, seed, bank, int(im["quality"]))
            img = np.asarray(Image.open(io.BytesIO(blob)), np.float32)
            best = min(best, _best_crop_gap(img, row))
        worst = max(worst, best)
    return {"feed_gap_levels": float(worst), "rows": int(len(picks))}


def _best_crop_gap(img: np.ndarray, row: np.ndarray) -> float:
    """Max abs difference between ``row`` and the crop of ``img`` (either
    mirror) that fits an 8x8 probe patch best."""
    h, w = row.shape[:2]
    best = np.inf
    for target in (row, row[:, ::-1]):
        cy, cx = h // 2 - 4, w // 2 - 4
        probe = target[cy:cy + 8, cx:cx + 8]
        win = np.lib.stride_tricks.sliding_window_view(
            img[cy:, cx:], (8, 8, img.shape[2]))[:img.shape[0] - h + 1,
                                                  :img.shape[1] - w + 1, 0]
        err = np.abs(win - probe).reshape(win.shape[0], win.shape[1], -1
                                          ).max(-1)
        y, x = np.unravel_index(np.argmin(err), err.shape)
        best = min(best, float(np.abs(img[y:y + h, x:x + w] - target).max()))
    return best
