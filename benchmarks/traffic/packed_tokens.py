"""The generator of the packed-token mixes (``train_packed8k.json``): a
seeded file of token ids for the program's ``iter = tokens``.

``make`` writes ``{out}/tokens.bin``, little-endian ``uint16``: documents
whose lengths are log-normal (the mix's ``documents``: ``median``
tokens, ``sigma``, clipped to ``min`` .. the row length), each made of
ids uniform over 1 .. vocab-1 and closed by the separator id 0, laid end
to end until ``{nsample}`` rows of ``{seq_len}`` tokens and one token
more are full (a row's labels are the stream moved on by one; nothing
is padded, a document is cut where a row ends).  All from ``{seed}``.
It hands the template ``{token_file}``.

``check_feed`` reads the file again, by itself, and holds the rows and
labels the program fed its first chunk against it id for id: the first
chunk of an unshuffled feed is the file's first rows in order.
"""

from __future__ import annotations

import math
import os

import numpy as np

SEP_ID = 0


def stream(n_tokens: int, seq_len: int, vocab: int, docs: dict,
           seed: int) -> np.ndarray:
    """``n_tokens`` ids of the packed stream, from the seed."""
    rng = np.random.RandomState(int(seed) % 2147483629)
    lo, hi = min(int(docs["min"]), seq_len), seq_len
    parts, have = [], 0
    while have < n_tokens:
        # draw lengths in bulk; the stream takes them in order
        lens = np.clip(np.round(np.exp(rng.normal(
            math.log(float(docs["median"])), float(docs["sigma"]), 64))),
            lo, hi).astype(np.int64)
        for n in lens:
            doc = rng.randint(1, vocab, int(n))
            doc[-1] = SEP_ID
            parts.append(doc)
            have += int(n)
            if have >= n_tokens:
                break
    return np.concatenate(parts)[:n_tokens].astype("<u2")


def make(mix: dict, fill: dict, out: str) -> dict:
    t, vocab = int(fill["seq_len"]), int(fill["vocab"])
    if vocab > 65536:
        raise SystemExit("packed_tokens: ids are written as uint16")
    raw = stream(int(fill["nsample"]) * t + 1, t, vocab, mix["documents"],
                 int(fill["seed"]))
    path = os.path.join(out, "tokens.bin")
    raw.tofile(path)
    return {"token_file": path}


def check_feed(mix: dict, fill: dict, data, labels):
    """The widest gap, in token ids, between what the program fed its
    first chunk and the file's first rows: 0 where they are equal id
    for id."""
    t = int(fill["seq_len"])
    raw = np.fromfile(os.path.join(fill["out"], "tokens.bin"), "<u2").astype(
        np.float64)
    rows = np.asarray(data, np.float64).reshape(-1, t)
    labs = np.asarray(labels, np.float64).reshape(-1, t)
    n = len(rows)
    if n * t + 1 > len(raw):
        return {"feed_gap_levels": float("inf"), "rows": int(n)}
    gap = max(float(np.abs(rows - raw[:n * t].reshape(n, t)).max()),
              float(np.abs(labs - raw[1:n * t + 1].reshape(n, t)).max()))
    return {"feed_gap_levels": gap, "rows": int(n)}
