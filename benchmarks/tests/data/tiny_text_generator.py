"""A throw-away generator of another kind than ``lib/traffic.py``: a
seeded byte file for the program's ``iter = text``, dropped in as a file
to prove that a mix can bring its own (``helpers.copy_with_dropins``
copies it to ``traffic/``).

``make`` writes ``{nsample}`` windows of ``{seq_len}`` bytes and one
byte more (a window's labels are its bytes moved on by one) and hands
the template ``{text_file}``.  ``check_feed`` reads the file again and
finds every fed row in it: a window starts at a multiple of ``seq_len``.
"""

from __future__ import annotations

import os

import numpy as np


def make(mix: dict, fill: dict, out: str) -> dict:
    n, t = int(fill["nsample"]), int(fill["seq_len"])
    raw = np.random.RandomState(int(fill["seed"])).randint(
        0, int(fill.get("vocab", 256)), n * t + 1).astype(np.uint8)
    path = os.path.join(out, "tokens.bin")
    raw.tofile(path)
    return {"text_file": path}


def check_feed(mix: dict, fill: dict, data, labels):
    """The widest gap, in token ids, between a row the program fed its
    first chunk and the window of the file it must have come from
    (found by the row's first half; infinite where none fits), and
    between its labels and the same window moved on by one."""
    t = int(fill["seq_len"])
    raw = np.fromfile(os.path.join(fill["out"], "tokens.bin"), np.uint8)
    starts = {raw[s:s + t // 2].tobytes(): s
              for s in range(0, len(raw) - t, t)}
    rows = np.asarray(data).reshape(-1, t)
    labs = np.asarray(labels).reshape(-1, t)
    worst = 0.0
    for row, lab in zip(rows, labs):
        s = starts.get(np.round(row[:t // 2]).astype(np.uint8).tobytes())
        if s is None:
            return {"feed_gap_levels": float("inf"), "rows": int(len(rows))}
        worst = max(worst,
                    float(np.abs(row - raw[s:s + t]).max()),
                    float(np.abs(lab - raw[s + 1:s + t + 1]).max()))
    return {"feed_gap_levels": worst, "rows": int(len(rows))}
