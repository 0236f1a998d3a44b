"""Parallel host data pipeline: determinism, vectorized-augment parity,
quarantine-through-the-pool, and the persistent compile cache.

The load-bearing contract (ISSUE 4): the augmentation stream is a pure
function of ``(seed_data, epoch, record index)`` — decode worker count,
chunking, buffer depth, and mid-epoch rewinds must produce
**bitwise-identical** batches to the serial path.
"""

import io as _io
import os

import numpy as np
import pytest

from cxxnet_tpu import config as cfgmod
from cxxnet_tpu.io.batch import DataInst, InstIterator
from cxxnet_tpu.io.data import create_iterator
from cxxnet_tpu.io.imgbin import BinPageWriter, encode_raw


def _write_jpeg_imgbin(tmp_path, n=23, size=16, page_size=4096):
    from PIL import Image

    rng = np.random.RandomState(0)
    binp = str(tmp_path / "d.bin")
    w = BinPageWriter(binp, page_size=page_size)
    lst = tmp_path / "d.lst"
    with open(lst, "w") as f:
        for i in range(n):
            img = (rng.rand(size, size, 3) * 255).astype(np.uint8)
            buf = _io.BytesIO()
            Image.fromarray(img).save(buf, "JPEG", quality=90)
            w.push(buf.getvalue())
            f.write(f"{i}\t{i % 3}\tx.jpg\n")
    w.close()
    return binp, str(lst)


AUG = """  rand_crop = 1
  rand_mirror = 1
  max_random_contrast = 0.2
  max_random_illumination = 5
  mean_value = 1,2,3
  scale = 0.0039
"""


def _chain(binp, lst, extra="", aug=AUG, batch=4, shape="3,12,12",
           round_batch=1):
    conf = f"""
data = train
iter = imgbin
  image_bin = "{binp}"
  image_list = "{lst}"
  native_decoder = 0
  silent = 1
{aug}  input_shape = {shape}
  batch_size = {batch}
  round_batch = {round_batch}
  label_width = 1
  seed_data = 7
{extra}
iter = end
"""
    sec = cfgmod.split_sections(cfgmod.parse_pairs(conf)).find("data")[0]
    it = create_iterator(sec.entries)
    it.init()
    return it


def _epochs(it, n_epochs=2):
    """Collect ``n_epochs`` of batches, then CLOSE the chain — every
    call site's last use of its iterator.  Leaving decode pools alive
    was this module's contribution to the suite-wide daemon-thread
    leak (the multi-file flake suspect conftest now bounds)."""
    out = []
    for _ in range(n_epochs):
        it.before_first()
        while it.next():
            b = it.value()
            out.append((b.data.tobytes(), b.label.tobytes(),
                        b.num_batch_padd))
    it.close()
    return out


@pytest.mark.parametrize("workers", [1, 4])
def test_pool_bitwise_identical_to_serial(tmp_path, workers):
    """num_decode_workers in {1, 4} == the serial path, bitwise, over
    two epochs (full augmentation armed: crop/mirror/mean/jitter/scale
    — the float tail runs split across worker and consumer)."""
    binp, lst = _write_jpeg_imgbin(tmp_path)
    ref = _epochs(_chain(binp, lst))
    got = _epochs(_chain(
        binp, lst,
        extra=f"  num_decode_workers = {workers}\n  decode_chunk = 3\n",
    ))
    assert got == ref


def test_pool_bitwise_identical_no_tail(tmp_path):
    """The tail-identity fast path (no mean/jitter/scale: uint8 flows
    to the batch store-cast) is also bitwise identical."""
    binp, lst = _write_jpeg_imgbin(tmp_path)
    aug = "  rand_crop = 1\n  rand_mirror = 1\n"
    ref = _epochs(_chain(binp, lst, aug=aug))
    got = _epochs(_chain(
        binp, lst, aug=aug,
        extra="  num_decode_workers = 4\n  decode_chunk = 3\n",
    ))
    assert got == ref


@pytest.mark.parametrize("workers", [0, 4])
def test_mid_epoch_rewind_restarts_the_stream(tmp_path, workers):
    """A before_first() mid-epoch starts the next epoch exactly where
    an uninterrupted run's next epoch would start: epoch 2 of run A ==
    the post-rewind pass of run B, serial and pooled alike."""
    binp, lst = _write_jpeg_imgbin(tmp_path)
    extra = (f"  num_decode_workers = {workers}\n  decode_chunk = 3\n"
             if workers else "")
    # round_batch=0: with round_batch=1 the tail wrap advances the
    # epoch mid-batch, so epochs are not self-contained units to align
    a = _chain(binp, lst, extra=extra, round_batch=0)
    full = _epochs(a, n_epochs=2)
    n_per_epoch = len(full) // 2
    epoch2 = full[n_per_epoch:]

    b = _chain(binp, lst, extra=extra, round_batch=0)
    b.before_first()
    for _ in range(2):  # half an epoch, then rewind
        assert b.next()
    got = _epochs(b, n_epochs=1)
    assert got == epoch2


def test_worker_count_changes_nothing_about_augment_draws(tmp_path):
    """Chunk geometry must not leak into the stream: odd chunk sizes
    and depths against each other."""
    binp, lst = _write_jpeg_imgbin(tmp_path)
    a = _epochs(_chain(
        binp, lst,
        extra="  num_decode_workers = 2\n  decode_chunk = 1\n"
              "  decode_queue_depth = 7\n",
    ))
    b = _epochs(_chain(
        binp, lst,
        extra="  num_decode_workers = 3\n  decode_chunk = 5\n"
              "  decode_queue_depth = 2\n",
    ))
    assert a == b


# ----------------------------------------------------------------------
# vectorized fast path == per-record path
class _ListSource(InstIterator):
    def __init__(self, insts):
        self.insts = insts
        self._pos = 0

    def before_first(self):
        self._pos = 0

    def next(self):
        if self._pos >= len(self.insts):
            return False
        self._pos += 1
        return True

    def value(self):
        return self.insts[self._pos - 1]


def _augmenter(params, meanimg=None):
    from cxxnet_tpu.io.augment import AugmentIterator

    aug = AugmentIterator(_ListSource([]))
    for k, v in params:
        aug.set_param(k, v)
    if meanimg is not None:
        aug._meanimg = meanimg
    return aug


def _rand_insts(rng, n=9, h=14, w=15, dtype=np.uint8):
    out = []
    for i in range(n):
        data = (rng.rand(h, w, 3) * 255).astype(dtype)
        out.append(DataInst(100 + i, data, np.asarray([i], np.float32)))
    return out


@pytest.mark.parametrize("mean", ["none", "value", "img_crop", "img_full"])
def test_augment_batch_matches_per_record(tmp_path, mean):
    rng = np.random.RandomState(3)
    params = [
        ("input_shape", "3,10,11"), ("rand_crop", "1"),
        ("rand_mirror", "1"), ("max_random_contrast", "0.3"),
        ("max_random_illumination", "8"), ("scale", "0.02"),
        ("seed_data", "11"),
    ]
    meanimg = None
    if mean == "value":
        params.append(("mean_value", "3,2,1"))
    elif mean == "img_crop":
        meanimg = (rng.rand(10, 11, 3) * 50).astype(np.float32)
    elif mean == "img_full":
        meanimg = (rng.rand(14, 15, 3) * 50).astype(np.float32)
    aug = _augmenter(params, meanimg)
    insts = _rand_insts(rng)
    vec = aug.augment_insts(insts, epoch=2)
    per = [
        aug._augmented(d, apply_mean=True, rng=aug.record_rng(2, d.index))
        for d in insts
    ]
    assert len(vec) == len(per)
    for v, p in zip(vec, per):
        assert v.data.dtype == p.data.dtype == np.float32
        assert v.data.tobytes() == p.data.tobytes()


def test_augment_pil_and_tail_match_per_record(tmp_path):
    """The split worker path (PIL crop/flip + consumer float tail) is
    bitwise-equal to the serial per-record augment."""
    from PIL import Image

    rng = np.random.RandomState(5)
    params = [
        ("input_shape", "3,10,11"), ("rand_crop", "1"),
        ("rand_mirror", "1"), ("max_random_contrast", "0.25"),
        ("max_random_illumination", "6"), ("mean_value", "4,5,6"),
        ("scale", "0.01"), ("seed_data", "13"),
    ]
    aug = _augmenter(params)
    assert aug.pil_path_ok() and not aug.tail_identity()
    insts = _rand_insts(rng)
    cropped = [
        aug.augment_pil(Image.fromarray(d.data), d.index, d.label, epoch=3)
        for d in insts
    ]
    assert all(c.data.dtype == np.uint8 for c in cropped)
    got = aug.augment_tail(cropped, epoch=3)
    want = [
        aug._augmented(d, apply_mean=True, rng=aug.record_rng(3, d.index))
        for d in insts
    ]
    for g, w_ in zip(got, want):
        assert g.data.tobytes() == w_.data.tobytes()


def test_mean_image_created_through_vectorized_pass(tmp_path):
    """First-run mean image: single pre-pool pass through the batch
    path, same value the serial per-record loop would produce, and the
    chain applies it."""
    imgs = np.ones((4, 8, 8, 3), np.float32) * np.arange(1, 5)[:, None, None, None]
    binp = str(tmp_path / "d.bin")
    w = BinPageWriter(binp)
    for im in imgs:
        w.push(encode_raw(im))
    w.close()
    lst = tmp_path / "d.lst"
    lst.write_text("".join(f"{i}\t0\tx.jpg\n" for i in range(4)))
    meanp = str(tmp_path / "mean.npz")
    it = _chain(binp, str(lst),
                aug=f'  raw_pixels = 1\n  image_mean = "{meanp}"\n',
                batch=4, shape="3,8,8")
    it.before_first()
    assert it.next()
    b = it.value()
    np.testing.assert_allclose(b.data[0], -1.5, rtol=1e-5)
    assert os.path.exists(meanp)
    with np.load(meanp) as z:
        np.testing.assert_allclose(z["mean"], 2.5, rtol=1e-6)
    it.close()


def test_pool_quarantines_corrupt_records(tmp_path):
    """A corrupt JPEG decoded by a pool worker is skipped and
    quarantined by the consumer in record order — same budget semantics
    as the serial reader."""
    binp, lst = _write_jpeg_imgbin(tmp_path, n=8, page_size=1 << 20)
    # flip bytes of one record's blob inside the single page
    blob = open(binp, "rb").read()
    frag = bytearray(blob)
    # CXBP: magic u32 | nrec u32 | lens | blobs — corrupt the 3rd blob
    import struct

    nrec = struct.unpack_from("<I", frag, 4)[0]
    lens = struct.unpack_from(f"<{nrec}I", frag, 8)
    start = 8 + 4 * nrec + sum(lens[:2])
    for off in range(start, start + 64):
        frag[off] ^= 0xFF
    open(binp, "wb").write(bytes(frag))

    it = _chain(
        binp, lst, aug="  rand_crop = 1\n",
        extra="  num_decode_workers = 2\n  decode_chunk = 3\n"
              "  max_bad_records = 2\n",
        batch=7,
    )
    it.before_first()
    seen = []
    while it.next():
        seen.append(it.value())
    got = {int(i) for b in seen for i in b.inst_index}
    assert 2 not in got or len(got) == 7  # record 2 skipped
    q = binp + ".quarantine"
    assert os.path.exists(q)
    assert open(q).read().splitlines()[0].startswith("2\t")
    it.close()


@pytest.mark.parametrize("workers", [0, 4])
def test_augment_epoch_anchor_reproduces_resume(tmp_path, workers):
    """`augment_epoch` (the CLI's per-round anchor) makes a FRESH
    process resumed at round r draw the exact stream an uninterrupted
    run drew at round r — epochs track training progress, not how many
    rewinds this process happened to make."""
    binp, lst = _write_jpeg_imgbin(tmp_path)
    extra = (f"  num_decode_workers = {workers}\n  decode_chunk = 3\n"
             if workers else "")
    a = _chain(binp, lst, extra=extra, round_batch=0)
    run_a = []
    for round_ in (1, 2, 3):  # uninterrupted rounds, anchored like cli
        a.before_first()
        a.set_param("augment_epoch", str(round_))
        while a.next():
            b = a.value()
            run_a.append((round_, b.data.tobytes()))
    a.close()
    # "resume": fresh iterator jumps straight to round 3
    b_it = _chain(binp, lst, extra=extra, round_batch=0)
    b_it.before_first()
    b_it.set_param("augment_epoch", "3")
    got = []
    while b_it.next():
        got.append((3, b_it.value().data.tobytes()))
    b_it.close()
    assert got == [x for x in run_a if x[0] == 3]


def test_pool_propagates_augment_errors(tmp_path):
    """An augmentation error (image smaller than the crop) RAISES in
    pool mode exactly like the serial path — it must not be laundered
    into the quarantine as a corrupt record."""
    binp, lst = _write_jpeg_imgbin(tmp_path, n=6, size=8)  # 8 < 12 crop
    it = _chain(binp, lst, aug="  rand_crop = 1\n",
                extra="  num_decode_workers = 2\n  max_bad_records = 99\n")
    it.before_first()
    with pytest.raises(ValueError, match="net input size"):
        while it.next():
            pass
    it.close()
    assert not os.path.exists(binp + ".quarantine")


def test_pool_watchdog_and_close_are_clean(tmp_path):
    """close() joins the workers; a second close is a no-op."""
    binp, lst = _write_jpeg_imgbin(tmp_path, n=6)
    it = _chain(binp, lst,
                extra="  num_decode_workers = 2\n")
    assert _epochs(it, 1)
    it.close()
    it.close()


# ----------------------------------------------------------------------
# persistent compile cache.  Both tests run in a SUBPROCESS: enabling
# jax's persistent compilation cache is process-global and permanent,
# and the suite itself runs with it switched off (tests/conftest.py).
# The resolver's precedence is pinned in tests/test_chip_contracts.py.
def _run_py(script, cwd):
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo
    env["JAX_ENABLE_COMPILATION_CACHE"] = "true"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)  # the conf key must place it
    return subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        cwd=str(cwd), env=env, timeout=240,
    )


def test_compile_cache_dir_persists_programs(tmp_path):
    r = _run_py(f"""
import numpy as np
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.io.data import DataBatch

cache_dir = {str(tmp_path / "xla_cache")!r}
cfg = [
    ("compile_cache_dir", cache_dir),
    ("dev", "cpu"), ("batch_size", "8"), ("input_shape", "1,1,6"),
    ("seed", "3"), ("eta", "0.1"),
    ("netconfig", "start"),
    ("layer[0->1]", "fullc:fc"), ("nhidden", "4"),
    ("layer[1->1]", "softmax"),
    ("netconfig", "end"),
]
tr = NetTrainer()
tr.set_params(cfg)
tr.init_model()
rng = np.random.RandomState(0)
tr.update(DataBatch(
    data=rng.randn(8, 6).astype(np.float32),
    label=rng.randint(0, 4, (8, 1)).astype(np.float32),
))
import os
entries = os.listdir(cache_dir)
assert entries, "persistent compile cache wrote no entries"
print("CACHE_OK", len(entries))
""", tmp_path)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "CACHE_OK" in r.stdout


def test_compile_cache_configure_scans_cfg(tmp_path):
    r = _run_py(f"""
from cxxnet_tpu.utils import compile_cache

d = {str(tmp_path / "cc")!r}
assert compile_cache.configure([("foo", "1"), ("compile_cache_dir", d)]) == d
assert compile_cache.enabled_dir() == d
import os
assert os.path.isdir(d)
# idempotent
assert compile_cache.configure([("compile_cache_dir", d)]) == d
print("CONFIGURE_OK")
""", tmp_path)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "CONFIGURE_OK" in r.stdout


# ----------------------------------------------------------------------
# per-stage observability
def test_pipeline_stats_snapshot_schema(tmp_path):
    from cxxnet_tpu.utils.profiler import pipeline_stats

    binp, lst = _write_jpeg_imgbin(tmp_path)
    pipeline_stats().reset()
    it = _chain(binp, lst, extra="  num_decode_workers = 2\n")
    _epochs(it, 1)
    it.close()
    snap = pipeline_stats().snapshot()
    for stage in ("decode", "augment", "batch", "h2d", "device_wait"):
        assert stage in snap
        for field in ("count", "rows", "total_s", "rows_per_sec"):
            assert field in snap[stage]
    assert snap["decode"]["rows"] > 0
    assert snap["batch"]["rows"] > 0
    assert pipeline_stats().report()
    pipeline_stats().reset()
    assert pipeline_stats().snapshot()["decode"]["count"] == 0


def test_io_bench_smoke_schema(tmp_path):
    """The PERF=1 lane's contract: io_bench --smoke validates its own
    JSON schema (no throughput assertions)."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    from tools.io_bench import validate_report

    good = {
        "n_images": 4, "size": 8,
        "results": [{
            "mode": "serial", "img_per_sec": 1.0,
            "decode_augment_per_sec": 2.0,
            "stages": {s: {"count": 0, "rows": 0, "total_s": 0.0,
                           "rows_per_sec": 0.0}
                       for s in ("decode", "augment", "batch", "h2d",
                                 "device_wait")},
        }],
    }
    validate_report(good)
    bad = dict(good)
    bad["results"] = [dict(good["results"][0], img_per_sec=float("nan"))]
    with pytest.raises(ValueError):
        validate_report(bad)
