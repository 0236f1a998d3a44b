"""Fault-injection tests for the checkpoint/recovery subsystem: corrupt
checkpoints (truncation, byte-flips), SIGTERM mid-epoch, NaN divergence
(abort and rollback policies), retention, exact resume, and producer-
thread exception propagation in the prefetch iterator.

Each test injects a REAL fault and asserts the documented recovery:
resume lands on the newest valid checkpoint and training continues."""

import os
import re
import signal
import subprocess
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from conftest import run_cli  # noqa: E402 - shared CLI harness
from test_cli import make_conf  # noqa: E402 - shared conf fixture

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _models(tmp_path):
    d = tmp_path / "models"
    if not d.exists():
        return []
    return sorted(f for f in os.listdir(d) if f.endswith(".model"))


# ----------------------------------------------------------------------
# resume discovery (the consecutive-scan bug) + corrupt-checkpoint fallback
def test_resume_with_gapped_checkpoints(tmp_path):
    """save_model=2 writes 0001, 0003, ... — the old consecutive scan
    from 0000 found nothing and raised FileNotFoundError; the glob-based
    resume must pick the newest.  (Also covers the default momentum-
    restart resume path: save_ustate stays 0.)"""
    conf = make_conf(tmp_path, num_round=4, extra="save_model = 2")
    r1 = run_cli([conf], str(tmp_path))
    assert r1.returncode == 0, r1.stderr + r1.stdout
    assert _models(tmp_path) == ["0001.model", "0003.model"]
    r2 = run_cli([conf, "continue=1", "num_round=6"], str(tmp_path))
    assert r2.returncode == 0, r2.stderr + r2.stdout
    assert "Continue training from round 4" in r2.stdout
    assert "0005.model" in _models(tmp_path)


def test_resume_falls_back_past_truncated_checkpoint(tmp_path):
    """A kill mid-write leaves a truncated newest checkpoint; resume must
    skip it (manifest size/CRC mismatch) and load the previous one
    instead of crashing."""
    conf = make_conf(tmp_path, num_round=3)
    r1 = run_cli([conf], str(tmp_path))
    assert r1.returncode == 0, r1.stderr + r1.stdout
    newest = tmp_path / "models" / "0003.model"
    blob = newest.read_bytes()
    newest.write_bytes(blob[: len(blob) // 3])  # preempted mid-write
    r2 = run_cli([conf, "continue=1", "num_round=4"], str(tmp_path))
    assert r2.returncode == 0, r2.stderr + r2.stdout
    assert "skipped" in r2.stdout and "0003.model" in r2.stdout
    # fell back to 0002 → resumes at round 3
    assert "Continue training from round 3" in r2.stdout
    assert "0004.model" in _models(tmp_path)


def test_resume_falls_back_past_byte_flipped_checkpoint(tmp_path):
    """A byte-flip deep in the payload keeps the file loadable-looking
    (magic + header intact, valid name); only the manifest CRC32 catches
    it.  Resume must fall back to the previous valid checkpoint."""
    conf = make_conf(tmp_path, num_round=3)
    r1 = run_cli([conf], str(tmp_path))
    assert r1.returncode == 0, r1.stderr + r1.stdout
    newest = tmp_path / "models" / "0003.model"
    blob = bytearray(newest.read_bytes())
    blob[-100] ^= 0xFF  # flip one payload byte, length unchanged
    newest.write_bytes(bytes(blob))
    r2 = run_cli([conf, "continue=1", "num_round=4"], str(tmp_path))
    assert r2.returncode == 0, r2.stderr + r2.stdout
    assert "crc32 mismatch" in r2.stdout
    assert "Continue training from round 3" in r2.stdout


def test_resume_with_all_checkpoints_corrupt_fails_clearly(tmp_path):
    conf = make_conf(tmp_path, num_round=1)
    r1 = run_cli([conf], str(tmp_path))
    assert r1.returncode == 0, r1.stderr + r1.stdout
    for m in _models(tmp_path):
        (tmp_path / "models" / m).write_bytes(b"garbage")
    r2 = run_cli([conf, "continue=1"], str(tmp_path))
    assert r2.returncode != 0
    assert "cannot find models for continue training" in (
        r2.stderr + r2.stdout
    )


def test_keep_latest_retention(tmp_path):
    """keep_latest=N prunes old checkpoints+manifests after each save;
    resume still works off the newest survivor."""
    conf = make_conf(tmp_path, num_round=5, extra="keep_latest = 2")
    r1 = run_cli([conf], str(tmp_path))
    assert r1.returncode == 0, r1.stderr + r1.stdout
    assert _models(tmp_path) == ["0004.model", "0005.model"]
    manifests = sorted(f for f in os.listdir(tmp_path / "models")
                       if f.endswith(".manifest.json"))
    assert manifests == ["0004.model.manifest.json",
                         "0005.model.manifest.json"]
    r2 = run_cli([conf, "continue=1", "num_round=6"], str(tmp_path))
    assert r2.returncode == 0, r2.stderr + r2.stdout
    assert "Continue training from round 6" in r2.stdout


# ----------------------------------------------------------------------
# SIGTERM mid-epoch (preemption)
@pytest.mark.slow
def test_sigterm_mid_epoch_saves_and_resumes(tmp_path):
    """Deliver SIGTERM while the train loop is inside a round: the
    process must snapshot state, exit 0 with the preemption message, and
    a continue=1 run must resume from that snapshot and finish."""
    conf = make_conf(tmp_path, num_round=2000, extra="save_model = 100")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    proc = subprocess.Popen(
        [sys.executable, "-m", "cxxnet_tpu", conf],
        cwd=str(tmp_path), env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True,
    )
    try:
        # wait until training is inside a round (round 2+ → round 1's
        # state exists), then preempt
        deadline = time.time() + 240
        for line in proc.stdout:
            if line.startswith("update round 2"):
                break
            assert time.time() < deadline, "training never reached round 2"
        proc.send_signal(signal.SIGTERM)
        out = proc.stdout.read()
        rc = proc.wait(timeout=240)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert rc == 0, out
    assert "received signal SIGTERM" in out
    m = re.search(r"preemption: state saved through round (\d+)", out)
    assert m, out
    last = int(m.group(1))
    assert f"{last:04d}.model" in _models(tmp_path)
    # the snapshot validates (atomic write: no truncation despite the kill)
    from cxxnet_tpu.utils import checkpoint as ckpt

    assert ckpt.validate_checkpoint(
        str(tmp_path / "models" / f"{last:04d}.model")
    ) is None
    # resume with per-round checkpointing so the continued run proves it
    # can both train AND checkpoint again after the preemption
    r2 = run_cli([conf, "continue=1", f"num_round={last + 2}",
                  "save_model=1"], str(tmp_path))
    assert r2.returncode == 0, r2.stderr + r2.stdout
    assert f"Continue training from round {last + 1}" in r2.stdout
    assert f"{last + 2:04d}.model" in _models(tmp_path)


# ----------------------------------------------------------------------
# divergence guard
def test_divergence_abort_policy(tmp_path):
    """A NaN loss (injected at update 5, round 1) with
    divergence_policy=abort stops training with a clear error instead of
    silently training on corrupt weights."""
    conf = make_conf(
        tmp_path, num_round=4,
        extra="divergence_policy = abort\ninject_nan_step = 5",
    )
    r = run_cli([conf], str(tmp_path))
    assert r.returncode != 0
    assert "DIVERGENCE" in r.stdout
    assert "non-finite loss" in r.stdout + r.stderr
    # blew up in round 1 (updates 4-7): rounds ≥ 1 never checkpointed
    assert _models(tmp_path) == ["0000.model", "0001.model"] or \
        _models(tmp_path) == ["0000.model"]


def test_divergence_rollback_policy(tmp_path):
    """divergence_policy=rollback: on a NaN loss the driver reloads the
    newest valid checkpoint, backs off the learning rate, and retries
    the round — the run completes all rounds with exit code 0."""
    conf = make_conf(
        tmp_path, num_round=4,
        extra=("divergence_policy = rollback\n"
               "divergence_lr_backoff = 0.5\n"
               "inject_nan_step = 9"),
    )
    r = run_cli([conf], str(tmp_path))
    assert r.returncode == 0, r.stderr + r.stdout
    assert "DIVERGENCE" in r.stdout
    assert "rolled back to round 2" in r.stdout
    assert "lr scale now 0.5" in r.stdout
    # training recovered and ran to completion
    assert "0004.model" in _models(tmp_path)
    lines = [l for l in r.stderr.splitlines() if l.startswith("[")]
    assert len(lines) == 4  # every round reported exactly once


def test_loss_spike_gate_rollback(tmp_path):
    """A FINITE loss explosion (inject_spike_step: x1e6 at update 9)
    trips the ``divergence_loss_ratio`` rolling-median gate even
    though every value passes the non-finite check — the staleness
    blow-up class that stays finite for whole rounds.  The existing
    rollback + lr-backoff path recovers and the run completes."""
    conf = make_conf(
        tmp_path, num_round=4,
        extra=("divergence_policy = rollback\n"
               "divergence_lr_backoff = 0.5\n"
               "divergence_loss_ratio = 50\n"
               "inject_spike_step = 9"),
    )
    r = run_cli([conf], str(tmp_path))
    assert r.returncode == 0, r.stderr + r.stdout
    assert "DIVERGENCE" in r.stdout
    assert "finite loss spike" in r.stdout
    assert "rolled back to round 2" in r.stdout
    assert "lr scale now 0.5" in r.stdout
    # training recovered and ran to completion
    assert "0004.model" in _models(tmp_path)


def _poison_weights(path):
    """Rewrite a checkpoint with NaN in its first weight tensor and a
    MATCHING manifest — CRC-valid, numerically poisoned (models the
    blow-up landing in the last update of the captured round, after its
    losses were measured)."""
    import io
    import struct

    from cxxnet_tpu.utils import checkpoint as ckpt

    raw = open(path, "rb").read()
    (hlen,) = struct.unpack("<I", raw[8:12])
    npz = np.load(io.BytesIO(raw[12 + hlen:]))
    flat = {k: npz[k] for k in npz.files}
    k0 = next(k for k in sorted(flat) if not k.startswith("ust:"))
    flat[k0] = np.full_like(flat[k0], np.nan)
    buf = io.BytesIO()
    np.savez(buf, **flat)
    blob = raw[: 12 + hlen] + buf.getvalue()
    man = ckpt.read_manifest(path)
    ckpt.write_checkpoint(path, blob, round_=man["round"],
                          net_fp=man["net_fingerprint"],
                          save_ustate=man["save_ustate"])


def test_divergence_rollback_skips_nan_poisoned_checkpoint(tmp_path):
    """A CRC-valid checkpoint whose weights are NaN (the divergence was
    baked in before the save) must not trap the rollback loop: resume
    hits a REAL NaN loss, rollback detects the poisoned newest
    checkpoint via the weight-finiteness check, falls back past it to
    round 2, and the run completes."""
    conf = make_conf(tmp_path, num_round=3,
                     extra="divergence_policy = rollback")
    r1 = run_cli([conf], str(tmp_path))
    assert r1.returncode == 0, r1.stderr + r1.stdout
    _poison_weights(str(tmp_path / "models" / "0003.model"))
    r2 = run_cli([conf, "continue=1", "num_round=4"], str(tmp_path))
    assert r2.returncode == 0, r2.stderr + r2.stdout
    assert "DIVERGENCE" in r2.stdout
    assert "non-finite weights; falling back past it" in r2.stdout
    assert "rolled back to round 2" in r2.stdout
    assert "0004.model" in _models(tmp_path)


def test_divergence_guard_in_process():
    """Trainer-level guard: a batch that produces a non-finite loss
    raises DivergenceError (both fused and accumulation paths) when the
    policy is set, and stays silent when it is not."""
    from cxxnet_tpu import config as C
    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.nnet.trainer import DivergenceError, NetTrainer
    from test_trainer import MLP_CFG

    x = np.random.RandomState(0).randn(16, 8).astype(np.float32)
    x[3, 2] = np.nan  # poisoned input → NaN loss
    y = np.zeros((16, 1), np.float32)

    tr = NetTrainer()
    tr.set_params(C.parse_pairs(MLP_CFG + "divergence_policy = rollback\n"))
    tr.init_model()
    with pytest.raises(DivergenceError) as ei:
        tr.update(DataBatch(data=x, label=y))
    assert ei.value.epoch == 0

    # guard disabled (default): the TRAINER does not raise — reference
    # behavior preserved (eval_train off: the logloss metric, like the
    # reference's, refuses a NaN prediction on its own)
    tr2 = NetTrainer()
    tr2.set_params(C.parse_pairs(MLP_CFG + "eval_train = 0\n"))
    tr2.init_model()
    tr2.update(DataBatch(data=x, label=y))

    # accumulation path (update_period=2): caught at the micro-batch
    tr3 = NetTrainer()
    tr3.set_params(C.parse_pairs(
        MLP_CFG + "update_period = 2\ndivergence_policy = abort\n"
    ))
    tr3.init_model()
    with pytest.raises(DivergenceError):
        tr3.update(DataBatch(data=x, label=y))


def test_divergence_guard_update_scan():
    """update_scan checks every per-step loss; the error names the
    offending update (inject_nan_step fault hook)."""
    from cxxnet_tpu import config as C
    from cxxnet_tpu.nnet.trainer import DivergenceError, NetTrainer
    from test_trainer import MLP_CFG

    rng = np.random.RandomState(1)
    data = rng.randn(3, 16, 8).astype(np.float32)
    labels = np.zeros((3, 16, 1), np.float32)
    tr = NetTrainer()
    tr.set_params(C.parse_pairs(
        MLP_CFG + "eval_train = 0\ndivergence_policy = abort\n"
        "inject_nan_step = 4\n"
    ))
    tr.init_model()
    assert tr.update_scan(data, labels).shape == (3,)  # epochs 0-2: clean
    with pytest.raises(DivergenceError) as ei:
        tr.update_scan(data, labels)  # epochs 3-5: update 4 injected
    assert ei.value.epoch == 4
    # one-shot: the transient fault does not re-arm
    assert tr.inject_nan_step == -1
    assert tr.update_scan(data, labels).shape == (3,)


# ----------------------------------------------------------------------
# exact resume
@pytest.mark.slow
def test_exact_resume_bit_identical(tmp_path):
    """save_ustate=1 + kill + resume must land bit-identical to an
    uninterrupted run: same weights, same updater moments, same epoch."""
    from cxxnet_tpu.nnet.trainer import NetTrainer

    extra = "save_ustate = 1\nshuffle = 0"
    (tmp_path / "a").mkdir(exist_ok=True)
    conf_a = make_conf(tmp_path / "a", num_round=4, extra=extra)
    r_a = run_cli([conf_a], str(tmp_path / "a"))
    assert r_a.returncode == 0, r_a.stderr + r_a.stdout

    (tmp_path / "b").mkdir(exist_ok=True)
    conf_b = make_conf(tmp_path / "b", num_round=2, extra=extra)
    r_b1 = run_cli([conf_b], str(tmp_path / "b"))
    assert r_b1.returncode == 0, r_b1.stderr + r_b1.stdout
    r_b2 = run_cli([conf_b, "continue=1", "num_round=4"], str(tmp_path / "b"))
    assert r_b2.returncode == 0, r_b2.stderr + r_b2.stdout

    ha, pa, _aa, ua = NetTrainer._read_model_file(
        str(tmp_path / "a" / "models" / "0004.model")
    )
    hb, pb, _ab, ub = NetTrainer._read_model_file(
        str(tmp_path / "b" / "models" / "0004.model")
    )
    assert ha["epoch_counter"] == hb["epoch_counter"]
    assert ha["rng_key"] == hb["rng_key"]
    for key in pa:
        for tag in pa[key]:
            np.testing.assert_array_equal(pa[key][tag], pb[key][tag])
    for key in ua:  # momentum state rode along and matches bit-exactly
        for tag in ua[key]:
            for slot in ua[key][tag]:
                np.testing.assert_array_equal(
                    ua[key][tag][slot], ub[key][tag][slot]
                )


# ----------------------------------------------------------------------
# prefetch producer-thread failure propagation
class _FlakyIter:
    """DataIter that raises mid-epoch on its first pass, then recovers."""

    def __init__(self, n_batches=4, fail_after=2):
        from cxxnet_tpu.io.data import DataBatch

        self._mk = lambda i: DataBatch(
            data=np.full((2, 3), i, np.float32), label=np.zeros((2, 1)),
        )
        self.n_batches = n_batches
        self.fail_after = fail_after
        self.epoch = -1
        self.i = 0

    def supports_dist_shard(self):
        return False

    def set_param(self, name, val):
        pass

    def init(self):
        pass

    def before_first(self):
        self.epoch += 1
        self.i = 0

    def next(self):
        self.i += 1
        if self.epoch == 0 and self.i > self.fail_after:
            raise RuntimeError("decode failed (injected)")
        return self.i <= self.n_batches

    def value(self):
        return self._mk(self.i)


def test_prefetch_producer_exception_propagates():
    """An exception in the producer thread must re-raise in the
    consumer's next() (previously: silent thread death, consumer blocked
    forever) — and the iterator must survive into the next epoch."""
    from cxxnet_tpu.io.prefetch import ThreadBufferIterator

    it = ThreadBufferIterator(_FlakyIter())
    it.set_param("silent", "1")
    it.init()
    it.before_first()
    assert it.next() and it.value().data[0, 0] == 1
    assert it.next() and it.value().data[0, 0] == 2

    ex = ThreadPoolExecutor(max_workers=1)
    try:
        # guard with a timeout so a regression fails instead of hanging
        fut = ex.submit(it.next)
        with pytest.raises(RuntimeError, match="decode failed"):
            fut.result(timeout=30)
        # a consumer that swallows the error and retries must see the
        # epoch END, not block on an empty queue
        fut = ex.submit(it.next)
        assert fut.result(timeout=30) is False
    finally:
        ex.shutdown(wait=False)

    # epoch 2: producer recovered; full epoch streams through
    it.before_first()
    got = []
    while it.next():
        got.append(int(it.value().data[0, 0]))
    assert got == [1, 2, 3, 4]
    it.close()
