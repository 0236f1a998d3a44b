"""Accuracy-parity evidence (VERDICT r2 #3).

* the bundled UCI-digits conv recipe must beat the MLP's ~4% and land in
  the reference's ~2%-in-15-rounds class
  (``/root/reference/example/MNIST/README.md``);
* membuffer-overfit smokes for the ImageNet models — cache one batch and
  drive train error to 0 — the reference's own sanity discipline
  (``/root/reference/src/io/iter_mem_buffer-inl.hpp``).
"""

import os
import re
import shutil

import numpy as np
import pytest

from conftest import run_cli
from cxxnet_tpu import config as C
from cxxnet_tpu.io.data import create_iterator
from cxxnet_tpu.models import alexnet_conf, googlenet_conf
from cxxnet_tpu.nnet.trainer import NetTrainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _digits_test_errors(tmp_path, *more):
    """``example/MNIST/digits_conv.conf`` through the real CLI on real
    handwritten digits: ``{round: test error}`` of its 15 rounds."""
    pytest.importorskip("sklearn")
    r = run_cli([os.path.join(REPO, "tools", "make_digits_idx.py"),
                 str(tmp_path / "data")], str(tmp_path), module=False)
    assert r.returncode == 0, r.stderr
    shutil.copy(os.path.join(REPO, "example", "MNIST", "digits_conv.conf"),
                str(tmp_path / "digits_conv.conf"))
    r = run_cli(["digits_conv.conf", "task=train", *more], str(tmp_path))
    assert r.returncode == 0, r.stderr[-2000:]
    errs = {
        int(m.group(1)): float(m.group(2))
        for m in re.finditer(r"\[(\d+)\]\ttrain-error:\S+\ttest-error:(\S+)",
                             r.stderr)
    }
    assert 15 in errs, r.stderr[-2000:]
    return errs


def test_digits_conv_beats_mlp_bar(tmp_path):
    """<= 4% test error in 15 rounds (the committed log records
    1.6%)."""
    errs = _digits_test_errors(tmp_path)
    assert errs[15] <= 0.04, f"round-15 test error {errs[15]:.3f} > 4%"
    # convergence, not luck: the tail of the trajectory stays under 6%
    assert max(errs[k] for k in (13, 14, 15)) <= 0.06


@pytest.mark.parametrize("wino", [1, 2])
def test_digits_conv_bf16_winograd_converges(tmp_path, wino):
    """bf16 Winograd training convergence (VERDICT r4 #3): the F(4x4)
    tile's |8| transform constants amplify bf16 rounding ~15x per op
    (layers/conv.py), so the layer-level pair bound alone can't justify
    a default — this pins the MODEL-scale behavior: digits-conv under
    ``compute_dtype=bfloat16`` + ``conv_wino`` must land in the same
    convergence class as the direct conv (measured A/B:
    example/MNIST/wino_bf16_ab.log — round-15 2.8% F(4x4) / 2.0%
    F(2x2) vs 0.8% direct; bounds leave headroom for run noise)."""
    errs = _digits_test_errors(tmp_path, "compute_dtype=bfloat16",
                               f"conv_wino={wino}")
    # same acceptance shape as the fp32 test, widened one notch for the
    # documented bf16-Winograd noise: the tail must reach the digits
    # class (<=4%) and must not diverge (<=6% at round 15)
    assert min(errs[k] for k in (13, 14, 15)) <= 0.04, errs
    assert errs[15] <= 0.06, errs


def _overfit_one_cached_batch(conf_text, shape, n_steps):
    """The membuffer discipline: synthetic source + ``iter = membuffer``
    caching ONE batch; training must drive eval-mode error to 0."""
    it = create_iterator(C.split_sections(C.parse_pairs(f"""
data = train
iter = synthetic
  nsample = 8
  input_shape = {shape}
  nclass = 10
  label_width = 1
  batch_size = 8
iter = membuffer
  max_nbatch = 1
iter = end
""")).find("data")[0].entries)
    it.init()
    tr = NetTrainer()
    tr.set_params(C.parse_pairs(conf_text))
    # memorization settings: the ImageNet schedules are tuned for real
    # data at scale, not for saturating 8 noise images
    for k, v in [("updater", "adam"), ("eta", "0.001"),
                 ("wmat:lr", "0.001"), ("bias:lr", "0.001"),
                 ("wd", "0.0"), ("wmat:wd", "0.0")]:
        tr.set_param(k, v)
    tr.eval_train = 0
    tr.init_model()
    it.before_first()
    assert it.next()
    cached = it.value()
    err = 1.0
    for step in range(n_steps):
        it.before_first()
        while it.next():
            tr.update(it.value())
        if (step + 1) % 10 == 0:
            pred = tr.predict(cached)
            err = float((pred != cached.label[:, 0]).mean())
            if err == 0.0:
                break
    assert err == 0.0, f"did not overfit the cached batch: err={err}"
    # and the second epoch really replayed the same cached data
    it.before_first()
    assert it.next()
    np.testing.assert_array_equal(np.asarray(it.value().data),
                                  np.asarray(cached.data))


def test_membuffer_overfit_alexnet():
    _overfit_one_cached_batch(
        alexnet_conf(batch_size=8, num_class=10, synthetic=False,
                     dev="cpu", input_size=67),
        "3,67,67", n_steps=300,
    )


def test_membuffer_overfit_googlenet():
    _overfit_one_cached_batch(
        googlenet_conf(batch_size=8, num_class=10, synthetic=False,
                       dev="cpu", input_size=48),
        "3,48,48", n_steps=300,
    )


def test_membuffer_overfit_resnet50():
    # exercises BN (one-pass stats), eltwise_sum shortcuts, and the
    # strided-fused stage-boundary 1x1 pairs on the convergence path
    from cxxnet_tpu.models import resnet50_conf

    _overfit_one_cached_batch(
        resnet50_conf(batch_size=8, num_class=10, synthetic=False,
                      dev="cpu", input_size=32),
        "3,32,32", n_steps=300,
    )
