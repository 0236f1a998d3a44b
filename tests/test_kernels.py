"""On-chip kernel library (``cxxnet_tpu/ops/kernels/``): interpret-mode
parity, selector/verdict discipline, and end-to-end dispatch.

The parity contract pinned here: each Pallas kernel, run under
``interpret=True`` on CPU, matches the JITTED stock lowering it
replaces.  The jitted reference is the honest one — the net's real
programs are always compiled, and on CPU the eager op-by-op spelling
differs from its own compiled form (FMA fusion), so "parity with the
stock lowering" means the lowering, not the eager replay.

``int8_gemm`` and ``zero_update`` replay the stock op chain and are
held BIT-EQUAL (``np.array_equal``).  ``conv_block`` replaces a
convolution by a GEMM, and two facts set its tolerance
(``_assert_conv_close``): XLA:CPU's conv and dot emitters order their
FMAs differently from host to host, so f32 agrees to a few ulps, not
bit for bit; and the kernel keeps an f32 accumulator through the bias
add (Mosaic refuses a bf16 one), rounding a bf16 tile once where the
stock conv-then-add rounds twice — one bf16 ulp.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu import config as C
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.ops import kernels as klib
from cxxnet_tpu.ops import quant as opsq
from cxxnet_tpu.ops.kernels import conv_block, int8_gemm, update_step
from cxxnet_tpu.updater import SGDUpdater


# ----------------------------------------------------------------------
# conv_block: fused conv+bias(+relu) GEMM vs the stock conv lowering
def _conv_ref(x, wk, bias, stride=1, relu=False):
    y = jax.lax.conv_general_dilated(
        x, wk, window_strides=(stride, stride), padding=((0, 0), (0, 0)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    if bias is not None:
        y = y + bias.astype(x.dtype)
    if relu:
        y = jnp.maximum(y, jnp.zeros((), y.dtype))
    return y


def _conv_case(dtype=np.float32, b=4, hw=6, cin=8, cout=16, seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(b, hw, hw, cin).astype(np.float32)).astype(dtype)
    wk = jnp.asarray(
        rng.randn(1, 1, cin, cout).astype(np.float32) * 0.1).astype(dtype)
    bias = jnp.asarray(rng.randn(cout).astype(np.float32)).astype(dtype)
    return x, wk, bias


def _assert_conv_close(ref, got):
    """conv_block vs the stock conv, at the tolerance the module
    docstring derives from the dtype: a few f32 ulps of the O(1)
    operands, or one bf16 ulp (2^-7 relative)."""
    assert got.dtype == ref.dtype
    tol = 2.0 ** -7 if ref.dtype == jnp.bfloat16 else 1e-6
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_conv_block_parity(dtype):
    x, wk, bias = _conv_case(dtype)
    ref = jax.jit(_conv_ref)(x, wk, bias)
    _assert_conv_close(
        ref, conv_block.conv1x1_block(x, wk, bias, interpret=True))


def test_conv_block_blocked_and_stride_and_relu():
    x, wk, bias = _conv_case(b=4, hw=8, cin=8, cout=16)
    # explicit bm/bn tiling (the MXU shape) keeps the full-K contraction
    got = conv_block.conv1x1_block(x, wk, bias, interpret=True, bm=8, bn=8)
    _assert_conv_close(jax.jit(_conv_ref)(x, wk, bias), got)
    # stride via host-side subsampling (exact for 1x1/pad-0)
    ref2 = jax.jit(lambda *a: _conv_ref(*a, stride=2))(x, wk, bias)
    _assert_conv_close(
        ref2, conv_block.conv1x1_block(x, wk, bias, stride=2,
                                       interpret=True))
    # relu folded into the epilogue
    ref3 = jax.jit(lambda *a: _conv_ref(*a, relu=True))(x, wk, bias)
    _assert_conv_close(
        ref3, conv_block.conv1x1_block(x, wk, bias, relu=True,
                                       interpret=True))


def test_conv_block_tiles_rows_past_one_block():
    """M past ``_ROWS`` that is no multiple of it: the launcher pads to
    whole row blocks and slices the pad off — the shape of every
    serving bucket that is not a power of two."""
    x, wk, bias = _conv_case(b=3, hw=14, cin=8, cout=16)  # M = 588
    assert conv_block.row_tiles(588) == (512, 1024)
    assert conv_block.col_tile(2560) == 512 and conv_block.col_tile(176) == 176
    got = conv_block.conv1x1_block(x, wk, bias, interpret=True)
    _assert_conv_close(jax.jit(_conv_ref)(x, wk, bias), got)


def test_conv_block_no_bias_and_probe():
    x, wk, _ = _conv_case()
    ref = jax.jit(lambda x, w: _conv_ref(x, w, None))(x, wk)
    _assert_conv_close(
        ref, conv_block.conv1x1_block(x, wk, None, interpret=True))
    assert conv_block.probe("cpu", x=x, wk=wk) is None
    assert "1x1" in conv_block.probe(
        "cpu", x=x, wk=jnp.zeros((3, 3, 8, 16), jnp.float32))
    assert "NHWC" in conv_block.probe("cpu", x=jnp.zeros((4, 8)), wk=wk)
    assert "dtype" in conv_block.probe(
        "cpu", x=jnp.zeros((1, 2, 2, 3), jnp.float16), wk=wk)


# ----------------------------------------------------------------------
# int8_gemm: the epilogue kernel vs the PR-10 dequant-free reference
def _int8_case(m=8, k=24, o=12, seed=1, act=np.float32):
    rng = np.random.RandomState(seed)
    w = rng.randn(o, k).astype(np.float32)
    q, s = opsq.quantize_weight(w, out_axis=0)
    lp = {opsq.QKEY: jnp.asarray(q), opsq.SKEY: jnp.asarray(s),
          "bias": jnp.asarray(rng.randn(o).astype(np.float32))}
    x = jnp.asarray(rng.randn(m, k).astype(np.float32)).astype(act)
    return lp, x


@pytest.mark.parametrize("act", [np.float32, jnp.bfloat16])
def test_int8_gemm_bit_equal_to_dequant_free_reference(act):
    """The acceptance bar: the in-kernel quantize->MXU->rescale epilogue
    is bit-equal to the stock ``fc_apply_q`` lowering (which feeds raw
    codes and folds the rescale into the f32 bias add outside the
    contraction)."""
    lp, x = _int8_case(act=act)
    ref = jax.jit(opsq.fc_apply_q)(lp, x)
    got = int8_gemm.int8_gemm_rescale(
        x, lp[opsq.QKEY], lp[opsq.SKEY], lp["bias"], interpret=True)
    assert got.dtype == ref.dtype
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


def test_int8_gemm_blocked_no_bias_relu():
    lp, x = _int8_case(m=8, k=32, o=16)
    ref = jax.jit(opsq.fc_apply_q)(lp, x)
    got = int8_gemm.int8_gemm_rescale(
        x, lp[opsq.QKEY], lp[opsq.SKEY], lp["bias"], interpret=True,
        bm=4, bn=8)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
    lp2 = {k: v for k, v in lp.items() if k != "bias"}
    ref2 = jax.jit(opsq.fc_apply_q)(lp2, x)
    got2 = int8_gemm.int8_gemm_rescale(
        x, lp[opsq.QKEY], lp[opsq.SKEY], None, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref2), np.asarray(got2))
    ref3 = jax.jit(
        lambda lp, x: jnp.maximum(opsq.fc_apply_q(lp, x), 0.0))(lp, x)
    got3 = int8_gemm.int8_gemm_rescale(
        x, lp[opsq.QKEY], lp[opsq.SKEY], lp["bias"], relu=True,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(ref3), np.asarray(got3))


def test_int8_gemm_probe():
    lp, x = _int8_case()
    assert int8_gemm.probe("cpu", x=x, q=lp[opsq.QKEY]) is None
    assert "dtype" in int8_gemm.probe(
        "cpu", x=x.astype(jnp.float16), q=lp[opsq.QKEY])
    assert "int8" in int8_gemm.probe(
        "cpu", x=x, q=np.zeros((3, 3), np.int32))


# ----------------------------------------------------------------------
# zero_update: the fused sgd step vs the stock updater rule
def _sgd(clip="0.0"):
    up = SGDUpdater("wmat")
    for k, v in (("eta", "0.05"), ("momentum", "0.9"),
                 ("wd", "0.0005"), ("clip_gradient", clip)):
        up.set_param(k, v)
    return up


def _upd_case(shape, seed=2, nan_at=None):
    rng = np.random.RandomState(seed)
    w = rng.randn(*shape).astype(np.float32)
    g = rng.randn(*shape).astype(np.float32)
    if nan_at is not None:
        g.reshape(-1)[nan_at] = np.nan
    m = rng.randn(*shape).astype(np.float32)
    return jnp.asarray(w), jnp.asarray(g), jnp.asarray(m)


@pytest.mark.parametrize("shape", [(3, 3, 4, 8), (7,), (256,), (5, 130)])
def test_zero_update_bit_parity(shape):
    up = _sgd()
    w, g, m = _upd_case(shape)
    epoch = jnp.asarray(2)
    ref_w, ref_s = jax.jit(
        lambda w, g, m, e: up.apply(w, g, {"m": m}, e))(w, g, m, epoch)
    p = up.param
    got_w, got_m = update_step.sgd_update(
        w, g, m, p.learning_rate(epoch).astype(w.dtype),
        p.momentum_at(epoch).astype(w.dtype), wd=p.wd,
        clip=p.clip_gradient, interpret=True)
    assert got_w.shape == shape and got_m.shape == shape
    np.testing.assert_array_equal(np.asarray(ref_w), np.asarray(got_w))
    np.testing.assert_array_equal(np.asarray(ref_s["m"]), np.asarray(got_m))


def test_zero_update_clip_nan_and_blocked():
    """The reference's clip quirk (``_nan_clip``: zero NaNs, then clamp
    — only when clip_gradient != 0) survives the fusion, NaNs
    included; row-tiling (``br``) changes nothing."""
    up = _sgd(clip="0.5")
    w, g, m = _upd_case((4, 130), nan_at=7)
    epoch = jnp.asarray(5)
    ref_w, ref_s = jax.jit(
        lambda w, g, m, e: up.apply(w, g, {"m": m}, e))(w, g, m, epoch)
    p = up.param
    for br in (0, 1):
        got_w, got_m = update_step.sgd_update(
            w, g, m, p.learning_rate(epoch).astype(w.dtype),
            p.momentum_at(epoch).astype(w.dtype), wd=p.wd,
            clip=p.clip_gradient, interpret=True, br=br)
        np.testing.assert_array_equal(np.asarray(ref_w), np.asarray(got_w))
        np.testing.assert_array_equal(
            np.asarray(ref_s["m"]), np.asarray(got_m))
    assert np.isfinite(np.asarray(got_w)).all()


def test_zero_update_probe():
    assert update_step.probe("cpu", w=jnp.zeros((3,), jnp.float32),
                             updater=_sgd()) is None
    assert "f32" in update_step.probe(
        "cpu", w=jnp.zeros((3,), jnp.bfloat16), updater=_sgd())

    class FakeAdam:
        type_name = "adam"

    assert "sgd only" in update_step.probe(
        "cpu", w=jnp.zeros((3,), jnp.float32), updater=FakeAdam())


# ----------------------------------------------------------------------
# selector / verdict discipline
def test_parse_mode_canonicalization_and_typo():
    assert klib.parse_mode("auto") == "auto"
    assert klib.parse_mode("-1") == "auto"
    for v in ("off", "0", "", "none"):
        assert klib.parse_mode(v) == "off"
    assert klib.parse_mode("int8_gemm, conv_block") == \
        "conv_block,int8_gemm"
    with pytest.raises(ValueError, match="conv_blok"):
        klib.parse_mode("conv_blok")


def test_auto_follows_recorded_verdicts():
    """``kernel_lib=auto`` runs a kernel exactly where a committed
    promote says it pays — the ``conv_branch_embed=-1`` discipline."""
    v = {"conv_block": {"cpu": {"verdict": "reject"},
                        "tpu": {"verdict": "promote"}}}
    sel = klib.KernelSelector("auto", verdicts=v)
    assert not sel.active("conv_block", "cpu")     # recorded reject
    assert sel.active("conv_block", "tpu")         # recorded promote
    assert not sel.active("int8_gemm", "cpu")      # no verdict = stock
    assert sel.fingerprint("cpu") == ""
    assert sel.fingerprint("tpu") == "conv_block"
    off = klib.KernelSelector("off", verdicts=v)
    assert not off.active("conv_block", "tpu")
    pinned = klib.KernelSelector("conv_block,zero_update", verdicts=v)
    assert pinned.active("conv_block", "cpu")      # list overrides
    assert not pinned.active("int8_gemm", "cpu")
    assert pinned.fingerprint("cpu") == "conv_block+zero_update"
    with pytest.raises(ValueError):
        sel.active("nope", "cpu")


def test_committed_cpu_verdicts_exist_and_auto_honors_them():
    """The package ships measured CPU verdicts (kernel_ab --record):
    every kernel has one, rejects are honest (Pallas-on-CPU is
    interpret emulation), and the default auto selector follows them."""
    doc = klib.load_verdicts()
    sel = klib.KernelSelector("auto")
    for name in klib.KERNELS:
        ent = doc.get(name, {}).get("cpu")
        assert ent, f"{name}: no committed cpu verdict"
        assert ent["verdict"] in ("promote", "reject")
        assert ent["parity"] is True  # never committed on wrong math
        assert sel.active(name, "cpu") == (ent["verdict"] == "promote")
        # nothing recorded for tpu yet: auto stays stock on-chip until
        # a chip A/B commits a promote
        assert not sel.active(name, "tpu")


def test_record_verdict_roundtrip(tmp_path):
    p = str(tmp_path / "verdicts.json")
    klib.record_verdict("int8_gemm", "tpu", "promote", path=p, ratio=1.7)
    klib.record_verdict("int8_gemm", "cpu", "reject", path=p)
    doc = json.load(open(p))
    assert doc["int8_gemm"]["tpu"] == {"verdict": "promote", "ratio": 1.7}
    sel = klib.KernelSelector("auto", verdicts=doc)
    assert sel.active("int8_gemm", "tpu")
    assert not sel.active("int8_gemm", "cpu")
    with pytest.raises(ValueError, match="unknown kernel"):
        klib.record_verdict("nope", "cpu", "reject", path=p)
    with pytest.raises(ValueError, match="promote/reject"):
        klib.record_verdict("int8_gemm", "cpu", "maybe", path=p)


def test_bound_kernels_probe_and_gauge():
    """BoundKernels.active = selected AND capable, and every decision
    lands on the ``kernel_selected{name,backend}`` gauge."""
    from cxxnet_tpu.obs.registry import registry

    sel = klib.KernelSelector("zero_update")
    kb = sel.bind("cpu")
    assert kb.interpret  # off-TPU: the interpret spelling
    assert kb.active("zero_update", w=jnp.zeros((3,), jnp.float32),
                     updater=_sgd())
    g = registry().gauge("kernel_selected", labelnames=("name", "backend"))
    assert g.labels(name="zero_update", backend="cpu").get() == 1.0
    # capable-but-wrong-dtype: probe rejects, gauge drops to 0
    assert not kb.active("zero_update", w=jnp.zeros((3,), jnp.bfloat16),
                         updater=_sgd())
    assert g.labels(name="zero_update", backend="cpu").get() == 0.0


# ----------------------------------------------------------------------
# end-to-end dispatch: net forward / quant predict / train step
def _sibling_trainer(kernel_lib, cfg=None, seed="7"):
    from tests.test_trainer import INCEPTION_CFG

    tr = NetTrainer()
    tr.set_params(C.parse_pairs(
        (cfg or INCEPTION_CFG)
        + f"fuse_1x1 = 1\nkernel_lib = {kernel_lib}\n"))
    tr.set_param("seed", seed)
    tr.init_model()
    return tr


def test_net_forward_parity_conv_block():
    """Scores of the kernel-forced net match the stock net (same seed)
    to f32 conv-vs-GEMM rounding — including the strided ResNet
    boundary pair."""
    from tests.test_trainer import RESNET_BOUNDARY_CFG

    rng = np.random.RandomState(5)
    for cfg in (None, RESNET_BOUNDARY_CFG):
        x = jnp.asarray(rng.randn(16, 6, 6, 3).astype(np.float32))
        t0 = _sibling_trainer("off", cfg)
        t1 = _sibling_trainer("conv_block", cfg)
        s0 = np.asarray(t0.predict_fn(None)(t0.params, t0.aux, x, ()))
        s1 = np.asarray(t1.predict_fn(None)(t1.params, t1.aux, x, ()))
        np.testing.assert_allclose(s1, s0, rtol=1e-5, atol=1e-7)


def test_net_quant_predict_parity_int8_gemm():
    from cxxnet_tpu.nnet import quant as nquant
    from tests.test_quant import _batch, _conv_trainer

    b = _batch()
    t0 = _conv_trainer((("kernel_lib", "off"),))
    t1 = _conv_trainer((("kernel_lib", "int8_gemm"),))
    for t in (t0, t1):
        nquant.apply_plan(t, nquant.build_plan(t))
    x = jnp.asarray(b.data)
    s0 = np.asarray(t0.predict_fn(None)(t0.params, t0.aux, x, ()))
    s1 = np.asarray(t1.predict_fn(None)(t1.params, t1.aux, x, ()))
    np.testing.assert_array_equal(s0, s1)


def test_train_step_parity_with_kernels_forced():
    """Training with every kernel pinned ON matches stock bit-for-bit:
    the forward stays stock in train builds (Pallas calls carry no vjp)
    and the zero_update kernel replays the sgd rule exactly — params
    AND momentum bitwise after 2 epochs."""
    from tests.test_trainer import batches

    rng = np.random.RandomState(5)
    xd = rng.randn(32, 6, 6, 3).astype(np.float32)
    yd = rng.randint(0, 4, (32, 1)).astype(np.float32)
    t0 = _sibling_trainer("off")
    t1 = _sibling_trainer("conv_block,int8_gemm,zero_update")
    for tr in (t0, t1):
        for _ in range(2):
            for b in batches(xd, yd):
                tr.update(b)
    for tree0, tree1 in ((t0.params, t1.params),
                         (t0.ustates, t1.ustates)):
        l0 = jax.tree_util.tree_leaves(tree0)
        l1 = jax.tree_util.tree_leaves(tree1)
        assert len(l0) == len(l1)
        for a, b in zip(l0, l1):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_update_kernel_actually_fires(monkeypatch):
    """Guard against the silent-stock failure mode: with zero_update
    pinned ON, the trainer's update program must route every sgd tensor
    through the kernel launcher."""
    calls = {"n": 0}
    real = update_step.sgd_update

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(update_step, "sgd_update", counting)
    from tests.test_trainer import batches

    rng = np.random.RandomState(3)
    xd = rng.randn(16, 6, 6, 3).astype(np.float32)
    yd = rng.randint(0, 4, (16, 1)).astype(np.float32)
    tr = _sibling_trainer("zero_update")
    for b in batches(xd, yd):
        tr.update(b)
    # one launch per (key, tag) at trace time: 5 layers x (wmat, bias)
    assert calls["n"] == 10


def test_kernel_lib_conf_typo_fails_at_set_param():
    tr = NetTrainer()
    with pytest.raises(ValueError, match="kernel_lib"):
        tr.set_param("kernel_lib", "conv_blok")


# ----------------------------------------------------------------------
# serve: cache-key isolation + stock/kernel coexistence
def test_bucket_cache_kernel_fingerprint_isolation():
    """The kernel selection rides in the `_run` key (second-to-last —
    the quant scheme stays last): stock and kernel programs of ONE net
    occupy distinct slots and serve side by side, bit-equal."""
    from cxxnet_tpu.serve.cache import ShapeBucketCache

    t_off = _sibling_trainer("off")
    t_on = _sibling_trainer("conv_block")
    c_off = ShapeBucketCache(t_off, 16)
    c_on = ShapeBucketCache(t_on, 16)
    assert c_off.kernel_fp() == ""
    assert c_on.kernel_fp() == "conv_block"
    rng = np.random.RandomState(0)
    x = rng.rand(4, 6, 6, 3).astype(np.float32)
    s_off = c_off.scores(x)
    s_on = c_on.scores(x)
    np.testing.assert_array_equal(s_off, s_on)  # coexisting, identical
    k_off, k_on = c_off.keys_snapshot()[0], c_on.keys_snapshot()[0]
    assert k_off[0] == k_on[0]          # same net fingerprint ...
    assert k_off[-2] == "" and k_on[-2] == "conv_block"  # ... new slot
    assert k_off[-1] == k_on[-1] == ""  # quant scheme stays last
    assert k_off != k_on


# ----------------------------------------------------------------------
# the A/B driver: verdict schema + parity gate, in-process
def test_kernel_ab_emits_schema_valid_verdict(tmp_path):
    import sys as _sys

    _sys.path.insert(0, "tools")
    import kernel_ab
    import perf_guard

    res = kernel_ab.run_kernel("int8_gemm", smoke=True, backend="cpu",
                               reps=1)
    assert res["parity"] is True
    assert res["verdict"] in ("promote", "reject")
    hist = str(tmp_path / "hist.jsonl")
    doc = perf_guard.run_once(
        "kernel_bench", {"backend": "cpu", "kernels": [res]}, hist,
        window=5, band=0.2)
    assert perf_guard.validate_verdict(doc) == []
    m = doc["metrics"]
    assert m["int8_gemm_parity"] == 1.0
    assert m["int8_gemm_stock_ms"] > 0 and m["int8_gemm_kernel_ms"] > 0
    # the lower-is-better orientation lands on the _ms series
    assert perf_guard.lower_is_better("int8_gemm_kernel_ms")
    assert not perf_guard.lower_is_better("int8_gemm_ratio")
