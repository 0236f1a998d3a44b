"""chip_smoke.py — does the system still start on the chip?

One process, three legs, exit 0 only if every leg passed on a TPU:

* **train** — the main path.  GoogLeNet at its full benchmark width
  (batch 128 per chip, 3x224x224, 1000 classes, bfloat16, the conf's
  own ``scan_steps = 8``, sgd+momentum) through ``cxxnet_tpu.cli``
  (``LearnTask.run``, what ``python -m cxxnet_tpu <conf>`` runs): conf
  text -> synthetic iterator -> two scanned 8-step chunks -> the eval
  pass -> a round checkpoint.  Checked: every mesh device is a TPU,
  train logloss finite on both rounds and moving, params finite and
  changed, the checkpoint loads back, and the chip's inference build
  (bf16, sibling-1x1 + branch-embed fusion — a path no default CPU test
  takes) agrees with the plain f32 reference forward on a small input.
* **lm** — the one Pallas kernel on a default path.  The d512 L4 byte
  LM at T=2048, batch 8, ``attn_impl = auto``, 16 steps through the
  CLI on a corpus made from a seed.  Checked: the train step's lowering
  holds the Mosaic custom call (``auto`` did not drop to ``mha``),
  logloss finite and falling.
* **kernels** — every Pallas kernel in ``ops/`` compiled for the chip
  (``interpret=False``) at a shape the zoo really uses and compared
  with its XLA reference; a kernel Mosaic is known to refuse must
  raise, with the compiler's message, rather than fall back.

Weights and data come from seeds; nothing is read but this checkout and
nothing is fetched.  Timings printed here are *smoke, not a benchmark*:
two warm-up-free rounds, compile included where it says so.

    python chip_smoke.py                  # the contract: one chip
    python chip_smoke.py --chips 4        # train leg data-parallel on four
    python chip_smoke.py --cpu-rehearsal  # toy sizes on CPU; never passes

Without an accelerator (and without ``--cpu-rehearsal``) it exits 2 in
seconds and says which platform JAX found.  The last stdout line of a
passing run is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))


def say(msg: str = "") -> None:
    print(msg, flush=True)


class Sizes:
    """Full width on the chip; toy on the CPU rehearsal (same code
    path, sized so the Pallas interpreter finishes in seconds)."""

    def __init__(self, rehearsal: bool, chips: int) -> None:
        self.rehearsal = rehearsal
        self.chips = chips
        if rehearsal:
            self.batch, self.image, self.nclass = 4 * chips, 64, 10
            self.lm = dict(seq_len=128, dim=64, nhead=2, nlayer=1,
                           batch_size=2)
            # T >= 1024 is where auto picks flash; the toy T needs the
            # explicit opt-in to run the same kernel (interpreted)
            self.attn_impl = "pallas"
        else:
            self.batch, self.image, self.nclass = 128 * chips, 224, 1000
            self.lm = dict(seq_len=2048, dim=512, nhead=8, nlayer=4,
                           batch_size=8)
            self.attn_impl = "auto"
        self.scan = 8       # the shipped confs' scan_steps
        self.rounds = 2     # 2 rounds x 8 steps = 16 steps, 2 chunks


# ----------------------------------------------------------------------
# shared helpers
def _run_cli(conf_path: str):
    """Drive the CLI in-process; returns the task (trainer attached)."""
    from cxxnet_tpu.cli import LearnTask

    task = LearnTask()
    rc = task.run([conf_path])
    if rc != 0:
        raise RuntimeError(f"cli returned {rc} for {conf_path}")
    return task


def _telemetry(path: str):
    with open(path, "r", encoding="utf-8") as f:
        return [json.loads(line) for line in f if line.strip()]


def _compile_seconds() -> float:
    from cxxnet_tpu.obs import device as obs_device

    return float(obs_device.summary()["compile_seconds"])


def _params_flat(tr):
    import jax
    import numpy as np

    return np.concatenate([
        np.asarray(leaf, np.float32).ravel()
        for leaf in jax.tree_util.tree_leaves(tr.params)])


def _check_rounds(recs, key: str, rounds: int):
    """Finite metric on the first and last round, and the two differ."""
    import math

    if len(recs) != rounds:
        raise AssertionError(f"expected {rounds} round records, got "
                             f"{len(recs)}")
    vals = [r["eval"].get(key) for r in recs]
    if any(v is None or not math.isfinite(v) for v in vals):
        raise AssertionError(f"{key} missing or non-finite: {vals}")
    if vals[0] == vals[-1]:
        raise AssertionError(f"{key} did not move: {vals}")
    return vals


# ----------------------------------------------------------------------
# leg 1: GoogLeNet task=train through the CLI
def leg_train(sz: Sizes, out: str) -> dict:
    import jax
    import numpy as np

    from cxxnet_tpu import config as cfgmod
    from cxxnet_tpu.models import googlenet_conf
    from cxxnet_tpu.nnet.trainer import NetTrainer

    model_dir = os.path.join(out, "train_models")
    tele = os.path.join(out, "train_telemetry.jsonl")
    if os.path.exists(tele):
        os.remove(tele)
    dev = "tpu" if sz.chips == 1 else f"tpu:0-{sz.chips - 1}"
    conf = googlenet_conf(
        batch_size=sz.batch, input_size=sz.image, num_class=sz.nclass,
        synthetic=True, nsample=sz.batch * sz.scan, dev=dev,
    ) + (
        "metric = logloss\n"
        f"num_round = {sz.rounds}\nmax_round = {sz.rounds}\n"
        f"model_dir = {model_dir}\n"
        "keep_latest = 1\n"
        f"telemetry = 1\ntelemetry_path = {tele}\n"
    )
    conf_path = os.path.join(out, "googlenet_smoke.conf")
    with open(conf_path, "w", encoding="utf-8") as f:
        f.write(conf)

    c0, t0 = _compile_seconds(), time.perf_counter()
    task = _run_cli(conf_path)
    wall = time.perf_counter() - t0
    tr = task.net_trainer

    devs = list(tr.mesh_plan.mesh.devices.flat)
    if not sz.rehearsal:
        bad = [str(d) for d in devs if d.platform != "tpu"]
        if bad:
            raise AssertionError(f"mesh holds non-TPU devices: {bad}")
    if len({d.id for d in devs}) != sz.chips:
        raise AssertionError(f"mesh has {len(devs)} devices, wanted "
                             f"{sz.chips} distinct")
    recs = _telemetry(tele)
    loss = _check_rounds(recs, "train-logloss", sz.rounds)
    _check_rounds(recs, "test-logloss", sz.rounds)
    if tr.epoch_counter != sz.rounds * sz.scan:
        raise AssertionError(f"ran {tr.epoch_counter} steps, wanted "
                             f"{sz.rounds * sz.scan}")
    after = _params_flat(tr)
    if not np.isfinite(after).all():
        raise AssertionError("non-finite parameters after training")

    # the checkpoint loads back into the plain reference build: f32, no
    # sibling-1x1 fusion, no branch embedding, full matmul precision
    ckpt = os.path.join(model_dir, f"{sz.rounds:04d}.model")
    if not os.path.exists(ckpt):
        raise AssertionError(f"no round checkpoint at {ckpt}")
    nref = 8
    ref = NetTrainer()
    ref.set_params(cfgmod.parse_pairs(conf))
    for k, v in (("batch_size", str(nref)), ("dev", "tpu"),
                 ("compute_dtype", "float32"), ("fuse_1x1", "0"),
                 ("conv_branch_embed", "0"), ("silent", "1")):
        ref.set_param(k, v)
    ref.load_model(ckpt)
    if not np.array_equal(_params_flat(ref), after):
        raise AssertionError("checkpoint does not hold the trained params")
    fresh = NetTrainer()
    fresh.set_params(cfgmod.parse_pairs(conf))
    fresh.set_param("silent", "1")
    fresh.init_model()
    if np.array_equal(_params_flat(fresh), after):
        raise AssertionError("training left the parameters unchanged")
    del fresh

    x = np.random.RandomState(7).randn(
        nref, sz.image, sz.image, 3).astype(np.float32)
    fast = np.asarray(tr.predict_fn(None)(tr.params, tr.aux, x, ()))
    with jax.default_matmul_precision("highest"):
        slow = np.asarray(ref.predict_fn(None)(ref.params, ref.aux, x, ()))
    if fast.shape != (nref, sz.nclass) or not np.isfinite(fast).all():
        raise AssertionError(f"bad inference output {fast.shape}")
    # bf16 carries ~3 significant digits through ~22 layers: hold the
    # class probabilities to 10% of the reference (plus 1e-4 absolute
    # for the near-zero tail)
    dev_rel = float(np.max(np.abs(fast - slow) / (np.abs(slow) + 1e-4)))
    if dev_rel > 0.10:
        raise AssertionError(
            f"chip inference build deviates {dev_rel:.3f} (relative) "
            "from the plain f32 forward")

    four = {}
    if sz.chips > 1:
        four = _check_spread(tr, devs, sz)
    for name in os.listdir(model_dir):  # ~28 MB each: not an artifact
        os.remove(os.path.join(model_dir, name))
    step = recs[-1]["step"]
    return {
        "steps": tr.epoch_counter, "chunks": sz.rounds,
        "logloss_first": loss[0], "logloss_last": loss[-1],
        "wall_s": round(wall, 1),
        "compile_s": round(_compile_seconds() - c0, 1),
        "step_ms_last_round": round(step["mean_ms"], 2),
        "fused_vs_f32_rel": round(dev_rel, 4),
        **four,
    }


def _check_spread(tr, devs, sz: Sizes) -> dict:
    """Data parallelism really uses every chip: the batch shards over
    all of them, each holds a copy of every parameter, each has memory
    in use — nothing piled on device 0."""
    import jax
    import numpy as np

    want = {d.id for d in devs}
    x = np.zeros((sz.batch, sz.image, sz.image, 3), np.float32)
    staged = jax.device_put(x, tr.mesh_plan.data_sharding())
    rows = {s.device.id: s.data.shape[0] for s in staged.addressable_shards}
    if set(rows) != want or set(rows.values()) != {sz.batch // sz.chips}:
        raise AssertionError(f"batch shards {rows}, wanted "
                             f"{sz.batch // sz.chips} rows on each of {want}")
    for leaf in jax.tree_util.tree_leaves(tr.params):
        held = {s.device.id for s in leaf.addressable_shards}
        if held != want:
            raise AssertionError(f"a parameter lives on {held}, not {want}")
    in_use = {}
    for d in devs:
        stats = d.memory_stats() or {}
        in_use[d.id] = int(stats.get("bytes_in_use", 0))
    if not sz.rehearsal and min(in_use.values()) <= 0:
        raise AssertionError(f"a device holds no memory: {in_use}")
    return {"devices": sorted(want), "batch_rows_per_device": rows,
            "bytes_in_use": in_use}


# ----------------------------------------------------------------------
# leg 2: byte LM through the CLI, flash attention on the default path
def leg_lm(sz: Sizes, out: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cxxnet_tpu.models import transformer_lm_conf

    t, b = sz.lm["seq_len"], sz.lm["batch_size"]
    # a learnable corpus from a seed: sentences over a 48-word lexicon,
    # exactly scan x batch windows so one round is one scanned chunk
    rng = np.random.RandomState(11)
    words = ["".join(chr(97 + c) for c in rng.randint(0, 26, rng.randint(2, 9)))
             for _ in range(48)]
    need = sz.scan * b * t + 1
    text, n = [], 0
    while n < need:
        w = words[rng.randint(len(words))]
        text.append(w)
        n += len(w) + 1
    corpus = os.path.join(out, "lm_corpus.txt")
    with open(corpus, "w", encoding="ascii") as f:
        f.write(" ".join(text)[:need])

    tele = os.path.join(out, "lm_telemetry.jsonl")
    if os.path.exists(tele):
        os.remove(tele)
    conf = transformer_lm_conf(
        text_file=corpus, num_round=sz.rounds, attn_impl=sz.attn_impl,
        dev="tpu", **sz.lm,
    ) + (
        "save_model = 0\n"
        f"telemetry = 1\ntelemetry_path = {tele}\n"
    )
    conf_path = os.path.join(out, "lm_smoke.conf")
    with open(conf_path, "w", encoding="utf-8") as f:
        f.write(conf)

    c0, t0 = _compile_seconds(), time.perf_counter()
    task = _run_cli(conf_path)
    wall = time.perf_counter() - t0
    tr = task.net_trainer
    recs = _telemetry(tele)
    loss = _check_rounds(recs, "train-logloss", sz.rounds)
    if tr.epoch_counter != sz.rounds * sz.scan:
        raise AssertionError(f"ran {tr.epoch_counter} steps, wanted "
                             f"{sz.rounds * sz.scan}")
    if not loss[-1] < loss[0]:
        raise AssertionError(f"LM logloss did not fall: {loss}")

    # did auto really take the flash kernel?  Lower (trace only, no
    # compile) the very scan program the CLI ran and look for Mosaic.
    stack = jax.ShapeDtypeStruct((sz.scan, b, t), jnp.float32)
    lowered = tr._scan_step_fn(sz.scan, True, True).lower(
        tr.params, tr.ustates, tr.aux, stack, stack, tr._rng_key,
        jnp.int32(0))
    mosaic = lowered.as_text().count("tpu_custom_call")
    if not sz.rehearsal and mosaic == 0:
        raise AssertionError(
            "the LM train step holds no Mosaic custom call: attn_impl="
            "auto dropped to the XLA mha path")
    if not np.isfinite(_params_flat(tr)).all():
        raise AssertionError("non-finite LM parameters")
    step = recs[-1]["step"]
    return {
        "steps": tr.epoch_counter, "logloss_first": loss[0],
        "logloss_last": loss[-1], "mosaic_calls_in_step": mosaic,
        "wall_s": round(wall, 1),
        "compile_s": round(_compile_seconds() - c0, 1),
        "step_ms_last_round": round(step["mean_ms"], 2),
    }


# ----------------------------------------------------------------------
# leg 3: every Pallas kernel, compiled for the chip, vs its XLA reference
def _kernel_cases(sz: Sizes, interpret: bool, abstract: bool = False):
    """``(name, expect, kernel, reference, args, tol)``: the kernel is
    jitted over ``args`` as they are (bf16 where the confs use bf16),
    the XLA reference over the same values in f32 at full matmul
    precision, and the outputs compared; ``expect`` is "ok" or "raises"
    (a geometry Mosaic cannot lower: selecting it must fail loudly,
    never fall back).  ``abstract`` swaps the operands for their
    shapes: tests/test_chip_contracts.py cross-lowers the same kernels
    at the same shapes for the TPU from a CPU, with nothing allocated."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from cxxnet_tpu.layers import create_layer, moe
    from cxxnet_tpu.layers.conv import _maxpool_eq
    from cxxnet_tpu.ops import quant as opsq
    from cxxnet_tpu.ops.attention import mha
    from cxxnet_tpu.ops.flash import (count_blocks, flash_attention,
                                      flash_mha, flash_mha_lse)
    from cxxnet_tpu.ops.gdn import gated_delta_recurrence
    from cxxnet_tpu.ops.gdn_fused import gated_delta_fused
    from cxxnet_tpu.ops.kernels import conv_block, int8_gemm, update_step
    from cxxnet_tpu.ops.lrn import lrn, lrn_xla
    from cxxnet_tpu.ops.ssd import ssd_recurrence
    from cxxnet_tpu.ops.ssd_fused import ssd_fused
    from cxxnet_tpu.ops.maxpool import maxpool_bwd_s1, maxpool_fused
    from cxxnet_tpu.updater import SGDUpdater

    rng = np.random.RandomState(3)
    toy = sz.rehearsal
    # a bf16 result against the exact one: one rounding of the output
    # (2^-9) plus bf16 operand passes inside the MXU dots — held to
    # 2^-6 of the tensor's own scale (measured on the v5e: <= 7e-3)
    BF16 = 2.0 ** -6

    def arr(*shape, dtype=jnp.bfloat16, scale=1.0):
        if abstract:
            return jax.ShapeDtypeStruct(shape, dtype)
        return jnp.asarray(
            rng.randn(*shape).astype(np.float32) * scale).astype(dtype)

    def with_grads(fn, nargs):
        """fn's outputs plus d(sum of squares of its outputs)/d(args):
        one program exercising the forward and the backward kernels."""
        def run(*args):
            def loss(*a):
                outs = fn(*a)
                return sum((o.astype(jnp.float32) ** 2).sum()
                           for o in outs), outs
            (_, outs), grads = jax.value_and_grad(
                loss, argnums=tuple(range(nargs)), has_aux=True)(*args)
            return outs + grads
        return run

    # -- flash attention at the LM geometry (heads fold into the grid)
    tq, nh = (256, 2) if toy else (2048, 8)

    # -- the ring building block: dynamic offsets, (out, lse) outputs.
    # Keys start before the queries, so the shifted causal mask cuts
    # through live blocks; the reference is plain softmax attention.
    ts = tq // 2
    q_off, k_off = jnp.int32(ts), jnp.int32(ts // 2)

    def lse_ref(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) / 8.0
        live = (q_off + jnp.arange(ts)[:, None]
                >= k_off + jnp.arange(ts)[None, :])
        s = jnp.where(live, s, -1e30)
        lse = jax.nn.logsumexp(s, axis=-1)               # (b, h, q)
        o = jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(s - lse[..., None]),
                       v.astype(jnp.float32))
        return o.astype(q.dtype), lse.transpose(0, 2, 1)

    # -- pooling at GoogLeNet i3a's inception pool and the stem pool
    nb = 4 if toy else 128
    xp, stem = arr(nb, 28, 28, 192), arr(nb, 112, 112, 64)

    def pool_pair(k, s, pad):
        return (
            with_grads(lambda x: (maxpool_fused(
                x, k, k, s, pad, pad, interpret),), 1),
            with_grads(lambda x: (_maxpool_eq(x, k, k, s, pad, pad),), 1))

    pool_s1 = lambda t: _maxpool_eq(t, 3, 3, 1, 1, 1)  # noqa: E731
    yp = jax.eval_shape(pool_s1, xp) if abstract else pool_s1(xp)

    # -- conv_block at GoogLeNet i3a's sibling-1x1 group (64 + 96 + 16)
    wk, bias = arr(1, 1, 192, 176, scale=0.1), arr(176)

    # -- int8 GEMM at GoogLeNet's classifier (1024 -> 1000)
    qw, qscale = opsq.quantize_weight(
        rng.randn(1000, 1024).astype(np.float32), out_axis=0)
    lp = {opsq.QKEY: jnp.asarray(qw), opsq.SKEY: jnp.asarray(qscale),
          "bias": arr(1000, dtype=jnp.float32)}

    # -- fused sgd step on the classifier weight (f32 master params)
    up = SGDUpdater("wmat")
    for kv in (("eta", "0.01"), ("momentum", "0.9"), ("wd", "0.0002")):
        up.set_param(*kv)
    epoch = jnp.int32(3)

    def sgd_kernel(w, g, m):
        p = up.param
        return update_step.sgd_update(
            w, g, m, p.learning_rate(epoch).astype(w.dtype),
            p.momentum_at(epoch).astype(w.dtype), wd=p.wd,
            clip=p.clip_gradient, interpret=interpret)

    def sgd_ref(w, g, m):
        w2, s2 = up.apply(w, g, {"m": m}, epoch)
        return w2, s2["m"]

    # -- the gated delta rule at Qwen3-Next's head widths (128 x 128,
    # two value heads a key head), documents inside a row; unit-length
    # keys, a decay and a write strength are made of the raw operands
    # on both sides
    td, hkd = (128, 1) if toy else (2048, 2)
    doc = jnp.asarray(np.cumsum(
        np.random.RandomState(4).rand(2, td) < 4.0 / td, axis=1), jnp.int32)

    def delta(scan):
        def run(q, k, v, g, b):
            unit = lambda a: a * lax.rsqrt(  # noqa: E731
                (a.astype(jnp.float32) ** 2).sum(-1, keepdims=True)
                + 1e-6).astype(a.dtype)
            return (scan(unit(q) * q.dtype.type(128 ** -0.5), unit(k), v,
                         -jax.nn.softplus(g), jax.nn.sigmoid(b), doc),)
        return with_grads(run, 5)

    # -- the Mamba-2 scan at granite's head widths (64 columns on a state
    # of 128, sixteen heads in two grid steps, chunks of 256), the same
    # documents; a positive step and a negative rate are made of the raw
    # operands on both sides (the rate is not differentiated: its
    # gradient is one number a head, what is left of sums that cancel)
    hs, qs = (2, 128) if toy else (16, 256)

    def ssd(scan):
        def run(x, dt, b, c, a):
            return (scan(x, jax.nn.softplus(dt - 3.0), -jnp.exp(a), b, c,
                         doc),)
        return with_grads(run, 4)

    # -- the masked kernels at latent attention's two widths, four query
    # heads a key-value head, a stated scale, against ``mha`` with the
    # same mask: rows of four blocks whose documents make a call run all
    # three kinds of block — no mask at all, one document crossed by the
    # diagonal or the window's edge, a document boundary — once without a
    # window and once under one of two and a half blocks
    blk = td // 4
    doc_m = jnp.asarray(
        np.searchsorted([int(.63 * td), int(.93 * td)], np.arange(td),
                        side="right")[None]
        + np.array([[0], [1]]) * (np.arange(td) >= td // 20), jnp.int32)
    masked_args = (arr(2, td, 4 * hkd, 192), arr(2, td, hkd, 192),
                   arr(2, td, hkd, 128))

    def masked(attn, window):
        mask = dict(causal=True, doc=doc_m, window=window)
        visited, free, one = (int(n) for n in count_blocks(
            *masked_args, block_q=blk, block_k=blk, **mask))
        assert 0 < free < one < visited, (visited, free, one)
        return with_grads(lambda q, k, v: (attn(q, k, v, scale=0.07,
                                                **mask),), 3)

    masked_pairs = [
        (name, "ok", masked(lambda *a, **kw: flash_attention(
            *a, block_q=blk, block_k=blk, interpret=interpret, **kw)[0], w),
         masked(mha, w), masked_args, BF16)
        for name, w in (("flash_attention masked fwd+bwd", 0),
                        ("flash_attention masked window fwd+bwd",
                         5 * blk // 2))]

    # -- latent attention at JoyAI-LLM-Flash's widths, a quarter of its
    # heads: the whole layer in bf16 against itself in f32, two documents
    # a row; on the chip its ``core`` is the flash kernels (a row of 2048
    # tokens: ``ops/attention.attend``), in the rehearsal ``mha``
    tm, dm, hm = (128, 64, 2) if toy else (2048, 2048, 8)
    mla = create_layer("latent_attention")
    mla_cfg = dict(nhead=hm, q_rank=32, kv_rank=16, nope_dim=16, rope_dim=8,
                   v_dim=16) if toy else dict(
        nhead=hm, q_rank=1536, kv_rank=512, nope_dim=128, rope_dim=64,
        v_dim=128)
    for key, val in dict(mla_cfg, rope_theta=3.2e7, causal=1).items():
        mla.set_param(key, str(val))
    mla.infer_shape([(1, tm, dm), (1, tm)])
    mla_shapes = jax.eval_shape(
        lambda: mla.init_params(jax.random.PRNGKey(0),
                                [(1, tm, dm), (1, tm)]))
    mla_p = {t: arr(*v.shape, scale=1.0 if v.ndim == 1 else 0.02)
             for t, v in sorted(mla_shapes.items())}
    mla_ids = jnp.ones((1, tm), jnp.float32).at[0, tm // 3].set(0.0)
    mla_run = with_grads(
        lambda x, p: (mla.apply(p, [x, mla_ids])[0],), 2)

    # -- a share of routed experts at Qwen3-Next's widths (32 of 512
    # held, top-10), a row of 2048 tokens, against the dense masked loop:
    # once as the router sends them (~1/16 of the picks held, one slab)
    # and once with the held experts chosen first by a selection bias
    # (every pick held: eight slabs through the loop of layers/moe.py)
    te, de, fe, ne, ke, ge = (256, 64, 32, 32, 4, 4) if toy else (
        2048, 2048, 512, 512, 10, 32)
    experts = create_layer("routed_experts")
    for key, val in dict(nexpert=ne, topk=ke, nhidden=fe, nheld=ge,
                         select_bias=1).items():
        experts.set_param(key, str(val))
    experts.infer_shape([(te, de)])
    exp_shapes = jax.eval_shape(
        lambda: experts.init_params(jax.random.PRNGKey(0), [(te, de)]))
    exp_p = {t: arr(*v.shape, scale=0.02)
             for t, v in sorted(exp_shapes.items()) if t != "score_bias"}
    biases = (jnp.zeros((ne,), jnp.float32),
              jnp.where(jnp.arange(ne) < ge, 2.0, 0.0))
    assert moe.slab_rows(te * ke, ge, ne) < te * ke      # there is a loop

    def experts_dense(x, p, bias):
        w, idx = moe.route(
            jnp.dot(x.astype(jnp.float32), p["wgate"].astype(jnp.float32).T,
                    precision=lax.Precision.HIGHEST), ke, True, bias=bias)
        w = lax.stop_gradient(w)
        y = jnp.zeros(x.shape, jnp.float32)
        for j in range(ge):
            gu = x @ p["wmat"][j]
            y = y + jnp.where(idx == j, w, 0.0).sum(-1)[:, None] * (
                (jax.nn.silu(gu[:, :fe]) * gu[:, fe:]) @ p["wproj"][j])
        return y

    experts_run, experts_ref = (with_grads(lambda x, p, fn=fn: tuple(
        fn(x, p, b) for b in biases), 2) for fn in (
        lambda x, p, b: experts.apply(dict(p, score_bias=b), [x])[0],
        experts_dense))

    return [
        ("routed_experts share fwd+bwd", "ok", experts_run, experts_ref,
         (arr(te, de), exp_p), BF16),
        ("latent_attention fwd+bwd", "ok", mla_run, mla_run,
         (arr(1, tm, dm), mla_p), 4 * BF16),
        ("gated_delta_fused fwd+bwd", "ok",
         delta(lambda *a: gated_delta_fused(*a, interpret=interpret)),
         delta(gated_delta_recurrence),
         tuple(arr(2, td, h, 128) for h in (hkd, hkd, 2 * hkd)) + tuple(
             arr(2, td, 2 * hkd, dtype=jnp.float32) for _ in range(2)),
         2 * BF16),
        ("ssd_fused fwd+bwd", "ok",
         ssd(lambda *a: ssd_fused(*a, qs, interpret=interpret)),
         ssd(ssd_recurrence),
         (arr(2, td, hs, 64), arr(2, td, hs, dtype=jnp.float32),
          arr(2, td, 128, scale=0.1), arr(2, td, 128),
          arr(hs, dtype=jnp.float32)), 2 * BF16),
        *masked_pairs,
        ("flash_mha fwd+bwd", "ok",
         with_grads(lambda q, k, v: (flash_mha(
             q, k, v, True, 512, 512, interpret),), 3),
         with_grads(lambda q, k, v: (mha(q, k, v, causal=True),), 3),
         tuple(arr(2, tq, nh, 64) for _ in range(3)), BF16),
        ("flash_mha_lse fwd+bwd", "ok",
         with_grads(lambda q, k, v: flash_mha_lse(
             q, k, v, q_off, k_off, True, 512, 512, interpret), 3),
         with_grads(lse_ref, 3),
         tuple(arr(2, ts, nh, 64) for _ in range(3)), BF16),
        ("maxpool_fused 3x3 s1 fwd+bwd", "ok", *pool_pair(3, 1, 1),
         (xp,), BF16),
        # stride > 1 is a strided slice, which Mosaic lowers as a
        # gather it does not support (the interpreter does)
        ("maxpool_fused 3x3 s2 fwd+bwd", "ok" if interpret else "raises",
         *pool_pair(3, 2, 0), (stem,), BF16),
        ("maxpool_bwd_s1", "ok",
         lambda x, y, g: maxpool_bwd_s1(x, y, g, 3, 1, interpret),
         lambda x, y, g: jax.vjp(pool_s1, x)[1](g)[0],
         (xp, yp, arr(*yp.shape)), BF16),
        # LRN at GoogLeNet n1 (after the stem pool)
        ("lrn fwd+bwd", "ok",
         with_grads(lambda x: (lrn(x, 5, 1e-4, 0.75, 1.0, interpret),), 1),
         with_grads(lambda x: (lrn_xla(x, 5, 1e-4, 0.75, 1.0),), 1),
         (arr(nb, 56, 56, 64, scale=4.0),), BF16),
        ("conv1x1_block", "ok",
         lambda x, w, b: conv_block.conv1x1_block(
             x, w, b, interpret=interpret),
         lambda x, w, b: lax.conv_general_dilated(
             x, w, (1, 1), ((0, 0), (0, 0)),
             dimension_numbers=("NHWC", "HWIO", "NHWC")) + b,
         (xp, wk, bias), BF16),
        ("int8_gemm_rescale", "ok",
         lambda lp, x: int8_gemm.int8_gemm_rescale(
             x, lp[opsq.QKEY], lp[opsq.SKEY], lp["bias"],
             interpret=interpret),
         opsq.fc_apply_q, (lp, arr(nb, 1024)), BF16),
        ("sgd_update", "ok", sgd_kernel, sgd_ref,
         tuple(arr(1000, 1024, dtype=jnp.float32, scale=s)
               for s in (0.05, 0.01, 0.01)), 1e-6),
    ]


def _max_rel(got, want) -> float:
    """Largest |got - want| over the reference tensor's own scale."""
    import jax
    import numpy as np

    worst = 0.0
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        if a.shape != b.shape:
            raise AssertionError(f"shape {a.shape} vs reference {b.shape}")
        if not np.isfinite(a).all():
            raise AssertionError("non-finite kernel output")
        worst = max(worst, float(np.abs(a - b).max()
                                 / max(float(np.abs(b).max()), 1e-30)))
    return worst


def leg_kernels(sz: Sizes, out: str) -> dict:
    import jax
    import jax.numpy as jnp

    interpret = sz.rehearsal
    results, failed = {}, []
    for name, expect, kernel, reference, args, tol in _kernel_cases(
            sz, interpret):
        t0 = time.perf_counter()
        try:
            got = jax.block_until_ready(jax.jit(kernel)(*args))
            with jax.default_matmul_precision("highest"):
                want = jax.jit(reference)(*jax.tree_util.tree_map(
                    lambda a: (a.astype(jnp.float32)
                               if a.dtype == jnp.bfloat16 else a), args))
            err = _max_rel(got, want)
            outcome = "ok" if err <= tol else "mismatch"
            detail = f"max rel err {err:.2e} (tol {tol:.1e})"
        except AssertionError as e:
            outcome, detail = "mismatch", str(e)
        except Exception as e:  # noqa: BLE001 - the compiler's refusal IS the result
            outcome = "raises"
            detail = f"{type(e).__name__}: {str(e).strip().splitlines()[0][:300]}"
        dt = time.perf_counter() - t0
        verdict = "as expected" if outcome == expect else f"EXPECTED {expect}"
        say(f"  kernel {name:32s} {outcome:8s} {dt:6.1f}s  {detail}  "
            f"[{verdict}]")
        results[name] = {"outcome": outcome, "detail": detail}
        if outcome != expect:
            failed.append(name)
    if failed:
        raise AssertionError(f"kernels off their expected outcome: {failed}")
    return results


# ----------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=os.path.join(
        REPO, "chiprun_out", "chip_smoke"), help="output directory")
    ap.add_argument("--chips", type=int, default=1,
                    help="devices for the train leg (4: dev=tpu:0-3, "
                         "global batch 512; runs the train leg only)")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="toy sizes on CPU, Pallas interpreted; prints "
                         "platform: cpu and never the pass line")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    try:
        import jax
        import jaxlib

        import cxxnet_tpu  # noqa: F401 - the program under test
        from cxxnet_tpu.utils import compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the program: {e}",
              file=sys.stderr, flush=True)
        return 2
    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    say(f"platform: {platform}\ndevice_kind: {kind}\n"
        f"device_count: {len(devs)}\n"
        f"jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"libtpu {libtpu} python {sys.version.split()[0]}")
    if args.cpu_rehearsal != (platform == "cpu"):
        print("chip_smoke: " + (
            f"no accelerator — JAX found platform {platform!r} "
            f"({kind}); this check only means something on the chip "
            "(--cpu-rehearsal runs the toy-size rehearsal)"
            if platform == "cpu" else
            "--cpu-rehearsal is for a CPU-only host; this one has "
            f"{platform!r}"), file=sys.stderr, flush=True)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX has "
              f"{len(devs)} device(s)", file=sys.stderr, flush=True)
        return 2

    os.makedirs(args.out, exist_ok=True)
    cache = compile_cache.enable()
    n_cache = len(os.listdir(cache))
    say(f"compile cache: {cache} ({n_cache} entries at start)")

    sz = Sizes(args.cpu_rehearsal, args.chips)
    legs = [("train", leg_train)]
    if args.chips == 1:
        legs += [("lm", leg_lm), ("kernels", leg_kernels)]
    report, ok = {}, True
    for name, fn in legs:
        say(f"\n=== leg {name} ===")
        t0 = time.perf_counter()
        try:
            report[name] = {"ok": True, **fn(sz, args.out)}
        except Exception as e:  # noqa: BLE001 - a leg fails, the rest still report
            traceback.print_exc()
            report[name] = {"ok": False,
                            "error": f"{type(e).__name__}: {e}"[:2000]}
            ok = False
        report[name]["leg_wall_s"] = round(time.perf_counter() - t0, 1)
        say(f"leg {name}: {'PASS' if report[name]['ok'] else 'FAIL'} "
            f"{json.dumps(report[name], sort_keys=True)}")

    n_after = len(os.listdir(cache))
    summary = {
        "legs": report,
        "wall_s": round(time.perf_counter() - t_start, 1),
        "compile_s": round(_compile_seconds(), 1),
        "cache_dir": cache, "cache_entries_start": n_cache,
        "cache_entries_added": n_after - n_cache,
        "note": "smoke timings, not a benchmark",
    }
    with open(os.path.join(args.out, "chip_smoke_report.json"), "w",
              encoding="utf-8") as f:
        json.dump(summary, f, indent=1, sort_keys=True)
    say(f"\nchip_smoke: wall {summary['wall_s']}s, XLA compile "
        f"{summary['compile_s']}s, cache entries added "
        f"{summary['cache_entries_added']} (smoke, not a benchmark)")
    sys.stderr.flush()
    if not ok:
        say("chip_smoke: FAILED")
        return 1
    if args.cpu_rehearsal:
        say("chip_smoke: cpu rehearsal complete — not a pass; the "
            "contract is the run on the chip")
        return 0
    say(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
