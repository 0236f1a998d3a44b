"""Benchmark: GoogLeNet training throughput, images/sec/chip.

Runs on a TPU only: with no accelerator it exits 2 and names the
platform JAX found, and a mode that fails exits non-zero — a number is
a measurement on the chip or it is not printed.  Prints ONE JSON line:
``{"metric": ..., "value": N, "unit": ..., "vs_baseline": N}``.
Baseline: BASELINE.json north star = 2000 images/sec/chip (v5e).

Measurement design:

* the benchmark drives the device-side multi-step path
  (``NetTrainer.update_scan``: ``lax.scan`` over the fused step), which
  pays host dispatch once per scan, not once per step;
* data is staged on device once (synthetic benchmark mode); ``--io``
  puts the real input pipeline in the measured path;
* the persistent XLA compilation cache (``utils/compile_cache.py``)
  makes every run after the first skip the GoogLeNet compile;
* a provisional JSON line is emitted right after the first timed scan,
  so a timeout mid-measurement still leaves a parseable (conservative)
  number on stdout; the final line overwrites it (drivers take the last
  JSON line).

This is one model per invocation on one staged batch; the benchmark
matrix that goes through the CLI is ROADMAP S1 and absorbs this file's
timing harness.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

BASELINE_IMG_S = 2000.0


def _set_stage(name: str) -> None:
    print(f"# stage[{name}]", file=sys.stderr, flush=True)


def _emit(tag: str, img_s: float, batch: int) -> None:
    print(json.dumps({
        "metric": "images/sec/chip (GoogLeNet b{} train)".format(batch),
        "value": round(img_s, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(img_s / BASELINE_IMG_S, 4),
    }), flush=True)
    print(f"# bench[{tag}]: {img_s:.1f} img/s/chip", file=sys.stderr, flush=True)


def _time_scans(tr, data, labels, scan_k: int, n_scans: int = 3,
                per_step_data: bool = False, step=None) -> float:
    """Warm twice, time n_scans device-side scans, return sec/step —
    the shared measurement harness of every bench mode.  ``step``
    overrides the dispatched program (default: the training
    ``update_scan``); its return value is what gets block-waited."""
    import jax

    if step is None:
        kw = {} if per_step_data else {"n_steps": scan_k}

        def step():
            tr.update_scan(data, labels, **kw)
            return tr.params

    last = None
    for _ in range(2):
        last = step()
    jax.block_until_ready(last)
    t0 = time.perf_counter()
    for _ in range(n_scans):
        last = step()
    jax.block_until_ready(last)
    return (time.perf_counter() - t0) / n_scans / scan_k


def bench_io(batch: int, scan_k: int) -> None:
    """``--io`` mode: the measured path includes the REAL input pipeline
    (imgbin JPEG shards -> native decode pool -> crop/mirror augment ->
    batch -> threadbuffer -> scan_steps staging).  Reported on stderr
    only — the stdout JSON stays the device-rate metric.

    Measures the pipeline BOTH ways (doc/io.md records the results):

    * serial: decode a chunk, then block on its device scan — the rate
      is the harmonic combination of host and device rates;
    * overlapped: async scans with a 2-deep in-flight window (the CLI's
      default train loop) — the device chews chunk k while the host
      decodes k+1, so the rate approaches min(host, device).  On this
      project's 1-core CI host the host side ceilings at ~1.1k
      img/s/core, so "overlap works" shows up as combined ~= host-only.
    """
    import tempfile

    import jax

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "tools"))
    from io_bench import generate_imgbin

    from cxxnet_tpu import config as cfgmod
    from cxxnet_tpu.models import googlenet_conf
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.io.data import create_iterator

    n_img = batch * scan_k * 2
    with tempfile.TemporaryDirectory() as workdir:
        t0 = time.perf_counter()
        generate_imgbin(workdir, n_img, 256)
        print(f"# imgbin: {n_img} jpegs in {time.perf_counter() - t0:.0f}s",
              file=sys.stderr, flush=True)
        itcfg = f"""
data = train
iter = imgbin
  image_bin = {workdir}/bench.bin
  image_list = {workdir}/bench.lst
  rand_crop = 1
  rand_mirror = 1
  input_shape = 3,224,224
  batch_size = {batch}
  round_batch = 1
  label_width = 1
iter = threadbuffer
iter = end
"""
        sec = cfgmod.split_sections(cfgmod.parse_pairs(itcfg)).find("data")[0]
        it = create_iterator(sec.entries)
        it.init()
        tr = NetTrainer()
        tr.set_params(cfgmod.parse_pairs(
            googlenet_conf(batch_size=batch, input_size=224,
                           synthetic=False, dev="tpu")
        ))
        tr.eval_train = 0
        tr.init_model()

        import numpy as np_

        def host_only() -> float:
            """Input pipeline alone (test_io discipline): everything the
            train loop pays on the host — batch copy + chunk stack —
            minus only the device dispatch, so the overlap target is the
            honest host ceiling."""
            it.before_first()
            got, pending = 0, []
            t0 = time.perf_counter()
            while it.next():
                b = it.value()
                pending.append((np_.array(b.data), np_.array(b.label)))
                if len(pending) == scan_k:
                    np_.stack([d for d, _ in pending])
                    np_.stack([l for _, l in pending])
                    got += batch * len(pending)
                    pending.clear()
            got += batch * len(pending)
            return got / (time.perf_counter() - t0)

        def epoch(overlap: bool) -> float:
            it.before_first()
            got, pending, in_flight = 0, [], []
            t0 = time.perf_counter()
            while it.next():
                b = it.value()
                pending.append((np_.array(b.data), np_.array(b.label)))
                if len(pending) == scan_k:
                    h = tr.update_scan(
                        np_.stack([d for d, _ in pending]),
                        np_.stack([l for _, l in pending]),
                        sync=not overlap,
                    )
                    if overlap:
                        in_flight.append(h)
                        while len(in_flight) > 1:
                            jax.block_until_ready(in_flight.pop(0))
                    got += batch * len(pending)
                    pending.clear()
            for d, l in pending:
                tr.update_all(d, l)
                got += batch
            jax.block_until_ready(tr.params)
            if in_flight:
                jax.block_until_ready(in_flight)
            return got / (time.perf_counter() - t0)

        epoch(False)  # compile + warm page cache
        host = host_only()
        serial = epoch(False)
        lapped = epoch(True)
        print(
            f"# bench[io]: host-only {host:.0f} img/s | serial "
            f"decode->scan {serial:.0f} img/s | overlapped {lapped:.0f} "
            f"img/s (target: ~= host-only when device is faster)",
            file=sys.stderr, flush=True,
        )


def bench_lm(batch: int, seq_len: int, scan_k: int) -> None:
    """``--lm`` mode: transformer-LM training throughput (stderr only —
    the stdout JSON stays the BASELINE GoogLeNet metric).  d512 h8 L4
    bf16, flash attention, device-side multi-step scan."""
    import jax

    from cxxnet_tpu import config as cfgmod
    from cxxnet_tpu.models import transformer_lm_conf
    from cxxnet_tpu.nnet.trainer import NetTrainer

    conf = transformer_lm_conf(
        seq_len=seq_len, dim=512, nhead=8, nlayer=4, batch_size=batch,
        dev="tpu", compute_dtype="bfloat16",
    )
    tr = NetTrainer()
    tr.set_params(cfgmod.parse_pairs(conf))
    tr.eval_train = 0
    tr.init_model()
    rng = np.random.RandomState(0)
    data = rng.randint(0, 255, (scan_k, batch, seq_len)).astype(np.float32)
    labels = rng.randint(0, 255, (scan_k, batch, seq_len)).astype(np.float32)
    dt = _time_scans(tr, data, labels, scan_k, n_scans=1,
                     per_step_data=True)
    print(
        f"# bench[lm]: T={seq_len} b{batch} d512 L4: {dt*1e3:.1f} ms/step "
        f"= {batch*seq_len/dt/1e3:.0f}k tokens/s/chip",
        file=sys.stderr, flush=True,
    )


def bench_flash(seq_lens) -> None:
    """``--flash`` mode: the flash-attention kernel vs the XLA mha path,
    fwd+bwd, causal, b4 h8 d64 bf16 (the doc/performance.md fixture) —
    codifies the round-2 ad-hoc numbers as a reproducible sweep.  The
    XLA path is skipped where its (B,H,T,T) score matrix cannot fit
    (T >= 8192 on a 16 GB v5e); any other failure fails the run."""
    import jax
    import jax.numpy as jnp

    from cxxnet_tpu.ops.attention import mha
    from cxxnet_tpu.ops.flash import flash_mha

    b, h, d = 4, 8, 64
    rng = np.random.RandomState(0)
    for t in seq_lens:
        # (B, T, H, Dh) — the layout flash_mha and attention.mha share
        qkv = [
            jax.device_put(rng.randn(b, t, h, d).astype(np.float32)
                           .astype(jnp.bfloat16))
            for _ in range(3)
        ]
        # Attention is 2 (T,d)x(d,T)-shaped matmuls forward (QK^T, PV)
        # and 5 backward (dV=P^T dO, dP=dO V^T, dS->dQ, dS->dK, plus the
        # recomputed QK^T under remat), each 2*T*T*d FLOPs per (b,h);
        # causal masking halves the useful work.  Same count applied to
        # flash and the XLA path, so the two TFLOP/s are comparable to
        # each other AND to external causal-MFU numbers.
        matmul = 2 * b * h * t * t * d
        flops = (2 + 5) * matmul / 2  # fwd + bwd, causal

        def timed(fn, tag):
            def loss(q, k, v):
                return (fn(q, k, v).astype(jnp.float32) ** 2).sum()

            g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            jax.block_until_ready(g(*qkv))
            t0 = time.perf_counter()
            for _ in range(10):
                out = g(*qkv)
            jax.block_until_ready(out)
            dt = (time.perf_counter() - t0) / 10
            print(f"# bench[flash]: T={t} {tag}: {dt*1e3:.2f} ms "
                  f"fwd+bwd = {flops/dt/1e12:.1f} TFLOP/s",
                  file=sys.stderr, flush=True)

        timed(lambda q, k, v: flash_mha(q, k, v, causal=True), "flash")
        if t < 8192:
            timed(lambda q, k, v: mha(q, k, v, causal=True), "xla")


def _bench_imagenet_conf(tag: str, desc: str, conf: str, batch: int,
                         scan_k: int, input_size: int = 224,
                         num_class: int = 1000,
                         fuse: bool = True, wino: bool = False) -> float:
    """Shared trainer setup + synthetic-data measurement for the
    ImageNet-model bench modes (stderr only — the stdout JSON stays the
    BASELINE GoogLeNet metric).  Also the harness tools/resnet_bisect.py
    times its diagnostic variants with, so bisect numbers stay
    comparable to bench numbers.  Returns sec/step."""
    import jax

    from cxxnet_tpu import config as cfgmod
    from cxxnet_tpu.nnet.trainer import NetTrainer

    if not fuse:
        conf += "fuse_1x1 = 0\n"
    if wino:
        # Winograd F(4x4,3x3) on every 3x3 s1 conv (layers/conv.py)
        conf += "conv_wino = 1\n"
    tr = NetTrainer()
    tr.set_params(cfgmod.parse_pairs(conf))
    tr.eval_train = 0
    tr.init_model()
    rng = np.random.RandomState(0)
    data = jax.device_put(
        rng.randn(batch, input_size, input_size, 3).astype(np.float32)
    )
    labels = jax.device_put(
        rng.randint(0, num_class, (batch, 1)).astype(np.float32)
    )
    dt = _time_scans(tr, data, labels, scan_k)
    print(
        f"# bench[{tag}]: {desc} b{batch} bf16: {dt*1e3:.1f} ms/step "
        f"= {batch/dt:.0f} img/s/chip",
        file=sys.stderr, flush=True,
    )
    return dt


def bench_resnet(batch: int, scan_k: int, fuse: bool = True,
                 depth: int = 50, wino: bool = False) -> None:
    """``--resnet`` / ``--resnet101`` / ``--resnet152`` modes: ResNet
    training throughput at the chosen depth."""
    from cxxnet_tpu.models import resnet50_conf

    _bench_imagenet_conf(
        f"resnet{depth}", f"ResNet-{depth}",
        resnet50_conf(batch_size=batch, input_size=224, synthetic=False,
                      dev="tpu", depth=depth),
        batch, scan_k, fuse=fuse, wino=wino,
    )


def bench_vgg(batch: int, scan_k: int, fuse: bool = True,
              depth: int = 16, wino: bool = False) -> None:
    """``--vgg`` / ``--vgg19`` modes: VGG training throughput.
    BASELINE.json's config list names "ImageNet GoogLeNet/VGG-16 DP
    v5e-8"; this is the single-chip number (doc/performance.md has the
    batch curve)."""
    from cxxnet_tpu.models import vgg16_conf

    _bench_imagenet_conf(
        f"vgg{depth}", f"VGG-{depth}",
        vgg16_conf(batch_size=batch, input_size=224, synthetic=False,
                   dev="tpu", depth=depth),
        batch, scan_k, fuse=fuse, wino=wino,
    )


def bench_alexnet(batch: int, scan_k: int, fuse: bool = True,
                  wino: bool = False) -> None:
    """``--alexnet`` mode: AlexNet training throughput (BASELINE.json's
    "ImageNet AlexNet single-chip" config)."""
    from cxxnet_tpu.models import alexnet_conf

    _bench_imagenet_conf(
        "alexnet", "AlexNet",
        alexnet_conf(batch_size=batch, synthetic=False, dev="tpu"),
        batch, scan_k, input_size=227, fuse=fuse, wino=wino,
    )


def bench_pred(batch: int, scan_k: int, fuse: bool = True,
               wino: bool = False) -> None:
    """``--pred`` mode: GoogLeNet INFERENCE throughput (stderr only —
    the stdout JSON stays the training metric).  The reference's
    deployment path (``task=pred``, ``cxxnet_main.cpp:405-441``) runs
    batch-at-a-time; here K staged batches run as ONE device program
    (``lax.map`` over the eval forward), the same dispatch-amortizing
    design as the training scan."""
    import jax
    import jax.numpy as jnp

    from __graft_entry__ import _build_googlenet

    tr = _build_googlenet(batch_size=batch, input_size=224, dev="tpu")
    if not fuse:
        tr.net.fuse_1x1 = 0
    if wino:
        for lay in tr.net.layer_objs:
            if hasattr(lay, "conv_wino"):
                lay.conv_wino = 1
    net = tr.net
    out_idx = net.out_node_index()

    def chunk(params, aux, data):
        # K distinct batches (a loop body that ignored its iterate
        # would invite XLA to hoist the invariant forward out of the
        # loop and fake a Kx number)
        def one(d):
            nodes, _aux = net.forward(params, d, train=False, aux=aux)
            return jnp.argmax(nodes[out_idx], axis=-1)

        return jax.lax.map(one, data)

    fwd = jax.jit(chunk)
    rng = np.random.RandomState(0)
    # fill f32 batch-by-batch: a single randn(K,...) call would make a
    # ~4x float64 transient (~3 GB at the default K=20, b128)
    host = np.empty((scan_k, batch, 224, 224, 3), np.float32)
    for k in range(scan_k):
        host[k] = rng.randn(batch, 224, 224, 3)
    data = jax.device_put(host)
    del host
    dt = _time_scans(tr, None, None, scan_k,
                     step=lambda: fwd(tr.params, tr.aux, data))
    print(
        f"# bench[pred]: GoogLeNet b{batch} bf16 inference: "
        f"{dt*1e3:.2f} ms/batch = {batch/dt:.0f} img/s/chip",
        file=sys.stderr, flush=True,
    )


def bench_bowl(batch: int, scan_k: int) -> None:
    """``--bowl`` mode: Kaggle NDSB plankton convnet throughput.  The
    reference's one semi-quantitative claim is ~5 min for 100 rounds at
    batch 64 on a GTX 780 (BASELINE.md); the printed steps/s implies the
    equivalent 100-round wall time for a 30k-image train set."""
    from cxxnet_tpu.models import kaggle_bowl_conf

    dt = _bench_imagenet_conf(
        "bowl", "NDSB convnet",
        kaggle_bowl_conf(batch_size=batch, synthetic=False, dev="tpu"),
        batch, scan_k, input_size=40, num_class=121,
    )
    rounds100 = 100 * 30000 / (batch / dt)
    print(
        f"# bench[bowl]: 100 rounds x 30k imgs = {rounds100:.0f}s device "
        "time (reference claim: ~300s on a GTX 780)",
        file=sys.stderr, flush=True,
    )


def require_accelerator() -> None:
    """Name the device on stderr, exit 2 when it is a CPU, and turn the
    persistent compile cache on — the preamble of every timing tool
    (this file's modes, the ``tools/*_bisect.py`` sweeps)."""
    import jax

    dev = jax.devices()[0]
    print(f"# platform: {dev.platform} ({dev.device_kind}) x"
          f"{len(jax.devices())}", file=sys.stderr, flush=True)
    if dev.platform == "cpu":
        print(f"bench: no accelerator — JAX found platform "
              f"{dev.platform!r}; a CPU timing is not a device number, "
              "so nothing is measured", file=sys.stderr, flush=True)
        raise SystemExit(2)
    from cxxnet_tpu.utils import compile_cache

    compile_cache.enable()


def main() -> None:
    require_accelerator()
    _run()


def _run() -> None:
    import jax

    args = [a for a in sys.argv[1:] if a not in ("--io", "--lm",
                                                 "--resnet", "--vgg",
                                                 "--alexnet", "--bowl",
                                                 "--resnet101",
                                                 "--resnet152", "--vgg19",
                                                 "--flash", "--nofuse",
                                                 "--wino", "--pred")]
    io_mode = "--io" in sys.argv[1:]
    lm_mode = "--lm" in sys.argv[1:]
    resnet_mode = "--resnet" in sys.argv[1:]
    depth_flags = [f for f in ("--resnet", "--resnet101", "--resnet152",
                                "--vgg", "--vgg19") if f in sys.argv[1:]]
    if len(depth_flags) > 1:
        raise SystemExit(f"pick ONE model mode, got {depth_flags}")
    resnet_depth = (101 if "--resnet101" in sys.argv[1:]
                    else 152 if "--resnet152" in sys.argv[1:] else 50)
    resnet_mode = resnet_mode or resnet_depth != 50
    vgg_mode = "--vgg" in sys.argv[1:]
    vgg_depth = 19 if "--vgg19" in sys.argv[1:] else 16
    vgg_mode = vgg_mode or vgg_depth != 16
    alexnet_mode = "--alexnet" in sys.argv[1:]
    bowl_mode = "--bowl" in sys.argv[1:]
    flash_mode = "--flash" in sys.argv[1:]
    pred_mode = "--pred" in sys.argv[1:]
    if "--fuse" in sys.argv[1:]:
        raise SystemExit("--fuse is now the default; use --nofuse for the A/B")
    nofuse_mode = "--nofuse" in sys.argv[1:]  # fuse_1x1=0 A/B on image modes
    wino_mode = "--wino" in sys.argv[1:]  # conv_wino=1 A/B on image modes
    batch_given = len(args) > 0
    batch = int(args[0]) if batch_given else 128
    scan_k = int(args[1]) if len(args) > 1 else 50
    n_scans = int(args[2]) if len(args) > 2 else 3
    if nofuse_mode and (io_mode or lm_mode or bowl_mode):
        # bowl too: its net has no sibling 1x1 convs, so an A/B there
        # would print two identical numbers — refuse instead
        raise SystemExit(
            "--nofuse only applies to the googlenet/resnet/vgg/alexnet modes"
        )
    if flash_mode:
        # positional args are the T sweep (default: the doc fixture Ts)
        bench_flash([int(a) for a in args] or [2048, 4096, 8192, 16384])
        return
    if pred_mode:
        bench_pred(batch, min(scan_k, 20), fuse=not nofuse_mode,
                   wino=wino_mode)
        return
    if io_mode:
        bench_io(batch, min(scan_k, 10))
        return
    if lm_mode:
        bench_lm(batch=batch if batch_given else 8, seq_len=2048,
                 scan_k=min(scan_k, 20))
        return
    if resnet_mode:
        bench_resnet(batch, min(scan_k, 30), fuse=not nofuse_mode,
                     depth=resnet_depth, wino=wino_mode)
        return
    if vgg_mode:
        bench_vgg(batch, min(scan_k, 20), fuse=not nofuse_mode,
                  depth=vgg_depth, wino=wino_mode)
        return
    if alexnet_mode:
        bench_alexnet(batch=batch if batch_given else 256,
                      scan_k=min(scan_k, 30), fuse=not nofuse_mode,
                      wino=wino_mode)
        return
    if bowl_mode:
        bench_bowl(batch=batch if batch_given else 64,
                   scan_k=min(scan_k, 50))
        return

    from __graft_entry__ import _build_googlenet

    _set_stage("model build")
    t_build = time.perf_counter()
    tr = _build_googlenet(batch_size=batch, input_size=224, dev="tpu")
    tr.eval_train = 0  # pure step time; no per-step metric fetch
    if nofuse_mode:
        # sibling 1x1 fusion is default-on; --nofuse is the A/B control
        tr.net.fuse_1x1 = 0
    if wino_mode:
        # Winograd on the 3x3 s1 convs (the inception 3x3 branches)
        for lay in tr.net.layer_objs:
            if hasattr(lay, "conv_wino"):
                lay.conv_wino = 1

    rng = np.random.RandomState(0)
    data = jax.device_put(rng.randn(batch, 224, 224, 3).astype(np.float32))
    labels = jax.device_put(
        rng.randint(0, 1000, size=(batch, 1)).astype(np.float32)
    )
    n_chips = max(1, tr.mesh_plan.n_devices if tr.mesh_plan else 1)

    # warmup / compile (cached across runs via .jax_cache); the second
    # scan reaches steady state (donation layout + persistent-cache write
    # happen on the first)
    _set_stage("compile+warmup")
    for _ in range(2):
        tr.update_scan(data, labels, n_steps=scan_k)
    jax.block_until_ready(tr.params)
    print(
        f"# compile+warmup: {time.perf_counter() - t_build:.1f}s",
        file=sys.stderr,
        flush=True,
    )

    # provisional number after ONE timed scan — parseable even if the
    # driver times the process out mid-measurement
    _set_stage("timed scan (provisional)")
    t0 = time.perf_counter()
    tr.update_scan(data, labels, n_steps=scan_k)
    jax.block_until_ready(tr.params)
    _emit("provisional", batch * scan_k / (time.perf_counter() - t0) / n_chips,
          batch)

    _set_stage("timed scans (final)")
    t0 = time.perf_counter()
    for _ in range(n_scans):
        tr.update_scan(data, labels, n_steps=scan_k)
    jax.block_until_ready(tr.params)
    dt = time.perf_counter() - t0
    _emit("final", batch * scan_k * n_scans / dt / n_chips, batch)


if __name__ == "__main__":
    main()
