"""On-chip kernel library bisect A/B (``cxxnet_tpu/ops/kernels/``).

The promotion discipline for the Pallas block kernels — the
``wino_bf16_ab.py --bembed-only`` shape applied per kernel.  For each
of ``conv_block`` / ``int8_gemm`` / ``zero_update``, three stages:

1. **interpret-parity gate** — the kernel (interpret mode off-TPU, the
   compiled Mosaic program on TPU) vs the JITTED stock lowering over
   the workload shapes: ``np.array_equal`` for ``int8_gemm`` and
   ``zero_update``, which replay the stock op chain; f32 rounding for
   ``conv_block``, which swaps a conv for a GEMM.  The reference is the
   jitted stock function, not an eager replay: the net's real programs
   are always compiled, and on CPU the eager op-by-op spelling differs
   from its own compiled form (FMA fusion) — "parity with the stock
   lowering" means the lowering.  A mismatch hard-fails the run; no
   timing happens on wrong math.
2. **timed legs** — alternating stock/kernel reps (the bisect
   discipline: interleaving lands machine drift on both legs), median
   wall per leg.  Each leg is a standalone jit instrumented as
   ``kind=kernel_<name>`` so per-kernel ``xla_program_*`` families land
   in the registry next to the ``kernel_selected`` gauge.
3. **verdict** — PROMOTE iff parity holds and the kernel/stock
   throughput ratio is >= 0.9 (the branch-embed band: a kernel may ride
   a tie, never a regression); REJECT otherwise.  ``--record`` writes
   the verdict for the measured backend into
   ``ops/kernels/verdicts.json`` — the committed state ``kernel_lib =
   auto`` follows.  On CPU the Pallas paths run under the interpreter
   (emulation), so CPU verdicts are honest rejects; the TPU verdicts
   are still owed (ROADMAP S9) — one process, so the tool goes to the
   chip as it is.

Each kernel's numbers also flow through ``perf_guard`` (bench
``kernel_bench``): the appended history makes later runs comparable
and the emitted per-kernel verdict document is schema-validated here —
a malformed verdict fails the run, not the reader.

Usage:
    python tools/kernel_ab.py [--kernel name[,name...]] [--smoke]
        [--record] [--json PATH] [--history PATH]
"""

import argparse
import functools
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

PROMOTE_RATIO = 0.9  # same band as the branch-embed CPU verdict


def _median(vals):
    s = sorted(vals)
    n = len(s)
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


def _instrumented(fn, name):
    """A standalone jit accounted as ``kind=kernel_<name>`` — the
    per-kernel ``xla_program_compile_seconds`` family."""
    import jax

    from cxxnet_tpu.obs import device as obs_device

    return obs_device.instrument(jax.jit(fn), kind=f"kernel_{name}",
                                 data_arg=0)


def _time_legs(legs, reps):
    """Alternate the (already-warm) legs ``reps`` times; median seconds
    per leg name."""
    import jax

    walls = {name: [] for name, _ in legs}
    for _ in range(reps):
        for name, fn in legs:
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            walls[name].append(time.perf_counter() - t0)
    return {name: _median(v) for name, v in walls.items()}


# ----------------------------------------------------------------------
# per-kernel workloads: (build) -> dict with parity + timings
def ab_conv_block(smoke, interpret, reps):
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from cxxnet_tpu.ops.kernels import conv_block

    b, hw, cin, cout = (4, 8, 16, 32) if smoke else (32, 28, 64, 256)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(b, hw, hw, cin).astype(np.float32))
    wk = jnp.asarray(rng.randn(1, 1, cin, cout).astype(np.float32) * 0.1)
    bias = jnp.asarray(rng.randn(cout).astype(np.float32))

    def stock(x):
        y = lax.conv_general_dilated(
            x, wk, window_strides=(1, 1), padding=((0, 0), (0, 0)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return y + bias.astype(x.dtype)

    kern = functools.partial(conv_block.conv1x1_block, wk=wk, bias=bias,
                             interpret=interpret)
    f_stock = _instrumented(stock, "conv_block")
    f_kern = _instrumented(lambda x: kern(x), "conv_block")
    a, k = f_stock(x), f_kern(x)
    # a conv replaced by a GEMM: the two emitters order their FMAs
    # differently, so f32 agrees to rounding, not bit for bit (the
    # tolerance tests/test_kernels.py states for this kernel)
    parity = bool(np.allclose(np.asarray(k), np.asarray(a),
                              rtol=1e-6, atol=1e-6))
    walls = _time_legs([("stock", lambda: f_stock(x)),
                        ("kernel", lambda: f_kern(x))], reps)
    return parity, walls, f"b{b} {hw}x{hw} {cin}->{cout} f32"


def ab_int8_gemm(smoke, interpret, reps):
    import jax.numpy as jnp
    import numpy as np

    from cxxnet_tpu.ops import quant as opsq
    from cxxnet_tpu.ops.kernels import int8_gemm

    m, k_dim, o = (8, 32, 16) if smoke else (128, 512, 1024)
    rng = np.random.RandomState(1)
    w = rng.randn(o, k_dim).astype(np.float32)
    q, s = opsq.quantize_weight(w, out_axis=0)
    lp = {opsq.QKEY: jnp.asarray(q), opsq.SKEY: jnp.asarray(s),
          "bias": jnp.asarray(rng.randn(o).astype(np.float32))}
    x = jnp.asarray(rng.randn(m, k_dim).astype(np.float32))

    f_stock = _instrumented(lambda x: opsq.fc_apply_q(lp, x), "int8_gemm")
    f_kern = _instrumented(
        lambda x: int8_gemm.int8_gemm_rescale(
            x, lp[opsq.QKEY], lp[opsq.SKEY], lp["bias"],
            interpret=interpret),
        "int8_gemm")
    a, kk = f_stock(x), f_kern(x)
    parity = bool(np.array_equal(np.asarray(a), np.asarray(kk)))
    walls = _time_legs([("stock", lambda: f_stock(x)),
                        ("kernel", lambda: f_kern(x))], reps)
    return parity, walls, f"{m}x{k_dim} @ int8 {o}ch f32-act"


def ab_zero_update(smoke, interpret, reps):
    import jax.numpy as jnp
    import numpy as np

    from cxxnet_tpu.ops.kernels import update_step
    from cxxnet_tpu.updater import SGDUpdater

    shape = (3, 3, 8, 16) if smoke else (3, 3, 256, 512)
    up = SGDUpdater("wmat")
    for k, v in (("eta", "0.05"), ("momentum", "0.9"), ("wd", "0.0005"),
                 ("clip_gradient", "1.0")):
        up.set_param(k, v)
    rng = np.random.RandomState(2)
    w = jnp.asarray(rng.randn(*shape).astype(np.float32))
    g = jnp.asarray(rng.randn(*shape).astype(np.float32))
    mom = jnp.asarray(rng.randn(*shape).astype(np.float32))
    epoch = jnp.asarray(3)
    p = up.param

    f_stock = _instrumented(
        lambda w: up.apply(w, g, {"m": mom}, epoch), "zero_update")
    f_kern = _instrumented(
        lambda w: update_step.sgd_update(
            w, g, mom, p.learning_rate(epoch).astype(w.dtype),
            p.momentum_at(epoch).astype(w.dtype),
            wd=p.wd, clip=p.clip_gradient, interpret=interpret),
        "zero_update")
    (w1, s1), (w2, m2) = f_stock(w), f_kern(w)
    parity = bool(np.array_equal(np.asarray(w1), np.asarray(w2))
                  and np.array_equal(np.asarray(s1["m"]), np.asarray(m2)))
    walls = _time_legs([("stock", lambda: f_stock(w)),
                        ("kernel", lambda: f_kern(w))], reps)
    return parity, walls, f"sgd {'x'.join(map(str, shape))} f32 clip"


AB = {"conv_block": ab_conv_block,
      "int8_gemm": ab_int8_gemm,
      "zero_update": ab_zero_update}


# ----------------------------------------------------------------------
def run_kernel(name, smoke, backend, reps):
    interpret = backend != "tpu"
    parity, walls, workload = AB[name](smoke, interpret, reps)
    stock_ms = walls["stock"] * 1e3
    kernel_ms = walls["kernel"] * 1e3
    ratio = stock_ms / kernel_ms if kernel_ms > 0 else 0.0
    verdict = ("promote" if parity and ratio >= PROMOTE_RATIO
               else "reject")
    reasons = []
    if not parity:
        reasons.append("parity gate failed")
    if ratio < PROMOTE_RATIO:
        reasons.append(f"throughput ratio {ratio:.3f} < {PROMOTE_RATIO}"
                       + (" (interpret-mode emulation)" if interpret
                          else ""))
    return {"name": name, "workload": workload, "parity": parity,
            "stock_ms": round(stock_ms, 4),
            "kernel_ms": round(kernel_ms, 4),
            "ratio": round(ratio, 4), "verdict": verdict,
            "reasons": reasons}


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--kernel", default="",
                    help="comma list (default: all three)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes + few reps (the KERNEL=1 lane)")
    ap.add_argument("--reps", type=int, default=0,
                    help="timing reps per leg (default 5, smoke 3)")
    ap.add_argument("--record", action="store_true",
                    help="write the verdicts into ops/kernels/"
                         "verdicts.json for the measured backend")
    ap.add_argument("--json", dest="json_path", default="",
                    help="write the full report document here")
    ap.add_argument("--history", default="",
                    help="perf_guard history JSONL (appends one "
                         "kernel_bench entry per kernel)")
    args = ap.parse_args()

    import jax

    import perf_guard
    from cxxnet_tpu.ops import kernels as klib

    backend = jax.default_backend()
    names = ([s.strip() for s in args.kernel.split(",") if s.strip()]
             or sorted(AB))
    bad = [n for n in names if n not in AB]
    if bad:
        ap.error(f"unknown kernel(s) {bad}; known: {sorted(AB)}")
    reps = args.reps or (3 if args.smoke else 5)

    report = {"tool": "kernel_ab", "backend": backend,
              "smoke": bool(args.smoke), "reps": reps,
              "promote_ratio": PROMOTE_RATIO, "kernels": []}
    rc = 0
    for name in names:
        res = run_kernel(name, args.smoke, backend, reps)
        report["kernels"].append(res)
        print(f"# {name} [{backend}] {res['workload']}: parity="
              f"{'OK' if res['parity'] else 'FAIL'} stock "
              f"{res['stock_ms']:.3f}ms kernel {res['kernel_ms']:.3f}ms "
              f"ratio {res['ratio']:.3f} -> {res['verdict'].upper()}"
              + (f" ({'; '.join(res['reasons'])})" if res["reasons"]
                 else ""), file=sys.stderr)
        if not res["parity"]:
            rc = 1
        if args.history:
            # one schema-validated perf_guard verdict per kernel — the
            # same document the opt-in lanes commit to their histories
            doc = perf_guard.run_once(
                "kernel_bench", {"backend": backend, "kernels": [res]},
                args.history, window=5, band=0.2)
            problems = perf_guard.validate_verdict(doc)
            for p in problems:
                print(f"FAIL {name}: {p}", file=sys.stderr)
                rc = 1
        if args.record:
            klib.record_verdict(
                name, backend, res["verdict"], ratio=res["ratio"],
                parity=res["parity"], stock_ms=res["stock_ms"],
                kernel_ms=res["kernel_ms"], smoke=bool(args.smoke),
                interpret=backend != "tpu",
                ts=time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
                tool="kernel_ab")
            print(f"# recorded {name}/{backend}: {res['verdict']}",
                  file=sys.stderr)
    print(json.dumps(report, indent=1))
    if args.json_path:
        with open(args.json_path, "w", encoding="utf-8") as f:
            json.dump(report, f, indent=1)
    return rc


if __name__ == "__main__":
    sys.exit(main())
