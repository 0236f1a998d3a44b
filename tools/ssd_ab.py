"""On-chip A/B of the Mamba-2 scan's two forms at the token cells' mixer
shapes: ``ops/ssd.ssd_xla`` (plain ``jax.numpy`` under ``jax.grad``)
against the fused kernels of ``ops/ssd_fused.py``.

One packed row of 8192 tokens cut into documents as the cells' traffic
cuts it; the forward alone and forward + backward (the gradient of a
weighted sum of the output with respect to ``x``, ``dt``, ``a``, ``B``
and ``C``) under one ``jax.jit``, the median wall time of ``--reps``
calls that end in ``block_until_ready``.  One JSON line a reading, also
written to ``chiprun_out/ssd_ab.jsonl``; the kernels' output and
gradients are held against the ``jax.numpy`` form's before anything is
timed.  A measurement path: it refuses a host without a TPU.

Usage:
    python tools/ssd_ab.py [--shapes granite,nemotron] [--docs 5]
        [--reps 10] [--no-xla] [--cpu-rehearsal --tokens 512]
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: heads, head width, state width, chunk
SHAPES = {
    "granite": (64, 64, 128, 256),
    "nemotron": (16, 64, 128, 128),
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--docs", type=int, default=5)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-xla", action="store_true")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="the same code on the CPU with the kernels "
                    "interpreted: finds faults, measures nothing")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from cxxnet_tpu.ops.ssd import ssd_xla
    from cxxnet_tpu.ops.ssd_fused import ssd_fused, supported

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.cpu_rehearsal:
        print(f"ssd_ab: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 2
    t = args.tokens
    rng = np.random.RandomState(args.seed)
    cuts = np.sort(rng.choice(np.arange(1, t), args.docs - 1, replace=False))
    doc = jnp.asarray(np.searchsorted(cuts, np.arange(t), side="right")
                      .astype(np.int32))[None]
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(REPO, "chiprun_out", "ssd_ab.jsonl"), "a")
    f32 = jnp.float32

    def timed(fn, *a):
        jax.block_until_ready(fn(*a))
        walls = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*a))
            walls.append(time.perf_counter() - t0)
        return float(np.median(walls)) * 1e3

    for name in args.shapes.split(","):
        h, p, s, chunk = SHAPES[name]
        mk = lambda *sh: jnp.asarray(rng.randn(*sh), jnp.bfloat16)  # noqa
        x, w = mk(1, t, h, p), mk(1, t, h, p)
        b, c = mk(1, t, s) * 0.1, mk(1, t, s)
        dt = jnp.asarray(np.log1p(np.exp(rng.randn(1, t, h) - 3.0)), f32)
        a = -jnp.asarray(rng.uniform(1.0, 16.0, (h,)), f32)
        ops = (x, dt, a, b, c)
        assert supported(x, b, c, chunk), name

        def grads(scan):
            def loss(x, dt, a, b, c, w, doc):
                return jnp.sum((scan(x, dt, a, b, c, doc) * w).astype(f32))
            return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))

        def fwd(scan):
            return jax.jit(lambda *v: (scan(*v[:5], v[6]),))

        forms = {
            "xla": lambda *v: ssd_xla(*v, chunk),
            "fused": lambda *v: ssd_fused(*v, chunk,
                                          interpret=args.cpu_rehearsal),
        }
        args_ = ops + (w, doc)
        ref = fwd(forms["xla"])(*args_)[0].astype(f32)
        ref_g = grads(forms["xla"])(*args_)
        for label in (["fused"] if args.no_xla else ["xla", "fused"]):
            scan = forms[label]
            try:
                got = fwd(scan)(*args_)[0].astype(f32)
                err = float(jnp.abs(got - ref).max() / jnp.abs(ref).max())
                g_err = [float(jnp.abs(u.astype(f32) - v.astype(f32)).max()
                               / jnp.abs(v.astype(f32)).max())
                         for u, v in zip(grads(scan)(*args_), ref_g)]
                line = {"shape": name, "form": label, "docs": args.docs,
                        "fwd_ms": timed(fwd(scan), *args_),
                        "fwd_bwd_ms": timed(grads(scan), *args_),
                        "y_rel_err": err,
                        "grad_rel_err": dict(zip(
                            ("x", "dt", "a", "b", "c"), g_err)),
                        "device": dev.device_kind}
            except Exception as e:  # noqa: BLE001 - a reading, reported
                line = {"shape": name, "form": label,
                        "error": f"{type(e).__name__}: {e}"[:2000]}
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
