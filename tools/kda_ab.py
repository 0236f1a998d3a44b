"""On-chip A/B of the Kimi delta rule's two forms at the token cell's
mixer shape: ``ops/kda.kimi_delta_xla`` (plain ``jax.numpy`` under
``jax.grad``, in checkpointed segments as the layer walks it) against
the fused kernels of ``ops/kda_fused.py``, and beside them
``ops/gdn_fused.py``'s kernels on the same row with one decay a head
(what the vector decay costs over the scalar one).

One packed row of 8192 tokens cut into documents as the cell's traffic
cuts it, the gate drawn over its whole range ``(-5, 0)``; the forward
alone and forward + backward (the gradient of a weighted sum of the
output with respect to ``q``, ``k``, ``v``, ``g`` and ``beta``) under
one ``jax.jit``, the median wall time of ``--reps`` calls that end in
``block_until_ready``.  One JSON line a reading, also written to
``chiprun_out/kda_ab.jsonl``; the kernels' output and gradients are held
against the ``jax.numpy`` form's before anything is timed.  A
measurement path: it refuses a host without a TPU.

Usage:
    python tools/kda_ab.py [--heads 32] [--docs 5] [--reps 10] [--no-xla]
        [--heads-a-step 4]
        [--cpu-rehearsal --tokens 256 --heads 2]
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--width", type=int, default=128)
    ap.add_argument("--docs", type=int, default=5)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-xla", action="store_true")
    ap.add_argument("--heads-a-step", type=int, default=0,
                    help="ops/kda_fused.HEADS for this run (0: as it is)")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="the same code on the CPU with the kernels "
                    "interpreted: finds faults, measures nothing")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from cxxnet_tpu.ops import kda_fused
    from cxxnet_tpu.ops.gdn_fused import gated_delta_fused
    from cxxnet_tpu.ops.kda import kimi_delta_xla
    from cxxnet_tpu.ops.kda_fused import kimi_delta_fused, supported

    kda_fused.HEADS = args.heads_a_step or kda_fused.HEADS

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.cpu_rehearsal:
        print(f"kda_ab: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 2
    t, h, d = args.tokens, args.heads, args.width
    rng = np.random.RandomState(args.seed)
    cuts = np.sort(rng.choice(np.arange(1, t), args.docs - 1, replace=False))
    doc = jnp.asarray(np.searchsorted(cuts, np.arange(t), side="right")
                      .astype(np.int32))[None]
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(REPO, "chiprun_out", "kda_ab.jsonl"), "a")
    f32 = jnp.float32
    unit = dict(unit=1e-6, q_scale=d ** -0.5)

    def timed(fn, *a):
        jax.block_until_ready(fn(*a))
        walls = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*a))
            walls.append(time.perf_counter() - t0)
        return float(np.median(walls)) * 1e3

    mk = lambda *sh: jnp.asarray(rng.randn(*sh), jnp.bfloat16)  # noqa: E731
    q, k, v, w = (mk(1, t, h, d) for _ in range(4))
    g = jnp.asarray(-5.0 / (1.0 + np.exp(-2.0 * rng.randn(1, t, h, d))), f32)
    beta = jnp.asarray(1.0 / (1.0 + np.exp(-rng.randn(1, t, h))), f32)
    assert supported(q, k, v, 64)

    def xla(q, k, v, g, beta, doc):
        from cxxnet_tpu.ops.gdn import unit_rows
        q = (unit_rows(q, unit["unit"]) * unit["q_scale"]).astype(q.dtype)
        return kimi_delta_xla(q, unit_rows(k, unit["unit"]).astype(k.dtype),
                              v, g, beta, doc, 64, 2048)

    forms = {
        "xla": xla,
        "fused": lambda *a: kimi_delta_fused(
            *a, interpret=args.cpu_rehearsal, **unit),
        # one decay a head, its channels' mean: the scalar rule's kernels
        "gdn_fused": lambda q, k, v, g, beta, doc: gated_delta_fused(
            q, k, v, g.mean(-1), beta, doc, interpret=args.cpu_rehearsal,
            **unit),
    }

    def grads(scan):
        def loss(q, k, v, g, beta, w, doc):
            return jnp.sum((scan(q, k, v, g, beta, doc) * w).astype(f32))
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))

    def fwd(scan):
        return jax.jit(lambda *a: (scan(*a[:5], a[6]),))

    args_ = (q, k, v, g, beta, w, doc)
    ref = ref_g = None
    for label in (["fused", "gdn_fused"] if args.no_xla else list(forms)):
        scan = forms[label]
        try:
            got = fwd(scan)(*args_)[0].astype(f32)
            got_g = grads(scan)(*args_)
            line = {"form": label, "tokens": t, "heads": h, "docs": args.docs,
                    "heads_a_step": kda_fused.HEADS}
            if label == "xla":
                ref, ref_g = got, got_g
            elif label == "fused" and ref is not None:
                line["o_rel_err"] = float(
                    jnp.abs(got - ref).max() / jnp.abs(ref).max())
                line["grad_rel_err"] = {
                    n: float(jnp.abs(a.astype(f32) - b.astype(f32)).max()
                             / jnp.abs(b.astype(f32)).max())
                    for n, a, b in zip(("q", "k", "v", "g", "beta"), got_g,
                                       ref_g)}
            line["finite"] = bool(
                jnp.isfinite(got).all()
                and all(jnp.isfinite(a.astype(f32)).all() for a in got_g))
            line.update(fwd_ms=timed(fwd(scan), *args_),
                        fwd_bwd_ms=timed(grads(scan), *args_),
                        device=dev.device_kind)
        except Exception as e:  # noqa: BLE001 - a reading, reported
            line = {"form": label, "error": f"{type(e).__name__}: {e}"[:2000]}
        print(json.dumps(line), flush=True)
        out.write(json.dumps(line) + "\n")
        out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
