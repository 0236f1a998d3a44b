"""On-chip A/B of masked attention's two forms at the token cells' head
shapes: ``ops/attention.mha``'s checkpointed row blocks against the
flash kernels of ``ops/flash.py`` at a list of block sizes.

One packed row of 8192 tokens (``--tokens``) cut into documents as the
cells' traffic cuts it, under ``--window W`` each query seeing its
document's last W keys only (the sliding layers of ``trinity``);
forward + backward (the gradient of a weighted sum of the
output with respect to q, k and v) under one ``jax.jit``, the median
wall time of ``--reps`` calls that end in ``block_until_ready``.  One
JSON line a reading, also written to ``chiprun_out/attn_ab.jsonl``; the
kernels' outputs are held against ``mha``'s before anything is timed.  A
kernel reading says what it measured: ``blocks_a_head``, the blocks the
forward visits a head for the row it timed, by what they hold — no mask
at all, one document crossed by the diagonal or the window's edge, a
document boundary — from ``flash.count_blocks``, the function the layers'
``attn_blocks`` counters use.  It also carries the backward's kernels
alone, ms a call on the forward's own ``o`` and ``lse``
(``bwd_kernels_ms``): ``flash_dq`` and ``flash_dkv``, their sum, and the
ONE kernel ``flash_bwd`` that ``ops/flash.py`` runs in their place where
a row fits its VMEM budget — whose ``dq``, ``dk`` and ``dv`` are held
against the two kernels' first (``one_vs_two_rel_err``); ``backward``
says which of the two forms the ``fwd_bwd_ms`` beside it ran.
A measurement path: it refuses a host without a TPU.

Usage:
    python tools/attn_ab.py [--shapes joyai,granite,qwen3_next]
        [--blocks 512x512,1024x512] [--docs 5] [--reps 10] [--no-xla]
        [--tokens 16384 --window 2048]
        [--shapes smallthinker --tokens 16384 --window 4096]
        [--cpu-rehearsal --tokens 256]
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: heads, key-value heads, q·k width, v width, scale
SHAPES = {
    "joyai": (32, 32, 192, 128, None),
    "granite": (32, 8, 64, 64, 0.015625),
    "qwen3_next": (16, 2, 256, 256, None),
    "trinity": (32, 4, 128, 128, None),
    "smallthinker": (28, 4, 128, 128, None),
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--blocks", default="512x512")
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--docs", type=int, default=5)
    ap.add_argument("--window", type=int, default=0)
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

    from cxxnet_tpu.ops import flash
    from cxxnet_tpu.ops.attention import mha
    from cxxnet_tpu.ops.flash import count_blocks, flash_attention

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.cpu_rehearsal:
        print(f"attn_ab: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 2
    t = args.tokens
    rng = np.random.RandomState(args.seed)
    cuts = np.sort(rng.choice(np.arange(1, t), args.docs - 1, replace=False))
    doc_np = np.searchsorted(cuts, np.arange(t), side="right").astype(
        np.int32)
    bounds = np.concatenate([[0], cuts, [t]])
    win = {"window": args.window} if args.window else {}
    lens = np.diff(bounds)
    head = np.minimum(lens, args.window) if args.window else lens
    pairs = int((head * (head + 1) // 2 + (lens - head) * args.window).sum())
    doc = jnp.asarray(doc_np)[None]
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(REPO, "chiprun_out", "attn_ab.jsonl"), "a")

    def timed(fn, *a):
        fn(*a)[0].block_until_ready()
        walls = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*a))
            walls.append(time.perf_counter() - t0)
        return float(np.median(walls)) * 1e3

    def backward_kernels(q, k, v, w, scale, bq, bk):
        """The backward's kernels alone at blocks ``bq x bk`` -> ``(ms a
        call by kernel, the one kernel's largest error against the
        two)``, each a jitted call of its own on the folded operands."""
        h = q.shape[2]
        scale = float(q.shape[-1] ** -0.5 if scale is None else scale)
        qf, kf, vf, do = (flash._fold(x) for x in (q, k, v, w))
        out, lse = flash._forward(
            qf, kf, vf, doc, None, None, causal=True, scale=scale, bq=bq,
            bk=bk, heads=h, interpret=args.cpu_rehearsal, window=args.window)
        dl = (do.astype(jnp.float32) * out.astype(jnp.float32)).sum(
            -1, keepdims=True)

        def alone(kern):
            def run(*a):
                geo = flash._Geometry(qf, kf, vf, doc, None, None, True,
                                      scale, bq, bk, h, args.window)
                got = kern(*a, geo, args.cpu_rehearsal)
                return got if isinstance(got, tuple) else (got,)
            return jax.jit(run)

        a = (qf, kf, vf, do, lse, dl)
        dq, dkv, one = (alone(kern) for kern in (
            flash._bwd_dq, flash._bwd_dkv, flash._bwd_one))
        ms = {"flash_dq": timed(dq, *a), "flash_dkv": timed(dkv, *a)}
        ms["two"] = ms["flash_dq"] + ms["flash_dkv"]
        if not flash._one_fits(kf.shape[1], qf.shape[-1], vf.shape[-1],
                               vf.dtype):
            return ms, None
        ms["flash_bwd"] = timed(one, *a)
        f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
        err = max(float(jnp.abs(f32(x) - f32(y)).max() / jnp.abs(f32(y)).max())
                  for x, y in zip(one(*a), dq(*a) + dkv(*a)))
        return ms, err

    for name in args.shapes.split(","):
        h, hk, dqk, dv, scale = SHAPES[name]
        mk = lambda *s: jnp.asarray(rng.randn(*s), jnp.bfloat16)  # noqa: E731
        q, k, v = mk(1, t, h, dqk), mk(1, t, hk, dqk), mk(1, t, hk, dv)
        w = mk(1, t, h, dv)
        flops = 2 * pairs * h * (dqk + dv)     # the forward's, on the docs

        def grads(attn):
            def loss(q, k, v, w, doc):
                return jnp.sum((attn(q, k, v, doc) * w).astype(jnp.float32))
            return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

        def fwd(attn):
            return jax.jit(lambda q, k, v, w, doc: (attn(q, k, v, doc),))

        ref_attn = lambda q, k, v, doc: mha(  # noqa: E731
            q, k, v, causal=True, scale=scale, doc=doc, block_q=512, **win)
        a = (q, k, v, w, doc)
        ref = fwd(ref_attn)(*a)[0].astype(jnp.float32)
        ref_g = grads(ref_attn)(*a)
        rows = [] if args.no_xla else [("xla_rows_512", ref_attn, None, None)]
        for blk in args.blocks.split(","):
            bq, bk = (int(x) for x in blk.split("x"))
            visited, free, one = (int(n) // h for n in count_blocks(
                q, k, v, causal=True, doc=doc, block_q=bq, block_k=bk,
                **win))
            rows.append((f"flash_{bq}x{bk}", lambda q, k, v, doc, bq=bq,
                         bk=bk: flash_attention(
                             q, k, v, causal=True, scale=scale, doc=doc,
                             block_q=bq, block_k=bk,
                             interpret=args.cpu_rehearsal, **win)[0],
                         {"visited": visited, "unmasked": free,
                          "positions_only": one - free,
                          "document_boundary": visited - one}, (bq, bk)))
        for label, attn, blocks, blk in rows:
            try:
                got = fwd(attn)(*a)[0].astype(jnp.float32)
                err = float(jnp.abs(got - ref).max())
                g_err = max(float(jnp.abs(x.astype(jnp.float32)
                                          - y.astype(jnp.float32)).max()
                                  / (jnp.abs(y.astype(jnp.float32)).max()))
                            for x, y in zip(grads(attn)(*a), ref_g))
                line = {"shape": name, "form": label, "docs": args.docs,
                        "tokens": t, "window": args.window, "pairs": pairs,
                        "fwd_ms": timed(fwd(attn), *a),
                        "fwd_bwd_ms": timed(grads(attn), *a),
                        "max_abs_err": err, "grad_rel_err": g_err,
                        "fwd_tflops_needed": flops / 1e12,
                        "device": dev.device_kind}
                if blocks:
                    line["blocks_a_head"] = blocks
                    line["bwd_kernels_ms"], line["one_vs_two_rel_err"] = (
                        backward_kernels(q, k, v, w, scale, *blk))
                    line["backward"] = (
                        "flash_bwd" if "flash_bwd" in line["bwd_kernels_ms"]
                        else "flash_dq + flash_dkv")
            except Exception as e:  # noqa: BLE001 - a reading, reported
                line = {"shape": name, "form": label,
                        "error": f"{type(e).__name__}: {e}"[:2000]}
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
