"""On-chip A/B of ``layers/moe.held_experts`` at the expert cells'
shapes: the parent's form (every buffer with room for all ``tokens x
topk`` pairs) against the slabs, with each candidate for the one step
the records could not cost — a slab's rows summed onto their tokens
(the way back, forward; ``dx``, backward): a scatter-add, one with the
rows sorted token-major first, and (alone) the parent's gather through
the inverse order — and at a list of slab factors.

One row of 8192 tokens routed at random over all experts, the held
ones drawn ``--hot`` times as often as the others in the runs named
``hot``; forward + backward (the gradient of a weighted sum of the
output with respect to ``x``, ``wmat`` and ``wproj``) under one
``jax.jit``, the median wall time of ``--reps`` calls that end in
``block_until_ready``.  One JSON line a reading, also written to
``chiprun_out/moe_ab.jsonl``; every form is held against the parent's
before anything is timed.  A measurement path: it refuses a host
without a TPU.

Usage:
    python tools/moe_ab.py [--shapes qwen3_next,joyai] [--factors 1.5,2,3]
        [--hot 3] [--reps 10] [--cpu-rehearsal --tokens 512]
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: topk, width, an expert's width, experts held, experts routed
SHAPES = {
    "qwen3_next": (10, 2048, 512, 32, 512),
    "joyai": (8, 2048, 768, 16, 256),
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--factors", default="1.5,2,3")
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--hot", type=float, default=3.0,
                    help="how much likelier a held expert is picked in "
                    "the runs that must pass a slab")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="the same code on the CPU: finds faults, "
                    "measures nothing")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from cxxnet_tpu.layers import moe

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.cpu_rehearsal:
        print(f"moe_ab: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 2
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(REPO, "chiprun_out", "moe_ab.jsonl"), "a")
    rng = np.random.RandomState(args.seed)

    def all_pairs(x, w, idx, wmat, wproj, first, nexpert):
        """The parent's ``held_experts``: buffers for all pairs."""
        del nexpert
        m, k = idx.shape
        g, f = wmat.shape[0], wmat.shape[-1] // 2
        local = idx.reshape(-1) - first
        key = jnp.where((local >= 0) & (local < g), local, g)
        pair = lax.iota(jnp.int32, m * k)
        skey, order = lax.sort((key, pair), num_keys=1)
        _, inv = lax.sort((order, pair), num_keys=1)
        counts = (key[:, None] == lax.iota(jnp.int32, g)[None]).sum(
            axis=0, dtype=jnp.int32)
        valid = (skey < g)[:, None]
        xs = jnp.where(valid, x[order // k], 0)
        gu = lax.ragged_dot(xs, wmat, counts, preferred_element_type=x.dtype)
        h = (jax.nn.silu(gu[:, :f].astype(jnp.float32))
             * gu[:, f:].astype(jnp.float32)).astype(x.dtype)
        ys = lax.ragged_dot(jnp.where(valid, h, 0), wproj, counts,
                            preferred_element_type=x.dtype)
        ys = jnp.where(valid, ys, 0)
        y = (ys[inv].reshape(m, k, -1)
             * w.astype(x.dtype)[..., None]).sum(axis=1)
        return y, counts

    # a slab's rows onto their tokens: the candidates
    def scatter_add(rows, tok, m):
        return jax.ops.segment_sum(rows, tok, num_segments=m)

    def scatter_add_sorted(rows, tok, m):
        # token-major: the scatter's indices ascend
        tok, perm = lax.sort((tok, lax.iota(jnp.int32, tok.size)),
                             num_keys=1)
        return jax.ops.segment_sum(rows[perm], tok, num_segments=m,
                                   indices_are_sorted=True)

    def gather_inverse(rows, pair, m, k):
        # the parent's way back: every one of the m x k pairs reads its
        # row of the slab, or the zero row behind it (timed alone: it
        # needs the pairs, which the layer's step is not handed)
        c = pair.size
        at = jnp.full((m * k,), c, jnp.int32).at[pair].set(
            lax.iota(jnp.int32, c))
        rows = jnp.concatenate([rows, jnp.zeros_like(rows[:1])])
        return rows[at].reshape(m, k, -1).sum(axis=1)

    ways = {"scatter_add": scatter_add,
            "scatter_add_sorted": scatter_add_sorted}
    shipped = (moe._onto_tokens, moe.SLAB_FACTOR)

    def timed(fn, *a):
        jax.block_until_ready(fn(*a))
        walls = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*a))
            walls.append(time.perf_counter() - t0)
        return float(np.median(walls)) * 1e3

    def emit(line):
        line["device"] = dev.device_kind
        print(json.dumps(line), flush=True)
        out.write(json.dumps(line) + "\n")
        out.flush()

    m = args.tokens
    for name in args.shapes.split(","):
        k, d, f, g, e = SHAPES[name]
        mk = lambda *s: jnp.asarray(rng.randn(*s) * 0.05,  # noqa: E731
                                    jnp.bfloat16)
        x, wmat, wproj = mk(m, d), mk(g, d, 2 * f), mk(g, f, d)
        cot = mk(m, d)
        w = jnp.asarray(rng.rand(m, k), jnp.float32)

        def picks(hot):
            # the k largest of log weight + Gumbel noise: k experts
            # drawn without replacement, a held one `hot` times as likely
            score = -np.log(-np.log(rng.rand(m, e)))
            score[:, :g] += np.log(hot)
            return jnp.asarray(np.argsort(-score, axis=-1)[:, :k], jnp.int32)

        def grads(held):
            def loss(x, wmat, wproj, idx):
                y, counts = held(x, w, idx, wmat, wproj, 0, e)
                return jnp.sum((y * cot).astype(jnp.float32)), counts
            return jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True))

        for traffic, hot in (("even", 1.0), ("hot", args.hot)):
            idx = picks(hot)
            ref, counts = grads(all_pairs)(x, wmat, wproj, idx)
            held = int(counts.sum())
            base = {"shape": name, "traffic": traffic, "pairs": m * k,
                    "pairs_held": held}
            emit(dict(base, form="all_pairs",
                      fwd_bwd_ms=timed(grads(all_pairs), x, wmat, wproj,
                                       idx)))
            forms = [(way, shipped[1]) for way in ways] + [
                (shipped[0], float(v)) for v in args.factors.split(",")]
            for way, factor in forms:
                moe._onto_tokens = ways.get(way, way)
                moe.SLAB_FACTOR = factor
                label = way if isinstance(way, str) else "shipped"
                try:
                    c = moe.slab_rows(m * k, g, e)
                    fn = grads(moe.held_experts)
                    got, _ = fn(x, wmat, wproj, idx)
                    err = max(float(
                        jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)
                                ).max() / jnp.abs(b.astype(jnp.float32)).max())
                        for a, b in zip(got, ref))
                    emit(dict(base, form=label, factor=factor, slab=c,
                              slabs=-(-held // c),
                              fwd_bwd_ms=timed(fn, x, wmat, wproj, idx),
                              grad_rel_err=err))
                except Exception as exc:  # noqa: BLE001 - a reading
                    emit(dict(base, form=label, factor=factor,
                              error=f"{type(exc).__name__}: {exc}"[:2000]))
            moe._onto_tokens, moe.SLAB_FACTOR = shipped

        # the one step alone, at the shipped slab
        c = moe.slab_rows(m * k, g, e)
        rows = jnp.asarray(rng.randn(c, d), jnp.float32)
        pair = jnp.asarray(rng.permutation(m * k)[:c], jnp.int32)
        alone = {label: jax.jit(lambda r, p, way=way: way(r, p // k, m))
                 for label, way in ways.items()}
        alone["gather_inverse"] = jax.jit(
            lambda r, p: gather_inverse(r, p, m, k))
        for label, fn in alone.items():
            emit({"shape": name, "form": label, "alone": True, "slab": c,
                  "ms": timed(fn, rows, pair)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
