"""Multi-head attention: plain, and ring (sequence-parallel) variants.

The reference framework predates attention entirely (SURVEY §5: no
sequence axis anywhere), so this op is new TPU-first scope: long-context
support via **ring attention** — the sequence is sharded over a mesh
axis, each device holds a query block, and key/value blocks rotate
around the ring with ``lax.ppermute`` while a numerically-stable
streaming softmax (log-sum-exp merging, the flash-attention recurrence)
accumulates the output.  Compute on each hop overlaps the neighbour
exchange; memory per device is O(T/n) instead of O(T), and the ICI ring
is exactly the topology TPU slices provide.

Layouts: ``q, k, v`` are ``(B, T, H, Dh)`` (batch, time, heads, head
dim).  ``mha`` is the single-device golden model; ``ring_attention`` is
the per-shard computation to run under ``shard_map`` with the time axis
sharded on ``axis_name``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30
#: from this length on the score matrix is never whole: ``attend`` takes
#: the flash kernels or ``mha``'s row blocks
LONG_T = 1024


def _scores(q: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """(B,Tq,H,D),(B,Tk,H,D) -> (B,H,Tq,Tk) scaled dot product (f32)."""
    d = q.shape[-1]
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    )
    return s * (1.0 / jnp.sqrt(jnp.float32(d)))


def _causal_mask(tq: int, tk: int, q_off, k_off) -> jnp.ndarray:
    """True where query position >= key position (may attend)."""
    qi = q_off + lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
    ki = k_off + lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
    return qi >= ki


def _attend(q, k, v, q_off: int, causal: bool, scale, doc_q, doc_k,
            window: int = 0, k_off: int = 0):
    """Softmax attention of a block of queries that starts at position
    ``q_off`` against keys from position ``k_off``: the full masked
    score matrix, (B,H,Tq,Tk) in f32.  ``window``: a query sees the keys
    less than ``window`` positions before it (0: all)."""
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    )
    s = s * jnp.float32(1.0 / (q.shape[-1] ** 0.5) if scale is None
                        else scale)
    mask = None
    if causal:
        mask = _causal_mask(q.shape[1], k.shape[1], q_off, k_off)[None, None]
    if window:
        shape = (q.shape[1], k.shape[1])
        near = (q_off + lax.broadcasted_iota(jnp.int32, shape, 0)
                - k_off - lax.broadcasted_iota(jnp.int32, shape, 1)
                < window)[None, None]
        mask = near if mask is None else mask & near
    if doc_q is not None:
        same = (doc_q[:, :, None] == doc_k[:, None, :])[:, None]
        mask = same if mask is None else mask & same
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    return jnp.einsum(
        "bhqk,bkhd->bqhd", _softmax(s).astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    ).astype(v.dtype)


def _softmax(s: jnp.ndarray) -> jnp.ndarray:
    """``jax.nn.softmax`` over the last axis with the row maximum held
    behind an optimization barrier.  Left to itself the TPU compiler
    fuses "reduce, then broadcast back" into the scores' producer as a
    ``reduce-window`` whose window is the whole row, so every one of a
    row's K elements recomputes the maximum of all K: K^2 compares a
    row, 47 ms a 512-query block at K = 8192 on a v5e against 0.1 ms
    for the two products (PERF.md, PR 29).  Behind the barrier the
    maximum is a (rows, 1) array like any other."""
    m = lax.optimization_barrier(
        lax.stop_gradient(s.max(axis=-1, keepdims=True)))
    e = jnp.exp(s - m)
    return e / e.sum(axis=-1, keepdims=True)


def doc_positions(doc, n: int, t: int) -> jnp.ndarray:
    """``(N, T)`` int32 positions counted from each document's first
    token (``doc``: a non-decreasing document index a token; ``None``:
    one document a row)."""
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (n, t))
    if doc is None:
        return pos
    start = jnp.concatenate(
        [jnp.ones((n, 1), bool), doc[:, 1:] != doc[:, :-1]], axis=1)
    return pos - lax.cummax(jnp.where(start, pos, 0), axis=1)


def rotary(x: jnp.ndarray, pos: jnp.ndarray, dim: int,
           theta: float = 10000.0, interleave: bool = False) -> jnp.ndarray:
    """Rotary positions (Su et al. 2021) on the first ``dim`` of each
    head of ``x (N, T, H, Dh)``, at the angles ``pos * theta^(-2i/dim)``;
    the rest of the head passes.  Rotate-half (as GPT-NeoX and the Qwen
    families apply them): with ``x1 | x2`` the two halves of those
    ``dim``, ``x1 cos - x2 sin | x2 cos + x1 sin``.  ``interleave``
    (the original pairing, as the DeepSeek-V3 family's checkpoints keep
    it): the pairs are ``(x[2i], x[2i+1])``, each turned in place.
    Angles and the rotation in float32."""
    half = dim // 2
    freq = jnp.exp(jnp.arange(half, dtype=jnp.float32)
                   * (-2.0 * math.log(theta) / dim))
    ang = pos.astype(jnp.float32)[..., None] * freq          # (N, T, half)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    xf = x[..., :dim].astype(jnp.float32)
    if interleave:
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
        turned = jnp.stack(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin],
            axis=-1).reshape(xf.shape).astype(x.dtype)
    else:
        x1, x2 = xf[..., :half], xf[..., half:]
        turned = jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin],
            axis=-1).astype(x.dtype)
    return jnp.concatenate([turned, x[..., dim:]], axis=-1)


def mha(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    scale: float | None = None,
    doc: jnp.ndarray | None = None,
    block_q: int = 0,
    window: int = 0,
) -> jnp.ndarray:
    """Plain softmax attention — the golden model for the ring variant.

    ``k`` and ``v`` may carry fewer heads than ``q`` (grouped-query
    attention: each serves ``H / Hkv`` consecutive query heads).
    ``scale`` replaces ``1 / sqrt(Dh)``.  ``doc`` ``(B, T)`` is a
    document index a token: a query sees only keys of its own document.
    ``window`` > 0: a query at position ``i`` sees the keys ``j`` with
    ``i - j < window`` (causal: itself and the ``window - 1`` before).
    ``block_q`` > 0 computes the rows ``block_q`` queries at a time,
    each block under ``jax.checkpoint`` and, where causal, against the
    keys up to its own end only (and, windowed, from its first query's
    reach on) — the score matrix is then ``(B, H, block_q, <=T)`` and
    never ``(B, H, T, T)``.
    """
    h, hk = q.shape[2], k.shape[2]
    if hk != h:
        k = jnp.repeat(k, h // hk, axis=2)
        v = jnp.repeat(v, h // hk, axis=2)
    t = q.shape[1]
    if window < 0 or (window and k.shape[1] != t):
        raise ValueError(f"mha: window = {window} needs queries and keys of "
                         "one length")
    if not block_q or t <= block_q or t % block_q or k.shape[1] != t:
        return _attend(q, k, v, 0, causal, scale, doc, doc, window)
    outs = []
    for lo in range(0, t, block_q):
        hi = lo + block_q
        end = hi if causal else t
        beg = max(0, lo - window + 1) if window else 0
        block = jax.checkpoint(functools.partial(
            _attend, q_off=lo, causal=causal, scale=scale, window=window,
            k_off=beg))
        outs.append(block(
            q[:, lo:hi], k[:, beg:end], v[:, beg:end],
            doc_q=None if doc is None else doc[:, lo:hi],
            doc_k=None if doc is None else doc[:, beg:end]))
    return jnp.concatenate(outs, axis=1)


def attend(q, k, v, *, causal: bool = False, scale: float | None = None,
           doc: jnp.ndarray | None = None, window: int = 0):
    """``(mha(q, k, v, ...), 1 if the flash kernels computed it else 0)``
    — the one place masked attention chooses its path, for every layer
    (``attention``'s masked path, ``latent_attention``).

    One algorithm in two forms, chosen from what the code can observe and
    by no conf key: the platform the program is LOWERED for
    (``jax.lax.platform_dependent``: a TPU takes ``ops/flash.py``'s
    kernels, also when the lowering host is a CPU that compiles for a
    described chip; everything else ``mha``, in checkpointed row blocks of
    512 queries from ``LONG_T`` tokens on) and the shapes the kernels are
    written for (``flash.block_for``: a long sequence that a block of 128
    or more divides, head widths the kernels take, bfloat16 or float32;
    one block size serves every head width, with a window or without).
    ``window`` goes to either form.  The flag is a uint32
    scalar each branch returns for itself, so it says what ran where the
    program was lowered for (the layers' ``attn_tokens_flash``)."""
    from . import flash

    t = q.shape[1]
    docs = () if doc is None else (doc,)
    block = flash.block_for(q, k, v)

    def rows(q, k, v, *doc):
        return (mha(q, k, v, causal=causal, scale=scale,
                    doc=doc[0] if doc else None,
                    block_q=512 if t >= LONG_T else 0, window=window),
                jnp.uint32(0))

    def kernels(q, k, v, *doc):
        return (flash.flash_attention(
            q, k, v, causal=causal, scale=scale, doc=doc[0] if doc else None,
            block_q=block, block_k=block, window=window)[0], jnp.uint32(1))

    if block is None:
        return rows(q, k, v, *docs)
    return lax.platform_dependent(q, k, v, *docs, tpu=kernels, default=rows)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    axis_name: str,
    causal: bool = False,
) -> jnp.ndarray:
    """Blockwise ring attention over a sequence-sharded mesh axis.

    Call under ``shard_map`` with q/k/v time-sharded on ``axis_name``;
    each of the ``n`` devices sees ``(B, T/n, H, Dh)`` blocks.  The kv
    block makes ``n`` hops around the ring; the output never leaves its
    device.
    """
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    b, tq, h, d = q.shape
    tk = k.shape[1]
    qf = q.astype(jnp.float32)

    o0 = jnp.zeros((b, tq, h, d), jnp.float32)
    m0 = jnp.full((b, h, tq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, tq), jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def merge(o, m, l, kb, vb, hop):
        """Streaming-softmax merge of the kv block that arrived from
        device (idx - hop) % n."""
        src = (idx - hop) % n
        s = _scores(qf, kb.astype(jnp.float32))
        if causal:
            mask = _causal_mask(tq, tk, idx * tq, src * tk)
            s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        if causal:
            p = jnp.where(mask[None, None], p, 0.0)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum(
            "bhqk,bkhd->bqhd", p, vb.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        o_new = o * corr.transpose(0, 2, 1)[..., None] + pv
        return o_new, m_new, l_new

    # hop 0 merges the resident kv block; n-1 rotations follow (not n —
    # the final block must not be rotated onward, that hop is wasted ICI)
    o, m, l = merge(o0, m0, l0, k, v, 0)

    def step(carry, hop):
        o, m, l, kb, vb = carry
        kb, vb = lax.ppermute((kb, vb), axis_name, perm)
        o, m, l = merge(o, m, l, kb, vb, hop)
        return (o, m, l, kb, vb), None

    if n > 1:
        (o, m, l, _, _), _ = lax.scan(
            step, (o, m, l, k, v), jnp.arange(1, n)
        )
    l = jnp.maximum(l, 1e-30)  # fully-masked rows (strict causal pad)
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(v.dtype)


def ring_self_attention(
    x_q: jnp.ndarray,
    x_k: jnp.ndarray,
    x_v: jnp.ndarray,
    mesh,
    seq_axis: str = "model",
    *,
    causal: bool = False,
) -> jnp.ndarray:
    """shard_map wrapper: global (B,T,H,Dh) arrays, T sharded on
    ``seq_axis`` (batch on ``data``); returns the same global layout."""
    from jax.sharding import PartitionSpec as P

    spec = P("data", seq_axis, None, None)
    fn = jax.shard_map(
        functools.partial(ring_attention, axis_name=seq_axis, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,  # ppermute under scan confuses the checker
    )
    return fn(x_q, x_k, x_v)


def a2a_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    axis_name: str,
    causal: bool = False,
    attn_fn=None,
) -> jnp.ndarray:
    """Ulysses-style all-to-all sequence parallelism.

    Call under ``shard_map`` with q/k/v time-sharded on ``axis_name``
    ((B, T/n, H, Dh) blocks).  Two ``lax.all_to_all`` re-shardings swap
    the sequence sharding for a head sharding: each device then runs
    *full-sequence* attention over H/n heads, so the math inside is
    exactly ``mha`` (no streaming softmax needed).  Communication is two
    all-to-alls of the activations vs the ring's n ppermute hops of
    k/v — better when heads divide the axis and T is large; the ring
    wins when H < n or memory for the full T scores is tight.
    """
    n = lax.psum(1, axis_name)
    del n  # static under shard_map; kept for symmetry/documentation

    def swap(x, fwd: bool):
        # fwd: (B, T/n, H, Dh) -> (B, T, H/n, Dh); tiled all_to_all
        # splits split_axis n ways and concatenates along concat_axis
        return lax.all_to_all(
            x, axis_name,
            split_axis=2 if fwd else 1,
            concat_axis=1 if fwd else 2,
            tiled=True,
        )

    local = attn_fn if attn_fn is not None else mha
    o = local(swap(q, True), swap(k, True), swap(v, True), causal=causal)
    return swap(o, False)


def a2a_self_attention(
    x_q: jnp.ndarray,
    x_k: jnp.ndarray,
    x_v: jnp.ndarray,
    mesh,
    seq_axis: str = "model",
    *,
    causal: bool = False,
    attn_fn=None,
) -> jnp.ndarray:
    """shard_map wrapper mirroring ``ring_self_attention`` — same global
    (B,T,H,Dh) contract, all-to-all schedule inside.  ``attn_fn`` swaps
    the per-device full-sequence attention (e.g. the Pallas flash kernel
    under ``attn_impl = pallas``)."""
    from jax.sharding import PartitionSpec as P

    spec = P("data", seq_axis, None, None)
    fn = jax.shard_map(
        functools.partial(a2a_attention, axis_name=seq_axis, causal=causal,
                          attn_fn=attn_fn),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    return fn(x_q, x_k, x_v)


def ring_attention_flash(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    axis_name: str,
    causal: bool = False,
    interpret: bool = False,
) -> jnp.ndarray:
    """Ring attention whose per-hop block math runs the fused flash
    kernel (``ops/flash.flash_mha_lse``) instead of XLA einsums.

    Same schedule as :func:`ring_attention` — kv blocks rotate around
    the ``axis_name`` ring — but each hop computes its ``(o, lse)``
    pair entirely in VMEM and partial results merge in log space:
    ``lse' = logaddexp``, outputs reweighted by ``exp(lse - lse')``.
    The causal mask uses dynamic global offsets (this device's query
    block start vs the hop's key block start); a hop that is entirely
    in the future yields ``lse ~ -1e30`` and washes out of the merge.
    """
    from .flash import flash_mha_lse

    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    b, tq, h, d = q.shape
    tk = k.shape[1]
    perm = [(i, (i + 1) % n) for i in range(n)]

    def hop(o, lse, kb, vb, hop_i):
        """o carried f32 across hops (the repo's accumulate-in-f32
        discipline); cast once at the final return."""
        src = (idx - hop_i) % n
        o_h, lse_h = flash_mha_lse(
            q, kb, vb, idx * tq, src * tk, causal, 512, 512, interpret
        )
        lse_new = jnp.logaddexp(lse, lse_h)
        w_old = jnp.exp(lse - lse_new)[:, :, :, None]
        w_new = jnp.exp(lse_h - lse_new)[:, :, :, None]
        o2 = o * w_old + o_h.astype(jnp.float32) * w_new
        return o2, lse_new

    o0 = jnp.zeros((b, tq, h, d), jnp.float32)
    lse0 = jnp.full((b, tq, h), NEG_INF, jnp.float32)
    o, lse = hop(o0, lse0, k, v, 0)

    def step(carry, hop_i):
        o, lse, kb, vb = carry
        kb, vb = lax.ppermute((kb, vb), axis_name, perm)
        o, lse = hop(o, lse, kb, vb, hop_i)
        return (o, lse, kb, vb), None

    if n > 1:
        (o, lse, _, _), _ = lax.scan(
            step, (o, lse, k, v), jnp.arange(1, n)
        )
    return o.astype(v.dtype)


def ring_self_attention_flash(
    x_q: jnp.ndarray,
    x_k: jnp.ndarray,
    x_v: jnp.ndarray,
    mesh,
    seq_axis: str = "model",
    *,
    causal: bool = False,
    interpret: bool = False,
) -> jnp.ndarray:
    """shard_map wrapper mirroring ``ring_self_attention`` with the
    flash per-hop kernel."""
    from jax.sharding import PartitionSpec as P

    spec = P("data", seq_axis, None, None)
    fn = jax.shard_map(
        functools.partial(ring_attention_flash, axis_name=seq_axis,
                          causal=causal, interpret=interpret),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,  # ppermute under scan confuses the checker
    )
    return fn(x_q, x_k, x_v)
