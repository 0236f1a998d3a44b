"""Sequence layers: multi-head attention, layer norm, sequence pooling.

New TPU-first scope — the reference is a CNN framework with no sequence
axis (SURVEY §5), so these layers have no parity source; they follow the
framework's own conventions (config-driven params, ``(nout, nin)`` weight
layout, NHWC-style batch-major nodes).  Sequence nodes are ``(N, T, D)``
(``input_layout = seq`` with ``input_shape = 1,T,D``).

``attention`` config keys:

* ``nhead`` — number of attention heads (D % nhead == 0)
* ``nkvhead`` — key/value heads (grouped-query attention: each serves
  ``nhead / nkvhead`` query heads; default ``nhead``).  The fused
  projection ``wmat`` is then ``((nhead + 2 nkvhead) * Dh, D)``
* ``score_scale`` — the score multiplier (default ``1 / sqrt(Dh)``)
* ``head_dim`` — a head's width ``Dh`` where it is not ``D / nhead``
  (``wproj`` is then ``(D, nhead * Dh)``)
* ``qk_norm`` — 1 norms every query and key head with an ``rms_norm``
  over ``Dh`` before the rotation (tags ``q_norm``, ``k_norm``, ``eps``)
* ``rotary_dim`` / ``rope_theta`` — rotate-half rotary positions on the
  first ``rotary_dim`` of each query and key head (``ops/attention.
  rotary``; theta default 10000).  With the ids input a position is
  counted from its document's first token
* ``out_gate`` — 1 doubles the query projection: head ``h``'s rows of
  ``wmat`` are its ``Dh`` of query then its ``Dh`` of gate, and the
  attention output is multiplied by ``sigmoid(gate)`` before ``wproj``
* ``no_bias`` — 1 drops ``bias`` and ``bproj``
* ``causal`` — 1 for autoregressive masking
* a second input, the net's token ids ``layer[x,0->y] = attention``:
  a token then sees only its own document (one begins after every
  separator id 0, ``ops/ssd.doc_index``).  No positional encoding is
  added anywhere: a net without ``pos`` on its embedding is
  position-free
* ``window`` — W > 0: a query sees the keys of its own document less
  than W positions before it, itself among them (``i - j < W``; the
  sliding-window layers of a local/global stack; default 0: none).  In
  the masked path only: the window is inside the flash kernels
  (``ops/flash.py``) and ``mha``'s row blocks; ``decode``,
  ``seq_parallel`` and position offsets have no windowed path (ROADMAP
  R3)
* ``prenorm`` / ``postnorm`` / ``residual_scale`` / ``eps`` — the
  residual branch in one layer, ``y = x + residual_scale *
  f(rms_norm(x))``, as ``mamba2`` and ``gated_mlp`` have it; with
  ``postnorm = 1`` a sandwich, ``y = x + residual_scale *
  rms_norm(f(rms_norm(x)))`` (``Branch`` below)
* ``seq_parallel`` — sequence/context parallelism over the mesh's
  ``model`` axis (``ops/attention.py``; off the mesh, or with a model
  axis of 1, both fall back to plain attention):
  * ``1`` / ``ring`` — **ring attention**: sequence sharded, kv blocks
    rotate over ICI with a streaming-softmax merge; needs
    T % model_axis == 0.  Scales to any T (never materializes full-T
    scores) and any head count.
  * ``2`` / ``alltoall`` — **Ulysses all-to-all**: two all_to_alls swap
    the sequence sharding for a head sharding, full-sequence attention
    per head subset; needs T % model_axis == 0 AND
    nhead % model_axis == 0.  Two activation collectives vs the ring's
    n kv hops — usually cheaper when heads divide the axis.

The masked path (any of ``nkvhead``, ``score_scale``, ``head_dim``,
``qk_norm``, ``rotary_dim``, ``out_gate``, ``no_bias``, ``window``, the
ids input)
goes through ``ops/attention.attend``, the one chooser
``latent_attention`` uses too: lowered for a TPU a long row runs the
flash kernels of ``ops/flash.py`` (document mask and window with whole
blocks skipped, grouped heads by the index map, the stated scale),
everywhere else ``ops/attention.mha``; the call sits under a scope of
its own inside the layer's, ``core_window`` on a windowed layer and
``core_full`` on any other.  Such a layer counts in its ``aux`` state
(``ATTN_COUNTERS``, read once a round by
``NetTrainer.count_layer_state``) ``attn_tokens``, the tokens through
it, and ``attn_tokens_flash``, those of them the kernels computed — the
branch that ran says so for itself — and, where they did,
``attn_blocks``, the (query block, key block) steps the forward kernel
computed over all heads, ``attn_blocks_unmasked``, those of them
whose every pair may attend (``ops/flash.count_blocks``, from the
tables the kernels read), and ``attn_tokens_bwd_fused``, the tokens
whose backward is the ONE kernel ``flash_bwd`` and not ``flash_dq`` +
``flash_dkv`` (``ops/flash.one_backward``, the kernels' own choice from
the shapes).
"""

from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp

from .base import Layer, Params, Shape, register


#: the masked attention layers' ``aux`` state (``attention``'s masked
#: path, ``latent_attention``), and the round's counters they add to
ATTN_COUNTERS = ("attn_tokens", "attn_tokens_flash", "attn_blocks",
                 "attn_blocks_unmasked", "attn_tokens_bwd_fused")


def attend_counted(scope, q, k, v, *, causal=False, scale=None, doc=None,
                   window=0):
    """``ops/attention.attend`` under the scope ``scope`` -> ``(o,
    ran)``, ``ran`` uint32 ``(4,)``: 1 where the flash kernels computed
    it, and then the blocks their forward visits, those of them whose
    every pair may attend (``ops/flash.count_blocks``) and 1 where their
    backward is the one kernel (``ops/flash.one_backward``); 0 where
    ``mha`` ran."""
    from ..ops.attention import attend
    from ..ops.flash import count_blocks, one_backward

    with jax.named_scope(scope):
        o, flash = attend(q, k, v, causal=causal, scale=scale, doc=doc,
                          window=window)
    blocks = count_blocks(q, k, v, causal=causal, doc=doc, window=window)
    one = jnp.uint32(one_backward(q, k, v))
    return o, jnp.concatenate([flash[None], blocks[:2] * flash,
                               (one * flash)[None]])


def count_attention(aux, x, ran):
    """``aux`` after ``x (N, T, D)`` went through attention, ``ran`` as
    ``attend_counted`` gives it (uint32, wrapping)."""
    tokens = jnp.uint32(x.shape[0] * x.shape[1])
    add = (tokens, tokens * ran[0], ran[1], ran[2], tokens * ran[3])
    return {name: aux[name] + n for name, n in zip(ATTN_COUNTERS, add)}


def _layer_norm(x, w, b, eps: float):
    """Shared layer-norm math: statistics in f32 under mixed precision."""
    xf = x.astype(jnp.float32)
    mu = xf.mean(axis=-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + jnp.float32(eps))
    return (
        y * w.astype(jnp.float32) + b.astype(jnp.float32)
    ).astype(x.dtype)


def rms_norm(x, w, eps: float):
    """``x / sqrt(mean(x^2) + eps) * w`` over the last axis (Zhang &
    Sennrich 2019); statistics in f32 under mixed precision."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt((xf * xf).mean(axis=-1, keepdims=True)
                           + jnp.float32(eps))
    return (y * w.astype(jnp.float32)).astype(x.dtype)


class Branch:
    """The keys of a residual branch kept in ONE layer, for the layer
    types a pre-norm residual net is made of (``mamba2``, ``attention``,
    ``gated_mlp``): ``prenorm = 1`` norms the input with an ``rms_norm``
    of the layer's own (tag ``norm``, ``eps``), ``postnorm = 1`` the
    branch's output with another (tag ``postnorm``: the sandwich norms
    of the afmoe family, inside the residual add), ``residual_scale =
    r`` returns ``x + r * f(...)`` and not ``f(...)`` alone.  Under
    ``remat = 1`` a conf layer is one ``jax.checkpoint``: a branch in
    one layer keeps one ``(N, T, D)`` input alive for the backward, not
    the norm's, the mixer's and the sum's — and, where the flash
    kernels computed its attention, their ``o`` and ``lse`` (``(N, T,
    heads, value width)`` in the compute dtype and ``(N, heads, T)``
    float32: ``nnet/net.REMAT_POLICY``), so the recompute does not run
    the forward kernel again."""

    prenorm = 0
    postnorm = 0
    residual_scale = 0.0
    eps = 1e-5

    def set_branch_param(self, name: str, val: str) -> bool:
        if name in ("prenorm", "postnorm"):
            setattr(self, name, int(val))
        elif name == "residual_scale":
            self.residual_scale = float(val)
        elif name == "eps":
            self.eps = float(val)
        else:
            return False
        return True

    def branch_params(self, d: int) -> Params:
        return {tag: jnp.ones((d,), jnp.float32)
                for tag, on in (("norm", self.prenorm),
                                ("postnorm", self.postnorm)) if on}

    def branch_in(self, params, x):
        return rms_norm(x, params["norm"], self.eps) if self.prenorm else x

    def branch_out(self, params, x, y):
        if self.postnorm:
            y = rms_norm(y, params["postnorm"], self.eps)
        if not self.residual_scale:
            return y
        return x + jnp.asarray(self.residual_scale, y.dtype) * y


_FLASH_PROBE: dict = {}  # attention geometry -> None (works) | the error


_SAID: set = set()


def _say_once(msg: str) -> None:
    """One stderr line plus a structured event, once per distinct
    message, for an attention-path decision an operator must be able to
    see: a failed kernel probe, an ``auto`` that lands on the XLA path
    on a chip."""
    if msg in _SAID:
        return
    _SAID.add(msg)
    import sys

    from ..obs import events as obs_events

    obs_events.emit("attention.impl", message=msg)
    print(f"attention: {msg}", file=sys.stderr, flush=True)


def _flash_probe(t: int, tk: int, dh: int, dtype, causal: bool,
                 ring: bool = False):
    """Compile and run the flash kernel, fwd AND bwd, at the real static
    attention geometry; returns None when it works, else the exception
    (cached per geometry, reported once — never swallowed).
    ``ring=True`` probes the dynamic-offset lse kernel the flash ring
    uses (per-shard shapes).

    Probes fire while the net is being jit-traced (layer ``apply`` is
    where the impl choice lives); JAX trace contexts are thread-local,
    so a worker thread executes the probe eagerly — really compiling
    and running the kernel — instead of tracing it into the outer
    program."""
    key = (t, tk, dh, jnp.dtype(dtype).name, causal, ring)
    if key not in _FLASH_PROBE:
        import concurrent.futures

        from ..ops.flash import flash_mha, flash_mha_lse

        def probe():
            q = jnp.ones((1, t, 1, dh), dtype)
            k = jnp.ones((1, tk, 1, dh), dtype)
            if ring:
                def f(a):
                    o, lse = flash_mha_lse(
                        a, k, k, jnp.int32(0), jnp.int32(0), causal,
                        512, 512, False,
                    )
                    return o.astype(jnp.float32).sum() + lse.sum() * 1e-3
            else:
                def f(a):
                    return flash_mha(
                        a, k, k, causal, 512, 512, False
                    ).astype(jnp.float32).sum()
            jax.grad(f)(q).block_until_ready()

        err = None
        with concurrent.futures.ThreadPoolExecutor(1) as ex:
            try:
                ex.submit(probe).result()
            except Exception as e:  # noqa: BLE001 - reported, then returned
                err = e
                _say_once(
                    f"flash kernel probe failed for T={t}, Tk={tk}, "
                    f"Dh={dh}, {key[3]}, causal={causal}, ring={ring}: "
                    f"{type(e).__name__}: {e}")
        _FLASH_PROBE[key] = err
    return _FLASH_PROBE[key]


def _check_ids_input(who: str, in_shapes: Sequence[Shape]) -> None:
    """One input, or two: the sequence node and the net's token ids
    ``(N, T)``, from which the layer reads where documents begin."""
    if len(in_shapes) not in (1, 2):
        raise ValueError(
            f"{who}: expected 1 or 2 input(s), got {len(in_shapes)}")
    if len(in_shapes) == 2 and tuple(in_shapes[1]) != tuple(
            in_shapes[0][:2]):
        raise ValueError(
            f"{who}: the second input is the (N, T) token ids of the "
            f"sequence node {tuple(in_shapes[0])}, got "
            f"{tuple(in_shapes[1])}")


@register
class AttentionLayer(Layer, Branch):
    type_name = "attention"
    #: state leaf -> the round's counter it is added to (the masked path)
    aux_counters = {name: name for name in ATTN_COUNTERS}
    f32_tags = frozenset({"norm", "postnorm", "q_norm", "k_norm"})

    def __init__(self) -> None:
        super().__init__()
        self.nhead = 1
        self.nkvhead = 0  # 0: as many as nhead
        self.scale = 0.0  # 0: 1 / sqrt(Dh)
        self.head_dim = 0  # 0: D / nhead
        self.qk_norm = 0
        self.rotary_dim = 0
        self.rope_theta = 10000.0
        self.out_gate = 0
        self.window = 0  # 0: every key of the document
        self.causal = 0
        self.seq_parallel = 0
        self.attn_impl = "auto"
        self.decode = 0
        self.decode_window = 0
        self.mesh_plan = None  # bound by the trainer (bind_mesh)

    _SP_MODES = {"0": 0, "1": 1, "2": 2, "off": 0, "ring": 1,
                 "alltoall": 2, "a2a": 2}

    def set_param(self, name, val):
        if name == "nhead":
            self.nhead = int(val)
        elif name == "nkvhead":
            self.nkvhead = int(val)
        elif name == "score_scale":
            self.scale = float(val)
        elif name in ("head_dim", "qk_norm", "rotary_dim", "out_gate",
                      "window"):
            setattr(self, name, int(val))
        elif name == "rope_theta":
            self.rope_theta = float(val)
        elif self.set_branch_param(name, val):
            pass
        elif name == "causal":
            self.causal = int(val)
        elif name == "attn_impl":
            if val not in ("auto", "pallas", "xla"):
                raise ValueError(
                    f"attn_impl must be auto|pallas|xla, got {val!r}"
                )
            self.attn_impl = val
        elif name == "seq_parallel":
            if val not in self._SP_MODES:
                raise ValueError(
                    f"seq_parallel must be one of {sorted(self._SP_MODES)},"
                    f" got {val!r}"
                )
            self.seq_parallel = self._SP_MODES[val]
        elif name == "decode":
            # KV-cache incremental decoding (generation): keys/values
            # accumulate in aux state; the loop's ``step`` is the
            # absolute position of this call's first token
            self.decode = int(val)
        elif name == "decode_window":
            self.decode_window = int(val)
        else:
            super().set_param(name, val)

    def _local_attn(self, causal_override=None):
        """Per-device full-sequence attention fn ``(q,k,v,causal)->o``.

        ``attn_impl = pallas`` is a hard opt-in (raises if the kernel
        probe fails on this backend); ``auto`` switches to the flash
        kernel for long sequences where the XLA path's full score
        matrix is the memory ceiling; ``xla`` always takes the
        reference path.  On CPU the identical kernel runs in interpret
        mode (tests).
        """
        from ..ops.attention import LONG_T, mha

        def xla_attn(q, k, v, causal=bool(self.causal)):
            return mha(q, k, v, causal=causal)

        if self.attn_impl == "xla":
            return xla_attn

        def flash_attn(q, k, v, causal=bool(self.causal)):
            from ..ops.flash import flash_mha

            interp = jax.default_backend() != "tpu"
            return flash_mha(q, k, v, causal, 512, 512, interp)

        def dispatch(q, k, v, causal=bool(self.causal)):
            from ..ops.flash import _pick_block

            t, tk, dh = q.shape[1], k.shape[1], q.shape[3]
            on_tpu = jax.default_backend() == "tpu"
            if self.attn_impl == "auto":
                # auto never takes the interpret-mode emulation (an
                # orders-of-magnitude slowdown off-TPU), and short
                # sequences are the XLA path's home ground: mha holds
                # (B,H,T,T) scores in HBM, which from LONG_T on is the
                # difference between running and OOM
                if not on_tpu or t < LONG_T:
                    return xla_attn(q, k, v, causal)
                # past here auto MEANS flash; landing on mha is reported
                if (_pick_block(t, 512) < 128
                        or _pick_block(tk, 512) < 128):
                    # an odd T shrinks blocks into scalar territory
                    # (block 1 kernels compile forever / run slow)
                    self._say_mha(
                        t, tk, "no block >= 128 divides the sequence")
                    return xla_attn(q, k, v, causal)
            if on_tpu:
                err = _flash_probe(t, tk, dh, q.dtype, causal)
                if err is not None:
                    if self.attn_impl == "pallas":
                        raise RuntimeError(
                            "attention: attn_impl=pallas requested but "
                            f"the flash kernel failed for T={t}, Dh={dh}, "
                            f"{q.dtype} on this backend: "
                            f"{type(err).__name__}: {err}"
                        ) from err
                    self._say_mha(t, tk, "the flash kernel probe failed")
                    return xla_attn(q, k, v, causal)
            return flash_attn(q, k, v, causal)

        return dispatch

    @staticmethod
    def _say_mha(t: int, tk: int, why: str) -> None:
        """``attn_impl = auto`` on a chip at flash-sized T is taking
        the XLA path — O(T^2) score memory where the operator expects
        O(T).  Said once per geometry, never silently."""
        _say_once(f"attn_impl=auto at T={t}, Tk={tk} runs the XLA mha "
                  f"path, not the flash kernel: {why}")

    def bind_mesh(self, plan) -> None:
        self.mesh_plan = plan

    def init_aux(self, in_shapes):
        """KV cache state for ``decode = 1``: keys/values for all past
        positions, written at the loop's ``step`` offset.  The masked
        path's counters otherwise; nothing for the plain layer."""
        if not self.decode:
            if self._plain(len(in_shapes)):
                return {}
            return {name: jnp.zeros((), jnp.uint32)
                    for name in ATTN_COUNTERS}
        if self.seq_parallel:
            raise ValueError(
                "attention: decode=1 (single-token KV caching) does not "
                "compose with seq_parallel"
            )
        if not self.causal:
            raise ValueError(
                "attention: decode=1 requires causal=1 — incremental "
                "decoding cannot reproduce bidirectional attention"
            )
        if self.decode_window <= 0:
            raise ValueError(
                "attention: decode=1 needs decode_window (max positions "
                "the cache holds — the training T)"
            )
        n, t, d = in_shapes[0]
        h, dh = self.nhead, d // self.nhead
        w = self.decode_window
        return {
            "kcache": jnp.zeros((n, w, h, dh), jnp.float32),
            "vcache": jnp.zeros((n, w, h, dh), jnp.float32),
        }

    def apply_stateful(self, params, aux, inputs, *, train=False, rng=None,
                       step=None):
        """Incremental attention: write this call's k/v into the cache
        at positions ``step..step+t-1`` and attend q against everything
        up to its own position (the causal rule against the cache).
        Without ``decode`` the state is the masked path's counters."""
        from jax import lax

        if not self.decode:
            y, ran = self._apply_masked(
                params, self.branch_in(params, inputs[0]),
                inputs[1] if len(inputs) > 1 else None)
            return ([self.branch_out(params, inputs[0], y)],
                    count_attention(aux, inputs[0], ran))
        x = inputs[0]
        n, t, d = x.shape
        h, dh = self.nhead, d // self.nhead
        qkv = x @ params["wmat"].astype(x.dtype).T + params["bias"].astype(
            x.dtype
        )
        qkv = qkv.reshape(n, t, 3, h, dh)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        start = jnp.asarray(0 if step is None else step, jnp.int32)
        kc = lax.dynamic_update_slice(
            aux["kcache"], k.astype(jnp.float32), (0, start, 0, 0)
        )
        vc = lax.dynamic_update_slice(
            aux["vcache"], v.astype(jnp.float32), (0, start, 0, 0)
        )
        w = kc.shape[1]
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", q.astype(jnp.float32), kc,
            preferred_element_type=jnp.float32,
        ) * (1.0 / (dh ** 0.5))
        q_pos = start + lax.broadcasted_iota(jnp.int32, (t, w), 0)
        k_pos = lax.broadcasted_iota(jnp.int32, (t, w), 1)
        s = jnp.where((k_pos <= q_pos)[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum(
            "bhqk,bkhd->bqhd", p, vc, preferred_element_type=jnp.float32
        ).astype(x.dtype).reshape(n, t, d)
        out = o @ params["wproj"].astype(x.dtype).T + params["bproj"].astype(
            x.dtype
        )
        return [out], {"kcache": kc, "vcache": vc}

    def _plain(self, n_in: int = 1) -> bool:
        """The layer as it was before grouped heads, a stated scale and
        documents: every path but the masked ``mha`` knows only this."""
        return (n_in == 1 and not self.scale and not self.param.no_bias
                and self.nkvhead in (0, self.nhead)
                and not (self.head_dim or self.qk_norm or self.rotary_dim
                         or self.out_gate or self.window))

    def infer_shape(self, in_shapes: Sequence[Shape]) -> List[Shape]:
        _check_ids_input("attention", in_shapes)
        shape = in_shapes[0]
        if len(shape) != 3:
            raise ValueError(
                "attention: input must be a sequence node (N, T, D); set "
                "input_layout = seq"
            )
        n, t, d = shape
        if self.nhead <= 0 or (d % self.nhead != 0 and not self.head_dim):
            raise ValueError(
                f"attention: nhead={self.nhead} must divide model dim {d}"
            )
        if self.rotary_dim % 2 or self.rotary_dim > (
                self.head_dim or d // self.nhead):
            raise ValueError(
                f"attention: rotary_dim={self.rotary_dim} must be even "
                "and no wider than a head")
        if self.nkvhead and self.nhead % self.nkvhead:
            raise ValueError(
                f"attention: nkvhead={self.nkvhead} must divide "
                f"nhead={self.nhead}"
            )
        if self.window < 0:
            raise ValueError(
                f"attention: window={self.window} counts the keys a query "
                "sees back from itself, 0 for all")
        if self.window and self.decode:
            raise ValueError(
                f"attention: window = {self.window} with decode = 1: the "
                "KV cache (nnet/generate.py) has one shape, every past "
                "position; a window's and a full layer's caches side by "
                "side are ROADMAP R3")
        if self.window and self.seq_parallel:
            raise ValueError(
                f"attention: window = {self.window} with seq_parallel: the "
                "ring hops and the all-to-all know no window and no "
                "position offsets under one (ops/flash.py refuses them); "
                "the masked and windowed path across chips is ROADMAP R3")
        if not self._plain(len(in_shapes)) and (
                self.seq_parallel or self.decode
                or self.attn_impl == "pallas"):
            raise ValueError(
                "attention: nkvhead, score_scale, no_bias, head_dim, "
                "qk_norm, rotary_dim, out_gate, window and a document input "
                "run the masked path, which chooses between the flash "
                "kernels and the XLA row blocks itself "
                "(ops/attention.attend); "
                "seq_parallel and decode know none of them, and "
                "attn_impl = pallas forces the plain layer's kernel only"
            )
        if self.seq_parallel and self.mesh_plan is not None:
            nm = self.mesh_plan.n_model
            if nm > 1 and t % nm != 0:
                raise ValueError(
                    f"attention: seq_parallel needs T={t} divisible by the "
                    f"model axis ({nm})"
                )
            if nm > 1 and self.seq_parallel == 2 and self.nhead % nm != 0:
                raise ValueError(
                    f"attention: seq_parallel=alltoall needs "
                    f"nhead={self.nhead} divisible by the model axis ({nm})"
                )
        return [tuple(shape)]

    def init_params(self, key, in_shapes) -> Params:
        d = in_shapes[0][2]
        p = self.param
        k1, k2 = jax.random.split(key)
        sigma = p.init_sigma  # framework default 0.01; set via init_sigma
        dh = self.head_dim or d // self.nhead
        nq = self.nhead * dh
        nqkv = nq * (2 if self.out_gate else 1) + 2 * (
            self.nkvhead or self.nhead) * dh
        out = {
            # framework (nout, nin) layout: fused qkv then output proj
            "wmat": jax.random.normal(k1, (nqkv, d), jnp.float32) * sigma,
            "wproj": jax.random.normal(k2, (d, nq), jnp.float32) * sigma,
        }
        if self.qk_norm:
            out["q_norm"] = jnp.ones((dh,), jnp.float32)
            out["k_norm"] = jnp.ones((dh,), jnp.float32)
        if not p.no_bias:
            out["bias"] = jnp.zeros((nqkv,), jnp.float32)
            out["bproj"] = jnp.zeros((d,), jnp.float32)
        out.update(self.branch_params(d))
        return out

    def _apply_masked(self, params, x, ids):
        """Grouped-query heads, a stated scale or head width, q/k
        norms, rotary positions, an output gate, documents: q, k and v
        from the one fused projection, then ``ops/attention.attend``
        with its mask — the flash kernels or ``mha``'s row blocks.
        Returns the output and what ran (``attend_counted``)."""
        from ..ops.attention import doc_positions, rotary
        from ..ops.ssd import doc_index

        n, t, d = x.shape
        h, hk = self.nhead, self.nkvhead or self.nhead
        dh = self.head_dim or d // h
        nq = h * dh
        qkv = x @ params["wmat"].astype(x.dtype).T
        if "bias" in params:
            qkv = qkv + params["bias"].astype(x.dtype)
        gate = None
        if self.out_gate:
            qg = qkv[..., :2 * nq].reshape(n, t, h, 2 * dh)
            q, gate = qg[..., :dh], qg[..., dh:].reshape(n, t, nq)
            qkv = qkv[..., nq:]
        else:
            q = qkv[..., :nq].reshape(n, t, h, dh)
        k = qkv[..., nq:nq + hk * dh].reshape(n, t, hk, dh)
        v = qkv[..., nq + hk * dh:].reshape(n, t, hk, dh)
        doc = None if ids is None else doc_index(ids)
        if self.qk_norm:
            with jax.named_scope("qk_norm"):
                q = rms_norm(q, params["q_norm"], self.eps)
                k = rms_norm(k, params["k_norm"], self.eps)
        if self.rotary_dim:
            with jax.named_scope("rotary"):
                pos = doc_positions(doc, n, t)
                q = rotary(q, pos, self.rotary_dim, self.rope_theta)
                k = rotary(k, pos, self.rotary_dim, self.rope_theta)
        o, ran = attend_counted(
            "core_window" if self.window else "core_full", q, k, v,
            causal=bool(self.causal), scale=self.scale or None, doc=doc,
            window=self.window)
        o = o.reshape(n, t, nq)
        if gate is not None:
            o = o * jax.nn.sigmoid(gate)
        out = o @ params["wproj"].astype(x.dtype).T
        if "bproj" in params:
            out = out + params["bproj"].astype(x.dtype)
        return out, ran

    def apply(self, params, inputs, *, train=False, rng=None, step=None):
        x = self.branch_in(params, inputs[0])
        if self._plain(len(inputs)):
            y = self._apply_plain(params, x)
        else:
            y, _ = self._apply_masked(
                params, x, inputs[1] if len(inputs) > 1 else None)
        return [self.branch_out(params, inputs[0], y)]

    def _apply_plain(self, params, x):
        from ..ops.attention import ring_self_attention

        n, t, d = x.shape
        h = self.nhead
        dh = d // h
        qkv = x @ params["wmat"].astype(x.dtype).T + params["bias"].astype(
            x.dtype
        )
        qkv = qkv.reshape(n, t, 3, h, dh)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        plan = self.mesh_plan
        if self.seq_parallel and plan is not None and plan.n_model > 1:
            if self.seq_parallel == 2:
                from ..ops.attention import a2a_self_attention

                o = a2a_self_attention(
                    q, k, v, plan.mesh, "model", causal=bool(self.causal),
                    attn_fn=self._local_attn(),
                )
            elif self.attn_impl == "pallas":
                # flash ring: per-hop (o, lse) pairs from the fused
                # kernel, merged in log space (ops/attention).  Same
                # opt-in discipline as the local pallas path: tiny
                # per-shard blocks and probe failures raise clearly
                # instead of surfacing as Mosaic errors mid-training.
                from ..ops.flash import _pick_block
                from ..ops.attention import ring_self_attention_flash

                ts = t // plan.n_model  # per-shard sequence length
                dh = d // h
                if jax.default_backend() == "tpu":
                    if _pick_block(ts, 512) < 128:
                        raise ValueError(
                            f"attention: seq_parallel=ring "
                            f"attn_impl=pallas needs per-shard T={ts} "
                            f"with a block >= 128; use attn_impl=xla "
                            f"for short shards"
                        )
                    err = _flash_probe(
                        ts, ts, dh, q.dtype, bool(self.causal), ring=True
                    )
                    if err is not None:
                        raise RuntimeError(
                            "attention: attn_impl=pallas requested but "
                            f"the flash ring kernel failed for T={ts}, "
                            f"Dh={dh}, {q.dtype} on this backend: "
                            f"{type(err).__name__}: {err}"
                        ) from err
                o = ring_self_attention_flash(
                    q, k, v, plan.mesh, "model", causal=bool(self.causal),
                    interpret=jax.default_backend() != "tpu",
                )
            else:
                o = ring_self_attention(
                    q, k, v, plan.mesh, "model", causal=bool(self.causal)
                )
        else:
            o = self._local_attn()(q, k, v)
        o = o.reshape(n, t, d)
        return (
            o @ params["wproj"].astype(x.dtype).T
            + params["bproj"].astype(x.dtype)
        )


@register
class LayerNormLayer(Layer):
    type_name = "layer_norm"

    def __init__(self) -> None:
        super().__init__()
        self.eps = 1e-6

    def set_param(self, name, val):
        if name == "eps":
            self.eps = float(val)
        else:
            super().set_param(name, val)

    def infer_shape(self, in_shapes: Sequence[Shape]) -> List[Shape]:
        self._check_arity(in_shapes, 1)
        return [tuple(in_shapes[0])]

    def init_params(self, key, in_shapes) -> Params:
        d = in_shapes[0][-1]
        return {
            "wmat": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32),
        }

    def apply(self, params, inputs, *, train=False, rng=None, step=None):
        x = inputs[0]
        return [_layer_norm(x, params["wmat"], params["bias"], self.eps)]


@register
class RMSNormLayer(Layer):
    """``rms_norm``: ``x / sqrt(mean(x^2) + eps) * wmat`` over the last
    axis; key ``eps`` (default 1e-5)."""

    type_name = "rms_norm"

    def __init__(self) -> None:
        super().__init__()
        self.eps = 1e-5

    def set_param(self, name, val):
        if name == "eps":
            self.eps = float(val)
        else:
            super().set_param(name, val)

    def infer_shape(self, in_shapes: Sequence[Shape]) -> List[Shape]:
        self._check_arity(in_shapes, 1)
        return [tuple(in_shapes[0])]

    def init_params(self, key, in_shapes) -> Params:
        return {"wmat": jnp.ones((in_shapes[0][-1],), jnp.float32)}

    def apply(self, params, inputs, *, train=False, rng=None, step=None):
        return [rms_norm(inputs[0], params["wmat"], self.eps)]


@register
class SeqPoolLayer(Layer):
    """Mean-pool the time axis: (N, T, D) -> (N, D) classification head."""

    type_name = "seq_pool"

    def infer_shape(self, in_shapes: Sequence[Shape]) -> List[Shape]:
        self._check_arity(in_shapes, 1)
        (shape,) = in_shapes
        if len(shape) != 3:
            raise ValueError("seq_pool: input must be a sequence node")
        return [(shape[0], shape[2])]

    def apply(self, params, inputs, *, train=False, rng=None, step=None):
        return [inputs[0].mean(axis=1)]


@register
class MoELayer(Layer):
    """``moe``: a DENSE mixture of linear expert projections.

    New TPU-first scope (no reference analog).  ``nexpert`` expert
    projections ``(nhidden, D)`` live in one ``(E, nhidden, D)`` tensor
    whose expert dim is sharded over the mesh ``model`` axis
    (``MeshPlan.param_sharding`` 3-D rule): every expert runs on every
    token, and GSPMD partitions the expert einsums across devices and
    inserts the combine reduction.  That shards the experts' weights
    and their FLOPs; it is not what a published mixture-of-experts
    block does — no token is dispatched, and ``topk`` only zero-weights
    the rest.  Routing is a softmax gate, optionally top-k masked
    (``topk = 0`` keeps the dense soft mixture).

    The routed layer is ``routed_experts`` (``layers/moe.py``): gated
    experts, sorted dispatch with grouped products, a device's held
    share.  The two are different functions with different parameters
    (this one projects to another width through one matrix an expert),
    so both remain; ROADMAP's design debts say when this one can go.

    Works on flat ``(N, D)`` and sequence ``(N, T, D)`` nodes.
    """

    type_name = "moe"

    def __init__(self) -> None:
        super().__init__()
        self.nexpert = 4
        self.topk = 0

    def set_param(self, name, val):
        if name == "nexpert":
            self.nexpert = int(val)
        elif name == "topk":
            self.topk = int(val)
        else:
            super().set_param(name, val)

    def infer_shape(self, in_shapes: Sequence[Shape]) -> List[Shape]:
        self._check_arity(in_shapes, 1)
        (shape,) = in_shapes
        if len(shape) not in (2, 3):
            raise ValueError("moe: input must be a matrix or sequence node")
        if self.param.num_hidden <= 0:
            raise ValueError("moe: must set nhidden correctly")
        if self.nexpert < 1 or not (0 <= self.topk <= self.nexpert):
            raise ValueError("moe: need nexpert >= 1 and 0 <= topk <= nexpert")
        return [tuple(shape[:-1]) + (self.param.num_hidden,)]

    def init_params(self, key, in_shapes) -> Params:
        d = in_shapes[0][-1]
        nh = self.param.num_hidden
        e = self.nexpert
        k1, k2 = jax.random.split(key)
        sigma = self.param.init_sigma
        return {
            "wgate": jax.random.normal(k1, (e, d), jnp.float32) * sigma,
            "wmat": jax.random.normal(k2, (e, nh, d), jnp.float32) * sigma,
            "bias": jnp.zeros((e, nh), jnp.float32),
        }

    def apply(self, params, inputs, *, train=False, rng=None, step=None):
        x = inputs[0]
        wg = params["wgate"].astype(x.dtype)
        wm = params["wmat"].astype(x.dtype)
        b = params["bias"].astype(x.dtype)
        logits = jnp.einsum("...d,ed->...e", x, wg).astype(jnp.float32)
        gate = jax.nn.softmax(logits, axis=-1)
        if self.topk:
            # keep exactly top-k gates (by index, so ties at the threshold
            # never admit extra experts), renormalize; the masked experts'
            # outputs are zero-weighted (FLOPs still run — dense dispatch)
            _, idx = jax.lax.top_k(gate, self.topk)
            mask = jax.nn.one_hot(idx, self.nexpert, dtype=gate.dtype).sum(
                axis=-2
            )
            gate = gate * mask
            gate = gate / jnp.maximum(
                gate.sum(axis=-1, keepdims=True), 1e-30
            )
        gate = gate.astype(x.dtype)
        h = jnp.einsum("...d,eod->...eo", x, wm) + b
        return [jnp.einsum("...e,...eo->...o", gate, h)]


class _PipelineStackLayer(Layer):
    """Shared plumbing for homogeneous block-stack layers that can run as
    a GPipe pipeline over the mesh model axis: the
    ``pipeline_parallel`` / ``n_microbatch`` config keys, mesh binding,
    stage/microbatch divisibility checks, and the
    pipeline-vs-scanned-stack dispatch.  Subclasses define ``nblock``,
    ``_block(p, x)``, and their params stack."""

    def __init__(self) -> None:
        super().__init__()
        self.nblock = 2
        self.pipeline_parallel = 0
        self.n_microbatch = 4
        self.mesh_plan = None

    def set_param(self, name, val):
        if name == "nblock":
            self.nblock = int(val)
        elif name == "pipeline_parallel":
            self.pipeline_parallel = int(val)
        elif name == "n_microbatch":
            self.n_microbatch = int(val)
        else:
            super().set_param(name, val)

    def bind_mesh(self, plan) -> None:
        self.mesh_plan = plan

    def _check_pipeline_shape(self, batch: int) -> None:
        if self.pipeline_parallel and self.mesh_plan is not None:
            nm = self.mesh_plan.n_model
            if nm > 1 and self.nblock % nm != 0:
                raise ValueError(
                    f"{self.type_name}: nblock={self.nblock} must divide "
                    f"over the model axis ({nm} stages)"
                )
            if nm > 1 and batch % self.n_microbatch != 0:
                raise ValueError(
                    f"{self.type_name}: batch {batch} must divide into "
                    f"{self.n_microbatch} microbatches"
                )

    def _apply_stack(self, stack, x):
        """Run the block stack pipelined (when configured on a >1 model
        axis) or as a plain lax.scan — identical math either way."""
        plan = self.mesh_plan
        if self.pipeline_parallel and plan is not None and plan.n_model > 1:
            from ..ops.pipeline import pipeline_apply

            return pipeline_apply(
                self._block, stack, x, plan.mesh,
                n_microbatch=self.n_microbatch, stage_axis="model",
            )

        def body(h, p):
            return self._block(p, h), None

        y, _ = jax.lax.scan(body, x, stack)
        return y


@register
class PipeTransformerLayer(_PipelineStackLayer):
    """A stack of ``nblock`` identical pre-LN transformer blocks runnable
    as a GPipe pipeline (``ops/pipeline.py``) over the mesh model axis.

    Pipeline parallelism over REAL model blocks: each block is
    layer_norm -> multi-head attention -> residual -> layer_norm ->
    gelu-MLP -> residual, exactly the ``transformer_conf`` block
    structure, with all ``nblock`` blocks' parameters living in stacked
    ``(L, ...)`` tensors.  With ``pipeline_parallel = 1`` the stack is
    sharded one-stage-per-device and microbatches stream through the
    gpipe schedule; with 0 the same blocks run as a plain ``lax.scan``
    (identical math — the parity fixture in tests/test_pipeline.py).

    SPMD pipelining requires homogeneous stages (every device runs the
    same program), hence a block *stack* rather than arbitrary layer
    ranges — the same constraint praxis/GSPMD pipelining has.
    """

    type_name = "pipe_transformer"
    f32_tags = frozenset({"ln1_w", "ln1_b", "ln2_w", "ln2_b"})

    def __init__(self) -> None:
        super().__init__()
        self.nhead = 1
        self.causal = 0
        self.ffn_hidden = 0  # default 4*D
        self.eps = 1e-6

    def set_param(self, name, val):
        if name == "nhead":
            self.nhead = int(val)
        elif name == "causal":
            self.causal = int(val)
        elif name == "ffn_hidden":
            self.ffn_hidden = int(val)
        elif name == "eps":
            self.eps = float(val)
        else:
            super().set_param(name, val)

    def infer_shape(self, in_shapes: Sequence[Shape]) -> List[Shape]:
        self._check_arity(in_shapes, 1)
        (shape,) = in_shapes
        if len(shape) != 3:
            raise ValueError(
                "pipe_transformer: input must be a sequence node (N, T, D)"
            )
        n, t, d = shape
        if self.nhead <= 0 or d % self.nhead != 0:
            raise ValueError(
                f"pipe_transformer: nhead={self.nhead} must divide dim {d}"
            )
        self._check_pipeline_shape(n)
        return [tuple(shape)]

    def init_params(self, key, in_shapes) -> Params:
        d = in_shapes[0][2]
        h = self.ffn_hidden or 4 * d
        l = self.nblock
        sigma = self.param.init_sigma
        k1, k2, k3, k4 = jax.random.split(key, 4)
        return {
            "ln1_w": jnp.ones((l, d), jnp.float32),
            "ln1_b": jnp.zeros((l, d), jnp.float32),
            "ln2_w": jnp.ones((l, d), jnp.float32),
            "ln2_b": jnp.zeros((l, d), jnp.float32),
            "wqkv": jax.random.normal(k1, (l, 3 * d, d), jnp.float32) * sigma,
            "bqkv": jnp.zeros((l, 3 * d), jnp.float32),
            "wproj": jax.random.normal(k2, (l, d, d), jnp.float32) * sigma,
            "bproj": jnp.zeros((l, d), jnp.float32),
            "wff1": jax.random.normal(k3, (l, h, d), jnp.float32) * sigma,
            "bff1": jnp.zeros((l, h), jnp.float32),
            "wff2": jax.random.normal(k4, (l, d, h), jnp.float32) * sigma,
            "bff2": jnp.zeros((l, d), jnp.float32),
        }

    def _block(self, p, x):
        from ..ops.attention import mha

        n, t, d = x.shape
        nh = self.nhead
        h = _layer_norm(x, p["ln1_w"], p["ln1_b"], self.eps)
        qkv = h @ p["wqkv"].T + p["bqkv"]
        qkv = qkv.reshape(n, t, 3, nh, d // nh)
        o = mha(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2],
                causal=bool(self.causal))
        x = x + o.reshape(n, t, d) @ p["wproj"].T + p["bproj"]
        h2 = _layer_norm(x, p["ln2_w"], p["ln2_b"], self.eps)
        f = (jax.nn.gelu(h2 @ p["wff1"].T + p["bff1"])
             @ p["wff2"].T + p["bff2"])
        return x + f

    def apply(self, params, inputs, *, train=False, rng=None, step=None):
        x = inputs[0]
        stack = {
            k: (v if k in self.f32_tags else v.astype(x.dtype))
            for k, v in params.items()
        }
        return [self._apply_stack(stack, x)]


@register
class PipeMLPLayer(_PipelineStackLayer):
    """A stack of ``nblock`` identical relu-MLP blocks runnable as a
    GPipe pipeline (``ops/pipeline.py``) over the mesh model axis.

    The minimal pipeline-parallel layer: blocks are homogeneous
    (``y = relu(x W_i + b_i)``, width = input dim), their params live in
    one ``(L, D, D)`` stack sharded one-stage-per-device when
    ``pipeline_parallel = 1``, and microbatches stream through the
    stages with activations hopping a ppermute ring.  For pipelining
    real model blocks use ``pipe_transformer``.
    """

    type_name = "pipe_mlp"

    def infer_shape(self, in_shapes: Sequence[Shape]) -> List[Shape]:
        self._check_arity(in_shapes, 1)
        (shape,) = in_shapes
        if len(shape) != 2:
            raise ValueError("pipe_mlp: input must be a matrix node")
        self._check_pipeline_shape(shape[0])
        return [tuple(shape)]

    def init_params(self, key, in_shapes) -> Params:
        d = in_shapes[0][1]
        sigma = self.param.init_sigma
        return {
            "wmat": jax.random.normal(
                key, (self.nblock, d, d), jnp.float32
            ) * sigma,
            "bias": jnp.zeros((self.nblock, d), jnp.float32),
        }

    @staticmethod
    def _block(p, x):
        return jax.nn.relu(x @ p["wmat"] + p["bias"])

    def apply(self, params, inputs, *, train=False, rng=None, step=None):
        x = inputs[0]
        stack = {
            "wmat": params["wmat"].astype(x.dtype),
            "bias": params["bias"].astype(x.dtype),
        }
        return [self._apply_stack(stack, x)]
