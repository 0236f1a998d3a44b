"""Latent attention: ``latent_attention``, the multi-head latent
attention of the DeepSeek-V2/V3 family (DeepSeek-AI 2024, "DeepSeek-V2",
section 2.1) in its training form.

New TPU-first scope.  Queries and keys/values are made through low-rank
latents with norms of their own; a head's query and key have a part
without position (``nope_dim``) and a rotary part (``rope_dim``), and
the rotary key is ONE head shared by all query heads; values have a
width of their own (``v_dim``), so the score product is ``nope_dim +
rope_dim`` wide and the value product ``v_dim`` wide.  With ``u`` the
(pre-normed) input:

    c_q = rms_norm(u W_qa^T)                    (q_rank)
    [q_nope | q_rope]_h = c_q W_qb^T            nhead x (nope_dim + rope_dim)
    [c_kv | k_rope] = u W_kva^T                 kv_rank + rope_dim
    [k_nope | v]_h = rms_norm(c_kv) W_kvb^T     nhead x (nope_dim + v_dim)
    q_rope, k_rope <- rotary(., pos)            pos from the document's start
    s = (q_nope . k_nope + q_rope . k_rope) / sqrt(nope_dim + rope_dim)
    o_h = softmax(s, causal, own document) v_h
    y = concat_h(o_h) W_o^T

With ``q_rank = 0`` (the family's ``q_lora_rank: null``) the query has no
latent and no norm: ``[q_nope | q_rope]_h = u W_q^T``.  With ``out_gate =
head`` every head's output is gated by one scalar a token before ``W_o``:
``o_h <- o_h * sigmoid(u . w_gate_h)``.

Training runs the **expanded** form: keys and values are made from the
latent for every head and go through ``ops/attention.attend``, the one
chooser ``attention``'s masked path uses too: lowered for a TPU, a long
row runs the flash kernels of ``ops/flash.py`` (document mask with whole
blocks skipped, the score product ``nope_dim + rope_dim`` wide beside
values ``v_dim`` wide, bf16 into the MXU, float32 softmax; the forward
and both backward kernels once a step — the net's ``remat`` keeps the
forward's ``o`` and ``lse`` across the backward pass, ``nhead x T x
(v_dim x 2 + 4)`` bytes a layer, and its recompute runs no second
forward kernel: ``flash.KEPT_NAMES``); everywhere else
``ops/attention.mha``, in checkpointed row blocks of 512 queries once
the row is long.  The absorbed form (``W_kvb`` folded into the query
and the output so that a decode step reads the latent cache alone) is
decode's; this layer has no cache and no decode path.

``latent_attention`` config keys:

* ``nhead``, ``kv_rank``, ``nope_dim``, ``rope_dim``, ``v_dim`` —
  required, positive; ``rope_dim`` even; ``q_rank`` — required, positive
  or 0 (no query latent)
* ``out_gate`` — ``none`` (default) or ``head``
* ``rope_theta`` (10000), ``rope_interleave`` (1: the pairs ``(2i,
  2i+1)``, as the family's checkpoints keep them; 0: rotate-half)
* ``causal`` (0)
* ``prenorm`` / ``residual_scale`` / ``eps`` — the residual branch in
  one layer (``sequence.Branch``); ``eps`` is the latents' norms' too;
  ``init_sigma`` for every matrix
* a second input: the net's ``(N, T)`` token ids, from which documents
  (the mask, the positions' restarts) are read

Parameters (tags), every matrix ``(out, in)`` and without bias: ``wqa``
(q_rank, D), ``q_norm`` (q_rank), ``wqb`` (nhead (nope_dim + rope_dim),
q_rank) — a head's rows are its ``nope_dim`` then its ``rope_dim``;
``wkva`` (kv_rank + rope_dim, D) — the latent's rows, then the shared
rotary key's; ``kv_norm`` (kv_rank); ``wkvb`` (nhead (nope_dim + v_dim),
kv_rank) — a head's rows are its keys' ``nope_dim`` then its ``v_dim``;
``wproj`` (D, nhead v_dim); ``norm`` (D) with ``prenorm``.  With ``q_rank
= 0`` the one ``wq`` (nhead (nope_dim + rope_dim), D) stands in the place
of ``wqa``, ``q_norm`` and ``wqb`` (a parameter tree that still brings one
of the three is refused); with ``out_gate = head`` there is ``wgate``
(nhead, D).  All float32 at rest, cast where used.

State (``aux``, carried through the step programs and read once a
round by ``NetTrainer.count_layer_state``, as ``gated_deltanet``'s):
``attn_tokens`` — tokens through ``core``; ``attn_tokens_flash`` — those
of them the flash kernels computed, which the branch that ran says for
itself; ``attn_blocks`` / ``attn_blocks_unmasked`` — the blocks the
forward kernel then visits over all heads, and those of them whose
every pair may attend; ``attn_tokens_bwd_fused`` — the tokens whose
backward is the one kernel.  uint32, wrapping; the round's counters of
the same names sum them over the layers (``attention``'s masked path
counts into the same five).

Scopes inside the layer's: ``q_proj``, ``kv_proj``, ``rotary``, ``core``
(scores, mask, softmax, values — forward and backward; on a TPU the
kernels ``flash_fwd`` and ``flash_bwd``, once each a layer a step — or
``flash_dq`` + ``flash_dkv`` where a row does not fit the one kernel's
VMEM budget, ``ops/flash.py`` — and the layout changes around them),
``gate`` (with ``out_gate = head``: the gates' product, the sigmoid and
the multiplication), ``out_proj``.
"""

from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp

from ..ops.attention import doc_positions, rotary
from ..ops.ssd import doc_index
from .base import Layer, Params, Shape, register
from .sequence import (ATTN_COUNTERS, Branch, _check_ids_input,
                       attend_counted, count_attention, rms_norm)


@register
class LatentAttentionLayer(Layer, Branch):
    type_name = "latent_attention"
    #: state leaf -> the round's counter it is added to
    aux_counters = {name: name for name in ATTN_COUNTERS}
    f32_tags = frozenset({"wqa", "q_norm", "wqb", "wq", "wkva", "kv_norm",
                          "wkvb", "wgate", "wproj", "norm", "postnorm"})

    #: every one must be set positive; ``q_rank`` must be set, and may be 0
    _INT_KEYS = ("nhead", "kv_rank", "nope_dim", "rope_dim", "v_dim")
    #: the query latent's leaves, absent with ``q_rank = 0``
    _Q_LATENT = ("wqa", "q_norm", "wqb")

    def __init__(self) -> None:
        super().__init__()
        for k in self._INT_KEYS:
            setattr(self, k, 0)
        self.q_rank = -1
        self.out_gate = "none"
        self.rope_theta = 10000.0
        self.rope_interleave = 1
        self.causal = 0

    def set_param(self, name, val):
        if name in self._INT_KEYS or name in ("q_rank", "rope_interleave",
                                              "causal"):
            setattr(self, name, int(val))
        elif name == "out_gate":
            if val not in ("none", "head"):
                raise ValueError(
                    f"latent_attention: out_gate is none or head, got {val!r}")
            self.out_gate = val
        elif name == "rope_theta":
            self.rope_theta = float(val)
        elif not self.set_branch_param(name, val):
            super().set_param(name, val)

    def infer_shape(self, in_shapes: Sequence[Shape]) -> List[Shape]:
        _check_ids_input("latent_attention", in_shapes)
        if len(in_shapes[0]) != 3:
            raise ValueError("latent_attention: input must be a sequence "
                             "node (N, T, D)")
        if min(getattr(self, k) for k in self._INT_KEYS) <= 0 or (
                self.q_rank < 0):
            raise ValueError("latent_attention: set " + ", ".join(
                self._INT_KEYS) + " and q_rank (0: no query latent)")
        if self.rope_dim % 2:
            raise ValueError(
                f"latent_attention: rope_dim={self.rope_dim} must be even")
        return [tuple(in_shapes[0])]

    def init_params(self, key, in_shapes) -> Params:
        d = in_shapes[0][2]
        h, dn, dr, dv = self.nhead, self.nope_dim, self.rope_dim, self.v_dim
        ks = jax.random.split(key, 5)
        sigma = self.param.init_sigma

        def normal(k, shape):
            return jax.random.normal(k, shape, jnp.float32) * sigma

        out = {
            "wqa": normal(ks[0], (self.q_rank, d)),
            "q_norm": jnp.ones((self.q_rank,), jnp.float32),
            "wqb": normal(ks[1], (h * (dn + dr), self.q_rank)),
        } if self.q_rank else {"wq": normal(ks[0], (h * (dn + dr), d))}
        out.update({
            "wkva": normal(ks[2], (self.kv_rank + dr, d)),
            "kv_norm": jnp.ones((self.kv_rank,), jnp.float32),
            "wkvb": normal(ks[3], (h * (dn + dv), self.kv_rank)),
            "wproj": normal(ks[4], (d, h * dv)),
        })
        if self.out_gate == "head":
            out["wgate"] = normal(jax.random.fold_in(key, 5), (h, d))
        out.update(self.branch_params(d))
        return out

    def init_aux(self, in_shapes):
        return {name: jnp.zeros((), jnp.uint32) for name in ATTN_COUNTERS}

    def apply(self, params, inputs, *, train=False, rng=None, step=None):
        return self._run(params, inputs)[0]

    def apply_stateful(self, params, aux, inputs, *, train=False, rng=None,
                       step=None):
        outs, ran = self._run(params, inputs)
        return outs, count_attention(aux, inputs[0], ran)

    def _run(self, params, inputs):
        """``([out], what ran in ``core``, as ``attend_counted`` says)``."""
        x0 = inputs[0]
        n, t, _ = x0.shape
        h, dn, dr, dv = self.nhead, self.nope_dim, self.rope_dim, self.v_dim
        cdt = x0.dtype
        doc = doc_index(inputs[1]) if len(inputs) > 1 else None
        u = self.branch_in(params, x0)
        if not self.q_rank and any(k in params for k in self._Q_LATENT):
            raise ValueError(
                "latent_attention: q_rank = 0 has the one query matrix wq; "
                "the parameters bring " + ", ".join(
                    k for k in self._Q_LATENT if k in params))
        with jax.named_scope("q_proj"):
            if self.q_rank:
                cq = rms_norm(u @ params["wqa"].astype(cdt).T,
                              params["q_norm"], self.eps)
                q = cq @ params["wqb"].astype(cdt).T
            else:
                q = u @ params["wq"].astype(cdt).T
            q = q.reshape(n, t, h, dn + dr)
        with jax.named_scope("kv_proj"):
            ckv = u @ params["wkva"].astype(cdt).T
            k_rope = ckv[..., self.kv_rank:].reshape(n, t, 1, dr)
            kv = (rms_norm(ckv[..., :self.kv_rank], params["kv_norm"],
                           self.eps)
                  @ params["wkvb"].astype(cdt).T).reshape(n, t, h, dn + dv)
        with jax.named_scope("rotary"):
            pos = doc_positions(doc, n, t)
            turn = bool(self.rope_interleave)
            q = jnp.concatenate(
                [q[..., :dn], rotary(q[..., dn:], pos, dr, self.rope_theta,
                                     turn)], axis=-1)
            # the ONE rotary key head, for every query head
            k = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(
                    rotary(k_rope, pos, dr, self.rope_theta, turn),
                    (n, t, h, dr))], axis=-1)
        o, ran = attend_counted("core", q, k, kv[..., dn:],
                                causal=bool(self.causal), doc=doc)
        if self.out_gate == "head":
            with jax.named_scope("gate"):
                o = o * jax.nn.sigmoid(
                    u @ params["wgate"].astype(cdt).T)[..., None]
        with jax.named_scope("out_proj"):
            out = o.reshape(n, t, h * dv) @ params["wproj"].astype(cdt).T
        return [self.branch_out(params, x0, out)], ran
