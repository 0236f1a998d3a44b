"""The plain reference of the ``bailing_hybrid`` family (inclusionAI,
Ling-3.0): layers in periods, every layer of a period a Kimi Delta
Attention mixer but the last, which is a multi-head latent attention
with no query latent and a head-wise output gate; a leading dense SwiGLU
layer, then group-limited sigmoid-routed SwiGLU experts chosen by score
+ bias with one ungated shared expert; an untied head; under adam.
Named by ``configs/ling_3_0_flash.json``.

Plain ``jax.numpy`` in float32 at ``highest`` matmul precision, written
from the published description (Kimi Team 2025, "Kimi Linear",
arXiv:2510.26692, section 3 for the delta rule with a decay a channel;
DeepSeek-AI 2024, "DeepSeek-V2", section 2.1 for latent attention;
"DeepSeek-V3 Technical Report", section 2.1.2 for the router, its groups
and its selection bias; the model's ``config.json`` for the widths and
the gate's bound; Shazeer 2020 for the gated experts; Su et al. 2021 for
the rotation; Zhang & Sennrich 2019 for rms norm; Kingma & Ba 2014 for
adam) with its own parse of the conf text.  It imports nothing of the
program, nothing of ``benchmarks/lib`` and nothing of the other
references: what it has in common with them (the conf grammar, the
weights from the seed, adam, the packed rows) is written out here again.

* Kimi Delta Attention, a head of ``H`` (``kimi_delta``)::

      [q | k | v | f | z] = u W_in;   b = u W_beta
      [q | k | v] = silu(conv([q | k | v]))      depthwise, causal, no tap
                                                 before its own document
      q = q / |q| / sqrt(Dk);   k = k / |k|      (1e-6 under the root)
      g = lower_bound * sigmoid(exp(a_log_h) * (f + dt_bias))    (Dk a head)
      S_t = (I - beta_t k_t k_t^T) Diag(e^{g_t}) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t;   S = 0 before a document's first token
      y = rms_norm_Dv(o; gate_norm) * sigmoid(z);   out = y W_out

  the recurrence TOKEN BY TOKEN (``delta_rule``), in segments of
  ``SCAN_SEGMENT`` tokens under ``jax.checkpoint`` so that its backward
  fits.
* Latent attention is the **expanded** form: a head's ``[q_nope |
  q_rope] = u W_q`` (``q_rank = 0``: no latent, no norm) or, with a
  rank, through ``rms_norm(u W_qa) W_qb``; ``[c_kv | k_rope] = u W_kva``
  with ONE rotary key head for all query heads, a head's ``[k_nope | v]
  = rms_norm(c_kv) W_kvb``; the rotation on the pairs ``(2i, 2i+1)`` at
  ``pos * theta^(-2i/dim)``, ``pos`` counted from a document's first
  token; scores over ``sqrt(nope + rope)``, causal, own document only, a
  block of rows at a time; with ``out_gate = head`` a head's output
  times ``sigmoid(u . w_gate_h)`` before ``W_o``.
* The router scores every expert alone (``sigmoid``) and adds the
  selection bias FOR THE CHOICE; with ``n_group`` groups of consecutive
  experts a group's score is the sum of its 2 largest, the token keeps
  its ``topk_group`` best groups, and its ``topk`` are the largest among
  the kept groups' experts (the lower id first where two are equal); the
  weights are the UNBIASED scores of the chosen over their sum (+ 1e-20)
  times ``routed_scale``.  The bias's gradient is exactly zero and adam
  leaves it at the seed's draw.
* The experts are a **dense loop over the experts held**, the shared
  expert added ungated, the share and its constant routing weights as
  ``joyai_llm_flash.py`` has them.
* Every conf layer is one ``jax.checkpoint``; ``train_chunk`` donates
  the weights it is handed and leaves its results on the device.

For the roofline readers: ``scan_flops(net)`` / ``scan_min_bytes(net)``
(the delta rule itself over one step's tokens, heads and ``kimi_delta``
layers), ``expert_flops`` / ``expert_min_bytes`` (the grouped products)
and ``mla_core_flops(net, pairs)`` (the score and value products of the
latent-attention layers).
"""

from __future__ import annotations

import math
from typing import Dict, List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

ROW_BLOCK = 128      # rows of a score matrix or the head at a time
SCAN_SEGMENT = 128   # tokens of the recurrence under one checkpoint
SEP_ID = 0           # a document begins after every separator
BIAS_SIGMA = 0.01    # the selection bias from the seed: normal at this

ONES = ("norm", "q_norm", "kv_norm", "gate_norm")


class Net(NamedTuple):
    layers: List[dict]
    glob: Dict[str, str]
    pshapes: Dict[int, Dict[str, tuple]]
    batch: int
    seq: int
    hidden: int


# ----------------------------------------------------------------------
def parse(text: str):
    """(layers in conf order, global keys)."""
    layers, glob, top, inside = [], {}, "0", False
    for raw in text.splitlines():
        line = raw.split("#", 1)[0].strip()
        if "=" not in line:
            continue
        k, v = (t.strip() for t in line.split("=", 1))
        if k == "netconfig":
            inside = v == "start"
        elif inside and k.startswith("layer["):
            body = k[len("layer["):-1]
            if body.startswith("+"):
                ins, out = [top], body.split(":", 1)[1]
            else:
                src, out = body.split("->")
                ins = ["0" if n == "in" else n for n in src.split(",")]
            kind, _, name = v.partition(":")
            layers.append({"index": len(layers), "type": kind, "name": name,
                           "ins": ins, "out": out, "cfg": {}})
            top = out
        elif inside and layers:
            layers[-1]["cfg"][k] = v
        else:
            glob[k] = v
    return layers, glob


def _mla_dims(cfg):
    """(heads, q rank, kv rank, no-position width, rotary width, value
    width)."""
    return tuple(int(cfg[k]) for k in (
        "nhead", "q_rank", "kv_rank", "nope_dim", "rope_dim", "v_dim"))


def _kda_dims(cfg):
    """(heads, key width, value width, conv taps)."""
    return (int(cfg["nhead"]), int(cfg["key_dim"]), int(cfg["value_dim"]),
            int(cfg.get("conv_width", 4)))


def _moe_dims(cfg):
    """(experts routed, top-k, first held, held, width, shared width)."""
    e, first = int(cfg["nexpert"]), int(cfg.get("first_expert", 0))
    return (e, int(cfg["topk"]), first, int(cfg.get("nheld", e - first)),
            int(cfg["nhidden"]), int(cfg.get("shared_hidden", 0)))


def describe(net_text: str, batch: int) -> Net:
    layers, glob = parse(net_text)
    seq = int(glob["input_shape"].split(",")[2])
    width = {"0": None}
    pshapes: Dict[int, Dict[str, tuple]] = {}
    hidden = 0
    for lay in layers:
        t, cfg, d = lay["type"], lay["cfg"], width[lay["ins"][0]]
        shp: Dict[str, tuple] = {}
        if t == "embedding":
            d = hidden = int(cfg["nhidden"])
            shp = {"wmat": (int(cfg["nvocab"]), d)}
        elif t == "kimi_delta":
            h, dk, dv, taps = _kda_dims(cfg)
            ek, ev = h * dk, h * dv
            shp = {"wmat": (3 * ek + 2 * ev, d), "wbeta": (h, d),
                   "conv": (2 * ek + ev, taps), "a_log": (h,),
                   "dt_bias": (ek,), "gate_norm": (dv,), "wproj": (d, ev)}
        elif t == "latent_attention":
            h, rq, rkv, dn, dr, dv = _mla_dims(cfg)
            shp = {"wkva": (rkv + dr, d), "kv_norm": (rkv,),
                   "wkvb": (h * (dn + dv), rkv), "wproj": (d, h * dv)}
            if rq:
                shp.update({"wqa": (rq, d), "q_norm": (rq,),
                            "wqb": (h * (dn + dr), rq)})
            else:
                shp["wq"] = (h * (dn + dr), d)
            if cfg.get("out_gate", "none") == "head":
                shp["wgate"] = (h, d)
        elif t == "gated_mlp":
            f = int(cfg["nhidden"])
            shp = {"wmat": (2 * f, d), "wproj": (d, f)}
        elif t == "routed_experts":
            e, _, _, g, f, sh = _moe_dims(cfg)
            shp = {"wgate": (e, d), "wmat": (g, d, 2 * f),
                   "wproj": (g, f, d)}
            if sh:
                shp.update({"shared_wmat": (2 * sh, d),
                            "shared_wproj": (d, sh)})
                if int(cfg.get("shared_gate", 1)):
                    shp["shared_gate"] = (1, d)
            if int(cfg.get("select_bias", 0)):
                shp["score_bias"] = (e,)
        elif t == "rms_norm":
            shp = {"wmat": (d,)}
        elif t == "lm_head":
            shp = {"wmat": (int(cfg["nhidden"]), d)}
            d = int(cfg["nhidden"])
        elif t != "softmax":
            raise ValueError(f"bailing_hybrid: no layer type {t!r}")
        if int(cfg.get("prenorm", 0)):
            shp["norm"] = (width[lay["ins"][0]],)
        if shp:
            pshapes[lay["index"]] = shp
        width[lay["out"]] = d
    return Net(layers, glob, pshapes, int(batch), seq, hidden)


# ----------------------------------------------------------------------
def make_weights(net: Net, seed: int):
    """Every leaf from the seed in one jitted call (the configuration's
    ``assumed.init``): matrices normal at 0.02, the routers among them;
    the EMBEDDING normal at 1 (``joyai_llm_flash.py`` has why); ``a_log``
    the log of a uniform draw in [1, 16]; ``dt_bias`` uniform in [-0.5,
    0), so that every head's gate starts inside its range and the decays
    e^g span it (under the scalar rule's draw, -6.9 to -2.3 times a rate
    of up to 16, most heads' sigmoid starts saturated at 0: no decay, and
    a program that dropped the decay would be nearly ``correct``); the
    convolution uniform at 1/sqrt(width); the norms 1; a router's
    selection bias normal at ``BIAS_SIGMA`` — wide enough to change the
    chosen experts of a good part of the tokens at these weights, so
    that a program that drops it is not ``correct``; any other vector
    0."""
    kinds = {lay["index"]: lay["type"] for lay in net.layers}

    @jax.jit
    def make(key):
        out = {}
        for i, tags in net.pshapes.items():
            out[i] = {}
            for n, (tag, shp) in enumerate(sorted(tags.items())):
                k = jax.random.fold_in(jax.random.fold_in(key, i), n)
                if tag == "score_bias":
                    w = jax.random.normal(k, shp, jnp.float32) * BIAS_SIGMA
                elif kinds[i] == "embedding":
                    w = jax.random.normal(k, shp, jnp.float32)
                elif tag == "a_log":
                    w = jnp.log(jax.random.uniform(k, shp, jnp.float32,
                                                   1.0, 16.0))
                elif tag == "dt_bias":
                    w = jax.random.uniform(k, shp, jnp.float32, -0.5, 0.0)
                elif tag == "conv":
                    b = 1.0 / math.sqrt(shp[1])
                    w = jax.random.uniform(k, shp, jnp.float32, -b, b)
                elif tag in ONES or (tag == "wmat" and len(shp) == 1):
                    w = jnp.ones(shp, jnp.float32)
                elif len(shp) == 1:
                    w = jnp.zeros(shp, jnp.float32)
                else:
                    w = jax.random.normal(k, shp, jnp.float32) * 0.02
                out[i][tag] = w
        return out

    return make(jax.random.PRNGKey(seed))


# ----------------------------------------------------------------------
def _q(x, quant):
    """An operand of a matrix product, rounded for the control."""
    return x if quant is None else x.astype(quant).astype(jnp.float32)


def _mm(x, w, quant):
    """``x W^T`` for a matrix kept ``(out, in)``."""
    return _q(x, quant) @ _q(w, quant).T


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def doc_starts(ids):
    """(B, T) bool: a row's first token, and every token that follows a
    separator, begins a document."""
    return jnp.concatenate(
        [jnp.ones_like(ids[:, :1], bool), ids[:, :-1] == SEP_ID], axis=1)


def _starts(ids, n, t):
    """``doc_starts``, or a row's first token alone without ids."""
    if ids is not None:
        return doc_starts(ids)
    return jnp.broadcast_to(jnp.arange(t)[None] == 0, (n, t))


def _row_blocks(fn, x, *more):
    """``fn`` over blocks of rows (axis 1) of ``x`` and of every array
    in ``more``, each block under ``jax.checkpoint``."""
    t = x.shape[1]
    nb = t // ROW_BLOCK if t % ROW_BLOCK == 0 and t > ROW_BLOCK else 1
    if nb == 1:
        return fn(x, *more)
    cut = lambda a: jnp.moveaxis(  # noqa: E731
        a.reshape((a.shape[0], nb, t // nb) + a.shape[2:]), 1, 0)
    out = lax.map(lambda a: jax.checkpoint(fn)(*a),
                  tuple(cut(a) for a in (x,) + more))
    return jnp.moveaxis(out, 0, 1).reshape((x.shape[0], t) + out.shape[3:])


def rotate(x, pos, theta, interleave=True):
    """Rotary positions on the whole last axis of ``x (B, T, H, dim)``
    at the angles ``pos * theta^(-2i/dim)``: ``interleave`` turns the
    pairs ``(x[2i], x[2i+1])``, else the pairs ``(x[i], x[i + dim/2])``
    (rotate-half)."""
    dim = x.shape[-1]
    half = dim // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dim)
    ang = pos.astype(jnp.float32)[..., None] * freq
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    if interleave:
        pairs = x.reshape(x.shape[:-1] + (half, 2))
        x1, x2 = pairs[..., 0], pairs[..., 1]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                         axis=-1).reshape(x.shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def delta_rule(q, k, v, g, b, start):
    """The delta rule with a decay a key channel, a token a step.
    ``q``/``k (B,T,H,Dk)``, ``v (B,T,H,Dv)``, ``g (B,T,H,Dk)`` (the log
    of the decay, never positive), ``b (B,T,H)`` (write strength),
    ``start (B,T)`` bool -> ``o (B,T,H,Dv)``."""
    n, t, h, dk = q.shape
    seg = next(s for s in range(min(SCAN_SEGMENT, t), 0, -1) if t % s == 0)

    def token(state, inp):
        qt, kt, vt, gt, bt, st = inp
        keep = jnp.where(st[:, None, None], 0.0, jnp.exp(gt))     # (B,H,Dk)
        state = keep[..., None] * state
        err = vt - jnp.einsum("bhkv,bhk->bhv", state, kt)
        state = state + kt[..., :, None] * (bt[..., None] * err)[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt)

    @jax.checkpoint
    def segment(state, inp):
        return lax.scan(token, state, inp)

    cut = lambda x: jnp.moveaxis(x, 1, 0).reshape(  # noqa: E731
        (t // seg, seg) + x.shape[:1] + x.shape[2:])
    _, o = lax.scan(segment, jnp.zeros((n, h, dk, v.shape[-1]), jnp.float32),
                    tuple(cut(x) for x in (q, k, v, g, b, start)))
    return jnp.moveaxis(o.reshape((t,) + o.shape[2:]), 0, 1)


def _conv(x, w, start):
    """Depthwise causal convolution, no bias; a tap that reaches before
    its token's document reads zero: tap j is live while no document
    began at any of the j tokens up to and including this one."""
    n, t, _ = x.shape
    k = w.shape[1]
    live = jnp.ones((n, t), bool)
    y = x * w[:, k - 1]
    for j in range(1, min(k, t)):
        live = live & ~jnp.pad(start, ((0, 0), (j - 1, 0)))[:, :t]
        past = jnp.pad(x, ((0, 0), (j, 0), (0, 0)))[:, :t]
        y = y + jnp.where(live[..., None], past, 0.0) * w[:, k - 1 - j]
    return y


def _unit(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def kimi_delta(p, u, ids, cfg, quant=None):
    """The mixer alone (no branch): ``u (B, T, D)`` the normed input,
    ``ids (B, T)`` or ``None`` (one document a row).  The gate is
    float32 always: the control rounds the products' operands, not it."""
    n, t, _ = u.shape
    h, dk, dv, _ = _kda_dims(cfg)
    ek, ev = h * dk, h * dv
    start = _starts(ids, n, t)
    mixed = _mm(u, p["wmat"], quant)
    qkv = silu(_conv(mixed[..., :2 * ek + ev], p["conv"], start))
    f = mixed[..., 2 * ek + ev:3 * ek + ev].reshape(n, t, h, dk)
    z = mixed[..., 3 * ek + ev:].reshape(n, t, h, dv)
    q = _unit(qkv[..., :ek].reshape(n, t, h, dk)) / math.sqrt(dk)
    k = _unit(qkv[..., ek:2 * ek].reshape(n, t, h, dk))
    v = qkv[..., 2 * ek:].reshape(n, t, h, dv)
    g = float(cfg.get("lower_bound", -5.0)) * jax.nn.sigmoid(
        jnp.exp(p["a_log"])[:, None] * (f + p["dt_bias"].reshape(h, dk)))
    beta = jax.nn.sigmoid(_mm(u, p["wbeta"], quant))
    o = delta_rule(_q(q, quant), _q(k, quant), _q(v, quant), g, beta, start)
    y = rms_norm(o, p["gate_norm"], float(cfg.get("eps", 1e-5))) \
        * jax.nn.sigmoid(z)
    return _mm(y.reshape(n, t, ev), p["wproj"], quant)


def latent_attention(p, u, ids, cfg, quant=None):
    """The mixer alone (no branch): ``u (B, T, D)`` the normed input,
    ``ids (B, T)`` or ``None`` (one document a row)."""
    n, t, _ = u.shape
    h, rq, rkv, dn, dr, dv = _mla_dims(cfg)
    eps = float(cfg.get("eps", 1e-5))
    theta = float(cfg.get("rope_theta", 10000.0))
    pairs = bool(int(cfg.get("rope_interleave", 1)))
    start = _starts(ids, n, t)
    doc = jnp.cumsum(start, axis=1)
    pos = jnp.broadcast_to(jnp.arange(t)[None], (n, t))
    # a position is counted from its document's first token
    rel = pos - lax.cummax(jnp.where(start, pos, 0), axis=1)

    if rq:
        q = _mm(rms_norm(_mm(u, p["wqa"], quant), p["q_norm"], eps),
                p["wqb"], quant)
    else:
        q = _mm(u, p["wq"], quant)
    q = q.reshape(n, t, h, dn + dr)
    ckv = _mm(u, p["wkva"], quant)
    kv = _mm(rms_norm(ckv[..., :rkv], p["kv_norm"], eps), p["wkvb"],
             quant).reshape(n, t, h, dn + dv)
    q_nope, q_rope = q[..., :dn], rotate(q[..., dn:], rel, theta, pairs)
    # ONE rotary key head, shared by every query head
    k_rope = rotate(ckv[..., rkv:][:, :, None], rel, theta, pairs)[:, :, 0]
    k_nope, v = kv[..., :dn], kv[..., dn:]
    scale = 1.0 / math.sqrt(dn + dr)

    def rows(qn, qr, posb, docb):
        sc = (jnp.einsum("bqhd,bkhd->bhqk", _q(qn, quant), _q(k_nope, quant))
              + jnp.einsum("bqhd,bkd->bhqk", _q(qr, quant),
                           _q(k_rope, quant))) * scale
        seen = docb[:, :, None] == doc[:, None, :]
        if int(cfg.get("causal", 0)):
            seen = seen & (posb[:, :, None] >= pos[:, None, :])
        sc = jnp.where(seen[:, None], sc, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          _q(jax.nn.softmax(sc, axis=-1), quant),
                          _q(v, quant))

    o = _row_blocks(rows, q_nope, q_rope, pos, doc)
    if cfg.get("out_gate", "none") == "head":
        # one scalar a head and token
        o = o * jax.nn.sigmoid(_mm(u, p["wgate"], quant))[..., None]
    return _mm(o.reshape(n, t, h * dv), p["wproj"], quant)


def router(p, x, cfg):
    """``x (M, D)`` -> (weights ``(M, k)``, expert ids ``(M, k)``): every
    expert's score, the ``topk`` largest of score + bias (the lower id
    first where two are equal) among the experts of the token's
    ``topk_group`` best of ``n_group`` groups, the weights the unbiased
    scores of the chosen over their sum, times ``routed_scale``;
    constants of the backward pass in a share.  Always float32 at the
    highest precision, the bias too: the control rounds them not."""
    e, topk, _, g, _, _ = _moe_dims(cfg)
    logits = x @ p["wgate"].T
    s = (jax.nn.sigmoid(logits) if cfg.get("score_func") == "sigmoid"
         else jax.nn.softmax(logits, axis=-1))
    chosen_by = lax.stop_gradient(
        s + p["score_bias"] if int(cfg.get("select_bias", 0)) else s)
    groups = int(cfg.get("n_group", 1))
    if groups > 1:
        per = e // groups
        in_groups = chosen_by.reshape(-1, groups, per)
        best2 = jnp.sort(in_groups, axis=-1)[..., -min(2, per):].sum(axis=-1)
        # a group is kept if fewer than topk_group groups beat it (a
        # lower id wins a tie)
        ahead = (best2[:, None, :] > best2[:, :, None]) | (
            (best2[:, None, :] == best2[:, :, None])
            & (jnp.arange(groups)[None, :] < jnp.arange(groups)[:, None]))
        kept = ahead.sum(axis=-1) < int(cfg.get("topk_group", 1))
        chosen_by = jnp.where(jnp.repeat(kept, per, axis=1), chosen_by,
                              -jnp.inf)
    _, idx = lax.top_k(chosen_by, topk)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if int(cfg.get("norm_topk", 1)):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * float(cfg.get("routed_scale", 1.0))
    # a share: the weights' cotangent needs the other ranks' terms
    return (lax.stop_gradient(w) if g < e else w), idx


def _swiglu(x, wmat, wproj, quant):
    f = wmat.shape[0] // 2
    gu = _mm(x, wmat, quant)
    return _mm(silu(gu[..., :f]) * gu[..., f:], wproj, quant)


def routed_experts(p, u, cfg, quant=None):
    """The expert layer alone (no branch) on the normed input ``u``."""
    _, _, first, g, _, sh = _moe_dims(cfg)
    x = u.reshape(-1, u.shape[-1])
    w, idx = router(p, x, cfg)

    @jax.checkpoint
    def one(y, ew):
        e, wmat, wproj = ew
        # the router's weight for expert e a token, or 0: dense, masked
        mask = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        # a held expert's matrices are (in, out)
        return y + mask[:, None] * _swiglu(x, wmat.T, wproj.T, quant), None

    y, _ = lax.scan(one, jnp.zeros_like(x),
                    (first + jnp.arange(g), p["wmat"], p["wproj"]))
    if sh:
        s = _swiglu(x, p["shared_wmat"], p["shared_wproj"], quant)
        if int(cfg.get("shared_gate", 1)):
            s = jax.nn.sigmoid(x @ p["shared_gate"].T) * s
        y = y + s
    return y.reshape(u.shape)


def loss_fn(net: Net, quant=None):
    def apply(lay, p, xs, ids):
        t, cfg, x = lay["type"], lay["cfg"], xs[0]
        if t == "embedding":
            return p["wmat"][x]
        if t == "rms_norm":
            return rms_norm(x, p["wmat"], float(cfg.get("eps", 1e-5)))
        u = x
        if int(cfg.get("prenorm", 0)):
            u = rms_norm(x, p["norm"], float(cfg.get("eps", 1e-5)))
        if t == "latent_attention":
            y = latent_attention(p, u, ids if len(xs) > 1 else None, cfg,
                                 quant)
        elif t == "kimi_delta":
            y = kimi_delta(p, u, ids if len(xs) > 1 else None, cfg, quant)
        elif t == "gated_mlp":
            y = _swiglu(u, p["wmat"], p["wproj"], quant)
        else:
            y = routed_experts(p, u, cfg, quant)
        r = float(cfg.get("residual_scale", 0.0))
        return x + r * y if r else y

    def loss(params, ids, labels):
        nodes = {"0": ids}
        head = total = None
        for lay in net.layers:
            xs = [nodes[n] for n in lay["ins"]]
            p = params.get(lay["index"], {})
            if lay["type"] == "lm_head":
                # the logits are formed where the loss reads them, a
                # block of rows at a time
                head = (p, xs[0])
                nodes[lay["out"]] = None
            elif lay["type"] == "softmax":
                table, x = head

                def rows(xb, lb):
                    logp = jax.nn.log_softmax(
                        _mm(xb, table["wmat"], quant), axis=-1)
                    return -jnp.take_along_axis(
                        logp, lb[..., None], axis=-1)[..., 0]

                total = (float(lay["cfg"].get("grad_scale", 1.0))
                         / ids.shape[0]
                         * jnp.sum(_row_blocks(rows, x, labels)))
            else:
                run = jax.checkpoint(
                    lambda p, xs, lay=lay: apply(lay, p, xs, ids))
                nodes[lay["out"]] = run(p, xs)
        if total is None:
            raise ValueError("bailing_hybrid: the net has no softmax")
        return total

    return loss


_ON_DEVICE: list = []  # the trees the last call returned, still there


def _last_results_to_host() -> None:
    """Fetch what the last ``train_chunk`` returned, in the dicts the
    caller holds: the chip needs its room for the next chunk."""
    for tree in _ON_DEVICE:
        for leaves in tree.values():
            for t in leaves:
                leaves[t] = np.asarray(leaves[t])
    _ON_DEVICE.clear()


def _int_rows(net: Net, a):
    k = int(np.shape(a)[0])
    return np.asarray(a).reshape(k, net.batch, net.seq).round().astype(
        np.int32)


def train_chunk(net: Net, weights, data, labels, key, control=None):
    """Follow one chunk of ``data`` and ``labels`` [K, B, T].  Returns
    (losses [K], params after, adam's first
    moment after); the two trees are left on the device (``np.asarray``
    of a leaf fetches it) and fetched whole when the next chunk is
    followed.  ``weights`` are donated.  ``control``: True for the step
    below the bfloat16 the configuration states (the matrix products,
    attention's q, k, v and probabilities and the experts' products on
    operands rounded to ``float8_e4m3fn``; the router, its bias and the
    delta rule's gate stay float32), or a type's name."""
    del key  # nothing here is random
    _last_results_to_host()
    glob = net.glob
    if glob.get("updater") != "adam" or glob.get("lr:schedule",
                                                  "constant") != "constant":
        raise ValueError("bailing_hybrid: adam at a constant rate only")
    quant = None
    if control is not None:
        quant = (jnp.float8_e4m3fn if control is True
                 else getattr(jnp, control))
    d1, d2 = float(glob.get("beta1", 0.1)), float(glob.get("beta2", 0.001))
    tags = {t for tg in net.pshapes.values() for t in tg}
    base_lr = float(glob.get("eta", glob.get("lr", 0.01)))
    lr = {t: float(glob.get(f"{t}:lr", glob.get(f"{t}:eta", base_lr)))
          for t in tags}
    wd = {t: float(glob.get(f"{t}:wd", glob.get("wd", 0.0))) for t in tags}
    loss = loss_fn(net, quant)

    def step(params, m1, m2, ids, lab, epoch):
        l, grads = jax.value_and_grad(loss)(params, ids, lab)
        fix1 = 1.0 - (1.0 - d1) ** (epoch + 1.0)
        fix2 = 1.0 - (1.0 - d2) ** (epoch + 1.0)
        new = ({}, {}, {})
        for i, leaves in params.items():
            for part in new:
                part[i] = {}
            for t, w in leaves.items():
                g = grads[i][t] - wd[t] * w
                a = m1[i][t] + d1 * (g - m1[i][t])
                b = m2[i][t] + d2 * (g * g - m2[i][t])
                new[0][i][t] = w - lr[t] * jnp.sqrt(fix2) / fix1 * (
                    a / (jnp.sqrt(b) + 1e-8))
                new[1][i][t], new[2][i][t] = a, b
        return new + (l,)

    step = jax.jit(step, donate_argnums=(0, 1, 2))
    ids, lab = _int_rows(net, data), _int_rows(net, labels)
    params = weights
    m1 = jax.tree_util.tree_map(jnp.zeros_like, params)
    m2 = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses = []
    with jax.default_matmul_precision("highest"):
        for i in range(ids.shape[0]):
            params, m1, m2, l = step(params, m1, m2, ids[i], lab[i],
                                     jnp.float32(i))
            losses.append(l)
    losses = np.asarray(jax.device_get(jnp.stack(losses)), np.float64)
    del m2
    _ON_DEVICE[:] = [params, m1]
    return losses, params, m1


def program_update_state(ustates):
    """Adam's first moment, ``m1``: a running mean of the gradients as
    the optimizer got them.  The second moment is dropped from the
    state handed in: a quarter of it, which no comparison reads."""
    out = {}
    for i, tags in ustates.items():
        out[i] = {}
        for t, state in tags.items():
            state.pop("m2", None)
            out[i][t] = state["m1"]
    return out


def seeded_chunk(net: Net, seed: int, scan: int):
    """For ``tools/limits.py``, which has no feed: ``scan`` batches of
    packed rows as the cell's mix makes them — documents of log-normal
    length (median 1024, sigma 1.2, clipped to 16..seq) of ids uniform
    over 1..V-1, a separator 0 after each, cut at a row's end; a row's
    labels are the stream moved on by one."""
    vocab = next(int(lay["cfg"]["nvocab"]) for lay in net.layers
                 if lay["type"] == "embedding")
    rng = np.random.RandomState(seed % 2147483629)
    need = scan * net.batch * net.seq + 1
    parts, have = [], 0
    while have < need:
        n = int(np.clip(np.round(np.exp(
            rng.normal(math.log(min(1024, net.seq)), 1.2))),
            min(16, net.seq), net.seq))
        parts.append(rng.randint(1, vocab, n - 1))
        parts.append(np.zeros(1, np.int64))
        have += n
    stream = np.concatenate(parts)[:need]
    rows = stream[:-1].reshape(scan, net.batch, net.seq)
    nxt = stream[1:].reshape(scan, net.batch, net.seq)
    return rows.astype(np.float32), nxt.astype(np.float32)


# ----------------------------------------------------------------------
# what a step needs, from the shapes alone
def _tokens(net: Net) -> float:
    return float(net.batch * net.seq)


def _of(net: Net, kind: str):
    return [lay for lay in net.layers if lay["type"] == kind]


def expected_pairs(net: Net) -> float:
    """The (token, held expert) pairs of one training step, all expert
    layers, under a router that spreads its picks evenly: ``topk *
    nheld / nexpert`` a token (0.5 at 8 of 256 with 16 held)."""
    total = 0.0
    for lay in _of(net, "routed_experts"):
        e, topk, _, g, _, _ = _moe_dims(lay["cfg"])
        total += _tokens(net) * topk * g / e
    return total


def _expert_macs_a_pair(net: Net) -> float:
    """Gate, up and down: ``3 D F`` multiply-adds a pair (the layers of
    one net share their widths; the mean where they do not)."""
    lays = _of(net, "routed_experts")
    return sum(3.0 * net.hidden * _moe_dims(lay["cfg"])[4]
               for lay in lays) / max(len(lays), 1)


def expert_flops(net: Net, pairs: float) -> float:
    """Operations of the held experts' products in one training step in
    which ``pairs`` (token, held expert) pairs were routed, all expert
    layers: 2 a multiply-add, 3 for the forward pass and the two
    gradients."""
    return float(pairs) * _expert_macs_a_pair(net) * 2.0 * 3.0


def expert_min_bytes(net: Net, pairs: float, itemsize: int = 2) -> float:
    """The least bytes those products move: the held experts' matrices
    read by the forward pass, read by the backward pass and their
    gradients written (once each way, at ``itemsize``); and a pair's
    rows — ``x`` read and ``y`` written forward, ``dy`` and ``x`` read
    and ``dx`` written backward, ``D`` wide — with the ``F``-wide
    intermediates held on chip."""
    weights = sum(float(np.prod(net.pshapes[lay["index"]][t]))
                  for lay in _of(net, "routed_experts")
                  for t in ("wmat", "wproj"))
    return itemsize * (3.0 * weights + 5.0 * float(pairs) * net.hidden)


def _core_macs_a_pair(net: Net) -> float:
    """Multiply-adds a (query, key) pair, all latent-attention layers:
    a head, the score product over ``nope + rope`` and the value product
    over ``v_dim``."""
    total = 0.0
    for lay in _of(net, "latent_attention"):
        h, _, _, dn, dr, dv = _mla_dims(lay["cfg"])
        total += h * (dn + dr + dv)
    return total


def mla_core_flops(net: Net, pairs: float) -> float:
    """Operations of the score and value products of every
    latent-attention layer in one training step whose rows hold
    ``pairs`` (query, key) pairs a causal query of its own document may
    see: 2 a multiply-add, 3 for the forward pass and the two
    gradients; a recomputed forward does not count, nor do the pairs a
    mask throws away."""
    return float(pairs) * _core_macs_a_pair(net) * 2.0 * 3.0


def scan_flops(net: Net) -> float:
    """Operations of the delta rule itself in one training step, all
    ``kimi_delta`` layers — whatever implements it: a token and head,
    ``Dk Dv`` to decay the state (one factor a key channel, a row of the
    state each: as many multiplications as the scalar decay's), ``2 Dk
    Dv`` to read what it holds for ``k``, ``2 Dk Dv`` for the rank-one
    write and ``2 Dk Dv`` to read ``o`` — ``7 Dk Dv``, as
    ``qwen3_next.py`` counts the scalar rule — times 3 for the forward
    pass and the two gradients.  The gate's ``Dk`` exponentials a token
    and head are not counted."""
    total = 0.0
    for lay in _of(net, "kimi_delta"):
        h, dk, dv, _ = _kda_dims(lay["cfg"])
        total += _tokens(net) * h * 7.0 * dk * dv * 3.0
    return total


def scan_min_bytes(net: Net, itemsize: int = 2) -> float:
    """The least bytes the rule moves in one training step, all
    ``kimi_delta`` layers, with the state held on chip: a token, the
    forward pass reads ``q``, ``k``, ``v`` (``itemsize`` each), the
    decay (``H Dk`` float32) and the write strength (``H`` float32) and
    writes ``o``; the backward reads them and ``do`` again and writes
    the five gradients."""
    total = 0.0
    for lay in _of(net, "kimi_delta"):
        h, dk, dv, _ = _kda_dims(lay["cfg"])
        ins = itemsize * (2 * h * dk + h * dv) + 4 * (h * dk + h)
        out = itemsize * h * dv
        total += _tokens(net) * ((ins + out) + (ins + out) + ins)
    return total


def _forward_macs(net: Net) -> float:
    """Multiply-adds of one forward pass: every matrix once a token (an
    embedding is a gather, the convolution is not counted), a held
    expert's three matrices once a pair at the expected ``topk * nheld /
    nexpert`` pairs a token, the two attention products over the
    positions a causal query may see (the whole row: documents are not
    counted), and the delta rule's own operations halved
    (``scan_flops``)."""
    macs = 0.0
    for lay in net.layers:
        if lay["type"] != "embedding":
            for tag, s in net.pshapes.get(lay["index"], {}).items():
                if len(s) == 2 and tag != "conv":
                    macs += _tokens(net) * s[0] * s[1]
    seen = (net.seq + 1) / 2.0
    macs += _tokens(net) * seen * _core_macs_a_pair(net)
    return (macs + expected_pairs(net) * _expert_macs_a_pair(net)
            + scan_flops(net) / 6.0)


def step_flops(net: Net) -> float:
    """2 a multiply-add, 3 for forward and the two gradients; a
    recomputed forward does not count.  The token cells' convention:
    attention over the positions a causal query may see with documents
    NOT counted (``mla_core_flops`` at a run's own pairs counts them),
    the experts at the EXPECTED pairs (``expected_pairs``), not at a
    run's count."""
    return _forward_macs(net) * 2.0 * 3.0


def step_min_bytes(net: Net) -> float:
    """Every parameter read, its gradient written and read, both
    moments and the weight read and written (8 passes in float32), and
    every layer's output through 5 passes at 2 bytes."""
    params = sum(float(np.prod(s)) for t in net.pshapes.values()
                 for s in t.values())
    widths = 0.0
    for lay in net.layers:
        if lay["type"] == "lm_head":
            widths += int(lay["cfg"]["nhidden"])
        elif lay["type"] != "softmax":
            widths += net.hidden
    return _tokens(net) * widths * 2 * 5.0 + params * 4 * 8.0
