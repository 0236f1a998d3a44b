"""The plain reference of the Nemotron-H family (NVIDIA, ``model_type:
nemotron_h``): a stack in which every layer is ONE of a Mamba-2 mixer
with grouped ``B`` / ``C``, a position-free grouped-query attention, or
a LatentMoE layer (ungated ``relu(.)^2`` experts in a narrow latent
behind a sigmoid router that chooses by score + bias, with one ungated
shared expert on the stream), each alone under its pre-norm and its
residual add; an untied head; a prediction module of depth 1 that
shares the embedding and the head; under adam.  Named by
``configs/nemotron_3_super_120b_a12b.json``.

Plain ``jax.numpy`` in float32 at ``highest`` matmul precision, written
from the published description (the model's ``config.json`` for the
widths and the pattern; NVIDIA 2025, "Nemotron-H", arXiv:2504.03624,
section 2.1 for the block — no MLP after a mixer, no positions in the
attention layers; Dao & Gu 2024 for the mixer and its groups;
DeepSeek-AI 2024, "DeepSeek-V3", sections 2.1.2 and 2.2 for the
router's bias and the prediction module; So et al. 2021 for squared
ReLU; Zhang & Sennrich 2019 for rms norm; Kingma & Ba 2014 for adam)
with its own parse of the conf text.  It imports nothing of the
program, nothing of ``benchmarks/lib`` and nothing of the other
references: what it has in common with them (the conf grammar, the
weights from the seed, adam, the packed rows) is written out here again.

* The state-space scan is the **recurrence itself**, one ``lax.scan``
  step a token: ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t``, ``y_t
  = S_t C_t + d x_t``, ``S = 0`` before a document's first token; head
  ``h`` of ``H`` reads ``B``, ``C`` of group ``h G // H``.  No chunks.
  The tokens are walked in segments, each under ``jax.checkpoint``.
  The gated norm is taken over each group's ``H P / G`` columns alone.
* Attention is the full masked score matrix (causal, own document only,
  ``head_dim^-1/2`` unless the conf states a scale, no positions), a
  block of rows at a time.
* The router scores every expert alone (``sigmoid``), chooses its
  ``topk`` by score + ``score_bias``, weighs them by the UNBIASED scores
  over their sum (+ 1e-20) times ``routed_scale``; float32 always.  The
  group limit is the identity at ``n_group = topk_group = 1`` and is
  not built; nor is the family's balance rule for the bias.
* The experts are a **dense loop over the experts held**, in the latent:
  ``l = u W_in^T``; every held expert runs on every token's ``l`` and
  its output ``W_d relu(W_u l)^2`` is multiplied by the router's weight
  for that token, or by 0; the sum goes back through ``W_out`` once a
  token, and the shared expert ``W_sd relu(W_su u)^2`` is added.
* The share (model-configs section 4): a layer's keys say what this
  rank holds — a mixer's heads and groups, an attention's query and
  key/value heads, the shared expert's columns, the experts from
  ``first_expert`` on, the vocabulary's slice — and every branch is the
  partial sum those give; router, norms and the latent projections are
  whole.  Where fewer than all experts are held the routing weights
  are constants of the backward pass (the program does the same).
* The prediction module (the layers from ``token_shift`` on): the
  shared embedding of the NEXT token and the last layer's output, each
  normed, side by side (embedding first) through ``eh_proj``, the
  module's layers, a last norm, the shared head, and the cross-entropy
  against the token after next.  The step's loss is the sum of both
  loss layers, each ``grad_scale / batch`` times its summed
  cross-entropy.
* Every conf layer is one ``jax.checkpoint``; ``train_chunk`` donates
  the weights it is handed and leaves its results on the device.

What it restates of the conf grammar: ``layer[a,b->c] = type:name``
(node ``0`` is the token ids); ``shared[name]`` computes with the named
layer's parameters and settings and owns none; every matrix is ``(out,
in)`` but the held experts', ``(expert, in, out)``; the fused
projections' orders (``mamba2``: ``z | x B C | dt`` with ``B`` and
``C`` group-major; ``attention``: ``q | k | v``); ``expert_act = relu2``
makes an expert ``wmat (nheld, W, nhidden)`` and ``wproj (nheld,
nhidden, W)`` with ``W`` the ``latent_hidden``; ``prenorm`` /
``residual_scale`` / ``eps`` on a branch layer; adam spelled with decay
rates (``beta1 = 0.1`` is the usual 0.9).

For the roofline readers: ``expert_flops(net, pairs)`` /
``expert_min_bytes(net, pairs)`` (the held experts' two grouped
products, ``pairs`` the (token, held expert) pairs of one training step
over all expert layers) and ``scan_flops(net)`` / ``scan_min_bytes(net)``
(the recurrence of every mixer).
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

ONES = ("norm", "d", "gate_norm")


class Net(NamedTuple):
    layers: List[dict]
    glob: Dict[str, str]
    pshapes: Dict[int, Dict[str, tuple]]
    batch: int
    seq: int
    hidden: int


# ----------------------------------------------------------------------
def parse(text: str):
    """(layers in conf order, global keys).  A ``shared[name]`` layer
    takes the named layer's type and settings and ``owner``, the index
    whose parameters it computes with."""
    layers, glob, top, inside = [], {}, "0", False
    by_name: Dict[str, dict] = {}
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
            lay = {"index": len(layers), "type": kind, "name": name,
                   "ins": ins, "out": out, "cfg": {}}
            lay["owner"] = lay["index"]
            if kind.startswith("shared["):
                first = by_name[kind[len("shared["):-1]]
                lay.update(type=first["type"], cfg=first["cfg"],
                           owner=first["index"], shared=True)
            elif name:
                by_name[name] = lay
            layers.append(lay)
            top = out
        elif inside and layers:
            layers[-1]["cfg"][k] = v
        else:
            glob[k] = v
    return layers, glob


def _mamba_dims(cfg):
    """(heads, head width, state, groups, inner width, conv taps)."""
    h, p, s = int(cfg["nhead"]), int(cfg["head_dim"]), int(cfg["nstate"])
    return h, p, s, int(cfg.get("ngroup", 1)), h * p, int(
        cfg.get("conv_width", 4))


def _attn_dims(cfg, d):
    """(query heads, key/value heads, head width)."""
    h = int(cfg["nhead"])
    return h, int(cfg.get("nkvhead", 0)) or h, int(
        cfg.get("head_dim", 0)) or d // h


def _moe_dims(cfg, d):
    """(experts routed, top-k, first held, held, an expert's width,
    shared width, the width the experts live in, matrices fused into
    ``wmat``)."""
    e, first = int(cfg["nexpert"]), int(cfg.get("first_expert", 0))
    return (e, int(cfg["topk"]), first, int(cfg.get("nheld", e - first)),
            int(cfg["nhidden"]), int(cfg.get("shared_hidden", 0)),
            int(cfg.get("latent_hidden", 0)) or d,
            1 if cfg.get("expert_act", "swiglu") == "relu2" else 2)


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
        elif t == "token_shift":
            d = None
        elif t == "mamba2":
            h, _, s, g, e, k = _mamba_dims(cfg)
            shp = {"wmat": (2 * e + 2 * g * s + h, d),
                   "conv": (e + 2 * g * s, k),
                   "conv_bias": (e + 2 * g * s,), "dt_bias": (h,),
                   "a_log": (h,), "d": (h,), "gate_norm": (e,),
                   "wproj": (d, e)}
        elif t == "attention":
            h, hk, dh = _attn_dims(cfg, d)
            shp = {"wmat": ((h + 2 * hk) * dh, d), "wproj": (d, h * dh)}
            if not int(cfg.get("no_bias", 0)):
                shp.update({"bias": ((h + 2 * hk) * dh,), "bproj": (d,)})
        elif t == "routed_experts":
            e, _, _, g, f, sh, lat, c = _moe_dims(cfg, d)
            shp = {"wgate": (e, d), "wmat": (g, lat, c * f),
                   "wproj": (g, f, lat)}
            if sh:
                shp.update({"shared_wmat": (c * sh, d),
                            "shared_wproj": (d, sh)})
                if int(cfg.get("shared_gate", 1)):
                    shp["shared_gate"] = (1, d)
            if int(cfg.get("select_bias", 0)):
                shp["score_bias"] = (e,)
            if int(cfg.get("latent_hidden", 0)):
                shp.update({"latent_in": (lat, d), "latent_out": (d, lat)})
        elif t == "rms_norm":
            shp = {"wmat": (d,)}
        elif t == "concat":
            d = sum(width[n] for n in lay["ins"])
        elif t == "fullc":
            shp = {"wmat": (int(cfg["nhidden"]), d)}
            if not int(cfg.get("no_bias", 0)):
                shp["bias"] = (int(cfg["nhidden"]),)
            d = int(cfg["nhidden"])
        elif t == "lm_head":
            shp = {"wmat": (int(cfg["nhidden"]), d)}
            d = int(cfg["nhidden"])
        elif t != "softmax":
            raise ValueError(f"nemotron_h: no layer type {t!r}")
        if int(cfg.get("prenorm", 0)):
            shp["norm"] = (width[lay["ins"][0]],)
        if shp and not lay.get("shared"):
            pshapes[lay["index"]] = shp
        width[lay["out"]] = d
    return Net(layers, glob, pshapes, int(batch), seq, hidden)


# ----------------------------------------------------------------------
def make_weights(net: Net, seed: int):
    """Every leaf from the seed in one jitted call (the configuration's
    ``assumed.init``): matrices normal at 0.02, the routers and the
    latent projections among them; the EMBEDDING normal at 1, so that a
    token's own row stands out of what the first mixer adds to the
    stream and the routers see tokens that differ; ``a_log`` the log of
    a uniform draw in [1, 16]; ``dt_bias`` the inverse softplus of a step
    drawn log-uniform in [1e-3, 1e-1]; ``d`` and the norms 1; the
    convolution uniform at 1/sqrt(width), its bias 0; a router's
    selection bias normal at ``BIAS_SIGMA`` — wide enough to change the
    chosen experts of most tokens at these weights, so that a program
    that drops it is not ``correct``; any other vector 0."""
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
                elif tag == "a_log":
                    w = jnp.log(jax.random.uniform(k, shp, jnp.float32,
                                                   1.0, 16.0))
                elif tag == "dt_bias":
                    step = jnp.exp(jax.random.uniform(
                        k, shp, jnp.float32, math.log(1e-3), math.log(1e-1)))
                    w = step + jnp.log(-jnp.expm1(-step))
                elif tag == "conv":
                    b = 1.0 / math.sqrt(shp[1])
                    w = jax.random.uniform(k, shp, jnp.float32, -b, b)
                elif kinds[i] == "embedding":
                    w = jax.random.normal(k, shp, jnp.float32)
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


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def doc_starts(ids):
    """(B, T) bool: a row's first token, and every token that follows a
    separator, begins a document."""
    return jnp.concatenate(
        [jnp.ones_like(ids[:, :1], bool), ids[:, :-1] == SEP_ID], axis=1)


def _starts(ids, n, t):
    """Without the ids a row is one document."""
    return (doc_starts(ids) if ids is not None
            else jnp.arange(t)[None].repeat(n, 0) == 0)


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


def selective_scan(x, dt, a, b, c, start):
    """The recurrence, a token a step.  ``x (B,T,H,P)``, ``dt (B,T,H)``,
    ``a (H,)``, ``b``/``c (B,T,G,S)`` in ``G`` groups of ``H / G`` heads,
    ``start (B,T)`` bool -> ``y`` of ``x``'s shape: ``y_t = S_t c_t``."""
    n, t, h, p = x.shape
    g, s = b.shape[2:]
    seg = next(q for q in range(min(SCAN_SEGMENT, t), 0, -1) if t % q == 0)

    def token(state, inp):
        xt, dtt, bt, ct, st = inp
        # head h reads group h G // H
        bt, ct = (jnp.repeat(v, h // g, axis=1) for v in (bt, ct))  # (B,H,S)
        keep = jnp.where(st[:, None], 0.0, jnp.exp(dtt * a))        # (B,H)
        state = (keep[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        return state, jnp.einsum("bhps,bhs->bhp", state, ct)

    @jax.checkpoint
    def segment(state, inp):
        return lax.scan(token, state, inp)

    cut = lambda v: jnp.moveaxis(v, 1, 0).reshape(  # noqa: E731
        (t // seg, seg) + v.shape[:1] + v.shape[2:])
    _, y = lax.scan(segment, jnp.zeros((n, h, p, s), jnp.float32),
                    tuple(cut(v) for v in (x, dt, b, c, start)))
    return jnp.moveaxis(y.reshape((t,) + y.shape[2:]), 0, 1)


def mamba2(p, u, ids, cfg, quant=None):
    """The mixer alone (no branch) on the normed input ``u (B, T, D)``."""
    n, t, _ = u.shape
    h, hp, s, g, e, k = _mamba_dims(cfg)
    gs = g * s
    start = _starts(ids, n, t)
    zxd = _mm(u, p["wmat"], quant)
    z, xbc, dt = zxd[..., :e], zxd[..., e:2 * e + 2 * gs], zxd[
        ..., 2 * e + 2 * gs:]
    # depthwise causal convolution; a tap that reaches before its
    # token's document reads zero: tap j is live while no document
    # began at any of the j tokens up to and including this one
    live = jnp.ones((n, t), bool)
    conv = xbc * p["conv"][:, k - 1]
    for j in range(1, min(k, t)):
        live = live & ~jnp.pad(start, ((0, 0), (j - 1, 0)))[:, :t]
        past = jnp.pad(xbc, ((0, 0), (j, 0), (0, 0)))[:, :t]
        conv = conv + jnp.where(live[..., None], past, 0.0) * p["conv"][
            :, k - 1 - j]
    xbc = silu(conv + p["conv_bias"])
    x = xbc[..., :e].reshape(n, t, h, hp)
    b = xbc[..., e:e + gs].reshape(n, t, g, s)
    c = xbc[..., e + gs:].reshape(n, t, g, s)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = selective_scan(_q(x, quant), dt, -jnp.exp(p["a_log"]), _q(b, quant),
                       _q(c, quant), start)
    y = (y + p["d"][:, None] * x).reshape(n, t, e) * silu(z)
    # the gated norm, over each group's columns alone
    y = rms_norm(y.reshape(n, t, g, e // g), p["gate_norm"].reshape(
        g, e // g), float(cfg.get("eps", 1e-5))).reshape(n, t, e)
    return _mm(y, p["wproj"], quant)


def attention(p, u, ids, cfg, quant=None):
    """The attention alone (no branch) on the normed input ``u``."""
    n, t, d = u.shape
    h, hk, dh = _attn_dims(cfg, d)
    scale = float(cfg.get("score_scale", 0.0)) or 1.0 / math.sqrt(dh)
    qkv = _mm(u, p["wmat"], quant) + p.get("bias", 0.0)
    q = qkv[..., :h * dh].reshape(n, t, hk, h // hk, dh)
    k = qkv[..., h * dh:(h + hk) * dh].reshape(n, t, hk, dh)
    v = qkv[..., (h + hk) * dh:].reshape(n, t, hk, dh)
    pos = jnp.broadcast_to(jnp.arange(t)[None], (n, t))
    doc = jnp.cumsum(_starts(ids, n, t), axis=1)

    def rows(qb, posb, docb):
        sc = jnp.einsum("bqgrd,bkgd->bgrqk", _q(qb, quant),
                        _q(k, quant)) * scale
        seen = docb[:, :, None] == doc[:, None, :]
        if int(cfg.get("causal", 0)):
            seen = seen & (posb[:, :, None] >= pos[:, None, :])
        sc = jnp.where(seen[:, None, None], sc, -jnp.inf)
        return jnp.einsum("bgrqk,bkgd->bqgrd",
                          _q(jax.nn.softmax(sc, axis=-1), quant),
                          _q(v, quant))

    o = _row_blocks(rows, q, pos, doc).reshape(n, t, h * dh)
    return _mm(o, p["wproj"], quant) + p.get("bproj", 0.0)


def router(p, x, cfg):
    """``x (M, D)`` -> (weights ``(M, k)``, expert ids ``(M, k)``): every
    expert's score, the ``topk`` largest of score + bias (the lower id
    first where two are equal), the weights the unbiased scores of the
    chosen over their sum, times ``routed_scale``; constants of the
    backward pass in a share.  Always float32 at the highest precision,
    the bias too: the control rounds them not."""
    e, topk, _, g = _moe_dims(cfg, x.shape[-1])[:4]
    logits = x @ p["wgate"].T
    s = (jax.nn.sigmoid(logits) if cfg.get("score_func") == "sigmoid"
         else jax.nn.softmax(logits, axis=-1))
    chosen_by = s + p["score_bias"] if int(cfg.get("select_bias", 0)) else s
    _, idx = lax.top_k(lax.stop_gradient(chosen_by), topk)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if int(cfg.get("norm_topk", 1)):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * float(cfg.get("routed_scale", 1.0))
    # a share: the weights' cotangent needs the other ranks' terms
    return (lax.stop_gradient(w) if g < e else w), idx


def _ffn(x, wmat, wproj, fused, quant):
    """One expert on ``x``, its matrices ``(out, in)``: ungated ``W_d
    relu(W_u x)^2``, or with ``fused = 2`` gated ``W_d (silu(W_g x) *
    W_u x)`` on a fused gate | up."""
    up = _mm(x, wmat, quant)
    if fused == 1:
        return _mm(relu2(up), wproj, quant)
    f = wmat.shape[0] // 2
    return _mm(silu(up[..., :f]) * up[..., f:], wproj, quant)


def routed_experts(p, u, cfg, quant=None):
    """The expert layer alone (no branch) on the normed input ``u``."""
    _, _, first, g, _, sh, _, fused = _moe_dims(cfg, u.shape[-1])
    x = u.reshape(-1, u.shape[-1])
    w, idx = router(p, x, cfg)
    # the held experts live in the latent, where the conf states one
    lat = _mm(x, p["latent_in"], quant) if "latent_in" in p else x

    @jax.checkpoint
    def one(y, ew):
        e, wmat, wproj = ew
        # the router's weight for expert e a token, or 0: dense, masked
        mask = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        # a held expert's matrices are (in, out)
        return y + mask[:, None] * _ffn(lat, wmat.T, wproj.T, fused,
                                        quant), None

    y, _ = lax.scan(one, jnp.zeros_like(lat),
                    (first + jnp.arange(g), p["wmat"], p["wproj"]))
    if "latent_out" in p:
        y = _mm(y, p["latent_out"], quant)
    if sh:
        s = _ffn(x, p["shared_wmat"], p["shared_wproj"], fused, quant)
        if int(cfg.get("shared_gate", 1)):
            s = jax.nn.sigmoid(x @ p["shared_gate"].T) * s
        y = y + s
    return y.reshape(u.shape)


BRANCHES = {"mamba2": mamba2, "attention": attention}


def loss_fn(net: Net, quant=None):
    def apply(lay, p, xs, ids):
        t, cfg, x = lay["type"], lay["cfg"], xs[0]
        if t == "embedding":
            return p["wmat"][x] * float(cfg.get("multiplier", 1.0))
        if t == "token_shift":
            return jnp.pad(x[:, 1:], ((0, 0), (0, 1)))
        if t == "rms_norm":
            return rms_norm(x, p["wmat"], float(cfg.get("eps", 1e-5)))
        if t == "concat":
            return jnp.concatenate(xs, axis=-1)
        if t == "fullc":
            return _mm(x, p["wmat"], quant) + p.get("bias", 0.0)
        u = x
        if int(cfg.get("prenorm", 0)):
            u = rms_norm(x, p["norm"], float(cfg.get("eps", 1e-5)))
        if t in BRANCHES:
            y = BRANCHES[t](p, u, ids if len(xs) > 1 else None, cfg, quant)
        else:
            y = routed_experts(p, u, cfg, quant)
        r = float(cfg.get("residual_scale", 0.0))
        return x + r * y if r else y

    def loss(params, ids, labels):
        nodes = {"0": ids}
        head = None
        total, losses = 0.0, 0
        for lay in net.layers:
            xs = [nodes[n] for n in lay["ins"]]
            p = params.get(lay["owner"], {})
            if lay["type"] == "lm_head":
                # the logits are formed where the loss reads them, a
                # block of rows at a time
                head = (p, float(lay["cfg"].get("divisor", 1.0)), xs[0])
                nodes[lay["out"]] = None
            elif lay["type"] == "softmax":
                table, divisor, x = head
                # position t is scored against label[t + shift]; a row's
                # last shift positions have weight 0
                s = int(lay["cfg"].get("target_shift", 0))
                lab = jnp.pad(labels[:, s:], ((0, 0), (0, s)))
                weight = (jnp.arange(labels.shape[1])
                          < labels.shape[1] - s).astype(jnp.float32)

                def rows(xb, lb):
                    logp = jax.nn.log_softmax(
                        _mm(xb, table["wmat"], quant) / divisor, axis=-1)
                    return -jnp.take_along_axis(
                        logp, lb[..., None], axis=-1)[..., 0]

                total = total + (
                    float(lay["cfg"].get("grad_scale", 1.0)) / ids.shape[0]
                    * jnp.sum(_row_blocks(rows, x, lab) * weight))
                losses += 1
            else:
                run = jax.checkpoint(
                    lambda p, xs, lay=lay: apply(lay, p, xs, ids))
                nodes[lay["out"]] = run(p, xs)
        if not losses:
            raise ValueError("nemotron_h: the net has no softmax")
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
    (losses [K] — both loss layers summed —, params after, adam's first
    moment after); the two trees are left on the device (``np.asarray``
    of a leaf fetches it) and fetched whole when the next chunk is
    followed.  ``weights`` are donated.  ``control``: True for the step
    below the bfloat16 the configuration states (the matrix products,
    the recurrence's x, B and C, attention's q, k, v and probabilities,
    the latent projections and the experts' products on operands
    rounded to ``float8_e4m3fn``; the router and its bias stay float32),
    or a type's name."""
    del key  # nothing here is random
    _last_results_to_host()
    glob = net.glob
    if glob.get("updater") != "adam" or glob.get("lr:schedule",
                                                  "constant") != "constant":
        raise ValueError("nemotron_h: adam at a constant rate only")
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
    """The layers of a type, a ``shared[...]`` use of one among them."""
    return [lay for lay in net.layers if lay["type"] == kind]


def expected_pairs(net: Net) -> float:
    """The (token, held expert) pairs of one training step, all expert
    layers, under a router that spreads its picks evenly: ``topk *
    nheld / nexpert`` a token (0.34 at 22 of 512 with 8 held)."""
    total = 0.0
    for lay in _of(net, "routed_experts"):
        e, topk, _, g = _moe_dims(lay["cfg"], net.hidden)[:4]
        total += _tokens(net) * topk * g / e
    return total


def _expert_macs_a_pair(net: Net) -> float:
    """An expert's matrices once: ``c W F`` up (``c`` = 2 with a gate)
    and ``F W`` down, ``W`` the width the experts live in (the layers of
    one net share their widths; the mean where they do not)."""
    lays = _of(net, "routed_experts")
    total = 0.0
    for lay in lays:
        _, _, _, _, f, _, lat, c = _moe_dims(lay["cfg"], net.hidden)
        total += (c + 1.0) * lat * f
    return total / max(len(lays), 1)


def expert_flops(net: Net, pairs: float) -> float:
    """Operations of the held experts' grouped products in one training
    step in which ``pairs`` (token, held expert) pairs were routed, all
    expert layers: 2 a multiply-add, 3 for the forward pass and the two
    gradients; a recomputed forward does not count."""
    return float(pairs) * _expert_macs_a_pair(net) * 2.0 * 3.0


def expert_min_bytes(net: Net, pairs: float, itemsize: int = 2) -> float:
    """The least bytes those products move: the held experts' matrices
    read by the forward pass, read by the backward pass and their
    gradients written (once each way, at ``itemsize``); and a pair's
    rows — ``x`` read and ``y`` written forward, ``dy`` and ``x`` read
    and ``dx`` written backward, as wide as the experts' input (the
    latent) — with the ``F``-wide intermediates held on chip."""
    weights = sum(float(np.prod(net.pshapes[lay["owner"]][t]))
                  for lay in _of(net, "routed_experts")
                  for t in ("wmat", "wproj"))
    width = np.mean([_moe_dims(lay["cfg"], net.hidden)[6]
                     for lay in _of(net, "routed_experts")] or [0.0])
    return itemsize * (3.0 * weights + 5.0 * float(pairs) * float(width))


def scan_flops(net: Net) -> float:
    """Operations of the recurrence in one training step, all mixers:
    a token and head, ``3 P S`` to move the state on (decay it, form
    ``dt x (x) B``, add) and ``2 P S`` to read ``y`` from it; times 3
    for the forward pass and the two gradients."""
    total = 0.0
    for lay in _of(net, "mamba2"):
        h, p, s = _mamba_dims(lay["cfg"])[:3]
        total += _tokens(net) * h * 5.0 * p * s * 3.0
    return total


def scan_min_bytes(net: Net, itemsize: int = 2) -> float:
    """The least bytes the recurrence moves in one training step, all
    mixers, with the state held on chip: a token, the forward pass
    reads ``x``, every group's ``B`` and ``C`` and ``dt`` and writes
    ``y``; the backward reads them and ``dy`` again and writes the four
    gradients."""
    total = 0.0
    for lay in _of(net, "mamba2"):
        h, _, s, g, e, _ = _mamba_dims(lay["cfg"])
        ins = e + 2 * g * s + h
        total += _tokens(net) * itemsize * ((ins + e) + (ins + e) + ins)
    return total


def _forward_macs(net: Net) -> float:
    """Multiply-adds of one forward pass: every matrix once a token for
    each layer that computes with it (a shared head counts again; an
    embedding is a gather; a mixer's depthwise convolution is its
    (columns, taps) matrix), a held expert's matrices once a pair at the
    expected ``topk * nheld / nexpert`` pairs a token, the two attention
    products over the positions a causal query may see (the whole row:
    documents are not counted), and the recurrence's own operations
    halved (``scan_flops``)."""
    macs = 0.0
    for lay in net.layers:
        if lay["type"] != "embedding":
            for s in net.pshapes.get(lay["owner"], {}).values():
                if len(s) == 2:
                    macs += _tokens(net) * s[0] * s[1]
    seen = (net.seq + 1) / 2.0
    for lay in _of(net, "attention"):
        h, _, dh = _attn_dims(lay["cfg"], net.hidden)
        macs += _tokens(net) * seen * 2.0 * h * dh
    return (macs + expected_pairs(net) * _expert_macs_a_pair(net)
            + scan_flops(net) / 6.0)


def step_flops(net: Net) -> float:
    """2 a multiply-add, 3 for forward and the two gradients; a
    recomputed forward does not count.  The token cells' convention:
    attention over the positions a causal query may see with documents
    NOT counted, the experts at the EXPECTED pairs (``expected_pairs``),
    not at a run's count."""
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
        elif lay["type"] == "concat":
            widths += 2 * net.hidden
        elif lay["type"] not in ("softmax", "token_shift"):
            widths += net.hidden
    return _tokens(net) * widths * 2 * 5.0 + params * 4 * 8.0
