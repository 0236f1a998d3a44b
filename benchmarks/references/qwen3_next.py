"""The plain reference of the Qwen3-Next family (Qwen, ``model_type:
qwen3_next``): Gated DeltaNet linear-attention mixers, gated softmax
attention with q/k norms and partial rotary positions, a routed
mixture of SwiGLU experts with a shared expert in every layer, an
untied head, under adam.  Named by ``configs/qwen3_next_80b_a3b.json``.

Plain ``jax.numpy`` in float32 at ``highest`` matmul precision, written
from the published description (Yang, Kautz & Hatamizadeh 2024 for the
gated delta rule; the model's ``config.json`` for the widths; Shazeer
2020 for the gated experts; Su et al. 2021 for the rotation; Zhang &
Sennrich 2019 for rms norm; Kingma & Ba 2014 for adam) with its own
parse of the conf text.  It imports nothing of the program and nothing
of ``benchmarks/lib``.

* The delta rule is the **recurrence itself**, one ``lax.scan`` step a
  token: ``S_t = a_t S_{t-1} + b_t k_t (v_t - (a_t S_{t-1})^T k_t)^T``,
  ``o_t = S_t^T q_t``, ``S = 0`` before a document's first token.  No
  chunks, no triangular solve.  Only the memory of its gradient is
  managed: the tokens are walked in segments, each under
  ``jax.checkpoint`` (8192 states of 32 x 128 x 128 floats would be
  17 GB a layer).
* The experts are a **dense loop over the experts held**: every held
  expert runs on every token and its output is multiplied by the
  router's weight for that token, or by 0.  No sort, no groups.
* The share (model-configs section 4): the router ranks all
  ``nexpert`` experts and renormalises over its ``topk``; only the
  terms of the ``nheld`` experts from ``first_expert`` on are added,
  and that partial sum goes on to the next layer.  Where fewer than
  all are held the routing weights are constants of the backward pass
  (``stop_gradient``): their cotangent is a sum over the ranks that
  hold a token's experts, a lone rank has its own terms of it only,
  and the part is no estimate of the whole (it says "held experts
  answer, the others do not").  So a share's router gets no gradient,
  and none reaches the stream through it.
* Attention is the full masked score matrix (causal, own document
  only), a block of rows at a time.
* Every conf layer is one ``jax.checkpoint``; ``train_chunk`` donates
  the weights it is handed and leaves its results on the device
  (``granite_hybrid.py`` has why).

Departures from the published model, each the conf's or the
configuration file's: the rows of the mixer's ``W_qkvz`` are ``q | k |
v | z`` (a permutation of the checkpoints' per-key-group order) and of
``W_ba`` ``b | a``; no auxiliary (load-balancing) loss and no
multi-token-prediction head, neither being in the catalog's ``config``;
the norm is ``x / rms(x) * w`` with ``w`` started at 1, the same
function and gradients as the published ``(1 + w)`` from 0 under adam
without decay; and what the file lists under ``assumed`` (the weights'
start, the documents, positions that restart at a document's first
token, adam's settings).

What it restates of the conf grammar: ``layer[a,b->c] = type:name``
(node ``0`` is the token ids), every matrix is ``(out, in)`` but the
held experts', which are ``(expert, in, out)``: ``wmat (nheld, D, 2
nhidden)`` gate | up and ``wproj (nheld, nhidden, D)``; ``attention``'s
``wmat`` with ``out_gate`` is, a head, its ``Dh`` of query then its
``Dh`` of gate, then ``k | v``;
``prenorm`` / ``residual_scale`` / ``eps`` on a branch layer;
``lm_head`` with its own ``wmat`` where it names no ``tied``; the loss
as ``grad_scale / batch`` times the summed cross-entropy; adam spelled
with decay rates (``beta1 = 0.1`` is the usual 0.9).

For the roofline readers: ``scan_flops`` / ``scan_min_bytes`` (the
recurrence), ``expert_flops(net, pairs)`` / ``expert_min_bytes(net,
pairs)`` (the grouped products, ``pairs`` the (token, held expert)
pairs of one training step over all expert layers).
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

ONES = ("norm", "gate_norm", "q_norm", "k_norm")


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


def _gdn_dims(cfg):
    """(Hk, Hv, Dk, Dv, conv width)."""
    return (int(cfg["nkhead"]), int(cfg["nvhead"]), int(cfg["key_dim"]),
            int(cfg["value_dim"]), int(cfg.get("conv_width", 4)))


def _attn_dims(cfg, d):
    """(heads, key-value heads, head width)."""
    h = int(cfg["nhead"])
    return h, int(cfg.get("nkvhead", h)), int(cfg.get("head_dim", d // h))


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
        elif t == "gated_deltanet":
            hk, hv, dk, dv, k = _gdn_dims(cfg)
            shp = {"wmat": (2 * hk * dk + 2 * hv * dv, d),
                   "wba": (2 * hv, d), "conv": (2 * hk * dk + hv * dv, k),
                   "dt_bias": (hv,), "a_log": (hv,), "gate_norm": (dv,),
                   "wproj": (d, hv * dv)}
        elif t == "attention":
            h, hk, dh = _attn_dims(cfg, d)
            nq = h * dh
            nqkv = nq * (2 if int(cfg.get("out_gate", 0)) else 1) + 2 * hk * dh
            shp = {"wmat": (nqkv, d), "wproj": (d, nq)}
            if int(cfg.get("qk_norm", 0)):
                shp.update({"q_norm": (dh,), "k_norm": (dh,)})
            if not int(cfg.get("no_bias", 0)):
                shp.update({"bias": (nqkv,), "bproj": (d,)})
        elif t == "routed_experts":
            e, _, _, g, f, sh = _moe_dims(cfg)
            shp = {"wgate": (e, d), "wmat": (g, d, 2 * f),
                   "wproj": (g, f, d)}
            if sh:
                shp.update({"shared_wmat": (2 * sh, d),
                            "shared_wproj": (d, sh), "shared_gate": (1, d)})
        elif t == "rms_norm":
            shp = {"wmat": (d,)}
        elif t == "lm_head":
            if not cfg.get("tied"):
                shp = {"wmat": (int(cfg["nhidden"]), d)}
            d = int(cfg["nhidden"])
        elif t != "softmax":
            raise ValueError(f"qwen3_next: no layer type {t!r}")
        if int(cfg.get("prenorm", 0)):
            shp["norm"] = (width[lay["ins"][0]],)
        if shp:
            pshapes[lay["index"]] = shp
        width[lay["out"]] = d
    return Net(layers, glob, pshapes, int(batch), seq, hidden)


# ----------------------------------------------------------------------
def make_weights(net: Net, seed: int):
    """Every leaf from the seed in one jitted call (the configuration's
    ``assumed.init``): matrices normal at 0.02; ``a_log`` the log of a
    uniform draw in [1, 16]; ``dt_bias`` the inverse softplus of a step
    drawn log-uniform in [1e-3, 1e-1]; the norms 1; the convolution
    uniform at 1/sqrt(width); any other vector 0."""

    @jax.jit
    def make(key):
        out = {}
        for i, tags in net.pshapes.items():
            out[i] = {}
            for n, (tag, shp) in enumerate(sorted(tags.items())):
                k = jax.random.fold_in(jax.random.fold_in(key, i), n)
                if tag == "a_log":
                    w = jnp.log(jax.random.uniform(k, shp, jnp.float32,
                                                   1.0, 16.0))
                elif tag == "dt_bias":
                    step = jnp.exp(jax.random.uniform(
                        k, shp, jnp.float32, math.log(1e-3), math.log(1e-1)))
                    w = step + jnp.log(-jnp.expm1(-step))
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


def delta_rule(q, k, v, a, b, start):
    """The gated delta rule, a token a step.  ``q``/``k (B,T,H,Dk)``,
    ``v (B,T,H,Dv)``, ``a``/``b (B,T,H)`` (decay in (0, 1], write
    strength), ``start (B,T)`` bool -> ``o (B,T,H,Dv)``."""
    n, t, h, dk = q.shape
    seg = next(s for s in range(min(SCAN_SEGMENT, t), 0, -1) if t % s == 0)

    def token(state, inp):
        qt, kt, vt, at, bt, st = inp
        keep = jnp.where(st[:, None], 0.0, at)                    # (B,H)
        state = keep[..., None, None] * state
        err = vt - jnp.einsum("bhkv,bhk->bhv", state, kt)
        state = state + kt[..., :, None] * (bt[..., None] * err)[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt)

    @jax.checkpoint
    def segment(state, inp):
        return lax.scan(token, state, inp)

    cut = lambda x: jnp.moveaxis(x, 1, 0).reshape(  # noqa: E731
        (t // seg, seg) + x.shape[:1] + x.shape[2:])
    _, o = lax.scan(segment, jnp.zeros((n, h, dk, v.shape[-1]), jnp.float32),
                    tuple(cut(x) for x in (q, k, v, a, b, start)))
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


def _gated_deltanet(p, u, ids, cfg, quant):
    n, t, _ = u.shape
    hk, hv, dk, dv, _ = _gdn_dims(cfg)
    ek, ev = hk * dk, hv * dv
    start = _starts(ids, n, t)
    qkvz = _mm(u, p["wmat"], quant)
    ba = _mm(u, p["wba"], quant)
    qkv = silu(_conv(qkvz[..., :2 * ek + ev], p["conv"], start))
    z = qkvz[..., 2 * ek + ev:].reshape(n, t, hv, dv)
    rep = hv // hk
    q = jnp.repeat(qkv[..., :ek].reshape(n, t, hk, dk), rep, axis=2)
    k = jnp.repeat(qkv[..., ek:2 * ek].reshape(n, t, hk, dk), rep, axis=2)
    v = qkv[..., 2 * ek:].reshape(n, t, hv, dv)
    q, k = _unit(q) / math.sqrt(dk), _unit(k)
    beta = jax.nn.sigmoid(ba[..., :hv])
    alpha = jnp.exp(-jnp.exp(p["a_log"])
                    * jax.nn.softplus(ba[..., hv:] + p["dt_bias"]))
    o = delta_rule(_q(q, quant), _q(k, quant), _q(v, quant), alpha, beta,
                   start)
    y = rms_norm(o, p["gate_norm"], float(cfg.get("eps", 1e-5))) * silu(z)
    return _mm(y.reshape(n, t, ev), p["wproj"], quant)


def _rotate(x, pos, dim, theta):
    """Rotate-half on the first ``dim`` of each head of ``(B,T,H,Dh)``."""
    half = dim // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dim)
    ang = pos.astype(jnp.float32)[..., None] * freq
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:dim]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., dim:]], axis=-1)


def _attention(p, u, ids, cfg, quant):
    n, t, d = u.shape
    h, hk, dh = _attn_dims(cfg, d)
    nq = h * dh
    scale = float(cfg.get("score_scale", 1.0 / math.sqrt(dh)))
    eps = float(cfg.get("eps", 1e-5))
    qkv = _mm(u, p["wmat"], quant) + p.get("bias", 0.0)
    gate = None
    if int(cfg.get("out_gate", 0)):
        qg = qkv[..., :2 * nq].reshape(n, t, h, 2 * dh)
        q, gate = qg[..., :dh], qg[..., dh:]
        qkv = qkv[..., 2 * nq:]
    else:
        q, qkv = qkv[..., :nq].reshape(n, t, h, dh), qkv[..., nq:]
    k = qkv[..., :hk * dh].reshape(n, t, hk, dh)
    v = qkv[..., hk * dh:].reshape(n, t, hk, dh)
    start = _starts(ids, n, t)
    doc = jnp.cumsum(start, axis=1)
    pos = jnp.broadcast_to(jnp.arange(t)[None], (n, t))
    if int(cfg.get("qk_norm", 0)):
        q, k = rms_norm(q, p["q_norm"], eps), rms_norm(k, p["k_norm"], eps)
    rot = int(cfg.get("rotary_dim", 0))
    if rot:
        # a position is counted from its document's first token
        first = lax.cummax(jnp.where(start, pos, 0), axis=1)
        theta = float(cfg.get("rope_theta", 10000.0))
        q, k = (_rotate(x, pos - first, rot, theta) for x in (q, k))
    q = q.reshape(n, t, hk, h // hk, dh)

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

    o = _row_blocks(rows, q, pos, doc).reshape(n, t, h, dh)
    if gate is not None:
        o = o * jax.nn.sigmoid(gate)
    return _mm(o.reshape(n, t, nq), p["wproj"], quant) + p.get("bproj", 0.0)


def router(p, x, cfg):
    """``x (M, D)`` -> (weights ``(M, k)``, expert ids ``(M, k)``):
    softmax over all the experts routed, the ``topk`` largest (the
    lower id first where two are equal), over their sum; constants of
    the backward pass in a share.  Always float32 at the highest
    precision: the control rounds it not."""
    e, topk, _, g, _, _ = _moe_dims(cfg)
    w, idx = lax.top_k(jax.nn.softmax(x @ p["wgate"].T, axis=-1), topk)
    if int(cfg.get("norm_topk", 1)):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    # a share: the weights' cotangent needs the other ranks' terms
    return (lax.stop_gradient(w) if g < e else w), idx


def _swiglu(x, wmat, wproj, quant):
    f = wmat.shape[0] // 2
    gu = _mm(x, wmat, quant)
    return _mm(silu(gu[..., :f]) * gu[..., f:], wproj, quant)


def _routed_experts(p, u, cfg, quant):
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
        y = y + jax.nn.sigmoid(x @ p["shared_gate"].T) * _swiglu(
            x, p["shared_wmat"], p["shared_wproj"], quant)
    return y.reshape(u.shape)


def loss_fn(net: Net, quant=None):
    by_name = {lay["name"]: lay["index"] for lay in net.layers if lay["name"]}

    def apply(lay, p, xs, ids):
        t, cfg, x = lay["type"], lay["cfg"], xs[0]
        if t == "embedding":
            return p["wmat"][ids] * float(cfg.get("multiplier", 1.0))
        if t == "rms_norm":
            return rms_norm(x, p["wmat"], float(cfg.get("eps", 1e-5)))
        u = x
        if int(cfg.get("prenorm", 0)):
            u = rms_norm(x, p["norm"], float(cfg.get("eps", 1e-5)))
        doc_ids = ids if len(xs) > 1 else None
        if t == "gated_deltanet":
            y = _gated_deltanet(p, u, doc_ids, cfg, quant)
        elif t == "attention":
            y = _attention(p, u, doc_ids, cfg, quant)
        else:
            y = _routed_experts(p, u, cfg, quant)
        r = float(cfg.get("residual_scale", 0.0))
        return x + r * y if r else y

    def loss(params, ids, labels):
        nodes = {"0": ids}
        head = None
        for lay in net.layers:
            xs = [nodes[n] for n in lay["ins"]]
            if lay["type"] == "lm_head":
                # the logits are formed where the loss reads them, a
                # block of rows at a time
                own = lay["cfg"].get("tied")
                head = (params[by_name[own] if own else lay["index"]],
                        float(lay["cfg"].get("divisor", 1.0)), xs[0])
                nodes[lay["out"]] = None
            elif lay["type"] == "softmax":
                table, divisor, x = head

                def rows(xb, lab):
                    logp = jax.nn.log_softmax(
                        _mm(xb, table["wmat"], quant) / divisor, axis=-1)
                    return -jnp.take_along_axis(
                        logp, lab[..., None], axis=-1)[..., 0]

                return (float(lay["cfg"].get("grad_scale", 1.0))
                        / ids.shape[0] * jnp.sum(_row_blocks(rows, x, labels)))
            else:
                run = jax.checkpoint(
                    lambda p, xs, lay=lay: apply(lay, p, xs, ids))
                nodes[lay["out"]] = run(params.get(lay["index"], {}), xs)
        raise ValueError("qwen3_next: the net ends in no softmax")

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
    (losses [K], params after, adam's first moment after); the two
    trees are left on the device (``np.asarray`` of a leaf fetches it)
    and fetched whole when the next chunk is followed.  ``weights`` are
    donated.  ``control``: True for the step below the bfloat16 the
    configuration states (matrix products, the recurrence's q, k and v
    and the experts' products on operands rounded to ``float8_e4m3fn``;
    the router stays float32), or a type's name."""
    del key  # nothing here is random
    _last_results_to_host()
    glob = net.glob
    if glob.get("updater") != "adam" or glob.get("lr:schedule",
                                                  "constant") != "constant":
        raise ValueError("qwen3_next: adam at a constant rate only")
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
    nheld / nexpert`` a token (0.625 at 10 of 512 with 32 held)."""
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


def scan_flops(net: Net) -> float:
    """Operations of the recurrence in one training step, all mixers:
    a token and value head, ``Dk Dv`` to decay the state, ``2 Dk Dv``
    to read what it holds for ``k``, ``2 Dk Dv`` for the rank-one
    write and ``2 Dk Dv`` to read ``o``; times 3 for the forward pass
    and the two gradients."""
    total = 0.0
    for lay in _of(net, "gated_deltanet"):
        _, hv, dk, dv, _ = _gdn_dims(lay["cfg"])
        total += _tokens(net) * hv * 7.0 * dk * dv * 3.0
    return total


def scan_min_bytes(net: Net, itemsize: int = 2) -> float:
    """The least bytes the recurrence moves in one training step, all
    mixers, with the state held on chip: a token, the forward pass
    reads ``q``, ``k`` (a key head each), ``v``, the decay and the
    write strength and writes ``o``; the backward reads them and ``do``
    again and writes the five gradients."""
    total = 0.0
    for lay in _of(net, "gated_deltanet"):
        hk, hv, dk, dv, _ = _gdn_dims(lay["cfg"])
        ins, ev = 2 * hk * dk + hv * dv + 2 * hv, hv * dv
        total += _tokens(net) * itemsize * ((ins + ev) + (ins + ev) + ins)
    return total


def _forward_macs(net: Net) -> float:
    """Multiply-adds of one forward pass: every matrix once a token
    (the embedding is a gather), a held expert's three matrices once a
    pair at the expected ``topk * nheld / nexpert`` pairs a token, the
    two attention products over the positions a causal query may see,
    and the recurrence's own operations halved (``scan_flops``)."""
    macs = 0.0
    for lay in net.layers:
        shp = net.pshapes.get(lay["index"], {})
        if lay["type"] != "embedding":
            for tag, s in shp.items():
                if len(s) == 2 and tag != "conv":
                    macs += _tokens(net) * s[0] * s[1]
        if lay["type"] == "attention":
            h, _, dh = _attn_dims(lay["cfg"], net.hidden)
            seen = ((net.seq + 1) / 2.0 if int(lay["cfg"].get("causal", 0))
                    else float(net.seq))
            macs += _tokens(net) * 2.0 * seen * h * dh
    macs += expected_pairs(net) * _expert_macs_a_pair(net)
    return macs + scan_flops(net) / 6.0


def step_flops(net: Net) -> float:
    """2 a multiply-add, 3 for forward and the two gradients; a
    recomputed forward does not count.  The experts at the EXPECTED
    pairs (``expected_pairs``), not at a run's count."""
    return _forward_macs(net) * 2.0 * 3.0


def step_min_bytes(net: Net) -> float:
    """Every parameter read, its gradient written and read, both
    moments and the weight read and written (8 passes in float32), and
    every layer's output through 5 passes at 2 bytes."""
    params = sum(float(np.prod(s)) for t in net.pshapes.values()
                 for s in t.values())
    widths = 0.0
    for lay in net.layers:
        if lay["type"] != "softmax":  # the loss reads the head's output
            widths += (int(lay["cfg"]["nhidden"])
                       if lay["type"] == "lm_head" else net.hidden)
    return _tokens(net) * widths * 2 * 5.0 + params * 4 * 8.0
