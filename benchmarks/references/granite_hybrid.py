"""The plain reference of the Granite-4.0-H family (ibm-granite,
``model_type: granitemoehybrid``, dense): Mamba-2 mixers, position-free
grouped-query attention, gated MLPs, a tied and scaled embedding, under
adam.  Named by ``configs/granite_4_0_h_micro.json``.

Plain ``jax.numpy`` in float32 at ``highest`` matmul precision, written
from the published description (Dao & Gu 2024 for the mixer; the
model's ``config.json`` for the widths and the four multipliers;
Shazeer 2020 for the gated MLP; Zhang & Sennrich 2019 for rms norm;
Kingma & Ba 2014 for adam) with its own parse of the conf text.  It
imports nothing of the program and nothing of ``benchmarks/lib``.

* The state-space scan is the **recurrence itself**, one ``lax.scan``
  step a token: ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t``,
  ``y_t = S_t C_t + d x_t``, ``S = 0`` before a document's first token.
  No chunks.  Only the memory of its gradient is managed: the tokens
  are walked in segments, each under ``jax.checkpoint``, so the
  backward keeps one state a segment and not one a token.
* Attention is the full masked score matrix (causal, own document
  only, scores times the stated multiplier, no positions), computed a
  block of rows at a time so that it fits.
* Every conf layer is one ``jax.checkpoint``: at the published widths
  the weights, gradients and adam's two moments are 12.4 GB of a 16 GB
  chip.  ``train_chunk`` donates the weights it is handed.
* The host's memory is managed too (a one-chip machine has 40 GiB, and
  the comparison holds the program's 9.3 GB of state, the start and two
  float64 trees of differences): what ``train_chunk`` returns stays on
  the device until the next call, and ``program_update_state`` drops
  the second moment it is handed.

Departures from the published description: none but the cut (depth and
vocabulary: the conf's) and what the configuration file lists under
``assumed`` (the weights' start, the documents, adam's settings).

What it restates of the conf grammar: ``layer[a,b->c] = type:name``
(node ``0`` is the token ids), every matrix is ``(out, in)``, the
fused projections' orders (``mamba2``: ``z | x B C | dt``;
``attention``: ``q | k | v``; ``gated_mlp``: gate | value),
``prenorm`` / ``residual_scale`` / ``eps`` on a branch layer,
``lm_head``'s ``tied`` and ``divisor``, the loss as ``grad_scale /
batch`` times the summed cross-entropy of every position, and adam
spelled with decay rates (``beta1 = 0.1`` is the usual 0.9) at the
constant rate ``eta``, subtracting ``wd * w`` from the gradient.
"""

from __future__ import annotations

import math
from typing import Dict, List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

ROW_BLOCK = 128      # rows of a score matrix, an MLP or the head at a time
SCAN_SEGMENT = 128   # tokens of the recurrence under one checkpoint
SEP_ID = 0           # a document begins after every separator


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


def _mamba_dims(cfg):
    h, p, s = int(cfg["nhead"]), int(cfg["head_dim"]), int(cfg["nstate"])
    return h, p, s, h * p, int(cfg.get("conv_width", 4))


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
        elif t == "mamba2":
            h, p, s, e, k = _mamba_dims(cfg)
            shp = {"wmat": (2 * e + 2 * s + h, d), "conv": (e + 2 * s, k),
                   "conv_bias": (e + 2 * s,), "dt_bias": (h,),
                   "a_log": (h,), "d": (h,), "gate_norm": (e,),
                   "wproj": (d, e)}
        elif t == "attention":
            h = int(cfg["nhead"])
            hk = int(cfg.get("nkvhead", h))
            nqkv = d + 2 * hk * (d // h)
            shp = {"wmat": (nqkv, d), "wproj": (d, d)}
            if not int(cfg.get("no_bias", 0)):
                shp.update({"bias": (nqkv,), "bproj": (d,)})
        elif t == "gated_mlp":
            nh = int(cfg["nhidden"])
            shp = {"wmat": (2 * nh, d), "wproj": (d, nh)}
        elif t == "rms_norm":
            shp = {"wmat": (d,)}
        elif t == "lm_head":
            d = int(cfg["nhidden"])
        elif t != "softmax":
            raise ValueError(f"granite_hybrid: no layer type {t!r}")
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
    drawn log-uniform in [1e-3, 1e-1]; ``d`` and the norms 1; the
    convolution uniform at 1/sqrt(width), its bias and any other 0."""

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
                elif tag in ("d", "gate_norm", "norm") or (
                        tag == "wmat" and len(shp) == 1):
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
    ``a (H,)``, ``b``/``c (B,T,S)``, ``start (B,T)`` bool -> ``y`` of
    ``x``'s shape: ``y_t = S_t c_t``."""
    n, t, h, p = x.shape
    seg = next(s for s in range(min(SCAN_SEGMENT, t), 0, -1) if t % s == 0)

    def token(state, inp):
        xt, dtt, bt, ct, st = inp
        keep = jnp.where(st[:, None], 0.0, jnp.exp(dtt * a))       # (B,H)
        state = (keep[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None] * bt[:, None, None, :])
        return state, jnp.einsum("bhps,bs->bhp", state, ct)

    @jax.checkpoint
    def segment(state, inp):
        return lax.scan(token, state, inp)

    cut = lambda v: jnp.moveaxis(v, 1, 0).reshape(  # noqa: E731
        (t // seg, seg) + v.shape[:1] + v.shape[2:])
    _, y = lax.scan(segment, jnp.zeros((n, h, p, b.shape[-1]), jnp.float32),
                    tuple(cut(v) for v in (x, dt, b, c, start)))
    return jnp.moveaxis(y.reshape((t,) + y.shape[2:]), 0, 1)


def _mamba2(p, u, ids, cfg, quant):
    n, t, _ = u.shape
    h, hp, s, e, k = _mamba_dims(cfg)
    # without the ids a row is one document
    start = (doc_starts(ids) if ids is not None
             else jnp.arange(t)[None].repeat(n, 0) == 0)
    zxd = _mm(u, p["wmat"], quant)
    z, xbc, dt = zxd[..., :e], zxd[..., e:2 * e + 2 * s], zxd[..., 2 * e + 2 * s:]
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
    b, c = xbc[..., e:e + s], xbc[..., e + s:]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = selective_scan(_q(x, quant), dt, -jnp.exp(p["a_log"]), _q(b, quant),
                       _q(c, quant), start)
    y = (y + p["d"][:, None] * x).reshape(n, t, e)
    y = rms_norm(y * silu(z), p["gate_norm"], float(cfg.get("eps", 1e-5)))
    return _mm(y, p["wproj"], quant)


def _attention(p, u, ids, cfg, quant):
    n, t, d = u.shape
    h = int(cfg["nhead"])
    hk = int(cfg.get("nkvhead", h))
    dh = d // h
    scale = float(cfg.get("score_scale", 1.0 / math.sqrt(dh)))
    qkv = _mm(u, p["wmat"], quant) + p.get("bias", 0.0)
    q = qkv[..., :d].reshape(n, t, hk, h // hk, dh)
    k = qkv[..., d:d + hk * dh].reshape(n, t, hk, dh)
    v = qkv[..., d + hk * dh:].reshape(n, t, hk, dh)
    pos = jnp.broadcast_to(jnp.arange(t)[None], (n, t))
    if ids is None:
        doc = jnp.zeros((n, t), jnp.int32)
    else:
        doc = jnp.cumsum(doc_starts(ids), axis=1)

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

    o = _row_blocks(rows, q, pos, doc).reshape(n, t, d)
    return _mm(o, p["wproj"], quant) + p.get("bproj", 0.0)


def _gated_mlp(p, u, cfg, quant):
    nh = int(cfg["nhidden"])

    def rows(ub):
        gv = _mm(ub, p["wmat"], quant)
        return _mm(silu(gv[..., :nh]) * gv[..., nh:], p["wproj"], quant)

    return _row_blocks(rows, u)


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
        if t == "mamba2":
            y = _mamba2(p, u, doc_ids, cfg, quant)
        elif t == "attention":
            y = _attention(p, u, doc_ids, cfg, quant)
        else:
            y = _gated_mlp(p, u, cfg, quant)
        r = float(cfg.get("residual_scale", 0.0))
        return x + r * y if r else y

    def loss(params, ids, labels):
        nodes = {"0": ids}
        head = None
        for lay in net.layers:
            xs = [nodes[n] for n in lay["ins"]]
            if lay["type"] == "lm_head":
                # the logits are formed where the loss reads them, a
                # block of rows at a time: (T, vocab) in float32, three
                # times over, is what the chip has no room for
                head = (params[by_name[lay["cfg"]["tied"]]],
                        float(lay["cfg"].get("divisor", 1.0)), xs[0])
                nodes[lay["out"]] = None
            elif lay["type"] == "softmax":
                tied, divisor, x = head

                def rows(xb, lab):
                    logp = jax.nn.log_softmax(
                        _mm(xb, tied["wmat"], quant) / divisor, axis=-1)
                    return -jnp.take_along_axis(
                        logp, lab[..., None], axis=-1)[..., 0]

                return (float(lay["cfg"].get("grad_scale", 1.0))
                        / ids.shape[0] * jnp.sum(_row_blocks(rows, x, labels)))
            else:
                run = jax.checkpoint(
                    lambda p, xs, lay=lay: apply(lay, p, xs, ids))
                nodes[lay["out"]] = run(params.get(lay["index"], {}), xs)
        raise ValueError("granite_hybrid: the net ends in no softmax")

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
    configuration states (matrix products and the scan on operands
    rounded to ``float8_e4m3fn``), or a type's name."""
    del key  # nothing here is random
    _last_results_to_host()
    glob = net.glob
    if glob.get("updater") != "adam" or glob.get("lr:schedule",
                                                  "constant") != "constant":
        raise ValueError("granite_hybrid: adam at a constant rate only")
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


def _forward_macs(net: Net) -> float:
    """Multiply-adds of one forward pass: every matrix once a token
    (the tied matrix once, as the head; the embedding is a gather), the
    two attention products over the positions a causal query may see,
    and the recurrence's own operations halved (``scan_flops``)."""
    macs = 0.0
    for lay in net.layers:
        shp = net.pshapes.get(lay["index"], {})
        for tag in ("wmat", "wproj"):
            if tag in shp and len(shp[tag]) == 2 and \
                    lay["type"] != "embedding":
                macs += _tokens(net) * shp[tag][0] * shp[tag][1]
        if lay["type"] == "lm_head":
            macs += _tokens(net) * net.hidden * int(lay["cfg"]["nhidden"])
        elif lay["type"] == "attention":
            seen = ((net.seq + 1) / 2.0 if int(lay["cfg"].get("causal", 0))
                    else float(net.seq))
            macs += _tokens(net) * 2.0 * seen * net.hidden
    return macs + scan_flops(net) / 6.0


def scan_flops(net: Net) -> float:
    """Operations of the recurrence in one training step, all mixers:
    a token and head, ``3 P S`` to move the state on (decay it, form
    ``dt x (x) B``, add) and ``2 P S`` to read ``y`` from it; times 3
    for the forward pass and the two gradients."""
    total = 0.0
    for lay in net.layers:
        if lay["type"] == "mamba2":
            h, p, s, _, _ = _mamba_dims(lay["cfg"])
            total += _tokens(net) * h * 5.0 * p * s * 3.0
    return total


def scan_min_bytes(net: Net, itemsize: int = 2) -> float:
    """The least bytes the recurrence moves in one training step, all
    mixers, with the state held on chip: a token, the forward pass
    reads ``x``, ``B``, ``C`` and ``dt`` and writes ``y``; the backward
    reads them and ``dy`` again and writes the four gradients."""
    total = 0.0
    for lay in net.layers:
        if lay["type"] == "mamba2":
            h, _, s, e, _ = _mamba_dims(lay["cfg"])
            ins = e + 2 * s + h
            total += _tokens(net) * itemsize * ((ins + e) + (ins + e) + ins)
    return total


def step_flops(net: Net) -> float:
    """2 a multiply-add, 3 for forward and the two gradients; a
    recomputed forward does not count."""
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
