"""The plain reference of the SmallThinker family (PowerInfer,
arXiv:2507.20984 — SmallThinker-21BA3B / 4BA0.6B; ``model_name:
smallthinker_21b_instruct``): pre-norm residual blocks of a grouped-query
attention (no biases, no q/k norms, no gate) and ReGLU experts behind a
softmax top-k router THAT READS THE ATTENTION'S INPUT — the router is
placed before the attention so that a device can fetch the chosen
experts while attention runs — while the experts read the attention's
output; one layer of four over the whole document with NO positions,
three under a sliding window with rotate-half rotary positions; no dense
layer, no shared expert; an untied head; under adam.  Named by
``configs/smallthinker_21b_a3b.json``.

Plain ``jax.numpy`` in float32 at ``highest`` matmul precision, written
from the model's ``config.json`` (the widths, ``sliding_window_layout``,
``rope_layout``, the router's keys) and the catalog's ``described_as``
("sparse ReGLU; router placed before attention; NoPE global").  The
family's modeling code is not on this machine (the installed
``transformers`` 4.57.6 has no ``models/smallthinker``), so two things
that neither source settles are listed under the configuration's
``assumed``: the router reads the NORMED input ``u`` (the attention's own
input), not the raw stream; no biases and no q/k norms.  Shazeer 2020 for
the gated experts; Su et al. 2021 for the rotation; Zhang & Sennrich 2019
for rms norm; Kingma & Ba 2014 for adam.  Its own parse of the conf text.
It imports nothing of the program, nothing of ``benchmarks/lib`` and
nothing of the other references: what it has in common with ``afmoe.py``
(the conf grammar, the weights from the seed, adam, the packed rows, the
windowed attention in row blocks) is written out here again.

* A layer, ``x`` the stream, ``n1`` / ``n2`` the two norm weights::

      u = rms(x, n1);   l = u W_r^T                    (E logits, float32)
      (e_1..e_k) = the k largest of softmax(l);  w_j = p_j / sum_k p_k
      x' = x + Attn(u);   v = rms(x', n2)
      x'' = x' + sum_j w_j W_d^e_j (relu(W_g^e_j v) * W_u^e_j v)

  In the conf the expert layer has TWO inputs, ``layer[x0,h0->h1] =
  routed_experts`` — the experts' (the attention layer's output node) and
  the router's (the attention layer's input node) — and ``route_norm =
  attn0`` names the layer whose ``norm`` weight the router's input is
  normed under: ONE leaf ``n1``, whose gradient is the sum of both uses.
  ``softmax`` over all ``E`` then the top ``k`` renormalised is the
  softmax over the chosen logits: the order is no assumption.
* ``Attn``: ``q`` (H heads of Dh), ``k``, ``v`` (Hkv heads) from ONE
  fused matrix (queries, then keys, then values); on a layer with
  ``rotary_dim`` rotate-half rotary at ``pos * theta^(-2i/dim)``, ``pos``
  counted from a document's first token; scores ``q . k / sqrt(Dh)``;
  query ``i`` sees key ``j`` iff same document, ``j <= i`` and, with
  ``window = W``, ``i - j < W`` (itself and the ``W - 1`` before it:
  ``assumed.window_edge``); the full masked matrix a block of rows at a
  time (28 heads x 16384 x 16384 floats would be 30 GB).
* The experts are a **dense loop over the experts held**: every held
  expert runs on every token and its output is multiplied by the
  router's weight for that token, or by 0 (2048 tokens at a time, so
  that the loop's running sums fit beside 9 GB of float32 state).
* The share (model-configs section 4): the router ranks all ``nexpert``
  experts; only the terms of the ``nheld`` experts from ``first_expert``
  on are added, and that partial sum goes on.  The layer's output is
  linear in the experts' terms: the ranks' parts add up to the whole
  layer's.  Where fewer than all are held the routing weights are
  constants of the backward pass (``qwen3_next.py`` has why; the program
  does the same), so nothing of the router reaches ``n1`` or the stream
  before the attention; in a whole layer it does.
* Every conf layer is one ``jax.checkpoint``; ``train_chunk`` donates
  the weights it is handed and leaves its results on the device.

What it restates of the conf grammar: ``layer[a,b->c] = type:name``
(node ``0`` is the token ids); every matrix is ``(out, in)`` but the
held experts', which are ``(expert, in, out)``: ``wmat (nheld, D, 2
nhidden)`` gate | up and ``wproj (nheld, nhidden, D)``; ``prenorm`` /
``residual_scale`` / ``eps`` on a branch layer; adam spelled with decay
rates (``beta1 = 0.1`` is the usual 0.9).

For the roofline readers: ``expert_flops(net, pairs)`` /
``expert_min_bytes(net, pairs)`` (the grouped products, ``pairs`` the
(token, held expert) pairs of one training step over all expert layers)
and ``attn_core_flops(net, window_pairs, full_pairs)`` (the score and
value products of every attention layer: a windowed layer's at
``window_pairs``, the (query, key) pairs a causal query of its own
document may see less than W back in one step's rows, a full layer's at
``full_pairs``, all of them under the diagonal).
"""

from __future__ import annotations

import math
from typing import Dict, List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

ROW_BLOCK = 128      # rows of a score matrix or the head at a time
EXPERT_BLOCK = 2048  # tokens of the dense expert loop at a time
SEP_ID = 0           # a document begins after every separator
DOC_MEDIAN = 4096    # seeded_chunk's documents, as the cell's mix draws them

ONES = ("norm",)


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


def _attn_dims(cfg, d):
    """(heads, key-value heads, head width, window or 0)."""
    h = int(cfg["nhead"])
    return (h, int(cfg.get("nkvhead", h)), int(cfg.get("head_dim", d // h)),
            int(cfg.get("window", 0)))


def _moe_dims(cfg):
    """(experts routed, top-k, first held, held, width)."""
    e, first = int(cfg["nexpert"]), int(cfg.get("first_expert", 0))
    return (e, int(cfg["topk"]), first, int(cfg.get("nheld", e - first)),
            int(cfg["nhidden"]))


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
        elif t == "attention":
            h, hk, dh, _ = _attn_dims(cfg, d)
            shp = {"wmat": ((h + 2 * hk) * dh, d), "wproj": (d, h * dh)}
            if not int(cfg.get("no_bias", 0)) or int(cfg.get("qk_norm", 0)):
                raise ValueError("smallthinker: attention has no biases "
                                 "and no q/k norms")
        elif t == "routed_experts":
            e, _, _, g, f = _moe_dims(cfg)
            if cfg.get("expert_act") != "reglu":
                raise ValueError("smallthinker: the experts are ReGLU")
            shp = {"wgate": (e, d), "wmat": (g, d, 2 * f),
                   "wproj": (g, f, d)}
        elif t == "rms_norm":
            shp = {"wmat": (d,)}
        elif t == "lm_head":
            shp = {"wmat": (int(cfg["nhidden"]), d)}
            d = int(cfg["nhidden"])
        elif t != "softmax":
            raise ValueError(f"smallthinker: no layer type {t!r}")
        if int(cfg.get("prenorm", 0)):
            shp["norm"] = (width[lay["ins"][0]],)
        if shp and not lay.get("shared"):
            pshapes[lay["index"]] = shp
        width[lay["out"]] = d
    return Net(layers, glob, pshapes, int(batch), seq, hidden)


# ----------------------------------------------------------------------
def make_weights(net: Net, seed: int):
    """Every leaf from the seed in one jitted call (the configuration's
    ``assumed.init``): matrices normal at 0.02, the routers among them;
    the EMBEDDING normal at 1 as ``joyai_llm_flash.py`` draws it (a
    token's own row has to stand out of what attention adds to the
    stream, or every token of a document asks a router for the same
    experts); the norms 1."""
    kinds = {lay["index"]: lay["type"] for lay in net.layers}

    @jax.jit
    def make(key):
        out = {}
        for i, tags in net.pshapes.items():
            out[i] = {}
            for n, (tag, shp) in enumerate(sorted(tags.items())):
                k = jax.random.fold_in(jax.random.fold_in(key, i), n)
                if kinds[i] == "embedding":
                    w = jax.random.normal(k, shp, jnp.float32)
                elif tag in ONES or (tag == "wmat" and len(shp) == 1):
                    w = jnp.ones(shp, jnp.float32)
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


def doc_starts(ids):
    """(B, T) bool: a row's first token, and every token that follows a
    separator, begins a document."""
    return jnp.concatenate(
        [jnp.ones_like(ids[:, :1], bool), ids[:, :-1] == SEP_ID], axis=1)


def _row_blocks(fn, x, *more, rows=ROW_BLOCK):
    """``fn`` over blocks of ``rows`` rows (axis 1) of ``x`` and of every
    array in ``more``, each block under ``jax.checkpoint``."""
    t = x.shape[1]
    nb = t // rows if t % rows == 0 and t > rows else 1
    if nb == 1:
        return fn(x, *more)
    cut = lambda a: jnp.moveaxis(  # noqa: E731
        a.reshape((a.shape[0], nb, t // nb) + a.shape[2:]), 1, 0)
    out = lax.map(lambda a: jax.checkpoint(fn)(*a),
                  tuple(cut(a) for a in (x,) + more))
    return jnp.moveaxis(out, 0, 1).reshape((x.shape[0], t) + out.shape[3:])


def rotate(x, pos, dim, theta):
    """Rotate-half on the first ``dim`` of each head of ``(B, T, H,
    Dh)``: the pairs ``(x[i], x[i + dim/2])`` at ``pos *
    theta^(-2i/dim)``."""
    half = dim // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dim)
    ang = pos.astype(jnp.float32)[..., None] * freq
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:dim]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., dim:]], axis=-1)


def attention(p, u, ids, cfg, quant=None):
    """The mixer alone (no branch): ``u (B, T, D)`` the normed input,
    ``ids (B, T)`` or ``None`` (one document a row)."""
    n, t, d = u.shape
    h, hk, dh, window = _attn_dims(cfg, d)
    nq = h * dh
    scale = float(cfg.get("score_scale", 1.0 / math.sqrt(dh)))
    qkv = _mm(u, p["wmat"], quant)
    q, qkv = qkv[..., :nq].reshape(n, t, h, dh), qkv[..., nq:]
    k = qkv[..., :hk * dh].reshape(n, t, hk, dh)
    v = qkv[..., hk * dh:].reshape(n, t, hk, dh)
    start = (doc_starts(ids) if ids is not None
             else jnp.arange(t)[None].repeat(n, 0) == 0)
    doc = jnp.cumsum(start, axis=1)
    pos = jnp.broadcast_to(jnp.arange(t)[None], (n, t))
    rot = int(cfg.get("rotary_dim", 0))
    if rot:
        # a position is counted from its document's first token
        rel = pos - lax.cummax(jnp.where(start, pos, 0), axis=1)
        theta = float(cfg.get("rope_theta", 10000.0))
        q, k = (rotate(x, rel, rot, theta) for x in (q, k))
    q = q.reshape(n, t, hk, h // hk, dh)

    def rows(qb, posb, docb):
        sc = jnp.einsum("bqgrd,bkgd->bgrqk", _q(qb, quant),
                        _q(k, quant)) * scale
        seen = docb[:, :, None] == doc[:, None, :]
        back = posb[:, :, None] - pos[:, None, :]
        if int(cfg.get("causal", 0)):
            seen = seen & (back >= 0)
        if window:
            # itself and the window - 1 before it
            seen = seen & (back < window)
        sc = jnp.where(seen[:, None, None], sc, -jnp.inf)
        return jnp.einsum("bgrqk,bkgd->bqgrd",
                          _q(jax.nn.softmax(sc, axis=-1), quant),
                          _q(v, quant))

    o = _row_blocks(rows, q, pos, doc)
    return _mm(o.reshape(n, t, nq), p["wproj"], quant)


def router(p, x, cfg):
    """``x (M, D)``, the router's OWN input -> (weights ``(M, k)``,
    expert ids ``(M, k)``): the softmax over all experts, the ``topk``
    largest (the lower id first where two are equal), divided by their
    sum (``norm_topk``); constants of the backward pass in a share.
    Always float32 at the highest precision: the control rounds it not."""
    e, topk, _, g, _ = _moe_dims(cfg)
    s = jax.nn.softmax(x @ p["wgate"].T, axis=-1)
    w, idx = lax.top_k(s, topk)
    if int(cfg.get("norm_topk", 1)):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    # a share: the weights' cotangent needs the other ranks' terms
    return (lax.stop_gradient(w) if g < e else w), idx


def _reglu(x, wmat, wproj, quant):
    f = wmat.shape[0] // 2
    gu = _mm(x, wmat, quant)
    return _mm(jax.nn.relu(gu[..., :f]) * gu[..., f:], wproj, quant)


def routed_experts(p, v, seen, cfg, quant=None):
    """The expert layer alone (no branch): the held experts on the
    normed input ``v``, weighed by the router's reading of ``seen`` (the
    router's own input, already normed; ``v`` itself in a layer of one
    input).  The dense loop runs a block of ``EXPERT_BLOCK`` tokens at a
    time: a loop over 16 experts keeps its running sum once an expert
    for the backward pass, 2.7 GB for a 16384-token row of 2560."""
    _, _, first, g, _ = _moe_dims(cfg)
    x = v.reshape(1, -1, v.shape[-1])
    w, idx = router(p, seen.reshape(x.shape[1:]), cfg)
    held = (first + jnp.arange(g), p["wmat"], p["wproj"])

    def rows(xb, wb, ib):
        def one(y, ew):
            e, wmat, wproj = ew
            # the router's weight for expert e a token, or 0: dense, masked
            mask = jnp.sum(jnp.where(ib == e, wb, 0.0), axis=-1)
            # a held expert's matrices are (in, out)
            return y + mask[..., None] * _reglu(xb, wmat.T, wproj.T,
                                                quant), None

        return lax.scan(one, jnp.zeros_like(xb), held)[0]

    return _row_blocks(rows, x, w[None], idx[None],
                       rows=EXPERT_BLOCK).reshape(v.shape)


def loss_fn(net: Net, quant=None):
    def apply(lay, p, xs, ids):
        t, cfg, x = lay["type"], lay["cfg"], xs[0]
        if t == "embedding":
            return p["wmat"][x]
        eps = float(cfg.get("eps", 1e-5))
        if t == "rms_norm":
            return rms_norm(x, p["wmat"], eps)
        u = x
        if int(cfg.get("prenorm", 0)):
            u = rms_norm(x, p["norm"], eps)
        if t == "attention":
            y = attention(p, u, ids if len(xs) > 1 else None, cfg, quant)
        else:
            seen = u
            if len(xs) > 1:
                # the router reads the layer's second input, under the
                # norm weight of the layer route_norm names
                seen = xs[1]
                if "route_norm" in p:
                    seen = rms_norm(seen, p["route_norm"], eps)
            y = routed_experts(p, u, seen, cfg, quant)
        r = float(cfg.get("residual_scale", 0.0))
        return x + r * y if r else y

    def loss(params, ids, labels):
        nodes = {"0": ids}
        named = {lay["name"]: lay["owner"] for lay in net.layers
                 if lay["name"]}
        head = None
        total, losses = 0.0, 0
        for lay in net.layers:
            xs = [nodes[n] for n in lay["ins"]]
            p = params.get(lay["owner"], {})
            if "route_norm" in lay["cfg"]:
                # one leaf, two uses: its gradient is their sum
                p = dict(p, route_norm=params[
                    named[lay["cfg"]["route_norm"]]]["norm"])
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
            raise ValueError("smallthinker: the net has no softmax")
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
    operands rounded to ``float8_e4m3fn``; the router stays float32), or
    a type's name."""
    del key  # nothing here is random
    _last_results_to_host()
    glob = net.glob
    if glob.get("updater") != "adam" or glob.get("lr:schedule",
                                                  "constant") != "constant":
        raise ValueError("smallthinker: adam at a constant rate only")
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
    length (median 4096, sigma 1.2, clipped to 16..seq) of ids uniform
    over 1..V-1, a separator 0 after each, cut at a row's end; a row's
    labels are the stream moved on by one."""
    vocab = next(int(lay["cfg"]["nvocab"]) for lay in net.layers
                 if lay["type"] == "embedding")
    rng = np.random.RandomState(seed % 2147483629)
    need = scan * net.batch * net.seq + 1
    parts, have = [], 0
    while have < need:
        n = int(np.clip(np.round(np.exp(
            rng.normal(math.log(min(DOC_MEDIAN, net.seq)), 1.2))),
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
    nheld / nexpert`` a token (1.5 at 6 of 64 with 16 held)."""
    total = 0.0
    for lay in _of(net, "routed_experts"):
        e, topk, _, g, _ = _moe_dims(lay["cfg"])
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


def _attn_layers(net: Net):
    """[(heads x 2 x head width, window or 0)] of the attention layers:
    the multiply-adds a (query, key) pair, the score product and the
    value product over one head width each."""
    out = []
    for lay in _of(net, "attention"):
        h, _, dh, window = _attn_dims(lay["cfg"], net.hidden)
        out.append((2.0 * h * dh, window))
    return out


def attn_core_flops(net: Net, window_pairs: float,
                    full_pairs: float) -> float:
    """Operations of the score and value products of every attention
    layer in one training step: a WINDOWED layer's at ``window_pairs``,
    the (query, key) pairs a causal query of its own document may see
    less than ``window`` positions back in the step's rows, a FULL
    layer's at ``full_pairs``, all those under the diagonal; 2 a
    multiply-add, 3 for the forward pass and the two gradients; a
    recomputed forward does not count, nor do the pairs a mask throws
    away."""
    macs = sum(m * (window_pairs if window else full_pairs)
               for m, window in _attn_layers(net))
    return float(macs) * 2.0 * 3.0


def row_pairs(seq: int, window: int = 0) -> float:
    """The (query, key) pairs of one row that is ONE document: under the
    diagonal, and within ``window`` of it."""
    w = min(window, seq) if window else seq
    return w * (w + 1) / 2.0 + (seq - w) * float(w)


def _forward_macs(net: Net) -> float:
    """Multiply-adds of one forward pass: every matrix once a token for
    each layer that computes with it (an embedding is a gather), a held
    expert's three matrices once a pair at the expected ``topk * nheld /
    nexpert`` pairs a token, and the two attention products over the
    positions a causal query may see — under the diagonal AND, on a
    windowed layer, the window (the whole row one document: documents
    are not counted)."""
    macs = 0.0
    for lay in net.layers:
        if lay["type"] != "embedding":
            for tag, s in net.pshapes.get(lay["owner"], {}).items():
                if len(s) == 2:
                    macs += _tokens(net) * s[0] * s[1]
    macs += net.batch * sum(m * row_pairs(net.seq, window)
                            for m, window in _attn_layers(net))
    return macs + expected_pairs(net) * _expert_macs_a_pair(net)


def step_flops(net: Net) -> float:
    """2 a multiply-add, 3 for forward and the two gradients; a
    recomputed forward does not count.  The token cells' convention:
    attention over the positions a causal query may see with documents
    NOT counted (``attn_core_flops`` at a run's own pairs counts them)
    — but a windowed layer's only within its window, so that a share of
    the peak credits no pair the model does not ask for — the experts
    at the EXPECTED pairs (``expected_pairs``), not at a run's count."""
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
