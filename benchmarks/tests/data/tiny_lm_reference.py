"""A throw-away reference of another family than ``lib/reference.py``:
a pre-norm causal transformer language model under adam, dropped in as
a file to prove that a configuration can bring its own
(``helpers.copy_with_dropins`` copies it to ``references/``).

Plain ``jax.numpy`` in float32 at ``highest`` matmul precision, written
from the equations (Vaswani et al. 2017; Ba et al. 2016 for layer norm;
Hendrycks & Gimpel 2016 for gelu, tanh form; Kingma & Ba 2014 for adam)
with its own parse of the conf text.  It imports nothing of the program
and nothing of ``benchmarks/lib``.  What it restates of the conf
grammar: ``embedding`` adds a learned position table, ``attention``
keeps q, k and v in one ``(3D, D)`` matrix, every matrix is ``(out,
in)``, the loss is ``grad_scale / batch`` times the summed
cross-entropy of every position, and adam is spelled with decay rates
(``beta1 = 0.1`` is the usual 0.9), takes the constant rate ``eta`` and
subtracts ``wd * w`` from the gradient.
"""

from __future__ import annotations

import math
from typing import Dict, List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

CAUSAL_MASK = True  # a control of the tests leaves the mask out


class Net(NamedTuple):
    layers: List[dict]
    glob: Dict[str, str]
    pshapes: Dict[int, Dict[str, tuple]]
    batch: int
    seq: int
    widths: List[int]  # every layer's output width, in conf order


def parse(text: str):
    """(layers in conf order, global keys): ``layer[a,b->c] = type:name``
    opens a layer inside the netconfig block and the keys after it are
    its own; ``layer[+1:c]`` reads the last output."""
    layers, glob, top, inside = [], {}, "in", False
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
                ins = ["in" if n == "0" else n for n in src.split(",")]
            layers.append({"index": len(layers), "type": v.partition(":")[0],
                           "ins": ins, "out": out, "cfg": {}})
            top = out
        elif inside and layers:
            layers[-1]["cfg"][k] = v
        else:
            glob[k] = v
    return layers, glob


def describe(net_text: str, batch: int) -> Net:
    layers, glob = parse(net_text)
    seq = int(glob["input_shape"].split(",")[2])
    width, widths = {"in": None}, []
    pshapes: Dict[int, Dict[str, tuple]] = {}
    for lay in layers:
        t, cfg, d = lay["type"], lay["cfg"], width[lay["ins"][0]]
        if t == "embedding":
            d = int(cfg["nhidden"])
            pshapes[lay["index"]] = {"wmat": (int(cfg["nvocab"]), d)}
            if cfg.get("pos") == "learned":
                pshapes[lay["index"]]["pos"] = (seq, d)
        elif t == "layer_norm":
            pshapes[lay["index"]] = {"wmat": (d,), "bias": (d,)}
        elif t == "attention":
            pshapes[lay["index"]] = {"wmat": (3 * d, d), "bias": (3 * d,),
                                     "wproj": (d, d), "bproj": (d,)}
        elif t == "fullc":
            nh = int(cfg["nhidden"])
            pshapes[lay["index"]] = {"wmat": (nh, d), "bias": (nh,)}
            d = nh
        elif t not in ("gelu", "eltwise_sum", "softmax"):
            raise ValueError(f"tiny_lm_reference: no layer type {t!r}")
        width[lay["out"]] = d
        widths.append(d)
    return Net(layers, glob, pshapes, int(batch), seq, widths)


def make_weights(net: Net, seed: int):
    """Every leaf from the seed in one jitted call: matrices gaussian at
    sqrt(1 / fan_in), the tables at 0.1, norm slopes 1, biases 0."""
    kinds = {lay["index"]: lay["type"] for lay in net.layers}

    @jax.jit
    def make(key):
        out = {}
        for i, tags in net.pshapes.items():
            out[i] = {}
            for n, (tag, shp) in enumerate(tags.items()):
                k = jax.random.fold_in(jax.random.fold_in(key, i), n)
                if len(shp) == 1:
                    one = kinds[i] == "layer_norm" and tag == "wmat"
                    w = jnp.full(shp, 1.0 if one else 0.0, jnp.float32)
                else:
                    sigma = (0.1 if kinds[i] == "embedding"
                             else math.sqrt(1.0 / shp[1]))
                    w = jax.random.normal(k, shp, jnp.float32) * sigma
                out[i][tag] = w
        return out

    return make(jax.random.PRNGKey(seed))


def _q(x, quant):
    return x if quant is None else x.astype(quant).astype(jnp.float32)


def _attention(p, x, cfg, quant):
    b, t, d = x.shape
    h = int(cfg["nhead"])
    qkv = _q(x, quant) @ _q(p["wmat"], quant).T + p["bias"]
    q, k, v = (qkv[..., j * d:(j + 1) * d].reshape(b, t, h, d // h)
               for j in range(3))
    s = jnp.einsum("bqhd,bkhd->bhqk", _q(q, quant), _q(k, quant))
    s = s / math.sqrt(d // h)
    if int(cfg.get("causal", 0)) and CAUSAL_MASK:
        seen = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        s = jnp.where(seen[None, None], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", _q(jax.nn.softmax(s, axis=-1), quant),
                   _q(v, quant)).reshape(b, t, d)
    return _q(o, quant) @ _q(p["wproj"], quant).T + p["bproj"]


def loss_fn(net: Net, quant=None):
    def loss(params, ids, labels):
        nodes = {"in": ids}
        for lay in net.layers:
            t, cfg = lay["type"], lay["cfg"]
            p = params.get(lay["index"], {})
            xs = [nodes[n] for n in lay["ins"]]
            x = xs[0]
            if t == "embedding":
                y = p["wmat"][ids]
                if "pos" in p:
                    y = y + p["pos"][None]
            elif t == "layer_norm":
                mean = jnp.mean(x, axis=-1, keepdims=True)
                var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
                y = ((x - mean) / jnp.sqrt(var + float(cfg.get("eps", 1e-6)))
                     * p["wmat"] + p["bias"])
            elif t == "attention":
                y = _attention(p, x, cfg, quant)
            elif t == "fullc":
                y = _q(x, quant) @ _q(p["wmat"], quant).T + p["bias"]
            elif t == "gelu":
                y = 0.5 * x * (1.0 + jnp.tanh(
                    math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))
            elif t == "eltwise_sum":
                y = sum(xs[1:], x)
            else:  # softmax: the loss reads the logits
                logp = jax.nn.log_softmax(x, axis=-1)
                ce = -jnp.take_along_axis(logp, labels[..., None], axis=-1)
                return (float(cfg.get("grad_scale", 1.0)) / ids.shape[0]
                        * jnp.sum(ce))
            nodes[lay["out"]] = y
        raise ValueError("tiny_lm_reference: the net ends in no softmax")

    return loss


def train_chunk(net: Net, weights, data, labels, key, control=None):
    """Follow one chunk of ``data`` and ``labels`` [K, B, T].  Returns
    (losses [K], params after, adam's first moment after).  ``control``:
    True for the step below the float32 the configuration states
    (bfloat16 matmul inputs), or a type's name."""
    del key  # nothing here is random
    glob = net.glob
    if glob.get("updater") != "adam" or glob.get("lr:schedule",
                                                  "constant") != "constant":
        raise ValueError("tiny_lm_reference: adam at a constant rate only")
    quant = None
    if control is not None:
        quant = jnp.bfloat16 if control is True else getattr(jnp, control)
    d1, d2 = float(glob.get("beta1", 0.1)), float(glob.get("beta2", 0.001))
    tags = {t for tg in net.pshapes.values() for t in tg}
    base_lr = float(glob.get("eta", glob.get("lr", 0.01)))
    lr = {t: float(glob.get(f"{t}:lr", glob.get(f"{t}:eta", base_lr)))
          for t in tags}
    wd = {t: float(glob.get(f"{t}:wd", glob.get("wd", 0.0))) for t in tags}
    loss = loss_fn(net, quant)

    @jax.jit
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

    k = int(data.shape[0])
    ids = np.asarray(data).reshape(k, net.batch, net.seq).round().astype(
        np.int32)
    lab = np.asarray(labels).reshape(k, net.batch, net.seq).round().astype(
        np.int32)
    params = weights
    m1 = jax.tree_util.tree_map(jnp.zeros_like, params)
    m2 = m1
    losses = []
    with jax.default_matmul_precision("highest"):
        for i in range(k):
            params, m1, m2, l = step(params, m1, m2, ids[i], lab[i],
                                     jnp.float32(i))
            losses.append(l)
    losses = np.asarray(jax.device_get(jnp.stack(losses)), np.float64)
    return losses, jax.device_get(params), jax.device_get(m1)


def seeded_chunk(net: Net, seed: int, scan: int):
    """For ``tools/limits.py``, which has no feed: ``scan`` batches of
    seeded token rows that all differ, each row's labels its tokens
    moved on by one."""
    vocab = next(int(lay["cfg"]["nvocab"]) for lay in net.layers
                 if lay["type"] == "embedding")
    rows = np.random.RandomState(seed % 2147483629).randint(
        0, vocab, (scan, net.batch, net.seq + 1))
    return (rows[..., :-1].astype(np.float32),
            rows[..., 1:].astype(np.float32))


def program_update_state(ustates):
    """Adam's first moment, ``m1``: a running mean of the gradients as
    the optimizer got them."""
    return {i: {t: s["m1"] for t, s in tags.items()}
            for i, tags in ustates.items()}


def _matmul_macs(net: Net) -> float:
    """Multiply-adds of one forward pass: every matrix once a token, and
    the two attention products over the positions a query may see."""
    tokens = float(net.batch * net.seq)
    macs = 0.0
    for lay in net.layers:
        shp = net.pshapes.get(lay["index"], {})
        if lay["type"] == "fullc":
            macs += tokens * shp["wmat"][0] * shp["wmat"][1]
        elif lay["type"] == "attention":
            d = shp["wproj"][0]
            seen = (net.seq + 1) / 2.0 if int(
                lay["cfg"].get("causal", 0)) else float(net.seq)
            macs += tokens * (4.0 * d * d + 2.0 * seen * d)
    return macs


def step_flops(net: Net) -> float:
    """2 a multiply-add, 3 for forward and the two gradients."""
    return _matmul_macs(net) * 2.0 * 3.0


def step_min_bytes(net: Net) -> float:
    """Every parameter read, its gradient written and read, both moments
    and the weight read and written (8 passes in float32), and every
    layer's output through 5 passes as in ``lib/netconf.py``."""
    params = sum(float(np.prod(s)) for t in net.pshapes.values()
                 for s in t.values())
    acts = float(net.batch * net.seq) * sum(net.widths)
    return acts * 4 * 5.0 + params * 4 * 8.0
