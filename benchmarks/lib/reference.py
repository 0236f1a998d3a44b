"""The plain reference: forward pass, softmax loss, gradients through
``jax.grad`` and the sgd-momentum update, in straightforward
``jax.numpy`` / ``lax`` at float32 and ``highest`` matmul precision.

Written from the layer equations (Szegedy et al. 2014; He et al. 2015;
Krizhevsky et al. 2012 for LRN; Ioffe & Szegedy 2015 for batch norm;
Srivastava et al. 2014 for dropout) for just the layer types the
benchmark's confs use.  It reads the conf's layer list through
``netconf.parse_net`` and imports nothing of ``cxxnet_tpu``; it is
given the weights the benchmark made from the seed, never the
program's.  Departures from the papers, all the conf grammar's own:

* pooling uses ceil-mode output sizes with partial edge windows,
  average pooling divides by k*k whatever the window holds, and max
  pooling's backward is cxxnet's unpool rule (``_max_pool``);
* batch norm normalises with the current batch's statistics (biased
  variance, eps from the conf, default 1e-10) and keeps no running
  average;
* the loss is the sum of the rows' cross-entropies over the batch size.

``quant`` computes the same network in a lower precision, for the
control that has to come out not correct: every conv and fullc input
and weight is rounded to that type first (``float8_e4m3fn`` is the
step below the bfloat16 both configurations state).
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .netconf import Layer, _pool_out, residual_branch_norms

Params = Dict[int, Dict[str, jnp.ndarray]]


def _q(x, quant):
    return x if quant is None else x.astype(quant).astype(jnp.float32)


def _pool_geometry(x, lay: Layer):
    k = int(lay.cfg["kernel_size"])
    s = int(lay.cfg.get("stride", 1))
    p = int(lay.cfg.get("pad", 0))
    pads, outs = [(0, 0)], []
    for n in x.shape[1:3]:
        out = _pool_out(n, k, s, p)
        outs.append(out)
        pads.append((p, max(0, (out - 1) * s + k - n - p)))
    pads.append((0, 0))
    return k, s, pads, outs


def _avg_pool(x, lay: Layer):
    k, s, pads, _ = _pool_geometry(x, lay)
    return lax.reduce_window(x, 0.0, lax.add, (1, k, k, 1), (1, s, s, 1),
                             pads) / float(k * k)


def _max_pool(x, lay: Layer):
    """Max over each window; backward by cxxnet's unpool rule, which the
    program documents as its own: every input equal to its window's max
    receives that window's gradient, so equal maxima are not split.  It
    matters wherever a pool reads a pool (overlapping windows hand the
    same maximum to neighbours)."""
    k, s, pads, (oh, ow) = _pool_geometry(x, lay)

    def windows(xp):
        return [xp[:, dy:dy + (oh - 1) * s + 1:s,
                   dx:dx + (ow - 1) * s + 1:s, :]
                for dy in range(k) for dx in range(k)]

    @jax.custom_vjp
    def pool(x):
        xp = jnp.pad(x, pads, constant_values=-jnp.inf)
        return functools.reduce(jnp.maximum, windows(xp))

    def fwd(x):
        y = pool(x)
        return y, (x, y)

    def bwd(res, g):
        x, y = res
        xp = jnp.pad(x, pads, constant_values=-jnp.inf)
        wins, back = jax.vjp(windows, xp)
        (dxp,) = back([jnp.where(w == y, g, 0.0) for w in wins])
        return (dxp[:, pads[1][0]:pads[1][0] + x.shape[1],
                    pads[2][0]:pads[2][0] + x.shape[2], :],)

    pool.defvjp(fwd, bwd)
    return pool(x)


def apply_layer(lay: Layer, params, xs, *, key=None, quant=None):
    x = xs[0]
    t = lay.type
    if t == "conv":
        s = int(lay.cfg.get("stride", 1))
        p = int(lay.cfg.get("pad", 0))
        y = lax.conv_general_dilated(
            _q(x, quant), _q(params["wmat"], quant), (s, s),
            ((p, p), (p, p)), dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return y + params["bias"] if "bias" in params else y
    if t == "fullc":
        y = _q(x, quant) @ _q(params["wmat"], quant).T
        return y + params["bias"] if "bias" in params else y
    if t == "relu":
        return jnp.maximum(x, 0.0)
    if t == "max_pooling":
        return _max_pool(x, lay)
    if t == "avg_pooling":
        return _avg_pool(x, lay)
    if t == "lrn":
        n = int(lay.cfg.get("local_size", 3))
        alpha = float(lay.cfg.get("alpha", 0.001))
        beta = float(lay.cfg.get("beta", 0.75))
        knorm = float(lay.cfg.get("knorm", 1.0))
        half = n // 2
        sq = jnp.pad(x * x, ((0, 0),) * 3 + ((half, n - 1 - half),))
        win = sum(sq[..., i:i + x.shape[3]] for i in range(n))
        return x * (knorm + (alpha / n) * win) ** (-beta)
    if t == "batch_norm":
        eps = float(lay.cfg.get("eps", 1e-10))
        mean = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.mean((x - mean) ** 2, axis=(0, 1, 2))
        return ((x - mean) / jnp.sqrt(var + eps) * params["wmat"]
                + params["bias"])
    if t == "ch_concat":
        return jnp.concatenate(xs, axis=3)
    if t == "eltwise_sum":
        return sum(xs[1:], xs[0])
    if t == "flatten":
        return x.reshape(x.shape[0], -1)
    if t == "dropout":
        drop = float(lay.cfg.get("threshold", 0.0))
        if key is None or drop <= 0.0:
            return x
        keep = 1.0 - drop
        mask = jax.random.bernoulli(jax.random.fold_in(key, lay.index),
                                    keep, x.shape)
        return jnp.where(mask, x / keep, 0.0)
    if t == "softmax":
        return x  # the loss reads the logits
    raise ValueError(f"reference: unknown layer type {t!r}")


def _segments(layers: Sequence[Layer]) -> List[Tuple[int, int, str, str]]:
    """Cut the layer list wherever exactly one node is live, so each
    piece can be recomputed in the backward pass (``jax.checkpoint``)
    and the float32 activations of a whole network never sit in memory
    at once.  Same mathematics; only what is kept changes.  Returns
    (first layer, one past the last, node read, node handed on)."""
    last_write: Dict[str, int] = {"in": -1}
    reads: List[Tuple[int, str, int]] = []  # (reader, node, its writer)
    for lay in layers:
        for n in lay.ins:
            reads.append((lay.index, n, last_write[n]))
        for n in lay.outs:
            last_write[n] = lay.index
    segs, lo, name_in = [], 0, "in"
    for i in range(len(layers) - 1):
        live = {(n, w) for r, n, w in reads if r > i and w <= i}
        if len(live) == 1 and i + 1 - lo >= 6:
            (name, _), = live
            segs.append((lo, i + 1, name_in, name))
            lo, name_in = i + 1, name
    segs.append((lo, len(layers), name_in, layers[-1].outs[0]))
    return segs


def logits_fn(layers: Sequence[Layer], quant=None, remat: bool = True):
    """``f(params, x, key) -> logits`` over the conf's layers."""
    segs = (_segments(layers) if remat
            else [(0, len(layers), "in", layers[-1].outs[0])])

    def piece(lo, hi, name_in, name_out):
        def seg(params, x, key):
            nodes = {name_in: x}
            for lay in layers[lo:hi]:
                y = apply_layer(lay, params.get(lay.index, {}),
                                [nodes[n] for n in lay.ins],
                                key=key, quant=quant)
                for n in lay.outs:
                    nodes[n] = y
            return nodes[name_out]
        return jax.checkpoint(seg) if remat else seg

    def f(params, x, key):
        for lo, hi, name_in, name_out in segs:
            sub = {i: params[i] for i in range(lo, hi) if i in params}
            x = piece(lo, hi, name_in, name_out)(sub, x, key)
        return x

    return f


def loss_fn(layers: Sequence[Layer], quant=None, remat: bool = True):
    """Mean over the batch of the rows' cross-entropy, and the logits."""
    f = logits_fn(layers, quant, remat)

    def loss(params, x, labels, key):
        z = f(params, x.astype(jnp.float32), key)
        logp = jax.nn.log_softmax(z, axis=-1)
        lab = labels.reshape(-1).astype(jnp.int32)
        ce = -jnp.take_along_axis(logp, lab[:, None], axis=-1)
        return jnp.sum(ce) / x.shape[0], z

    return loss


class Sgd:
    """The conf's updater settings as the reference's own sgd with
    momentum: ``m = mu*m - lr*(g + wd*w); w = w + m``, the rate and the
    decay chosen per tag (``wmat:lr`` overrides ``eta`` for weights),
    the schedule evaluated at the number of updates made so far."""

    def __init__(self, glob: Dict[str, str]) -> None:
        if glob.get("updater", "sgd") != "sgd":
            raise ValueError("reference: only updater = sgd is written")
        self.mom = float(glob.get("momentum", 0.9))
        base_lr = float(glob.get("eta", glob.get("lr", 0.01)))
        base_wd = float(glob.get("wd", 0.0))
        self.lr = {t: float(glob.get(f"{t}:lr", glob.get(f"{t}:eta",
                                                          base_lr)))
                   for t in ("wmat", "bias")}
        self.wd = {t: float(glob.get(f"{t}:wd", base_wd))
                   for t in ("wmat", "bias")}
        self.schedule = glob.get("lr:schedule", "constant")
        self.gamma = float(glob.get("lr:gamma", 0.5))
        self.alpha = float(glob.get("lr:alpha", 0.5))
        self.step = int(glob.get("lr:step", 1))
        self.minimum = float(glob.get("lr:minimum_lr", 1e-5))
        if self.schedule not in ("constant", "polydecay"):
            raise ValueError(
                f"reference: lr:schedule {self.schedule!r} not written")

    def rate(self, tag: str, epoch):
        e = jnp.asarray(epoch, jnp.float32)
        lr = jnp.full_like(e, self.lr[tag])
        if self.schedule == "polydecay":
            lr = lr * (1.0 + jnp.floor(e / self.step) * self.gamma) ** (
                -self.alpha)
        return jnp.maximum(lr, self.minimum)

    def apply(self, params: Params, mom: Params, grads: Params, epoch):
        new_p, new_m = {}, {}
        for i, tags in params.items():
            new_p[i], new_m[i] = {}, {}
            for tag, w in tags.items():
                m = (self.mom * mom[i][tag]
                     - self.rate(tag, epoch)
                     * (grads[i][tag] + self.wd[tag] * w))
                new_m[i][tag] = m
                new_p[i][tag] = w + m
        return new_p, new_m


def dropout_keys(run_key, n_steps: int):
    """The per-step keys of one scanned chunk.  The only thing the
    reference repeats of the program: a chunk takes one key split off
    the run's key, and every step splits its own off that in turn; a
    layer folds its index in (``apply_layer``).  Without the same mask
    GoogLeNet's gradients could not be compared at all."""
    _, k = jax.random.split(run_key)
    keys = []
    for _ in range(n_steps):
        k, sub = jax.random.split(k)
        keys.append(sub)
    return keys


def train_chunk(layers, glob, params: Params, data, labels, run_key,
                quant=None, first_epoch: int = 0, precision="highest"):
    """Follow one chunk: ``data`` [K, B, H, W, C], ``labels`` [K, B, 1].
    Returns (losses [K], params after, momentum after) as numpy."""
    sgd = Sgd(glob)
    loss = loss_fn(layers, quant)

    @jax.jit
    def step(p, m, x, y, key, epoch):
        (l, _), g = jax.value_and_grad(loss, has_aux=True)(p, x, y, key)
        p2, m2 = sgd.apply(p, m, g, epoch)
        return p2, m2, l

    k = int(data.shape[0])
    keys = dropout_keys(run_key, k)
    mom = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses = []
    with jax.default_matmul_precision(precision):
        for i in range(k):
            params, mom, l = step(params, mom, jnp.asarray(data[i]),
                                  jnp.asarray(labels[i]), keys[i],
                                  jnp.int32(first_epoch + i))
            losses.append(l)
    losses = np.asarray(jax.device_get(jnp.stack(losses)), np.float64)
    return losses, jax.device_get(params), jax.device_get(mom)


def make_weights(layers: Sequence[Layer], shapes: Dict[int, dict],
                 pshapes: Dict[int, Dict[str, tuple]], seed: int) -> Params:
    """Every weight from the seed, on the device, in one jitted call, in
    float32 (the type the program keeps its master weights in).  Convs:
    gaussian at sqrt(2 / fan_in) (He et al. 2015, and PR 21's finding
    for the inception 1x1 reduces); fullc: uniform at
    sqrt(6 / (in + out)) (Glorot & Bengio 2010); biases 0; batch-norm
    slope 1, shift 0, but slope 0 where a batch norm closes a residual
    branch (Goyal et al. 2017: each block starts as the identity; with
    slope 1 the shipped rate of 0.1 drives the loss from 7.7 up to 10-12
    inside one chunk and no comparison of precisions holds, PERF.md
    PR 24).  One gaussian and one uniform draw are cut into the leaves,
    so the program that makes them stays small."""
    kinds = {lay.index: lay.type for lay in layers}
    closing = set(residual_branch_norms(list(layers)))
    size = lambda shp: int(np.prod(shp))  # noqa: E731
    n_conv = sum(size(t["wmat"]) for i, t in pshapes.items()
                 if kinds[i] == "conv")
    n_fc = sum(size(t["wmat"]) for i, t in pshapes.items()
               if kinds[i] == "fullc")

    @jax.jit
    def make(key):
        kc, kf = jax.random.split(key)
        gauss = jax.random.normal(kc, (max(n_conv, 1),), jnp.float32)
        unif = jax.random.uniform(kf, (max(n_fc, 1),), jnp.float32, -1.0, 1.0)
        out: Params = {}
        at_c = at_f = 0
        for i, tags in pshapes.items():
            out[i] = {}
            for tag, shp in tags.items():
                if tag == "bias":
                    w = jnp.zeros(shp, jnp.float32)
                elif kinds[i] == "batch_norm":
                    w = jnp.full(shp, 0.0 if i in closing else 1.0,
                                 jnp.float32)
                elif kinds[i] == "conv":
                    fan_in = shp[0] * shp[1] * shp[2]
                    w = gauss[at_c:at_c + size(shp)].reshape(shp) * math.sqrt(
                        2.0 / fan_in)
                    at_c += size(shp)
                else:
                    w = unif[at_f:at_f + size(shp)].reshape(shp) * math.sqrt(
                        6.0 / (shp[0] + shp[1]))
                    at_f += size(shp)
                out[i][tag] = w
        return out

    return make(jax.random.PRNGKey(seed))


# ----------------------------------------------------------------------
# the comparison that decides `correct`
def _leaf_norms(tree: Params) -> Dict[Tuple[int, str], float]:
    return {(i, t): float(np.linalg.norm(np.asarray(w, np.float64)))
            for i, tags in tree.items() for t, w in tags.items()}


def worst_leaf_gap(prog: Params, ref: Params) -> Tuple[float, str]:
    """The widest gap, over the leaves, between the program's norm and
    the reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger (some leaves are all but zero)."""
    a, b = _leaf_norms(prog), _leaf_norms(ref)
    med = float(np.median(list(b.values())))
    worst, where = 0.0, ""
    for k, nb in b.items():
        gap = abs(a[k] - nb) / max(nb, med, 1e-30)
        if not math.isfinite(gap):
            gap = float("inf")
        if gap >= worst:
            worst, where = gap, f"l{k[0]}.{k[1]}"
    return worst, where


def tree_sub(a: Params, b: Params) -> Params:
    return {i: {t: np.asarray(a[i][t], np.float64)
                - np.asarray(b[i][t], np.float64) for t in a[i]}
            for i in a}


def compare_chunk(prog: dict, ref: dict, start: Params) -> Dict[str, float]:
    """The numbers `correct` is decided on.  ``prog`` and ``ref`` hold
    ``losses``, ``params`` and ``momentum`` after the same chunk from
    the same ``start`` weights."""
    lp = np.asarray(prog["losses"], np.float64)
    lr = np.asarray(ref["losses"], np.float64)
    with np.errstate(all="ignore"):
        loss_gap = np.abs(lp - lr) / np.abs(lr)
    loss_gap = float(np.max(np.where(np.isfinite(loss_gap), loss_gap,
                                     np.inf)))
    upd, upd_at = worst_leaf_gap(prog["momentum"], ref["momentum"])
    dp, dp_at = worst_leaf_gap(tree_sub(prog["params"], start),
                               tree_sub(ref["params"], start))
    return {"loss_gap": loss_gap, "update_norm_gap": upd,
            "update_norm_gap_at": upd_at, "dparam_norm_gap": dp,
            "dparam_norm_gap_at": dp_at}
