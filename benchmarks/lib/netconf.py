"""The benchmark's own reading of a cxxnet conf text.

Nothing here imports the program: the plain reference, the weight
maker and the FLOP count all work from this parse of the conf text the
program is given, so a fault in ``cxxnet_tpu/nnet/graph.py`` or in a
layer's shape rule shows as a disagreement and is not inherited.

Grammar (doc/ of the program, restated): ``name = value`` lines, ``#``
comments; between ``netconfig = start`` and ``netconfig = end`` a line
``layer[src->dst] = type:name`` opens a layer and the keys after it
belong to that layer; ``layer[+1:tag]`` reads the last output and
writes node ``tag``; ``layer[+0]`` works in place.  Keys outside the
netconfig block are global.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple


@dataclasses.dataclass
class Layer:
    index: int
    type: str
    name: str
    ins: List[str]
    outs: List[str]
    cfg: Dict[str, str]


def parse_pairs(text: str) -> List[Tuple[str, str]]:
    out = []
    for raw in text.splitlines():
        line = raw.split("#", 1)[0].strip()
        if not line or "=" not in line:
            continue
        k, v = line.split("=", 1)
        out.append((k.strip(), v.strip()))
    return out


def parse_net(text: str) -> Tuple[List[Layer], Dict[str, str]]:
    """(layers in conf order, global keys with the last value winning)."""
    layers: List[Layer] = []
    glob: Dict[str, str] = {}
    top = "in"
    mode = 0  # 0 outside netconfig, 1 inside, 2 after a layer line
    for k, v in parse_pairs(text):
        if k == "netconfig":
            mode = 1 if v == "start" else 0
            continue
        if k.startswith("layer["):
            body = k[len("layer["):-1]
            if body.startswith("+"):
                if ":" in body:
                    ins, outs = [top], [body.split(":", 1)[1]]
                elif int(body[1:]) == 0:
                    ins, outs = [top], [top]
                else:
                    ins, outs = [top], [f"!after-{top}"]
            else:
                src, dst = body.split("->", 1)
                ins, outs = src.split(","), dst.split(",")
            ins = ["in" if n == "0" else n for n in ins]
            ltype, _, tag = v.partition(":")
            layers.append(Layer(len(layers), ltype, tag, ins, outs, {}))
            top = outs[0]
            mode = 2
            continue
        if mode == 2:
            layers[-1].cfg[k] = v
        else:
            glob[k] = v
    return layers, glob


def _pool_out(n: int, k: int, s: int, p: int) -> int:
    """Ceil-mode pooling size with partial edge windows (cxxnet's
    pooling rule; with a pad, caffe's: the last window starts inside
    the left-padded input)."""
    if p == 0:
        return min(n - k + s - 1, n - 1) // s + 1
    out = (n + 2 * p - k + s - 1) // s + 1
    if (out - 1) * s >= n + p:
        out -= 1
    return out


def infer_shapes(layers: List[Layer], batch: int,
                 chw: Tuple[int, int, int]) -> Dict[int, dict]:
    """Per layer index: ``{"in": [shapes], "out": shape}`` in NHWC (or
    (N, D) once flat), following the conf's own node names."""
    c, h, w = chw
    nodes: Dict[str, tuple] = {"in": (batch, h, w, c)}
    info: Dict[int, dict] = {}
    for lay in layers:
        ins = [nodes[n] for n in lay.ins]
        x = ins[0]
        t = lay.type
        if t == "conv":
            k = int(lay.cfg["kernel_size"])
            s = int(lay.cfg.get("stride", 1))
            p = int(lay.cfg.get("pad", 0))
            out = (x[0], (x[1] + 2 * p - k) // s + 1,
                   (x[2] + 2 * p - k) // s + 1, int(lay.cfg["nchannel"]))
        elif t in ("max_pooling", "avg_pooling"):
            k = int(lay.cfg["kernel_size"])
            s = int(lay.cfg.get("stride", 1))
            p = int(lay.cfg.get("pad", 0))
            out = (x[0], _pool_out(x[1], k, s, p), _pool_out(x[2], k, s, p),
                   x[3])
        elif t == "ch_concat":
            out = x[:3] + (sum(i[3] for i in ins),)
        elif t == "flatten":
            out = (x[0], int(x[1] * x[2] * x[3]))
        elif t == "fullc":
            out = (x[0], int(lay.cfg["nhidden"]))
        elif t in ("relu", "lrn", "batch_norm", "dropout", "eltwise_sum",
                   "softmax"):
            out = x
        else:
            raise ValueError(
                f"layer {lay.index} ({t}): not a layer type the "
                "benchmark's reference knows; add it to benchmarks/lib/"
                "netconf.py and reference.py")
        info[lay.index] = {"in": ins, "out": out}
        for n in lay.outs:
            nodes[n] = out
    return info


def param_shapes(layers: List[Layer], shapes: Dict[int, dict]
                 ) -> Dict[int, Dict[str, tuple]]:
    """Per parametrised layer: tag -> shape, in the layouts the conf
    grammar documents (conv HWIO, fullc (out, in), batch_norm (C,))."""
    out: Dict[int, Dict[str, tuple]] = {}
    for lay in layers:
        x = shapes[lay.index]["in"][0]
        if lay.type == "conv":
            k, co = int(lay.cfg["kernel_size"]), int(lay.cfg["nchannel"])
            out[lay.index] = {"wmat": (k, k, x[3], co)}
            if int(lay.cfg.get("no_bias", 0)) == 0:
                out[lay.index]["bias"] = (co,)
        elif lay.type == "fullc":
            nh = int(lay.cfg["nhidden"])
            out[lay.index] = {"wmat": (nh, x[1])}
            if int(lay.cfg.get("no_bias", 0)) == 0:
                out[lay.index]["bias"] = (nh,)
        elif lay.type == "batch_norm":
            out[lay.index] = {"wmat": (x[3],), "bias": (x[3],)}
    return out


def describe_net(text: str, batch: int):
    """(layers, global keys, shapes, parameter shapes) of a conf text at
    one batch size: what the weight maker, the reference and the FLOP
    count all start from."""
    layers, glob = parse_net(text)
    chw = tuple(int(t) for t in glob["input_shape"].split(","))
    shapes = infer_shapes(layers, batch, chw)
    return layers, glob, shapes, param_shapes(layers, shapes)


def residual_branch_norms(layers: List[Layer]) -> List[int]:
    """Indices of the batch norms that close a residual branch: of the
    chains that meet in an ``eltwise_sum``, the one with the most convs
    since the fork is the branch (the other is the shortcut, with one
    projection conv or none), and its last layer, if a batch norm, is
    what is returned.  The weight maker starts those slopes at 0
    (Goyal et al. 2017, arXiv:1706.02677, section 5.1)."""
    writer: Dict[str, int] = {}
    found: List[int] = []
    for lay in layers:
        if lay.type == "eltwise_sum":
            chains = []
            for node in lay.ins:
                convs, i = 0, writer.get(node)
                last = i
                while i is not None and len(layers[i].ins) == 1:
                    convs += layers[i].type == "conv"
                    src = layers[i].ins[0]
                    nxt = max((j for j in range(i) if src in layers[j].outs),
                              default=None)
                    i = nxt
                    if i is not None and layers[i].type == "eltwise_sum":
                        break
                chains.append((convs, last))
            convs, last = max(chains, key=lambda c: c[0])
            if convs >= 2 and last is not None \
                    and layers[last].type == "batch_norm":
                found.append(last)
        for n in lay.outs:
            writer[n] = lay.index
    return found


def step_flops(layers: List[Layer], shapes: Dict[int, dict]) -> float:
    """FLOPs one training step needs: the multiply-adds of every conv
    and fullc, times 2 (a multiply and an add), times 3 (forward, the
    gradient to the input, the gradient to the weights).  Nothing
    recomputed is counted, and pooling, LRN, batch norm and the
    elementwise layers are left out: under 1% of either model."""
    macs = 0.0
    for lay in layers:
        sh = shapes[lay.index]
        if lay.type == "conv":
            k = int(lay.cfg["kernel_size"])
            n, oh, ow, co = sh["out"]
            macs += float(n) * oh * ow * co * k * k * sh["in"][0][3]
        elif lay.type == "fullc":
            n, nh = sh["out"]
            macs += float(n) * nh * sh["in"][0][1]
    return macs * 2.0 * 3.0


def step_min_bytes(layers: List[Layer], shapes: Dict[int, dict],
                   act_bytes: int = 2, param_bytes: int = 4) -> float:
    """Bytes one training step cannot avoid moving through HBM: every
    layer's output written once and read once forward, read once
    backward, its gradient written and read once (5 passes over the
    activations in the compute dtype), and every parameter read, its
    gradient written, weight and momentum read and written (6 passes in
    float32).  A floor for the roofline, not a count of what XLA moves."""
    acts = 0.0
    for lay in layers:
        n = 1
        for d in shapes[lay.index]["out"]:
            n *= d
        acts += n
    params = 0.0
    for tags in param_shapes(layers, shapes).values():
        for shp in tags.values():
            n = 1
            for d in shp:
                n *= d
            params += n
    return acts * act_bytes * 5.0 + params * param_bytes * 6.0
