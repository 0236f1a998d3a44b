"""VGG-16 step-time bisection (tools/resnet_bisect.py discipline) —
the Winograd rollout A/B at per-stage granularity.

The F(4x4,3x3) rewrite inflates each conv's input 2.25x in HBM
(doc/performance.md, Winograd section), so the big early layers
(224/112px) may trade worse than the late ones; these variants bound
the sweet spot before promoting a conf default.

Run on the chip (one process; exits 2 without an accelerator):

    python tools/vgg_bisect.py [variant ...]

Variants (default: all):

* base       — vgg16_conf as-is (direct convs)
* wino       — conv_wino = 1 globally (all 3x3 s1 convs; conv1_1 is
               Cin=3 and keeps the direct path via the Cin>=8 gate)
* wino2      — conv_wino = 2 globally: the F(2x2,3x3) tile (2.25x MAC
               reduction, near-direct bf16 numerics)
* wino45     — Winograd only on stages 4-5 (28/14px, C=512): smallest
               HBM inflation, biggest per-FLOP MXU benefit
* wino345    — Winograd on stages 3-5 (56px and down)
"""

import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _wino_on_layers(conf: str, want) -> str:
    """Insert ``conv_wino = 1`` into the body of the conv layers whose
    name matches ``want`` (a predicate over the layer tag)."""
    out = []
    hits = 0
    for i, blk in enumerate(conf.split("layer[")):
        m = re.match(r"[^\]]*\] = conv:([\w.]+)\n", blk) if i else None
        if m and want(m.group(1)):
            head, rest = blk.split("\n", 1)
            blk = head + "\n  conv_wino = 1\n" + rest
            hits += 1
        out.append(blk)
    assert hits, "no conv layers matched the variant predicate"
    return "layer[".join(out)


def variant_conf(name: str, batch: int) -> str:
    from cxxnet_tpu.models import vgg16_conf

    conf = vgg16_conf(batch_size=batch, input_size=224, synthetic=False,
                      dev="tpu")
    if name == "base":
        return conf
    if name == "wino":
        return conf + "conv_wino = 1\n"
    if name == "wino2":
        return conf + "conv_wino = 2\n"
    if name == "wino45":
        return _wino_on_layers(
            conf, lambda tag: re.match(r"conv[45]_", tag) is not None
        )
    if name == "wino345":
        return _wino_on_layers(
            conf, lambda tag: re.match(r"conv[345]_", tag) is not None
        )
    raise SystemExit(f"unknown variant {name}")


if __name__ == "__main__":
    from bisect_common import run_bisect

    run_bisect(variant_conf,
               ["base", "wino", "wino2", "wino45", "wino345"],
               scan_k=20)
