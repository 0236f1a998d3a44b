"""The attention layers' score and value products' share of their
roofline: the least time the chip could take for them in one training
step — their operations / the bf16 peak, the operations counted by the
configuration's reference module (``attn_core_flops(net, window_pairs,
full_pairs)``: 3 x 2 x heads x 2 x head width a (query, key) pair and
layer) at the pairs the run itself counted on the rows fed
(``io/tokens.py``): a windowed layer's at ``attn_window_pairs`` / steps,
a full layer's at ``attn_pairs`` / steps, over the window's whole rounds
— over the device time under the layers' ``core_window`` and
``core_full`` scopes (``attn_window_core_ms_step`` +
``attn_full_core_ms_step``).  Bound by operations: the products' bytes
(q, k, v and o once each way) are under a tenth of that time.  A pair a
mask throws away inside a live block is the kernel's own cost and not
credited.

The reference is found through the configuration: the run's cell
(``bench_out/<cell>/...``, or ``run["workload"]``) names its
configuration in ``BENCHMARK.json``, whose file names its ``reference``
— no path is written here, so a later cell of another family that
brings ``attn_core_flops`` needs no twin of this reader.  ``None``
without a trace, without the scopes, without the counters, or where the
configuration's reference has no such function."""

import json
import os

from benchmarks.lib import scopes, stage_scopes

LAYER = "layers and kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_samples_s_chip"

KIND = "attention"


def reference_path(run):
    """The ``reference`` the run's configuration names, or ``None``."""
    out = scopes.run_dir(run)
    cell = run.get("workload") or (
        os.path.basename(os.path.dirname(out)) if out else None)
    try:
        with open(os.path.join(scopes.ROOT, "BENCHMARK.json"), "r",
                  encoding="utf-8") as f:
            bench = json.load(f)
        name = next(w["config"] for w in bench["workloads"]
                    if w["name"] == cell)
        entry = next(c for c in bench["configs"] if c["name"] == name)
        with open(os.path.join(scopes.ROOT, entry["file"]), "r",
                  encoding="utf-8") as f:
            return json.load(f).get("reference")
    except (OSError, StopIteration, KeyError, ValueError):
        return None


def read(run):
    ms = stage_scopes.ms_per_step(run, KIND, ("core_window", "core_full"))
    near = stage_scopes.counter(run, "attn_window_pairs")
    pairs = stage_scopes.counter(run, "attn_pairs")
    if not ms or near is None or pairs is None or not run.get("peaks"):
        return None
    rel = reference_path(run)
    if rel is None:
        return None
    mod, net = scopes.reference_of(run, rel)
    if not hasattr(mod, "attn_core_flops"):
        return None
    least_s = (mod.attn_core_flops(net, near[0] / near[1],
                                   pairs[0] / pairs[1])
               / run["peaks"]["bf16_flops"])
    return 100.0 * least_s / run["chips"] / (ms / 1e3)
