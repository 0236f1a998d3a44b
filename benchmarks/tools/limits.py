"""Read the numbers a limit is set from, in one process on the chip.

    python3 benchmarks/tools/limits.py --config googlenet --seeds 12 --control 3

For each seed: weights from the seed, a chunk of seeded rows, the
program's own scanned step (the ``update_scan`` the CLI's round loop
calls, at the cell's batch and ``scan_steps``), and its state after.
Then, with the program freed, the configuration's plain reference
follows the same chunk; the gaps between the two are the *sound*
readings.  The control is the reference computed one precision below
the configuration's (``train_chunk(..., control=True)``:
``float8_e4m3fn`` for bfloat16), put in the program's place; its gaps
to the reference are the *control* readings.  A limit goes above the
sound runs' largest and below the control's smallest (PERF.md section 2
has the readings this produced).  ``--cpu-toy`` runs the same flow at
toy size; its numbers say nothing about the chip.

The reference is the module the configuration's file names, found as
``run.py`` finds it (``run.load_reference``).  The rows are gaussian
images with uniform labels unless that module has
``seeded_chunk(net, seed, scan) -> (data, labels)`` for its own kind.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def chunk_from_seed(seed, scan, batch, hwc, nclass):
    import numpy as np

    rng = np.random.RandomState(seed % 2147483629)
    data = np.empty((scan, batch) + hwc, np.float32)
    for i in range(scan):
        data[i] = rng.randn(batch, *hwc)
    labels = rng.randint(0, nclass, (scan, batch, 1)).astype(np.float32)
    return data, labels


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=9000)
    ap.add_argument("--cpu-toy", action="store_true")
    ap.add_argument("--also-bf16", action="store_true",
                    help="the reference in bfloat16 too, for insight")
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.lib.reference import compare_chunk
    from benchmarks.run import (conf_globals, load_json, load_reference,
                                net_text, param_index as idx)

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not a.cpu_toy:
        sys.stderr.write(f"needs a TPU, found {dev.platform!r}\n")
        return 2
    config = load_json(os.path.join(os.path.dirname(HERE), "configs",
                                    a.config + ".json"))
    ref = load_reference(config)
    args = dict(config["args"])
    if a.cpu_toy:
        args.update(config.get("rehearsal_args", {}))
    from cxxnet_tpu import config as cfgmod
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.utils import compile_cache

    compile_cache.enable()
    conf = net_text(config, args, "cpu" if a.cpu_toy else "tpu")
    batch = int(args["batch_size"])
    net = ref.describe(conf, batch)
    glob = conf_globals(conf)
    scan = int(glob["scan_steps"])

    def rows_of(seed):
        if hasattr(ref, "seeded_chunk"):
            return ref.seeded_chunk(net, seed, scan)
        chw = tuple(int(t) for t in glob["input_shape"].split(","))
        return chunk_from_seed(seed, scan, batch, (chw[1], chw[2], chw[0]),
                               int(args["num_class"]))

    tr = NetTrainer()
    tr.set_params(cfgmod.parse_pairs(conf))
    tr.set_param("silent", "1")
    tr.init_model()
    seeds = [a.first_seed + 7919 * i for i in range(a.seeds)]
    prog = {}
    for s in seeds:
        t0 = time.perf_counter()
        made = ref.make_weights(net, s)
        tr.params = {k: {t: made[idx(k)][t] for t in tags}
                     for k, tags in tr.params.items()}
        tr.ustates = jax.tree_util.tree_map(jnp.zeros_like, tr.ustates)
        tr._rng_key = jax.random.PRNGKey(s)
        tr.epoch_counter = 0
        tr._place_state()
        data, labels = rows_of(s)
        losses = tr.update_scan(data, labels, sync=True, check_steps=False)
        prog[s] = {
            "losses": np.asarray(losses, np.float64),
            "params": {idx(k): v for k, v in
                       jax.device_get(tr.params).items()},
            "momentum": ref.program_update_state(
                {idx(k): v for k, v in jax.device_get(tr.ustates).items()}),
        }
        print(f"program seed {s}: losses {np.round(losses, 4).tolist()} "
              f"({time.perf_counter() - t0:.1f} s)", flush=True)
    tr.params = tr.ustates = tr.aux = None
    tr._jit_cache.clear()
    del tr

    rows = []
    for n, s in enumerate(seeds):
        t0 = time.perf_counter()
        data, labels = rows_of(s)
        w = ref.make_weights(net, s)
        start = jax.device_get(w)
        rl, rp, rm = ref.train_chunk(net, w, data, labels,
                                     jax.random.PRNGKey(s))
        plain = {"losses": rl, "params": rp, "momentum": rm}
        row = {"seed": s, "sound": compare_chunk(prog[s], plain, start),
               "ref_losses": [float(x) for x in rl], "ref_s": None}
        row["ref_s"] = time.perf_counter() - t0
        if n < a.control:
            variants = [("control", True)]
            if a.also_bf16:
                variants.append(("bf16_reference", "bfloat16"))
            for name, q in variants:
                w = ref.make_weights(net, s)
                try:
                    cl, cp, cm = ref.train_chunk(
                        net, w, data, labels, jax.random.PRNGKey(s),
                        control=q)
                    row[name] = compare_chunk(
                        {"losses": cl, "params": cp, "momentum": cm}, plain,
                        start)
                except Exception as e:  # noqa: BLE001 - a control that
                    # crashes has failed, and sets no upper end
                    row[name] = {"crashed": repr(e)[:300]}
        print(json.dumps(row), flush=True)
        rows.append(row)
        prog.pop(s)
    out = os.path.join(ROOT, "chiprun_out", "limits")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"{a.config}.json"), "w") as f:
        json.dump({"device": dev.device_kind, "rows": rows}, f, indent=1)
    for name in ("loss_gap", "update_norm_gap", "dparam_norm_gap"):
        sound = max(r["sound"][name] for r in rows)
        ctl = [r["control"][name] for r in rows
               if "control" in r and name in r["control"]]
        print(f"{name}: sound largest {sound:.6g}; control smallest "
              f"{min(ctl) if ctl else float('nan'):.6g}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
