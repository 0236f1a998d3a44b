"""One cell of the benchmark, once: ``task=train`` through the CLI.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process.  Builds the cell's conf from its configuration file and its
traffic file, drives ``cxxnet_tpu.cli.LearnTask.run([conf])`` (what
``python -m cxxnet_tpu <conf>`` runs), warms up through one whole round,
measures fence to fence over whole chunks (``lib/window.py``), stops the
run through the program's own SIGTERM path, compares the first chunk the
program trained with the configuration's plain reference (the module
its file names, or ``references/conv_sgd.py``) and prints, last, one
JSON object.  Without a TPU it exits 2 and prints no
result; ``--cpu-rehearsal`` walks the same control flow at toy sizes on
the CPU, prints no device metric and always exits non-zero.
README.md in this directory has the layout and how to add to it.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # process start, as near as Python can stamp it

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import inspect  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def say(msg: str = "") -> None:
    print(msg, flush=True)


def load_json(path: str) -> dict:
    with open(path, "r", encoding="utf-8") as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def load_file(path: str, kind: str):
    """A module of the benchmark's own, by its path."""
    stem = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_metric(name: str):
    """A per-layer metric is a file of its own, found by its name."""
    return load_file(os.path.join(HERE, "metrics", f"{name}.py"), "metric")


def load_named(owner: dict, key: str, default: str):
    """The seam: a configuration may name its ``reference`` and a mix its
    ``generator``, each a file under ``benchmarks/`` given as a path from
    the root of the checkout (``benchmarks/references/<name>.py``).  One
    that names none gets ``default``, the code every cell ran before."""
    rel = owner.get(key, default)
    path = os.path.normpath(os.path.join(ROOT, rel))
    if not path.startswith(HERE + os.sep):
        raise SystemExit(f"{key} {rel!r} of {owner.get('name')!r} is not a "
                         "file under benchmarks/")
    return load_file(path, key)


def load_reference(config: dict):
    """``describe``, ``make_weights``, ``train_chunk``,
    ``program_update_state``, ``step_flops``, ``step_min_bytes``."""
    return load_named(config, "reference", "benchmarks/references/conv_sgd.py")


def load_generator(mix: dict):
    """``make(mix, fill, out)`` and ``check_feed(mix, fill, data, labels)``."""
    return load_named(mix, "generator", "benchmarks/lib/traffic.py")


def conf_globals(text: str) -> dict:
    """The global keys of a conf text, the last value winning: the
    ``name = value`` lines outside the ``netconfig`` block.  All the
    harness reads of a net itself (``scan_steps``, ``input_shape``); the
    layers are the reference module's to read."""
    glob, inside = {}, False
    for raw in text.splitlines():
        line = raw.split("#", 1)[0].strip()
        if "=" not in line:
            continue
        k, v = (t.strip() for t in line.split("=", 1))
        if k == "netconfig":
            inside = v == "start"
        elif not inside:
            glob[k] = v
    return glob


def param_index(key: str) -> int:
    """The program keys a layer's parameters ``l<index>_<name>``."""
    return int(key[1:key.index("_")])


def fold_seed(seed: int) -> int:
    """``--seed`` may pass 2**31; numpy's and jax's seeds may not."""
    return int(seed) % 2147483629


# ----------------------------------------------------------------------
# conf text: configuration file + traffic file -> what the CLI reads
def net_text(config: dict, args: dict, dev: str) -> str:
    """The net and its run settings: from the program's builder, or from
    a conf text kept beside the configuration file."""
    if "builder" in config:
        mod, _, fn = config["builder"].rpartition(".")
        build = getattr(importlib.import_module(mod), fn)
        # a builder that can write an iterator block of its own is told
        # not to: the mix brings the feed
        own_feed = "synthetic" in inspect.signature(build).parameters
        return build(dev=dev, **args, **({"synthetic": False} if own_feed
                                         else {}))
    with open(os.path.join(ROOT, config["net_conf"]), "r",
              encoding="utf-8") as f:
        return f.read().format(dev=dev, **args)


def build_conf(config: dict, traffic: dict, seed: int, out: str,
               rehearsal: bool) -> dict:
    args = dict(config["args"])
    tr = dict(traffic)
    if rehearsal:
        args.update(config.get("rehearsal_args", {}))
        tr.update(traffic.get("rehearsal", {}))
    batch = int(args["batch_size"]) * int(tr.get("batch_scale", 1))
    args["batch_size"] = batch
    net = net_text(config, args, tr["dev"])
    glob = conf_globals(net)
    scan = int(glob.get("scan_steps", 1))
    # a mix's template may use every key of the configuration's args
    # (num_class, seq_len, ...) beside the harness's own
    fill = dict(args)
    fill.update({"nsample": batch * scan * int(tr["chunks_per_round"]),
                 "batch_size": batch, "seed": seed, "out": out})
    if "input_shape" in glob:
        fill["input_shape"] = glob["input_shape"]
    if int(tr["chunks_per_round"]) < 3:
        raise SystemExit("a round holds at least three chunks, whatever "
                         "makes the mix's files (lib/traffic.py has why)")
    gen = load_generator(tr)
    fill.update(gen.make(tr, fill, out))
    data = "\n".join(tr["conf"]).format(**fill) + "\n"
    tail = (
        "num_round = 1000000\nmax_round = 1000000\n"
        "save_model = 0\n"
        f"model_dir = {os.path.join(out, 'models')}\n"
        f"telemetry = 1\ntelemetry_path = {os.path.join(out, 'telemetry.jsonl')}\n"
        + "\n".join(tr.get("settings", [])) + "\n"
    )
    path = os.path.join(out, "cell.conf")
    with open(path, "w", encoding="utf-8") as f:
        f.write(data + net + tail)
    return {"path": path, "net": net, "batch": batch, "scan": scan,
            "nsample": fill["nsample"], "fill": fill, "mix": tr,
            "generator": gen,
            "chunks_per_round": int(tr["chunks_per_round"])}


# ----------------------------------------------------------------------
class Run:
    """What one run holds between the hooks and the report."""

    def __init__(self, a, conf, out, ref) -> None:
        self.a, self.conf, self.out = a, conf, out
        self.ref = ref           # the configuration's reference module
        self.seed = fold_seed(a.seed)
        self.split = {}          # set-up split, seconds since _T0
        self.chunk_losses = []   # one array per update_scan call
        self.spans = {}          # name -> [(chunk, host-clock seconds)]
        self.first = None        # the first chunk: feed, losses, state after
        self.window_t0 = None
        self.stopping = False
        self.dev_at_setup = None
        self.dev_at_stop = None
        self.trace_session = None  # (dir, steps traced), once it is closed
        self._tracing = None
        self.task = None
        self.recorder = None

    # -- weights from the seed, in the program's place -----------------
    def inject(self, task) -> None:
        import jax

        self.split["net_built"] = time.perf_counter() - _T0
        tr = task.net_trainer
        self.net = self.ref.describe(self.conf["net"], self.conf["batch"])
        pshapes = self.net.pshapes
        made = self.ref.make_weights(self.net, self.seed)
        new = {}
        for key, tags in tr.params.items():
            i = param_index(key)
            want = {t: tuple(v.shape) for t, v in tags.items()}
            if want != {t: tuple(s) for t, s in pshapes.get(i, {}).items()}:
                raise SystemExit(
                    f"the program's parameters of {key} are {want}; the "
                    f"benchmark's reading of the conf gives {pshapes.get(i)}")
            new[key] = {t: made[i][t] for t in tags}
        tr.params = new
        tr._rng_key = jax.random.PRNGKey(self.seed)
        tr._place_state()
        self.span_wrap(tr, "_local_scan_rows")
        self.span_wrap(tr, "update_scan")
        inner = tr.update_scan

        def update_scan(data, labels, *args, **kw):
            first = self.first is None
            if first:
                self.first = {"data": data, "labels": labels}
            losses = inner(data, labels, *args, **kw)
            self.chunk_losses.append(losses)
            if first:
                self.first["losses"] = losses
                self.first["params"] = jax.device_get(tr.params)
                self.first["ustates"] = jax.device_get(tr.ustates)
            return losses

        tr.update_scan = update_scan
        self.split["weights_made"] = time.perf_counter() - _T0

    def span_wrap(self, obj, name: str) -> None:
        """Time one of the trainer's calls by the host clock and name it
        in the profiler's trace (``bench.<name>``): spans taken from the
        benchmark's side, around the program's call."""
        import jax

        fn = getattr(obj, name, None)
        if fn is None:
            return
        self.spans[name.lstrip("_")] = []

        def timed(*args, **kw):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench." + name.lstrip("_")):
                out = fn(*args, **kw)
            self.spans[name.lstrip("_")].append(
                (len(self.chunk_losses), time.perf_counter() - t0))
            return out

        setattr(obj, name, timed)

    def spans_in_window(self, win: dict) -> dict:
        """Seconds inside each wrapped call, over the window's chunks."""
        lo, hi = win["first_fence"], win["last_fence"]
        return {name: sum(dt for i, dt in rows if lo <= i <= hi)
                for name, rows in self.spans.items()}

    # -- at every fence -------------------------------------------------
    def on_fence(self, i, now, rnd, in_round) -> None:
        st = self.recorder.stamps
        if self.window_t0 is None:
            if rnd >= 1 and st.rounds[rnd - 1][1] is not None:
                self.window_t0 = now
                self.split["warm"] = now - _T0
                self.dev_at_setup = self.device_summary()
                self.window_round0 = rnd
            return
        if self.stopping:
            return
        if self.dev_at_stop is None:
            if now - self.window_t0 < self.a.seconds:
                return
            self.dev_at_stop = self.device_summary()  # the window is over
        # a traced run goes on for one more round and traces its chunks 2
        # and 3, in a window of its own: tracing slows the host (a chunk
        # period of 2.25 s read 3.95 s under it), so the host-clock
        # metrics are taken from the untraced window before
        last = in_round == self.conf["chunks_per_round"]
        if self.a.trace and self.trace_session is None:
            if self._tracing is None:
                if in_round == 1:
                    self.start_trace(rnd)
                return
            if not last:
                return
            self.stop_trace()
        self.stopping = True
        # the program's own way out: its SIGTERM handler sets a flag the
        # round loop reads at the next batch boundary, which is here,
        # with nothing staged
        signal.raise_signal(signal.SIGTERM)

    def start_trace(self, rnd: int) -> None:
        import jax

        d = os.path.join(self.out, f"trace_round{rnd}")
        opts = jax.profiler.ProfileOptions()
        # no Python tracer: it doubled the chunk period (PR 24), and a
        # trace that slows the host misreports the device's idle share;
        # the bench.* annotations name the gaps instead
        opts.python_tracer_level = 0
        # level 2 records every futex wait of the runtime's threads: 16
        # million host events in 8 s, and a stop of 75 s
        opts.host_tracer_level = 1
        jax.profiler.start_trace(d, profiler_options=opts)
        self._tracing = (d, len(self.recorder.stamps.fences))

    def stop_trace(self) -> None:
        import jax

        jax.profiler.stop_trace()
        d, f0 = self._tracing
        fences = self.recorder.stamps.fences[f0:]
        self.trace_session = (d, sum(n for *_, n in fences))
        self._tracing = None

    @staticmethod
    def device_summary() -> dict:
        from cxxnet_tpu.obs import device as obs_device

        return dict(obs_device.summary())


# ----------------------------------------------------------------------
def held_to_limits(nums: dict, limits: dict) -> bool:
    """Each number of ``compare_chunk`` beside its limit, and whether all
    are inside: what a run's ``correct`` and a control's test both read."""
    ok = True
    for name in ("loss_gap", "update_norm_gap", "dparam_norm_gap"):
        lim = float(limits[name])
        good = math.isfinite(nums[name]) and nums[name] <= lim
        ok = ok and good
        say(f"compare {name}: {nums[name]:.6g} (limit {lim:g}) "
            f"{'ok' if good else 'OVER'}"
            + (f" at {nums[name + '_at']}" if name + "_at" in nums else ""))
    return ok


def check_reference(run: Run, limits: dict) -> dict:
    """Follow the first chunk with the configuration's plain reference,
    from weights made again from the seed, and compare
    (``lib/reference.compare_chunk``: generic on trees, one for all)."""
    import jax
    import numpy as np

    from benchmarks.lib import reference

    ref = run.ref
    first = run.first
    t0 = time.perf_counter()
    weights = ref.make_weights(run.net, run.seed)
    start = jax.device_get(weights)
    ref_l, ref_p, ref_m = ref.train_chunk(
        run.net, weights, first["data"], first["labels"],
        jax.random.PRNGKey(run.seed))
    prog = {
        "losses": np.asarray(first["losses"], np.float64),
        "params": {param_index(k): v for k, v in first["params"].items()},
        "momentum": ref.program_update_state(
            {param_index(k): v for k, v in first["ustates"].items()}),
    }
    nums = reference.compare_chunk(
        prog, {"losses": ref_l, "params": ref_p, "momentum": ref_m}, start)
    nums["reference_s"] = time.perf_counter() - t0
    nums["losses_program"] = [float(x) for x in prog["losses"]]
    nums["losses_reference"] = [float(x) for x in ref_l]
    ok = held_to_limits(nums, limits)
    feed = run.conf["generator"].check_feed(
        run.conf["mix"], run.conf["fill"], first["data"], first["labels"])
    if feed is not None:
        lim = float(limits["feed_gap_levels"])
        good = feed["feed_gap_levels"] <= lim
        ok = ok and good
        nums.update(feed)
        say(f"compare feed_gap_levels: {feed['feed_gap_levels']:.6g} over "
            f"{feed['rows']} rows (limit {lim:g}) {'ok' if good else 'OVER'}")
    nums["ok"] = ok
    return nums


def free_program(run: Run) -> None:
    """Drop the program's device state so the reference has the chip's
    memory, after ``memory_peak_bytes`` was read."""
    task = run.task
    for it in [task.itr_train] + list(task.itr_evals):
        close = getattr(it, "close", None)
        if close is not None:
            close()
    tr = task.net_trainer
    tr.params = tr.ustates = tr.aux = None
    tr._jit_cache.clear()
    task.net_trainer = None
    run.task = None
    gc.collect()


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    return ap.parse_args(argv)


def run_cell(a):
    """Run the cell.  Returns the result object, or an exit code where
    there is nothing to measure on."""
    bench_path = os.path.join(ROOT, "BENCHMARK.json")
    bench = load_json(bench_path)
    cell = find_cell(bench, a.workload)
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))

    sys.path.insert(0, ROOT)
    try:
        import cxxnet_tpu  # noqa: F401
    except ImportError as e:
        sys.stderr.write(f"cannot import the program from {ROOT}: {e}\n")
        return 2
    import jax

    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    if a.cpu_rehearsal:
        say(f"CPU REHEARSAL on {platform} ({kind}): toy sizes, no device "
            "metric is printed and the exit code is never 0")
    elif platform != "tpu" or len(devs) < cell["chips"]:
        sys.stderr.write(
            f"this benchmark measures on a TPU and never falls back: JAX "
            f"found platform {platform!r} with {len(devs)} device(s), the "
            f"cell needs {cell['chips']} TPU chip(s)\n")
        return 2
    from benchmarks.lib import peaks as peaks_mod
    from benchmarks.lib import window

    peak = None if a.cpu_rehearsal else peaks_mod.peaks(kind)
    from cxxnet_tpu.utils import compile_cache

    cache_dir = compile_cache.enable()
    run_split_imports = time.perf_counter() - _T0

    out = os.path.join(ROOT, "bench_out", a.workload,
                       f"seed{a.seed}_trace{a.trace}")
    os.makedirs(out, exist_ok=True)
    tele_path = os.path.join(out, "telemetry.jsonl")
    if os.path.exists(tele_path):  # the program appends
        os.remove(tele_path)
    conf = build_conf(config, traffic, fold_seed(a.seed), out, a.cpu_rehearsal)
    run = Run(a, conf, out, load_reference(config))
    run.split["imports"] = run_split_imports
    run.split["data_made"] = time.perf_counter() - _T0

    from cxxnet_tpu.cli import LearnTask

    task = LearnTask()
    run.task = task
    run.recorder = window.Recorder(run.on_fence)
    window.install(task, run.recorder)
    inner_train = task.task_train

    def task_train():
        run.inject(task)
        return inner_train()

    task.task_train = task_train
    say(f"cell {a.workload}: config {cell['config']}, traffic "
        f"{cell['traffic']}, batch {conf['batch']} x scan {conf['scan']}, "
        f"{conf['nsample']} samples a round, seed {a.seed}, compile cache "
        f"{cache_dir}")
    rc = task.run([conf["path"]])
    if rc != 0:
        sys.stderr.write(f"the CLI returned {rc}\n")
        return 1
    t_stopped = time.perf_counter()

    # ---- the window, from the stamps alone --------------------------
    st = run.recorder.stamps
    with open(os.path.join(out, "stamps.json"), "w", encoding="utf-8") as f:
        json.dump(st.to_json(), f)
    win = window.reduce_window(st, a.seconds, conf["batch"], cell["chips"])
    losses = run.chunk_losses[win["first_fence"]:win["last_fence"] + 1]
    import numpy as np

    failed = sum(1 for l in losses if not np.all(np.isfinite(np.asarray(l))))
    setup_s = run.split["warm"]
    # the allocator keeps two peaks: arrays in use, and what it reserved
    # for programs' scratch (6.16 GB for GoogLeNet's step, which its
    # compiler sizes at 6.20 GB); the chip's peak is the two together
    stats = [d.memory_stats() or {} for d in devs[:cell["chips"]]]
    peak_bytes = max(int(m.get("peak_bytes_in_use", 0))
                     + int(m.get("peak_bytes_reserved", 0)) for m in stats)
    say("device memory (fullest chip's peak in use + peak reserved = %d B): %s"
        % (peak_bytes, json.dumps(stats[0])))
    telemetry = []
    if os.path.exists(tele_path):
        with open(tele_path, "r", encoding="utf-8") as f:
            telemetry = [json.loads(x) for x in f if x.strip()]
    # rounds that ran whole inside the window (round 0 is warm-up)
    whole = [r for r in telemetry
             if r["round"] >= run.window_round0
             and st.rounds[r["round"]][1] is not None
             and st.rounds[r["round"]][1] <= win["t1"] + 1e-9]

    sp = run.split
    say("set-up split (s): imports %.1f | conf and data files %.1f | init "
        "(net build, iterator's data) %.1f | weights from the seed %.1f | "
        "program loaded or compiled and first round %.1f | total %.1f" % (
            sp["imports"], sp["data_made"] - sp["imports"],
            sp["net_built"] - sp["data_made"],
            sp["weights_made"] - sp["net_built"],
            sp["warm"] - sp["weights_made"], sp["warm"]))
    say("window: %d chunks, %d steps, periods %.3f s of %.3f s wall; round "
        "boundaries %.1f%% of wall (not in any period); stop took %.1f s"
        % (win["chunks"], win["steps"], win["sum_periods_s"], win["wall_s"],
           win["round_boundary_pct"], t_stopped - win["t1"]))
    say("chunk periods (s): " + " ".join(f"{p:.3f}" for p in win["periods_s"]))
    steps = sum(r["steps"] for r in whole) or 1
    say("host stages over the window's %d whole rounds (ms/step): " % len(whole)
        + " | ".join(
            "%s %.1f" % (k, 1e3 * sum(r["stages"].get(k, {}).get("total_s", 0.0)
                                     for r in whole) / steps)
            for k in ("decode", "augment", "batch", "h2d", "device_wait")))
    spans = run.spans_in_window(win)
    say("host spans over the window's chunks (ms/step): " + " | ".join(
        "%s %.1f" % (k, 1e3 * v / win["steps"]) for k, v in spans.items())
        + " | outside update_scan %.1f" % (
            1e3 * (win["sum_periods_s"] - spans.get("update_scan", 0.0))
            / win["steps"]))

    # ---- the traced run's reduction ---------------------------------
    trace = None
    if run.trace_session is not None:
        from benchmarks.lib import tracered

        d, traced_steps = run.trace_session
        rows = tracered.load_events(tracered.find_xplane(d))
        with open(os.path.join(d, "planes.json"), "w", encoding="utf-8") as f:
            json.dump(tracered.describe(rows), f, indent=1)
        trace = tracered.reduce(rows, traced_steps)

    record = {
        "window": win, "telemetry": whole, "trace": trace, "spans": spans,
        "device_at_setup": run.dev_at_setup, "device_at_stop": run.dev_at_stop,
        "memory_peak_bytes": peak_bytes, "peaks": peak,
        "flops_per_step": run.ref.step_flops(run.net),
        "min_bytes_per_step": run.ref.step_min_bytes(run.net),
        "batch": conf["batch"], "scan": conf["scan"], "chips": cell["chips"],
        "setup_s": setup_s,
    }

    # ---- correctness, outside the window ----------------------------
    free_program(run)
    limits = config["limits"]
    nums = check_reference(run, limits)
    say("reference followed the first chunk in %.1f s (not in setup_s)"
        % nums["reference_s"])
    finite = all(np.all(np.isfinite(np.asarray(l))) for l in run.chunk_losses)
    say(f"every chunk's train loss finite: {finite}; chunks in the window "
        f"{win['chunks']}, failed {failed}")
    correct = bool(nums["ok"] and finite and failed == 0)
    with open(os.path.join(out, "compare.json"), "w", encoding="utf-8") as f:
        json.dump(nums, f, indent=1)

    # ---- the result line ---------------------------------------------
    metrics = {}
    if a.trace:
        wanted = [m for m in bench["per_layer"]
                  if a.workload in m.get("workloads", [a.workload])]
        for m in wanted:
            val = load_metric(m["name"]).read(record)
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    else:
        metrics = {
            "train_samples_s_chip": {"value": win["samples_s_chip"],
                                     "unit": "samples/s/chip"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    device = {"platform": platform, "kind": kind, "count": cell["chips"],
              "memory_peak_bytes": peak_bytes}
    result = {"correct": correct, "attempted": win["chunks"],
              "failed": failed, "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["top_ops"],
                               "idle_gaps": trace["gaps"]}
    return result


def main(argv=None) -> int:
    a = parse_args(argv)
    result = run_cell(a)
    if isinstance(result, int):
        return result
    if a.cpu_rehearsal:
        say("rehearsal reached its end; correct=%s; nothing measured here is "
            "a device number" % result["correct"])
        return 3
    say(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
