"""ASYNC=1 lane: bitwise parity + bounded-staleness convergence A/B.

The async data-parallel subsystem (``cxxnet_tpu/parallel/async_ps``,
doc/parallel.md "Async data-parallel") makes two claims with two very
different proof obligations, and this tool runs both:

* ``--parity`` — **bitwise**: a 4-process CPU-mesh CLI train with
  ``async_overlap = 1, staleness = 0`` must write checkpoint CRCs
  IDENTICAL to the synchronous ``det_reduce = 1`` fused step of the
  same conf/seed (same all-gather + ordered fold, same updater math —
  the overlap is dispatch scheduling, not different arithmetic).
  Hard gate: CRC mismatch exits 1.
* default (A/B) — **measured convergence**: ``staleness > 0`` DOES
  change the math (k-step-delayed aggregates), so it is gated the way
  wino/bembed kernel promotions were: REAL handwritten digits (the
  repo's digits.conf recipe, fixed seeds), sync vs staleness in
  {0, 1, 2} on the same stream, final test error + wall-clock deltas
  in a schema-stable verdict JSON.  ``staleness = 0`` must match sync
  EXACTLY; ``staleness = 1`` must stay within ``--tol`` of sync at
  full lr; ``staleness = 2`` at full lr is measured and RECORDED
  (reject expected — delay x momentum instability, the classic
  result) and must pass within ``--tol`` under the standard mitigation
  (lr halved, rounds doubled) against the same-lr sync baseline.  The
  committed CPU verdict lives in example/MNIST/async_ab.json.
* ``--overlap-bench`` — in-process step-wall micro-bench (sync fence
  per step vs one round fence).  One process, so ``--dev tpu`` goes to
  a multi-chip host as it is; the measurement is still owed (ROADMAP
  S10): CPU numbers are dispatch-overhead weather, the chip is where
  overlap pays.

Usage::

    python tools/async_ab.py --parity --out /tmp/_async      # hard gate
    python tools/async_ab.py --out /tmp/_async               # full A/B
    python tools/async_ab.py --smoke --out /tmp/_async       # CI lane
    python tools/perf_guard.py --bench async_bench \\
        --input /tmp/_async/async_ab.json --history bench_history.jsonl
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N_IMAGES = 256
GLOBAL_BATCH = 32


def _free_port() -> int:
    from cxxnet_tpu.parallel.elastic import free_port

    return free_port()


def make_data(out_dir: str, n_images: int) -> None:
    import numpy as np

    from cxxnet_tpu.io.mnist import write_idx_images, write_idx_labels

    rng = np.random.RandomState(0)
    imgs = rng.randint(0, 256, (n_images, 4, 4)).astype(np.uint8)
    labels = (imgs.reshape(n_images, -1).mean(1) > 127).astype(np.uint8)
    write_idx_images(os.path.join(out_dir, "img.idx"), imgs)
    write_idx_labels(os.path.join(out_dir, "lab.idx"), labels)


def make_conf(out_dir: str, rounds: int, save_model: int) -> str:
    """The MNIST-MLP conf every leg shares (fixed seed; per-leg keys
    ride as CLI overrides).  An eval section scores the full set each
    round so telemetry carries ``test-error`` — the A/B's metric."""
    conf = os.path.join(out_dir, "async_ab.conf")
    with open(conf, "w", encoding="utf-8") as f:
        f.write(f"""
data = train
iter = mnist
  path_img = "{out_dir}/img.idx"
  path_label = "{out_dir}/lab.idx"
  shuffle = 1
  dist_shard = block
iter = end
eval = test
iter = mnist
  path_img = "{out_dir}/img.idx"
  path_label = "{out_dir}/lab.idx"
iter = end
netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 16
  init_sigma = 0.1
layer[fc1->out] = fullc:fc2
  nhidden = 2
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end
input_shape = 1,1,16
batch_size = {GLOBAL_BATCH}
dev = cpu:0-3
num_round = {rounds}
eval_train = 0
eta = 0.1
momentum = 0.9
seed = 7
save_model = {save_model}
metric = error
silent = 1
telemetry = 1
""")
    return conf


def run_leg(conf: str, workdir: str, overrides, nproc: int = 1,
            timeout: float = 240.0, port: int = 0) -> float:
    """One CLI training leg; returns its wall seconds.  ``nproc > 1``
    launches a real jax.distributed job (the parity mode's 4-process
    mesh; gloo collectives, 1 device per process)."""
    ndev = 4 // nproc
    env = {
        **os.environ,
        "PYTHONPATH": REPO,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={ndev}",
    }
    procs = []
    t0 = time.time()
    for r in range(nproc):
        d = os.path.join(workdir, f"p{r}")
        os.makedirs(d, exist_ok=True)
        over = list(overrides)
        if nproc > 1:
            over += [f"dist_coordinator=localhost:{port}",
                     f"dist_num_proc={nproc}", f"dist_proc_id={r}",
                     "dev=cpu"]
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "cxxnet_tpu", conf] + over,
            env=env, cwd=d,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        ))
    try:
        # ONE shared deadline for the whole leg, not one per process —
        # a wedged 4-process leg must die at t0+timeout, not at
        # 4 x timeout (which would blow the ASYNC=1 lane's outer
        # budget and lose the diagnostics)
        deadline = t0 + timeout
        outs = [p.communicate(timeout=max(1.0, deadline - time.time()))[0]
                for p in procs]
    except subprocess.TimeoutExpired:
        # kill the leg, then salvage whatever each rank printed — the
        # timeout must surface as a diagnosable RuntimeError the caller
        # seals into the verdict JSON, not a bare stack trace
        for p in procs:
            if p.poll() is None:
                p.kill()
        tails = []
        for r, p in enumerate(procs):
            try:
                o = p.communicate(timeout=5)[0] or b""
            except Exception:  # noqa: BLE001 - salvage is best-effort
                o = b""
            tails.append(f"--- rank {r} (rc={p.returncode}) ---\n"
                         + o.decode(errors="replace")[-2000:])
        raise RuntimeError(
            f"async_ab leg timed out after {timeout:.0f}s "
            f"(overrides={overrides}):\n" + "\n".join(tails)) from None
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for p, o in zip(procs, outs):
        if p.returncode != 0:
            raise RuntimeError(
                f"async_ab leg failed (rc={p.returncode}, "
                f"overrides={overrides}):\n{o.decode()[-4000:]}")
    return time.time() - t0


def read_telemetry(rank_dir: str) -> dict:
    """Last telemetry record of a leg (final-round eval + async block)."""
    last = {}
    try:
        with open(os.path.join(rank_dir, "telemetry.jsonl"),
                  "r", encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if line:
                    last = json.loads(line)
    except (OSError, ValueError):
        return {}
    return last


def read_crcs(rank_dir: str) -> dict:
    from cxxnet_tpu.utils import checkpoint as ckpt

    out = {}
    for round_, path in ckpt.list_checkpoints(
            os.path.join(rank_dir, "models")):
        man = ckpt.read_manifest(path)
        if man is not None:
            out[round_] = man["crc32"]
    return out


def final_error(tele: dict) -> float:
    ev = tele.get("eval") or {}
    for k in sorted(ev):
        if "test-" in k and "error" in k:
            return float(ev[k])
    return float("nan")


# ----------------------------------------------------------------------
def run_parity(out_dir: str, rounds: int, timeout: float) -> dict:
    """The hard gate: 4-process async(staleness=0) CRCs == 4-process
    det_reduce sync CRCs, checkpoint for checkpoint."""
    workdir = os.path.join(out_dir, "parity")
    conf = make_conf(out_dir, rounds, save_model=1)
    legs = {}
    for name, over in (
            ("sync", ["det_reduce=1"]),
            ("async0", ["async_overlap=1", "staleness=0"])):
        wall = run_leg(conf, os.path.join(workdir, name), over,
                       nproc=4, timeout=timeout, port=_free_port())
        crcs = read_crcs(os.path.join(workdir, name, "p0"))
        legs[name] = {"wall_sec": round(wall, 3), "crcs": crcs}
    problems = []
    if not legs["sync"]["crcs"]:
        problems.append("parity: sync leg wrote no checkpoints")
    if legs["sync"]["crcs"] != legs["async0"]["crcs"]:
        problems.append(
            f"BITWISE PARITY FAILED: sync CRCs {legs['sync']['crcs']} "
            f"!= async CRCs {legs['async0']['crcs']}")
    return {
        "crc_equal": legs["sync"]["crcs"] == legs["async0"]["crcs"]
        and bool(legs["sync"]["crcs"]),
        "rounds": rounds,
        "sync_wall_sec": legs["sync"]["wall_sec"],
        "async_wall_sec": legs["async0"]["wall_sec"],
        "crcs": {str(k): f"{v:#010x}" for k, v in
                 sorted(legs["sync"]["crcs"].items())},
        "problems": problems,
    }


def make_digits_conf(out_dir: str) -> str:
    """The REAL-data A/B conf: the repo's digits.conf recipe (UCI
    handwritten digits via sklearn, idx-encoded by
    tools/make_digits_idx.py) on the 4-device mesh — batch 48 so the
    data axis divides.  eta / num_round / async keys ride per leg as
    CLI overrides (last entry wins)."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from make_digits_idx import write_digits_idx

    data_dir = os.path.join(out_dir, "data")
    write_digits_idx(data_dir)
    conf = os.path.join(out_dir, "async_digits.conf")
    with open(conf, "w", encoding="utf-8") as f:
        f.write(f"""
data = train
iter = mnist
  path_img = "{data_dir}/digits-train-images-idx3-ubyte"
  path_label = "{data_dir}/digits-train-labels-idx1-ubyte"
  shuffle = 1
iter = end
eval = test
iter = mnist
  path_img = "{data_dir}/digits-t10k-images-idx3-ubyte"
  path_label = "{data_dir}/digits-t10k-labels-idx1-ubyte"
iter = end
netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 100
  init_sigma = 0.01
layer[+1:sg1] = sigmoid:se1
layer[sg1->fc2] = fullc:fc2
  nhidden = 10
  init_sigma = 0.01
layer[+0] = softmax
netconfig=end
input_shape = 1,1,64
batch_size = 48
dev = cpu:0-3
eval_train = 0
random_type = gaussian
seed = 1
eta = 0.1
momentum = 0.9
save_model = 0
metric[label] = error
silent = 1
telemetry = 1
""")
    return conf


def run_ab(out_dir: str, rounds: int, tol: float, timeout: float,
           smoke: bool = False) -> dict:
    """The convergence A/B on real digits: single process over the
    4-device mesh, save_model=0 (no checkpoint drain — the staleness
    pipeline persists across rounds; the resync period caps it).

    Per-leg verdicts: ``exact`` (bitwise-math legs), ``pass`` /
    ``reject`` by ``tol`` for the stale legs — a reject is a RECORDED
    measurement (the wino-verdict discipline), and only gates the lane
    where the contract says it must pass."""
    workdir = os.path.join(out_dir, "ab")
    conf = make_digits_conf(out_dir)
    asynck = ["async_overlap=1", "async_resync_period=1000"]
    specs = [
        # name, overrides, baseline leg, must_pass
        ("sync", ["det_reduce=1", f"num_round={rounds}"], None, True),
        ("staleness0", asynck + ["staleness=0", f"num_round={rounds}"],
         "sync", True),
        ("staleness1", asynck + ["staleness=1", f"num_round={rounds}"],
         "sync", True),
        ("staleness2", asynck + ["staleness=2", f"num_round={rounds}"],
         "sync", False),  # full-lr delay-2: measured, reject expected
        ("sync_lr_backoff",
         ["det_reduce=1", "eta=0.05", f"num_round={2 * rounds}"],
         None, True),
        ("staleness2_lr_backoff",
         asynck + ["staleness=2", "eta=0.05", f"num_round={2 * rounds}"],
         "sync_lr_backoff", True),  # the standard mitigation must work
    ]
    if smoke:  # the CI lane: exactness + schema only, tiny budget
        specs = [s for s in specs if s[0] in ("sync", "staleness0")]
    legs, problems = {}, []
    for name, over, _base, _must in specs:
        d = os.path.join(workdir, name)
        wall = run_leg(conf, d, over, nproc=1, timeout=timeout)
        tele = read_telemetry(os.path.join(d, "p0"))
        err = final_error(tele)
        leg = {"final_err": err, "wall_sec": round(wall, 3),
               "rounds": tele.get("round")}
        a = tele.get("async")
        if a:
            leg["overlap_fraction"] = a.get("overlap_fraction")
            leg["pushes"] = a.get("pushes")
            leg["applies"] = a.get("applies")
        legs[name] = leg
        if err != err:  # NaN
            problems.append(f"{name}: no test-error in telemetry")
    deltas = {}
    for name, _over, base, must_pass in specs:
        if base is None:
            legs[name]["verdict"] = "baseline"
            continue
        base_err = legs[base]["final_err"]
        delta = abs(legs[name]["final_err"] - base_err)
        if name == "staleness0":
            ok = legs[name]["final_err"] == base_err
            legs[name]["verdict"] = "exact" if ok else "reject"
            if not ok:
                problems.append(
                    f"staleness=0 final error {legs[name]['final_err']} "
                    f"!= sync {base_err} (must be EXACT — same math)")
            continue
        deltas[name] = {
            "err_delta": round(delta, 6),
            "vs": base,
            "wall_delta_sec": round(
                legs[name]["wall_sec"] - legs[base]["wall_sec"], 3),
        }
        ok = delta <= tol
        legs[name]["verdict"] = "pass" if ok else "reject"
        if must_pass and not ok:
            problems.append(
                f"{name}: final error {legs[name]['final_err']} drifted "
                f"{delta:.4f} > tol {tol} from {base} {base_err}")
    return {"legs": legs, "deltas": deltas, "tol": tol,
            "dataset": "uci-digits (tools/make_digits_idx.py)",
            "problems": problems}


def run_overlap_bench(dev: str, steps: int, hidden: int) -> dict:
    """In-process step-wall micro-bench on ``dev``: per-step fence
    (sync) vs one round-boundary fence (async) over the same stream.
    CPU numbers only show dispatch overhead; the chip shows
    exchange/compute overlap."""
    import numpy as np

    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.nnet.trainer import NetTrainer

    bs, nin, nout = 64, 64, 8
    cfg = [
        ("dev", dev), ("batch_size", str(bs)),
        ("input_shape", f"1,1,{nin}"), ("seed", "7"), ("eta", "0.05"),
        ("eval_train", "0"),
        ("netconfig", "start"),
        ("layer[0->1]", "fullc:fc1"), ("nhidden", str(hidden)),
        ("layer[1->2]", "sigmoid"),
        ("layer[2->3]", "fullc:fc2"), ("nhidden", str(nout)),
        ("layer[3->3]", "softmax"),
        ("netconfig", "end"),
    ]

    def build(extra):
        tr = NetTrainer()
        tr.set_params(cfg + extra)
        tr.init_model()
        return tr

    rng = np.random.RandomState(3)
    batches = [
        DataBatch(data=rng.randn(bs, nin).astype(np.float32),
                  label=rng.randint(0, nout, (bs, 1)).astype(np.float32))
        for _ in range(steps)
    ]
    out = {"dev": dev, "steps": steps, "hidden": hidden}
    for name, extra in (("sync", [("det_reduce", "1")]),
                        ("async", [("async_overlap", "1"),
                                   ("staleness", "1"),
                                   ("async_resync_period", "1")])):
        tr = build(extra)
        if name == "async" and not tr.fence_at_round_end:
            raise SystemExit(
                f"overlap-bench: async mode inactive on dev={dev!r} "
                "(1-device mesh?) — the measurement would time a no-op")
        tr.update(batches[0])  # warm the compiles outside the timing
        tr.sync() if name == "sync" else tr.async_round_end(0)
        t0 = time.perf_counter()
        for b in batches:
            tr.update(b)
            if name == "sync":
                tr.sync()
        if name == "async":
            tr.async_round_end(1)
        wall = time.perf_counter() - t0
        out[f"{name}_step_wall_sec"] = round(wall / steps, 6)
        if name == "async":
            out["overlap_fraction"] = round(
                tr.async_snapshot()["overlap_fraction"], 4)
    out["speedup"] = round(
        out["sync_step_wall_sec"] / out["async_step_wall_sec"], 3)
    return out


def validate_doc(doc: dict):
    problems = []
    for key in ("bench", "ts", "verdict"):
        if key not in doc:
            problems.append(f"verdict missing key {key!r}")
    if doc.get("verdict") not in ("ok", "fail"):
        problems.append(f"bad verdict {doc.get('verdict')!r}")
    legs = (doc.get("ab") or {}).get("legs")
    if legs is not None:
        for name, leg in legs.items():
            for f in ("final_err", "wall_sec"):
                if not isinstance(leg.get(f), (int, float)):
                    problems.append(f"leg {name}: missing {f}")
    return problems


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="/tmp/_async_ab")
    ap.add_argument("--rounds", type=int, default=15,
                    help="A/B rounds at full lr (the digits.conf "
                         "budget; lr-backoff legs run 2x)")
    ap.add_argument("--tol", type=float, default=0.1,
                    help="allowed |final_err - sync| for staleness>0")
    ap.add_argument("--timeout", type=float, default=240.0,
                    help="per-leg wall budget (seconds)")
    ap.add_argument("--parity", action="store_true",
                    help="ONLY the 4-process bitwise parity gate")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny A/B + parity (the ASYNC=1 CI lane)")
    ap.add_argument("--overlap-bench", action="store_true",
                    help="in-process step-wall micro-bench (TPU queue)")
    ap.add_argument("--dev", default="cpu:0-3",
                    help="--overlap-bench device string")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--json", dest="json_path", default="")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    doc = {"bench": "async_ab", "ts": time.time()}
    problems = []

    if args.overlap_bench:
        if ":" not in args.dev:
            # a bare platform ("tpu") would parse to ONE device and
            # silently deactivate async mode (1-device no-op) — expand
            # to every device of the platform so the bench measures a
            # real exchange; cpu needs the forced-host-count flag below
            # and therefore must be passed explicitly (e.g. cpu:0-3)
            if args.dev.startswith("cpu"):
                ap.error("--overlap-bench needs an explicit multi-"
                         "device cpu spec (e.g. --dev cpu:0-3)")
            import jax

            n = jax.device_count()
            if n < 2:
                ap.error(f"--overlap-bench: only {n} {args.dev} "
                         "device(s) visible; async mode needs >= 2")
            args.dev = f"{args.dev}:0-{n - 1}"
        if args.dev.startswith("cpu") and ":" in args.dev:
            # the in-process bench runs on a forced multi-device host
            # platform (the subprocess legs set this per leg); must
            # land before jax initializes its backends
            spec = args.dev.split(":", 1)[1]
            n = 1 + max(int(p.split("-")[-1]) for p in spec.split(","))
            flags = os.environ.get("XLA_FLAGS", "")
            if "host_platform_device_count" not in flags:
                os.environ["XLA_FLAGS"] = (
                    f"{flags} --xla_force_host_platform_device_count={n}"
                ).strip()
        doc["overlap"] = run_overlap_bench(args.dev, args.steps,
                                           args.hidden)
        o = doc["overlap"]
        # one self-contained verdict line, greppable from a long log
        print(f"bench[async_overlap:{o['dev']}] "
              f"sync_step={o['sync_step_wall_sec']}s "
              f"async_step={o['async_step_wall_sec']}s "
              f"speedup={o['speedup']}x "
              f"overlap_fraction={o['overlap_fraction']}")
    else:
        try:
            make_data(args.out, 64 if args.smoke else N_IMAGES)
            # the parity gate always runs in data mode: a committed A/B
            # verdict without the bitwise proof is only half the
            # contract
            doc["parity"] = run_parity(args.out, 2 if args.smoke else 3,
                                       args.timeout)
            problems += doc["parity"]["problems"]
            if not args.parity:
                doc["ab"] = run_ab(args.out,
                                   3 if args.smoke else args.rounds,
                                   args.tol, args.timeout,
                                   smoke=args.smoke)
                problems += doc["ab"]["problems"]
        except RuntimeError as e:
            # a failed/timed-out leg still produces a fail-verdict
            # artifact with the captured child output — perf_guard and
            # the lane diagnose from the JSON, never from a stack trace
            problems.append(f"leg failure: {str(e)[:6000]}")

    doc["problems"] = problems
    doc["verdict"] = "ok" if not problems else "fail"
    schema_problems = validate_doc(doc)
    if schema_problems:
        # seal the schema failures INTO the written artifact — the
        # committed JSON must never say "ok" while the exit code says
        # fail (perf_guard and the example verdict consume the file)
        problems += schema_problems
        doc["problems"] = problems
        doc["verdict"] = "fail"
    json_path = args.json_path or os.path.join(args.out, "async_ab.json")
    with open(json_path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps(doc, indent=1))
    for p in problems:
        print(f"FAIL {p}", file=sys.stderr)
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
