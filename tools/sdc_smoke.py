"""SDC=1 lane: bit-flip detect/quarantine with bitwise parity + canary.

The integrity-plane acceptance (doc/robustness.md "Integrity plane"),
proven end to end through the real CLI on a 4-process CPU mesh:

* **Run A (flip)** — 4 ``jax.distributed`` processes train the
  MNIST-format MLP conf with ``integrity_every = 1``.  Rank 3 is armed
  with ``fault_inject=device.state:bitflip:1:1``: one real bit of one
  live parameter tensor flips on that rank at its first
  ``start_round``.  The fingerprint vote must detect it within
  ``integrity_every`` rounds, name rank 3, quarantine it (exit code
  41), and the survivors must evict + rebuild **in-process** and
  resume from the last consensus (fingerprint-verified) checkpoint.
* **Run B (clean)** — the surviving schedule executed deliberately: a
  3-process run that never contained the corrupt rank (the flip lands
  in run A's first round, which the quarantine discards and re-runs on
  the survivors from the seeded init checkpoint).
* **Parity** — every checkpoint manifest CRC32 the two runs write must
  be IDENTICAL: a run that absorbed and excised real silent data
  corruption ends bit-equal to one where the bad replica never
  existed.
* **Serve canary** — an engine over run B's checkpoints
  (``integrity_probe = 1``) records its golden, survives a clean
  sweep, degrades ``/healthz`` with ``integrity_failed`` on an
  injected CRC drift, and readmits itself on the next clean score.
* **Overhead** — a single-process run of the same conf measures the
  fingerprint sweep against the round wall clock; the ratio must stay
  ≤ 2% and lands in the ``perf_guard`` history (``--bench
  integrity_bench``) with the detection latency so both are
  regression-tracked.

Usage::

    python tools/sdc_smoke.py --out /tmp/_sdc            # the CI lane
    python tools/perf_guard.py --bench integrity_bench \\
        --input /tmp/_sdc/sdc.json --history bench_history.jsonl

Exit code: 0 when detection, quarantine, parity, canary, and the
overhead bound all hold; 1 otherwise (hard gate, not weather).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

NUM_ROUND = 6
GLOBAL_BATCH = 12          # divides 4-way AND 3-way data meshes
N_IMAGES = 960             # 80 global batches/round; blocks tile 4 and 3
N_HIDDEN = 256
FLIP_RANK = 3              # never rank 0 (it hosts both coordinators)
# Seed chosen so the deterministic payload stream picks a NONZERO
# weight (l0_fc1/wmat, mantissa bit 12 — a ~0.05% relative
# perturbation).  A flip that lands on an exactly-zero element at a
# denormal-scale bit is absorbed by the next update's rounding (the
# difference is below one ulp of the updated value) and leaves no
# corruption to detect — mathematically benign, not a missed verdict.
FAULT_SEED = 4
OVERHEAD_MAX = 0.02        # fingerprint sweep / round wall bound
QUARANTINE_RC = 41


def _free_port() -> int:
    from cxxnet_tpu.parallel.elastic import free_port

    return free_port()


def make_data(out_dir: str) -> None:
    import numpy as np

    from cxxnet_tpu.io.mnist import write_idx_images, write_idx_labels

    rng = np.random.RandomState(0)
    imgs = rng.randint(0, 256, (N_IMAGES, 4, 4)).astype(np.uint8)
    labels = (imgs.reshape(N_IMAGES, -1).mean(1) > 127).astype(np.uint8)
    write_idx_images(os.path.join(out_dir, "img.idx"), imgs)
    write_idx_labels(os.path.join(out_dir, "lab.idx"), labels)


def netconfig(hidden: int = N_HIDDEN, dev: str = "cpu") -> str:
    return f"""netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = {hidden}
  init_sigma = 0.1
layer[fc1->out] = fullc:fc2
  nhidden = 2
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end
input_shape = 1,1,16
batch_size = {GLOBAL_BATCH}
dev = {dev}
"""


NETCONFIG = netconfig()


def make_conf(out_dir: str, hidden: int = N_HIDDEN,
              dev: str = "cpu") -> str:
    """One conf for every process of both runs; per-run/per-rank keys
    ride as CLI overrides."""
    conf = os.path.join(out_dir, "sdc.conf")
    with open(conf, "w", encoding="utf-8") as f:
        f.write(f"""
data = train
iter = mnist
  path_img = "{out_dir}/img.idx"
  path_label = "{out_dir}/lab.idx"
  shuffle = 1
  dist_shard = block
iter = end
{netconfig(hidden, dev)}num_round = {NUM_ROUND}
eval_train = 0
eta = 0.1
momentum = 0.9
seed = 7
save_ustate = 1
det_reduce = 1
metric = error
silent = 1
telemetry = 1
integrity_every = 1
integrity_probe = 1
elastic = 1
elastic_min_replicas = 2
elastic_heartbeat_s = 0.25
elastic_timeout_s = 3
collective_timeout_s = 30
""")
    return conf


def launch_rank(conf: str, workdir: str, model_dir: str, rank: int,
                nproc: int, jax_port: int, elastic_port: int, extra=(),
                platform: str = "cpu"):
    d = os.path.join(workdir, f"p{rank}")
    os.makedirs(d, exist_ok=True)
    env = {
        **os.environ,
        "PYTHONPATH": REPO,
        "JAX_PLATFORMS": platform,
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
    }
    over = [f"model_dir={model_dir}"]
    if elastic_port:
        over.append(f"elastic_coordinator=localhost:{elastic_port}")
    if rank >= 0 and nproc > 1:
        over += [f"dist_coordinator=localhost:{jax_port}",
                 f"dist_num_proc={nproc}", f"dist_proc_id={rank}"]
    over += list(extra)
    log = open(os.path.join(d, "out.log"), "wb")
    p = subprocess.Popen(
        [sys.executable, "-u", "-m", "cxxnet_tpu", conf] + over,
        env=env, cwd=d, stdout=log, stderr=subprocess.STDOUT,
    )
    p._log_file = log  # type: ignore[attr-defined]
    p._workdir = workdir  # type: ignore[attr-defined]
    p._rank = rank     # type: ignore[attr-defined]
    return p


def rank_log(workdir: str, rank: int) -> str:
    try:
        with open(os.path.join(workdir, f"p{rank}", "out.log"), "r",
                  encoding="utf-8", errors="replace") as f:
            return f.read()
    except OSError:
        return ""


def drain(procs, timeout: float, problems, tag: str,
          expect_fail_ranks=()):
    deadline = time.time() + timeout
    for p in procs:
        left = max(1.0, deadline - time.time())
        try:
            p.wait(timeout=left)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()
            problems.append(f"{tag}: rank {p._rank} process timed out")
        finally:
            p._log_file.close()
    for p in procs:
        if p._rank in expect_fail_ranks:
            continue
        if p.returncode != 0:
            problems.append(
                f"{tag}: rank {p._rank} exited rc={p.returncode}; "
                "tail:\n" + rank_log(p._workdir, p._rank)[-2500:])


def read_crcs(model_dir: str) -> dict:
    from cxxnet_tpu.utils import checkpoint as ckpt

    out = {}
    for round_, path in ckpt.list_checkpoints(model_dir):
        man = ckpt.read_manifest(path)
        if man is not None:
            out[round_] = man["crc32"]
    return out


def read_telemetry(workdir: str, rank: int = 0) -> list:
    path = os.path.join(workdir, f"p{rank}", "telemetry.jsonl")
    recs = []
    try:
        with open(path, "r", encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if line:
                    recs.append(json.loads(line))
    except (OSError, ValueError):
        pass
    return recs


def run_flip(conf: str, workdir: str, model_dir: str,
             timeout: float, problems) -> dict:
    """Run A: 4 ranks; rank 3 flips one real bit at its first
    start_round.  Detection -> exit-41 quarantine -> in-process evict +
    rebuild -> consensus rollback, all inside one CLI invocation."""
    os.makedirs(model_dir, exist_ok=True)
    jax_port, elastic_port = _free_port(), _free_port()
    procs = []
    for r in range(4):
        extra = ()
        if r == FLIP_RANK:
            # ordered stream: fault_seed must precede the spec it
            # seeds (faults.configure contract)
            extra = (f"fault_seed={FAULT_SEED}",
                     "fault_inject=device.state:bitflip:1:1")
        procs.append(launch_rank(conf, workdir, model_dir, r, 4,
                                 jax_port, elastic_port, extra=extra))
    drain(procs, timeout, problems, "flip",
          expect_fail_ranks={FLIP_RANK})
    if procs[FLIP_RANK].returncode != QUARANTINE_RC:
        problems.append(
            f"flip: rank {FLIP_RANK} exited "
            f"rc={procs[FLIP_RANK].returncode}, expected the "
            f"quarantine code {QUARANTINE_RC}; tail:\n"
            + rank_log(workdir, FLIP_RANK)[-2500:])
    flip_log = rank_log(workdir, FLIP_RANK)
    if "self-quarantining (exit 41)" not in flip_log:
        problems.append("flip: the corrupt rank never announced its "
                        "quarantine; tail:\n" + flip_log[-2000:])
    log0 = rank_log(workdir, 0)
    detect = [int(m) for m in re.findall(
        r"INTEGRITY: integrity state check failed at round (\d+)", log0)]
    named = re.findall(r"corrupt rank (\d+)", log0)
    if not detect:
        problems.append("flip: rank 0 never reported the state verdict; "
                        "log tail:\n" + log0[-2500:])
    if not named or int(named[0]) != FLIP_RANK:
        problems.append(f"flip: vote named rank {named[:1]}, expected "
                        f"{FLIP_RANK}")
    resume = [int(m) for m in re.findall(
        r"integrity_evict -> rebuilding.*?\n.*?resuming at round (\d+)",
        log0, re.S)]
    if not resume:
        problems.append("flip: survivors never rebuilt after the evict; "
                        "log tail:\n" + log0[-2500:])
    tele = read_telemetry(workdir)
    rebuild_s = max((r.get("elastic", {}).get("last_rebuild_s", 0.0)
                     for r in tele), default=0.0)
    return {
        "detect_round": detect[0] if detect else None,
        "resume_round": resume[0] if resume else None,
        "rebuild_wall_s": rebuild_s,
    }


def run_clean(conf: str, workdir: str, model_dir: str,
              timeout: float, problems) -> None:
    """Run B: the corrupt rank's schedule, minus the corrupt rank.

    The flip lands in run A's FIRST round, so the quarantine discards
    that round entirely and re-runs the whole schedule on the 3
    survivors from the (seeded, mesh-independent) init checkpoint.
    The bitwise-parity partner is therefore a 3-process run that never
    contained rank 3 at all — a strictly stronger claim than replaying
    a planned shrink: a run that absorbed and excised real corruption
    is indistinguishable from one where the bad replica never existed."""
    os.makedirs(model_dir, exist_ok=True)
    jax_port, elastic_port = _free_port(), _free_port()
    procs = [launch_rank(conf, workdir, model_dir, r, 3, jax_port,
                         elastic_port)
             for r in range(3)]
    drain(procs, timeout, problems, "clean")


def run_overhead(conf: str, workdir: str, model_dir: str,
                 timeout: float, problems, platform: str = "cpu") -> dict:
    """Single-process run of the same conf: the fingerprint sweep's
    share of the round wall clock, warmup round excluded."""
    os.makedirs(model_dir, exist_ok=True)
    p = launch_rank(conf, workdir, model_dir, 0, 1, 0, 0,
                    extra=["elastic=0"], platform=platform)
    drain([p], timeout, problems, "overhead")
    tele = read_telemetry(workdir)
    ratios = []
    for rec in tele:
        integ = rec.get("integrity", {})
        step = rec.get("step", {})
        wall = step.get("steps", 0) * step.get("mean_ms", 0) / 1e3
        # the FIRST sweep (checks == 1) carries the digest-program
        # compiles; steady state starts at the second check
        if wall > 0 and integ.get("checks", 0) >= 2:
            ratios.append(integ.get("last_elapsed_s", 0.0) / wall)
    if not ratios:
        problems.append("overhead: no usable telemetry records")
        return {"overhead_ratio": None}
    ratio = sum(ratios) / len(ratios)
    if ratio > OVERHEAD_MAX:
        problems.append(
            f"overhead: fingerprint sweep is {ratio:.2%} of round wall "
            f"(bound {OVERHEAD_MAX:.0%})")
    return {"overhead_ratio": round(ratio, 5),
            "rounds_measured": len(ratios)}


def run_serve_canary(model_dir: str, problems) -> dict:
    """Engine over the clean run's checkpoints: golden recorded at
    load, clean sweep, injected drift -> degraded healthz with the
    integrity_failed token, next clean sweep readmits."""
    from cxxnet_tpu import serve

    cfg = NETCONFIG + "integrity_probe = 1\n"
    eng = serve.Engine(cfg=cfg, model_dir=model_dir, max_batch_size=8,
                       batch_timeout_ms=0, silent=True)
    out = {"canary_golden_src": None, "canary_detected": False,
           "canary_readmitted": False}
    try:
        snap = eng.snapshot_stats().get("integrity", {})
        out["canary_golden_src"] = snap.get("golden_src")
        if snap.get("golden_crc32") is None:
            problems.append("canary: engine recorded no golden")
            return out
        if not eng.check_canary():
            problems.append("canary: clean sweep failed (false alarm)")
        eng.inject_canary_mismatch = 1
        if eng.check_canary():
            problems.append("canary: injected drift went undetected")
        h = eng.healthz()
        detected = (h["status"] == "degraded"
                    and "integrity_failed" in h.get("reasons", ()))
        out["canary_detected"] = detected
        if not detected:
            problems.append(f"canary: healthz did not degrade: {h}")
        clean = eng.check_canary()
        ok = eng.healthz()["status"] == "ok"
        out["canary_readmitted"] = clean and ok
        if not (clean and ok):
            problems.append("canary: latch did not clear on the clean "
                            "sweep")
    finally:
        eng.close()
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="/tmp/_sdc",
                    help="scratch + verdict directory")
    ap.add_argument("--timeout", type=float, default=420.0,
                    help="per-run wall-clock budget (seconds)")
    ap.add_argument("--json", dest="json_path", default="",
                    help="verdict path (default <out>/sdc.json)")
    ap.add_argument("--overhead-only", action="store_true",
                    help="skip the flip/parity/canary walk and measure "
                         "only the fingerprint-sweep overhead (the "
                         "full-size bench entry)")
    ap.add_argument("--dev", default="cpu",
                    help="conf dev= value for the overhead run (e.g. "
                         "tpu: the run is ONE child process, which "
                         "owns the chip — this parent never "
                         "initializes a JAX backend)")
    ap.add_argument("--hidden", type=int, default=N_HIDDEN,
                    help="fc1 width for the overhead run (scale the "
                         "model up for the on-chip measurement)")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    make_data(args.out)
    conf = make_conf(args.out, hidden=args.hidden, dev=args.dev)
    problems: list = []
    platform = "tpu" if args.dev.startswith("tpu") else "cpu"

    if args.overhead_only:
        over_dir = os.path.join(args.out, "overhead")
        overhead = run_overhead(conf, over_dir,
                                os.path.join(over_dir, "models"),
                                args.timeout, problems,
                                platform=platform)
        doc = {
            "bench": "integrity_bench",
            "ts": time.time(),
            "rounds": NUM_ROUND,
            "global_batch": GLOBAL_BATCH,
            "hidden": args.hidden,
            "dev": args.dev,
            **overhead,
            "problems": problems,
            "verdict": "ok" if not problems else "fail",
        }
        json_path = args.json_path or os.path.join(args.out, "sdc.json")
        with open(json_path, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=1)
        print(json.dumps(doc, indent=1))
        for p in problems:
            print(f"FAIL {p}", file=sys.stderr)
        return 1 if problems else 0

    t0 = time.time()
    flip_dir = os.path.join(args.out, "flip")
    flip = run_flip(conf, flip_dir, os.path.join(flip_dir, "models"),
                    args.timeout, problems)
    flip_s = time.time() - t0

    detect_rounds = None
    if flip["detect_round"] is not None:
        # the flip lands at the corrupt rank's FIRST start_round
        # (round 0); with integrity_every = 1 the verdict must land at
        # that round's boundary check
        detect_rounds = flip["detect_round"] + 1
        if detect_rounds > 1:
            problems.append(
                f"flip: detection took {detect_rounds} rounds with "
                "integrity_every = 1")

    crc_equal = False
    flip_crcs: dict = {}
    clean_crcs: dict = {}
    clean_s = 0.0
    if flip["resume_round"] is not None and not problems:
        t1 = time.time()
        clean_dir = os.path.join(args.out, "clean")
        run_clean(conf, clean_dir, os.path.join(clean_dir, "models"),
                  timeout=args.timeout, problems=problems)
        clean_s = time.time() - t1
        flip_crcs = read_crcs(os.path.join(flip_dir, "models"))
        clean_crcs = read_crcs(os.path.join(clean_dir, "models"))
        if len(flip_crcs) != NUM_ROUND + 1:
            problems.append(
                f"flip run wrote rounds {sorted(flip_crcs)}, expected "
                f"{NUM_ROUND + 1} checkpoints")
        crc_equal = bool(flip_crcs) and flip_crcs == clean_crcs
        if not crc_equal:
            problems.append(
                "BITWISE PARITY FAILED: flipped-and-quarantined CRCs "
                f"{ {k: hex(v) for k, v in sorted(flip_crcs.items())} } "
                "!= clean-schedule CRCs "
                f"{ {k: hex(v) for k, v in sorted(clean_crcs.items())} }")

    canary = {"canary_golden_src": None}
    if not problems:
        canary = run_serve_canary(
            os.path.join(args.out, "clean", "models"), problems)

    over_dir = os.path.join(args.out, "overhead")
    overhead = run_overhead(conf, over_dir,
                            os.path.join(over_dir, "models"),
                            args.timeout, problems)

    doc = {
        "bench": "integrity_bench",
        "ts": time.time(),
        "rounds": NUM_ROUND,
        "global_batch": GLOBAL_BATCH,
        "detect_rounds": detect_rounds,
        "resume_round": flip["resume_round"],
        "rebuild_wall_s": flip["rebuild_wall_s"],
        "crc_equal": crc_equal,
        "crcs": {str(k): f"{v:#010x}"
                 for k, v in sorted(flip_crcs.items())},
        **canary,
        **overhead,
        "flip_wall_sec": round(flip_s, 3),
        "clean_wall_sec": round(clean_s, 3),
        "problems": problems,
        "verdict": "ok" if not problems else "fail",
    }
    json_path = args.json_path or os.path.join(args.out, "sdc.json")
    with open(json_path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps(doc, indent=1))
    for p in problems:
        print(f"FAIL {p}", file=sys.stderr)
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
