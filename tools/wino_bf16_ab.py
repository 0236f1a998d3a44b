"""bf16 Winograd + branch-embed A/B (CPU).

The F(4x4,3x3) tile's transform constants reach |8|, amplifying bf16
rounding ~15x vs the direct conv (``cxxnet_tpu/layers/conv.py`` — the
known fp16-Winograd tradeoff); F(2x2,3x3) stays within ~3x.  Layer-level
pair tests bound the per-op error; this tool characterizes what that
error does to END-TO-END TRAINING — the evidence a default flip needs
(the reference's pairtest ethos applied at model scale,
``/root/reference/src/layer/pairtest_layer-inl.hpp:160-198``).

Two model-scale probes, all under ``compute_dtype = bfloat16``:

* digits-conv (``example/MNIST/digits_conv.conf``, real handwritten
  digits, the repo's MNIST stand-in): full 15-round test-error
  trajectory for conv_wino = 0 / 1 / 2 (+ an fp32 direct reference);
* GoogLeNet membuffer-overfit (the ``iter = membuffer`` one-batch
  discipline): steps until eval error hits 0 — a deep-net gradient-path
  sanity check with 3x3 branches on the Winograd path.

A third probe (``--bembed-only``) records the CPU half of the
branch-embedding promotion verdict (PR 10 flipped
``conv_branch_embed`` to auto: ON for inference program builds): on
the GoogLeNet builder conf it measures fused-vs-unfused EXACTNESS of
the inference forward (max |score delta| + top-1 flips over random
batches) and the CPU predict throughput delta.  PROMOTE requires
zero top-1 flips and throughput inside a 10% band; the on-chip
step-time A/B for the train side is still owed (ROADMAP S4,
``googlenet_bisect.py bembed``).

Usage:  python tools/wino_bf16_ab.py
        [--digits-only|--googlenet-only|--bembed-only]
Writes: example/MNIST/wino_bf16_ab.log (the committed artifact).
"""

import os
import re
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

LOG_PATH = os.path.join(REPO, "example", "MNIST", "wino_bf16_ab.log")


def _cpu_env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    return env


def digits_trajectory(workdir: str, extra_args) -> dict:
    """Run the digits-conv recipe through the real CLI; return
    {round: test_error}."""
    r = subprocess.run(
        [sys.executable, "-m", "cxxnet_tpu", "digits_conv.conf",
         "task=train", "save_model=0"] + list(extra_args),
        cwd=workdir, env=_cpu_env(), capture_output=True, text=True,
    )
    if r.returncode != 0:
        raise RuntimeError(f"digits run failed: {r.stderr[-2000:]}")
    return {
        int(m.group(1)): float(m.group(2))
        for m in re.finditer(
            r"\[(\d+)\]\ttrain-error:\S+\ttest-error:(\S+)", r.stderr)
    }


def run_digits(out) -> None:
    import shutil

    tmp = tempfile.mkdtemp(prefix="wino_ab_")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "make_digits_idx.py"),
         os.path.join(tmp, "data")],
        capture_output=True, text=True,
    )
    if r.returncode != 0:
        raise RuntimeError(f"make_digits_idx failed: {r.stderr}")
    shutil.copy(os.path.join(REPO, "example", "MNIST", "digits_conv.conf"),
                os.path.join(tmp, "digits_conv.conf"))
    variants = [
        ("fp32 direct", []),
        ("bf16 direct", ["compute_dtype=bfloat16"]),
        ("bf16 wino F(4x4)", ["compute_dtype=bfloat16", "conv_wino=1"]),
        ("bf16 wino F(2x2)", ["compute_dtype=bfloat16", "conv_wino=2"]),
    ]
    results = {}
    for name, args in variants:
        t0 = time.time()
        errs = digits_trajectory(tmp, args)
        results[name] = errs
        out(f"# digits {name}: {time.time() - t0:.0f}s, "
            f"round-15 test-error {errs.get(15, float('nan')):.4f}")
    out("")
    out("digits-conv, 15 rounds, test-error trajectory")
    out("round | " + " | ".join(n for n, _ in variants))
    rounds = sorted(results[variants[0][0]])
    for k in rounds:
        out(f"{k:5d} | " + " | ".join(
            f"{results[n].get(k, float('nan')):11.4f}" for n, _ in variants))
    out("")
    shutil.rmtree(tmp, ignore_errors=True)


def googlenet_overfit(wino: int, n_steps: int = 300):
    """Return (steps_to_zero_err, final_err) for a bf16 GoogLeNet
    membuffer overfit with the given conv_wino."""
    from cxxnet_tpu import config as C
    from cxxnet_tpu.io.data import create_iterator
    from cxxnet_tpu.models import googlenet_conf
    from cxxnet_tpu.nnet.trainer import NetTrainer

    it = create_iterator(C.split_sections(C.parse_pairs("""
data = train
iter = synthetic
  nsample = 8
  input_shape = 3,64,64
  nclass = 10
  label_width = 1
  batch_size = 8
iter = membuffer
  max_nbatch = 1
iter = end
""")).find("data")[0].entries)
    it.init()
    tr = NetTrainer()
    tr.set_params(C.parse_pairs(googlenet_conf(
        batch_size=8, num_class=10, synthetic=False, dev="cpu",
        input_size=64)))
    for k, v in [("updater", "adam"), ("eta", "0.001"),
                 ("wmat:lr", "0.001"), ("bias:lr", "0.001"),
                 ("wd", "0.0"), ("wmat:wd", "0.0"),
                 ("compute_dtype", "bfloat16"),
                 ("conv_wino", str(wino))]:
        tr.set_param(k, v)
    tr.eval_train = 0
    tr.init_model()
    it.before_first()
    assert it.next()
    cached = it.value()
    err = 1.0
    for step in range(n_steps):
        it.before_first()
        while it.next():
            tr.update(it.value())
        if (step + 1) % 25 == 0:
            pred = tr.predict(cached)
            err = float((pred != cached.label[:, 0]).mean())
            if err == 0.0:
                return step + 1, err
    return None, err


def run_googlenet(out) -> None:
    out("GoogLeNet bf16 membuffer-overfit (8 cached images, adam 1e-3;"
        " steps checked every 25)")
    out("conv_wino | steps-to-0-error | final-error")
    for wino in (0, 1, 2):
        t0 = time.time()
        steps, err = googlenet_overfit(wino)
        out(f"{wino:9d} | {steps if steps is not None else '>300':>16} "
            f"| {err:.3f}   ({time.time() - t0:.0f}s)")
    out("")


def run_bembed(out) -> None:
    """CPU promote/reject evidence for inference-build branch-embed:
    exactness (top-1 flips must be 0) + predict-throughput band."""
    import numpy as np

    from cxxnet_tpu import config as C
    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.models import googlenet_conf
    from cxxnet_tpu.nnet.trainer import NetTrainer

    def build(bembed: str):
        tr = NetTrainer()
        tr.set_params(C.parse_pairs(googlenet_conf(
            batch_size=8, num_class=10, synthetic=False, dev="cpu",
            input_size=64)))
        tr.set_param("conv_branch_embed", bembed)
        tr.set_param("seed", "7")
        tr.init_model()
        return tr

    t_off, t_on = build("0"), build("1")
    rng = np.random.RandomState(0)
    flips = 0
    max_dd = 0.0
    rates = {}
    for name, tr in (("unfused", t_off), ("fused", t_on)):
        b = DataBatch(data=rng.rand(8, 64, 64, 3).astype(np.float32),
                      label=np.zeros((8, 1), np.float32))
        tr.predict(b)  # warm the compile
    for k in range(6):
        x = rng.rand(8, 64, 64, 3).astype(np.float32)
        b = DataBatch(data=x, label=np.zeros((8, 1), np.float32))
        s_off = t_off.extract_feature(b, "top[-1]")
        s_on = t_on.extract_feature(b, "top[-1]")
        max_dd = max(max_dd, float(np.abs(s_off - s_on).max()))
        flips += int((s_off.argmax(1) != s_on.argmax(1)).sum())
    for name, tr in (("unfused", t_off), ("fused", t_on)):
        b = DataBatch(data=rng.rand(8, 64, 64, 3).astype(np.float32),
                      label=np.zeros((8, 1), np.float32))
        t0 = time.time()
        n = 0
        while time.time() - t0 < 5.0:
            tr.predict(b)
            n += 8
        rates[name] = n / (time.time() - t0)
    ratio = rates["fused"] / rates["unfused"]
    verdict = ("PROMOTE" if flips == 0 and ratio >= 0.9 else "REJECT")
    out("branch-embed inference A/B (GoogLeNet 64px b8, CPU)")
    out(f"  top-1 flips over 48 rows: {flips}; max |score delta| "
        f"{max_dd:.2e}")
    out(f"  predict rows/s unfused {rates['unfused']:.1f} -> fused "
        f"{rates['fused']:.1f} (ratio {ratio:.3f})")
    out(f"  CPU-backend verdict: {verdict} (exactness + 10% band) — "
        "the conv_branch_embed=-1 auto default follows it: fused "
        "inference builds on accelerator backends only, never on "
        "CPU; the on-chip confirmation is still owed "
        "(googlenet_bisect.py bembed / serve_bench --quant)")
    out("")


def main() -> None:
    lines = []

    def out(s: str) -> None:
        print(s, flush=True)
        lines.append(s)

    only = [a for a in sys.argv[1:] if a.endswith("-only")]
    out(f"# wino_bf16_ab @ {time.strftime('%Y-%m-%dT%H:%M:%SZ', time.gmtime())}")
    if not only or "--digits-only" in only:
        run_digits(out)
    if not only or "--googlenet-only" in only:
        run_googlenet(out)
    if not only or "--bembed-only" in only:
        run_bembed(out)
    # append: split --digits-only / --googlenet-only invocations build
    # one log; the timestamp header delimits runs
    with open(LOG_PATH, "a") as f:
        f.write("\n".join(lines) + "\n")
    print(f"# wrote {LOG_PATH}")


if __name__ == "__main__":
    main()
