"""GoogLeNet convergence proxy on synthetic-but-learnable imgbin data
(VERDICT r4 "What's missing" #4 / "Next round" #6).

Real ImageNet is unreachable from the sandbox (zero egress), so
"top-1 parity" (BASELINE.json) cannot be demonstrated directly.  This
is the strongest available stand-in beyond the one-batch overfit
smoke: a full multi-round training run of the real GoogLeNet conf
through the REAL input path (imgbin shard -> JPEG decode -> rand-crop/
mirror augment -> batch -> train), on a 10-class dataset whose signal
is genuinely visual — each class is a sinusoidal grating at a
class-specific spatial frequency, with random orientation, phase,
offset and pixel noise per image, so the net must learn a
texture-frequency discriminator rather than memorize pixels.  The
signal is crop- and mirror-invariant by construction, so augmentation
is exercised honestly.

What the committed trajectory proves: the full stack (pipeline,
augmentation, BN batch stats, inception topology, schedules) *learns*
— train/eval error fall from 90% (chance) toward ~0 over rounds, with
a held-out eval split.  What it does NOT prove: ImageNet-scale top-1;
that stays flagged until real data exists in the sandbox.

    python tools/convergence_proxy.py [n_train] [n_eval] [rounds] [batch]

Writes example/ImageNet/convergence_proxy.log (the committed artifact).
"""

import io
import os
import re
import subprocess
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

LOG_PATH = os.path.join(REPO, "example", "ImageNet", "convergence_proxy.log")

# class k -> grating wavelength in pixels (distinct, ratio ~1.23 apart
# so JPEG + bilinear survive the spacing)
WAVELENGTHS = [3.0, 3.7, 4.6, 5.7, 7.0, 8.7, 10.7, 13.2, 16.3, 20.2]


def generate_class_imgbin(workdir: str, prefix: str, n: int, size: int,
                          seed: int) -> None:
    """n JPEGs whose label is decodable only from texture frequency."""
    from PIL import Image

    from cxxnet_tpu.io.imgbin import BinPageWriter

    rng = np.random.RandomState(seed)
    writer = BinPageWriter(os.path.join(workdir, f"{prefix}.bin"))
    yy, xx = np.mgrid[0:size, 0:size].astype(np.float32)
    with open(os.path.join(workdir, f"{prefix}.lst"), "w") as lst:
        for i in range(n):
            k = int(rng.randint(10))
            wl = WAVELENGTHS[k]
            theta = rng.uniform(0, np.pi)          # orientation: nuisance
            phase = rng.uniform(0, 2 * np.pi)      # phase: nuisance
            u = xx * np.cos(theta) + yy * np.sin(theta)
            img = 128 + rng.uniform(50, 90) * np.sin(2 * np.pi * u / wl
                                                     + phase)
            img = img[..., None] + rng.uniform(-30, 30, (1, 1, 3))
            img += rng.randn(size, size, 3) * 10
            pil = Image.fromarray(
                np.clip(img, 0, 255).astype(np.uint8), "RGB")
            buf = io.BytesIO()
            pil.save(buf, "JPEG", quality=90)
            writer.push(buf.getvalue())
            lst.write(f"{i}\t{k}\tgrating_{i}.jpg\n")
    writer.close()


def main() -> None:
    n_train = int(sys.argv[1]) if len(sys.argv) > 1 else 1024
    n_eval = int(sys.argv[2]) if len(sys.argv) > 2 else 512
    rounds = int(sys.argv[3]) if len(sys.argv) > 3 else 10
    batch = int(sys.argv[4]) if len(sys.argv) > 4 else 16

    from cxxnet_tpu.models import googlenet_conf

    t0 = time.time()
    with tempfile.TemporaryDirectory() as workdir:
        generate_class_imgbin(workdir, "train", n_train, 80, seed=1)
        generate_class_imgbin(workdir, "eval", n_eval, 80, seed=2)
        conf = f"""
data = train
iter = imgbin
  image_bin = {workdir}/train.bin
  image_list = {workdir}/train.lst
  rand_crop = 1
  rand_mirror = 1
  shuffle = 1
  mean_value = 128,128,128
  divideby = 64
  input_shape = 3,64,64
  batch_size = {batch}
  round_batch = 1
  label_width = 1
iter = threadbuffer
iter = end
eval = test
iter = imgbin
  image_bin = {workdir}/eval.bin
  image_list = {workdir}/eval.lst
  mean_value = 128,128,128
  divideby = 64
  input_shape = 3,64,64
  batch_size = {batch}
  round_batch = 1
  label_width = 1
iter = end
""" + googlenet_conf(batch_size=batch, num_class=10, input_size=64,
                     synthetic=False, dev="cpu") + f"""
num_round = {rounds}
max_round = {rounds}
save_model = 0
eval_train = 1
metric = logloss
# the builder's sgd schedule is tuned for b128 ImageNet and diverges
# (NaN logits) at b{batch} on this 10-class set — the adam recipe the
# membuffer-overfit tests use on this exact model is the stable choice
updater = adam
eta = 0.001
wmat:lr = 0.001
bias:lr = 0.001
wd = 0.0001
"""
        conf_path = os.path.join(workdir, "proxy.conf")
        with open(conf_path, "w") as f:
            f.write(conf)
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO
        env["JAX_PLATFORMS"] = "cpu"
        r = subprocess.run(
            [sys.executable, "-m", "cxxnet_tpu", conf_path, "task=train"],
            env=env, capture_output=True, text=True, cwd=workdir,
        )
        if r.returncode != 0:
            sys.stderr.write(r.stderr[-4000:])
            raise SystemExit(f"training failed rc={r.returncode}")
    rows = [ln for ln in r.stderr.splitlines()
            if re.match(r"\[\d+\]\t", ln)]
    lines = [
        f"# convergence_proxy @ "
        f"{time.strftime('%Y-%m-%dT%H:%M:%SZ', time.gmtime())}",
        f"# GoogLeNet (builders.googlenet_conf, 64px, b{batch}) on "
        f"{n_train}-image / 10-class frequency-grating imgbin, "
        f"held-out eval {n_eval}; full pipeline in-path "
        f"(decode -> rand-crop/mirror -> threadbuffer); "
        f"{rounds} rounds, CPU, {time.time() - t0:.0f}s total",
        "# chance level: error 0.900",
    ] + rows
    with open(LOG_PATH, "w") as f:
        f.write("\n".join(lines) + "\n")
    print("\n".join(lines))
    print(f"# wrote {LOG_PATH}")


if __name__ == "__main__":
    main()
