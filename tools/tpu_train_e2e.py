"""End-to-end ON-CHIP training with IO in-path (VERDICT r3 #4).

The reference's actual operating mode (``cxxnet_main.cpp:344-403``):
JPEG shards -> decode -> augment -> batch -> train loop, as opposed to
the synthetic-data device-rate bench.  Generates an imgbin shard set,
writes a conf that feeds GoogLeNet through the real pipeline (native
decode pool + threadbuffer + chunked async scan), runs ``task=train``
for a few rounds via the CLI, and leaves the log for committing to
``example/ImageNet/``.

One process, so it goes to the chip as it is (the CLI names the device
it bound and turns the compile cache on):

    python tools/tpu_train_e2e.py [n_images] [rounds] [batch]
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main() -> None:
    from io_bench import generate_imgbin

    from cxxnet_tpu.cli import LearnTask
    from cxxnet_tpu.models import googlenet_conf

    n_img = int(sys.argv[1]) if len(sys.argv) > 1 else 4096
    rounds = int(sys.argv[2]) if len(sys.argv) > 2 else 3
    batch = int(sys.argv[3]) if len(sys.argv) > 3 else 128

    with tempfile.TemporaryDirectory() as workdir:
        generate_imgbin(workdir, n_img, 256)
        # small eval split from the same shard (pipeline parity is the
        # point here, not held-out accuracy)
        conf = f"""
data = train
iter = imgbin
  image_bin = {workdir}/bench.bin
  image_list = {workdir}/bench.lst
  rand_crop = 1
  rand_mirror = 1
  input_shape = 3,224,224
  batch_size = {batch}
  round_batch = 1
  label_width = 1
iter = threadbuffer
iter = end
eval = test
iter = imgbin
  image_bin = {workdir}/bench.bin
  image_list = {workdir}/bench.lst
  input_shape = 3,224,224
  batch_size = {batch}
  round_batch = 1
  label_width = 1
iter = end
""" + googlenet_conf(batch_size=batch, input_size=224, synthetic=False,
                     dev="tpu") + f"""
num_round = {rounds}
scan_steps = 8
print_step = 1
model_dir = {workdir}/models
"""
        conf_path = os.path.join(workdir, "e2e.conf")
        with open(conf_path, "w") as f:
            f.write(conf)
        LearnTask().run([conf_path])


if __name__ == "__main__":
    main()
