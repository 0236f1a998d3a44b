"""The reference's shipped example confs are the grammar fixture
(SURVEY §4.5): they must tokenize, section-split, and — where the layer
graph is complete — build a net with correct shapes.  Data files are
absent, so only parsing/graph construction is exercised, never IO.
"""

import os

import pytest

from conftest import build_from_shapes
from cxxnet_tpu import config as C
from cxxnet_tpu.nnet.trainer import NetTrainer

REF = "/root/reference/example"

ALL_CONFS = [
    "MNIST/MNIST.conf",
    "MNIST/MNIST_CONV.conf",
    "MNIST/mpi.conf",
    "ImageNet/ImageNet.conf",
    "kaggle_bowl/bowl.conf",
    "kaggle_bowl/pred.conf",
]


@pytest.mark.parametrize("rel", ALL_CONFS)
def test_reference_conf_parses(rel):
    path = os.path.join(REF, rel)
    if not os.path.exists(path):
        pytest.skip(f"{rel} not present")
    cfg = C.parse_file(path)
    assert cfg, f"{rel}: no pairs parsed"
    split = C.split_sections(cfg)
    # every opened iterator section must have been closed by iter=end
    for sec in split.sections:
        assert sec.entries is not None


@pytest.mark.parametrize(
    "rel,nclass",
    [("MNIST/MNIST.conf", 10), ("MNIST/MNIST_CONV.conf", 10),
     ("ImageNet/ImageNet.conf", 1000), ("kaggle_bowl/bowl.conf", 121)],
)
def test_reference_conf_builds_net(rel, nclass):
    """The netconfig sections build, shape-infer, and end in the right
    class count on this framework unchanged."""
    path = os.path.join(REF, rel)
    if not os.path.exists(path):
        pytest.skip(f"{rel} not present")
    cfg = C.split_sections(C.parse_file(path)).global_entries
    tr = NetTrainer()
    tr.set_params(cfg)
    tr.set_param("dev", "cpu")
    tr.set_param("batch_size", "4")  # tiny for CPU shape inference
    assert build_from_shapes(tr)
    out = tr.net.node_shapes[tr.net.out_node_index()]
    assert out[-1] == nclass, f"{rel}: output {out}"


REPO_EXAMPLES = [
    ("MNIST/MNIST.conf", 10),
    ("MNIST/MNIST_CONV.conf", 10),
    ("MNIST/digits.conf", 10),
    ("MNIST/dist.conf", 10),
    ("ImageNet/alexnet.conf", 1000),
    ("ImageNet/googlenet.conf", 1000),
    ("ImageNet/vgg16.conf", 1000),
    ("kaggle_bowl/bowl.conf", 121),
]


@pytest.mark.parametrize("rel,nclass", REPO_EXAMPLES)
def test_repo_example_conf_builds_net(rel, nclass):
    """This repo's shipped example confs stay buildable with correct
    output class counts (the dist.conf case strips the distributed
    launch keys — joining a job needs real peers)."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "example", rel)
    cfg = [
        (k, v)
        for k, v in C.split_sections(C.parse_file(path)).global_entries
        if not k.startswith("dist_")
    ]
    tr = NetTrainer()
    tr.set_params(cfg)
    tr.set_param("dev", "cpu")
    tr.set_param("batch_size", "4")
    assert build_from_shapes(tr)
    out = tr.net.node_shapes[tr.net.out_node_index()]
    assert out[-1] == nclass, f"{rel}: output {out}"


def test_reference_only_keys_accepted():
    """The reference's GPU/PS-specific knobs (cuDNN `algo`, mshadow
    layout `force_contiguous`, async-PS `bigarray_bound` /
    `init_on_worker` / `pull_at_backprop`, vestigial `net_type` /
    `reset_net_type` — cxxnet_main.cpp:85-86, CreateNet_ always returns
    the one trainer) parse and train without error: on TPU they are
    no-ops by design (XLA autotunes convs; SPMD replaces the parameter
    server).  `test_on_server` is NOT a no-op — the CLI implements it
    as the per-round cross-process weight-sync check
    (tests/test_distributed.py)."""
    import numpy as np

    from cxxnet_tpu.io.data import DataBatch

    conf = """
netconfig = start
layer[0->1] = conv:cv
  nchannel = 4
  kernel_size = 1
  algo = 1
layer[1->2] = flatten:fl
layer[2->3] = fullc:fc2
  nhidden = 4
  force_contiguous = 1
layer[3->3] = softmax:sm
netconfig = end
input_shape = 1,4,4
batch_size = 8
dev = cpu
updater = sgd
eta = 0.01
net_type = 0
reset_net_type = 0
bigarray_bound = 1000000
init_on_worker = 1
pull_at_backprop = 1
test_on_server = 0
param_server = local
"""
    tr = NetTrainer()
    tr.set_params(C.parse_pairs(conf))
    tr.init_model()
    b = DataBatch(data=np.random.RandomState(0).randn(8, 4, 4, 1)
                  .astype("float32"),
                  label=np.zeros((8, 1), "float32"))
    tr.update(b)
