"""Model zoo: every builder parses, shape-infers, and takes a train step.

The reference's examples ARE its regression suite (SURVEY §4.5); these
tests are the equivalent for the generated model confs — including
GoogLeNet, the BASELINE.json benchmark model.
"""

import numpy as np
import pytest

from conftest import build_from_shapes
from cxxnet_tpu import config as cfgmod
from cxxnet_tpu.models import MODEL_BUILDERS
from families import FAMILIES
from cxxnet_tpu.nnet.trainer import NetTrainer


def _global_cfg(conf_text: str):
    """Netconfig + globals only — iterator sections stripped the way the
    CLI does before handing entries to the trainer."""
    return cfgmod.split_sections(cfgmod.parse_pairs(conf_text)).global_entries


def _shaped(text):
    """The conf's trainer with its net built, and its parameters' shapes."""
    tr = NetTrainer()
    tr.set_params(_global_cfg(text))
    return tr, build_from_shapes(tr)


@pytest.mark.parametrize("name", sorted(MODEL_BUILDERS))
def test_model_shapes(name):
    """Parse + init at tiny batch; checks graph wiring and shape rules."""
    builder = MODEL_BUILDERS[name]
    if name in FAMILIES:  # the defaults are the published widths
        text = builder(**dict(FAMILIES[name].tiny, batch_size=4))
    elif name.startswith("mnist") or name in ("kaggle_bowl",
                                              "transformer_lm"):
        text = builder(batch_size=4, dev="cpu")
    else:
        text = builder(batch_size=4, dev="cpu", nsample=8)
    tr, params = _shaped(text)
    assert params
    shapes = tr.net.node_shapes
    assert all(s is not None for s in shapes)
    # output layer is softmax over the right class count
    out = shapes[tr.net.out_node_index()]
    expect = {"mnist_mlp": 10, "mnist_conv": 10, "alexnet": 1000,
              "googlenet": 1000, "vgg16": 1000, "vgg19": 1000,
              "kaggle_bowl": 121,
              "transformer": 10, "transformer_lm": 256,
              "granite_h": 64, "qwen3_next": 64, "joyai_llm_flash": 64,
              "nemotron_h": 64, "afmoe": 64, "smallthinker": 64,
              "bailing_hybrid": 64,
              "resnet50": 1000, "resnet101": 1000,
              "resnet152": 1000}[name]
    assert out[-1] == expect
    if name in ("resnet101", "resnet152", "vgg19"):
        # depth variants really are deeper than their base model
        base = {"resnet101": "resnet50", "resnet152": "resnet50",
                "vgg19": "vgg16"}[name]
        base_text = MODEL_BUILDERS[base](batch_size=4, dev="cpu",
                                         nsample=8)
        assert text.count("= conv:") > base_text.count("= conv:")


def test_resnet50_structure():
    """Bottleneck plan matches He et al. table 1: stage widths
    256/512/1024/2048, spatial 56/28/14/7 at 224px, ~25.5M params."""
    text = MODEL_BUILDERS["resnet50"](batch_size=2, dev="cpu", nsample=4,
                                      input_size=224)
    tr, params = _shaped(text)
    g = tr.graph
    shapes = {g.node_names[i]: s for i, s in enumerate(tr.net.node_shapes)
              if s is not None and i < len(g.node_names)}
    assert shapes["s0b2"][1:] == (56, 56, 256)
    assert shapes["s1b3"][1:] == (28, 28, 512)
    assert shapes["s2b5"][1:] == (14, 14, 1024)
    assert shapes["s3b2"][1:] == (7, 7, 2048)
    total = sum(
        int(np.prod(w.shape))
        for tags in params.values() for w in tags.values()
    )
    assert 25e6 < total < 26e6, f"param count {total/1e6:.1f}M"


def test_googlenet_channel_plan():
    """Inception concat widths match Szegedy et al. table 1."""
    text = MODEL_BUILDERS["googlenet"](batch_size=2, dev="cpu", nsample=4)
    tr, _ = _shaped(text)
    g = tr.graph
    shapes = tr.net.node_shapes
    want = {"i3a": 256, "i3b": 480, "i4a": 512, "i4b": 512, "i4c": 512,
            "i4d": 528, "i4e": 832, "i5a": 832, "i5b": 1024}
    for node, ch in want.items():
        s = shapes[g.node_index_of(node)]
        assert s[-1] == ch, f"{node}: {s} want C={ch}"


@pytest.mark.parametrize("name", ["mnist_conv", "kaggle_bowl"])
def test_model_train_step(name):
    """One real fused train step on a small model."""
    text = MODEL_BUILDERS[name](batch_size=4, dev="cpu")
    tr = NetTrainer()
    tr.set_params(_global_cfg(text))
    tr.init_model()
    c, h, w = tr.graph.input_shape
    shape = (4, w) if c == 1 and h == 1 else (4, h, w, c)
    rng = np.random.RandomState(0)
    data = rng.randn(*shape).astype(np.float32)
    nclass = 10 if name == "mnist_conv" else 121
    labels = rng.randint(0, nclass, size=(4, 1)).astype(np.float32)
    before = {k: {t: np.asarray(v) for t, v in tags.items()}
              for k, tags in tr.params.items()}
    tr.update_all(data, labels)
    changed = any(
        not np.allclose(before[k][t], np.asarray(tr.params[k][t]))
        for k in before for t in before[k]
    )
    assert changed, "parameters did not move after a train step"


def test_googlenet_train_step_small():
    """GoogLeNet at 64px input: fused step compiles and runs on CPU."""
    text = MODEL_BUILDERS["googlenet"](
        batch_size=2, dev="cpu", input_size=64, nsample=4
    )
    tr = NetTrainer()
    tr.set_params(_global_cfg(text))
    tr.init_model()
    rng = np.random.RandomState(0)
    data = rng.randn(2, 64, 64, 3).astype(np.float32)
    labels = rng.randint(0, 1000, size=(2, 1)).astype(np.float32)
    tr.update_all(data, labels)
    assert tr.epoch_counter == 1


def test_googlenet_fuse_1x1_prediction_parity():
    """fuse_1x1 finds 9 groups of 3 on the real GoogLeNet graph and the
    fused forward matches the plain one on identical weights."""
    text = MODEL_BUILDERS["googlenet"](
        batch_size=2, dev="cpu", input_size=64, nsample=4
    )
    rng = np.random.RandomState(1)
    data = rng.randn(2, 64, 64, 3).astype(np.float32)

    def build(fuse):
        tr = NetTrainer()
        tr.set_params(_global_cfg(text + f"fuse_1x1 = {fuse}\n"))
        tr.set_param("seed", "3")
        tr.init_model()
        return tr

    t0, t1 = build(0), build(1)
    groups, member = t1.net._sibling_1x1_groups()
    assert sorted(len(v) for v in groups.values()) == [3] * 9
    from cxxnet_tpu.io.data import DataBatch
    b = DataBatch(data=data, label=None)
    p0 = t0.extract_feature(b, "top[-1]")
    p1 = t1.extract_feature(b, "top[-1]")
    np.testing.assert_allclose(np.asarray(p0), np.asarray(p1),
                               rtol=2e-4, atol=2e-5)


#: sha256 (16 hex digits) of the conf text each token builder wrote on
#: the commit before ``_packed_lm`` and ``_mtp_module`` were pulled out
#: of the three (2b18f56, PR 39; taken from a ``git archive`` of it):
#: what the accepted cell trains (``benchmarks/run.net_text`` at the
#: configuration's ``args``, which are the defaults), the same at its
#: ``rehearsal_args``, and every argument of the shared tail set.
PARENT_CONF_TEXT = {
    "granite_h": ("granite_4_0_h_micro", dict(
        cell="0cc1133a75c753a8", rehearsal="86660b77f1a45d2e",
        tail="c9652877e02ed91b")),
    "qwen3_next": ("qwen3_next_80b_a3b", dict(
        cell="e0569a494367e6ef", rehearsal="cee57487c6665880",
        tail="cfcc76031b218ebe")),
    "joyai_llm_flash": ("joyai_llm_flash", dict(
        cell="6fcfc3c618b3970b", rehearsal="b0fb223ded4f0985",
        tail="0a6ce8cf6464fc5e")),
    # SmallThinker's, as PR 46 (which brought the builder) wrote it: a
    # later edit to a shared piece of the builders shows here too
    "smallthinker": ("smallthinker_21b_a3b", dict(
        cell="395b180fc7918c4e", rehearsal="217efae201c01c02",
        tail="2793fddd0d477c79")),
    # Ling-3.0-flash's, as PR 49 (which brought the builder) wrote it
    "bailing_hybrid": ("ling_3_0_flash", dict(
        cell="2d0673208147d6b9", rehearsal="12be26b8d8e34358",
        tail="c5e8f915b318fe02")),
}


@pytest.mark.parametrize("what", ["cell", "rehearsal", "tail"])
@pytest.mark.parametrize("name", sorted(PARENT_CONF_TEXT))
def test_an_accepted_token_builder_writes_the_text_it_wrote(name, what):
    """The conf text to the byte, layers and tail (``remat``,
    ``eval_train``, ``wd``, ``label_vec``, the updater, the iterator
    block): the accepted cells train the conf they trained."""
    import hashlib
    import os

    from benchmarks import run

    config_name, want = PARENT_CONF_TEXT[name]
    build = MODEL_BUILDERS[name]
    config = run.load_json(os.path.join(
        run.ROOT, "benchmarks", "configs", config_name + ".json"))
    if what == "tail":
        text = build(token_file="t.bin", batch_size=2, num_round=3,
                     dev="cpu", compute_dtype="float32", eta=0.001,
                     scan_steps=4)
    elif what == "rehearsal":
        text = run.net_text(config, dict(config["args"],
                                         **config["rehearsal_args"]), "cpu")
    else:
        text = run.net_text(config, dict(config["args"]), "tpu")
        assert text == build()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == want[what]
