"""Model zoo: reference-format ``.conf`` builders.

The reference ships models *as config files* (``example/MNIST``,
``example/ImageNet``, ``example/kaggle_bowl``); this package generates the
same networks programmatically in the identical config grammar, so they
run through the normal config → graph → jit pipeline.  Builders return
conf *text*; feed it to ``cxxnet_tpu.config.parse_pairs`` / the CLI.

Parity sources (structure, hyper-parameters, schedules):
* MNIST MLP — ``/root/reference/example/MNIST/MNIST.conf``
* MNIST conv (LeNet-style) — ``/root/reference/example/MNIST/MNIST_CONV.conf``
* AlexNet — ``/root/reference/example/ImageNet/ImageNet.conf``
* kaggle plankton — ``/root/reference/example/kaggle_bowl/bowl.conf``
* GoogLeNet / VGG-16 — not shipped by the reference (its README names
  them as goals); built here from the papers as the benchmark models
  (BASELINE.json: images/sec/chip on GoogLeNet).
"""

from .builders import (  # noqa: F401
    afmoe_conf,
    alexnet_conf,
    bailing_hybrid_conf,
    googlenet_conf,
    granite_h_conf,
    joyai_llm_flash_conf,
    kaggle_bowl_conf,
    mnist_conv_conf,
    mnist_mlp_conf,
    nemotron_h_conf,
    qwen3_next_conf,
    resnet50_conf,
    resnet101_conf,
    resnet152_conf,
    smallthinker_conf,
    transformer_conf,
    transformer_lm_conf,
    vgg16_conf,
    vgg19_conf,
)

MODEL_BUILDERS = {
    "mnist_mlp": mnist_mlp_conf,
    "mnist_conv": mnist_conv_conf,
    "alexnet": alexnet_conf,
    "googlenet": googlenet_conf,
    "vgg16": vgg16_conf,
    "vgg19": vgg19_conf,
    "resnet50": resnet50_conf,
    "resnet101": resnet101_conf,
    "resnet152": resnet152_conf,
    "kaggle_bowl": kaggle_bowl_conf,
    "transformer": transformer_conf,
    "transformer_lm": transformer_lm_conf,
    "granite_h": granite_h_conf,
    "qwen3_next": qwen3_next_conf,
    "joyai_llm_flash": joyai_llm_flash_conf,
    "nemotron_h": nemotron_h_conf,
    "afmoe": afmoe_conf,
    "smallthinker": smallthinker_conf,
    "bailing_hybrid": bailing_hybrid_conf,
}
