"""The reference a configuration gets when its file names none: the
convolutional nets under sgd with momentum, as ``lib/netconf.py`` reads
them and ``lib/reference.py`` computes them.  A thin adapter and nothing
else: every function hands its arguments on, so the two accepted
configurations read what they read before there was a seam.

What ``run.py`` and ``tools/limits.py`` take from a configuration's
reference module (README.md, "A configuration's reference"):

* ``describe(net_text, batch) -> net``: opaque but for ``net.pshapes``;
* ``make_weights(net, seed)``;
* ``train_chunk(net, weights, data, labels, key, control=None)
  -> (losses, params, update_state)``;
* ``program_update_state(ustates) -> tree``;
* ``step_flops(net)``, ``step_min_bytes(net)``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from benchmarks.lib import netconf, reference


class Net(NamedTuple):
    layers: list
    glob: dict
    shapes: dict
    pshapes: dict


def describe(net_text: str, batch: int) -> Net:
    return Net(*netconf.describe_net(net_text, batch))


def make_weights(net: Net, seed: int):
    return reference.make_weights(net.layers, net.shapes, net.pshapes, seed)


def control_dtype(control):
    """``None``: the reference itself.  ``True``: the step below the
    bfloat16 both configurations state.  A type's name: that type, for
    insight (``tools/limits.py --also-bf16``)."""
    if control is None:
        return None
    return jnp.float8_e4m3fn if control is True else getattr(jnp, control)


def train_chunk(net: Net, weights, data, labels, key, control=None):
    """(losses, params after, momentum after): the momentum after a
    chunk is what sgd keeps of the gradients it was given."""
    return reference.train_chunk(net.layers, net.glob, weights, data, labels,
                                 key, quant=control_dtype(control))


def program_update_state(ustates):
    """sgd's one state, ``m``, of every leaf of the program's
    ``{layer index: {tag: states}}``."""
    return {i: {t: s["m"] for t, s in tags.items()}
            for i, tags in ustates.items()}


def step_flops(net: Net) -> float:
    return netconf.step_flops(net.layers, net.shapes)


def step_min_bytes(net: Net) -> float:
    return netconf.step_min_bytes(net.layers, net.shapes)
