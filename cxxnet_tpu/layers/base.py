"""Layer protocol, shared hyper-parameters, weight init, and the registry.

Design (TPU-first, not a translation):

* The reference mutates 4-D ``Node`` buffers in place and hand-writes
  ``Backprop`` per layer (``/root/reference/src/layer/layer.h:161-279``).
  Here a layer is three *pure* functions — ``infer_shape``, ``init_params``,
  ``apply`` — over immutable arrays; ``jax.grad`` of the graph's loss
  replaces every hand-written backprop, and XLA fuses the elementwise
  chains that mshadow expression templates used to fuse.

* Data layout is **NHWC** (TPU-native) instead of the reference's NCHW.
  Image nodes are ``(N, H, W, C)``; flat "matrix" nodes are ``(N, D)``
  (the reference stores them as ``(N, 1, 1, D)``, layer.h:30-54).

* Per-layer weights are a flat dict tagged ``wmat`` / ``bias`` — the same
  tag scheme the reference's weight visitors use
  (``/root/reference/src/layer/visitor.h``), which the updaters rely on for
  per-tag hyper-parameter overrides (``wmat:lr``, ``bias:wd``).

Randomness is functional: ``apply`` receives an optional PRNG key; layers
that need train-time noise (dropout, insanity, prelu noise) fold it.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

Shape = Tuple[int, ...]
Params = Dict[str, jnp.ndarray]


class LayerParam:
    """Shared layer hyper-parameters + weight initialization.

    Parity: ``/root/reference/src/layer/param.h:15-138`` (names, defaults,
    and the gaussian / xavier-uniform / kaiming init rules).
    """

    def __init__(self) -> None:
        self.init_sigma = 0.01
        self.init_uniform = -1.0
        self.init_sparse = 10
        self.init_bias = 0.0
        self.random_type = 0  # 0 gaussian, 1 uniform/xavier, 2 kaiming
        self.num_hidden = 0
        self.num_channel = 0
        self.num_group = 1
        self.kernel_width = 0
        self.kernel_height = 0
        self.stride = 1
        self.pad_x = 0
        self.pad_y = 0
        self.no_bias = 0
        self.silent = 0
        self.num_input_channel = 0
        self.num_input_node = 0
        self.temp_col_max = 64 << 18

    def set_param(self, name: str, val: str) -> None:
        if name == "init_sigma":
            self.init_sigma = float(val)
        elif name == "init_uniform":
            self.init_uniform = float(val)
        elif name == "init_bias":
            self.init_bias = float(val)
        elif name == "init_sparse":
            self.init_sparse = int(val)
        elif name == "random_type":
            table = {"gaussian": 0, "uniform": 1, "xavier": 1, "kaiming": 2}
            if val not in table:
                raise ValueError(f"invalid random_type {val!r}")
            self.random_type = table[val]
        elif name == "nhidden":
            self.num_hidden = int(val)
        elif name == "nchannel":
            self.num_channel = int(val)
        elif name == "ngroup":
            self.num_group = int(val)
        elif name == "kernel_size":
            self.kernel_width = self.kernel_height = int(val)
        elif name == "kernel_height":
            self.kernel_height = int(val)
        elif name == "kernel_width":
            self.kernel_width = int(val)
        elif name == "stride":
            self.stride = int(val)
        elif name == "pad":
            self.pad_y = self.pad_x = int(val)
        elif name == "pad_y":
            self.pad_y = int(val)
        elif name == "pad_x":
            self.pad_x = int(val)
        elif name == "no_bias":
            self.no_bias = int(val)
        elif name == "silent":
            self.silent = int(val)
        elif name == "temp_col_max":
            self.temp_col_max = int(val) << 18

    def rand_init_weight(
        self, key: jax.Array, shape: Shape, in_num: int, out_num: int
    ) -> jnp.ndarray:
        """Draw an initial weight tensor (param.h:113-138 rules)."""
        if self.random_type == 0:
            return self.init_sigma * jax.random.normal(key, shape, jnp.float32)
        if self.random_type == 1:
            a = math.sqrt(3.0 / (in_num + out_num))
            if self.init_uniform > 0:
                a = self.init_uniform
            return jax.random.uniform(key, shape, jnp.float32, -a, a)
        if self.random_type == 2:
            if self.num_hidden > 0:
                sigma = math.sqrt(2.0 / self.num_hidden)
            else:
                sigma = math.sqrt(
                    2.0 / (self.num_channel * self.kernel_width * self.kernel_height)
                )
            return sigma * jax.random.normal(key, shape, jnp.float32)
        raise ValueError(f"unsupported random_type {self.random_type}")


class Layer:
    """Base class of all layer types.

    Subclasses override ``infer_shape`` (shape inference + validation, the
    analog of the reference's ``InitConnection``), ``init_params`` and
    ``apply``.  ``apply`` maps a list of input arrays to a list of output
    arrays and must be traceable under ``jax.jit``.
    """

    # registered config-file type name, e.g. "conv"
    type_name: str = ""
    # True for loss layers (self-loop in reference configs)
    is_loss: bool = False
    # param tags kept float32 under mixed precision (norm scales/biases
    # whose math runs in f32 — a bf16 round-trip would only lose bits);
    # whole-layer exemptions live in FunctionalNet._f32_param_keys
    f32_tags: frozenset = frozenset()

    def __init__(self) -> None:
        self.param = LayerParam()

    def set_param(self, name: str, val: str) -> None:
        self.param.set_param(name, val)

    # --- protocol -------------------------------------------------------
    def infer_shape(self, in_shapes: Sequence[Shape]) -> List[Shape]:
        raise NotImplementedError

    def init_params(self, key: jax.Array, in_shapes: Sequence[Shape]) -> Params:
        return {}

    def borrows(self) -> dict:
        """``{tag: (layer name, its tag)}``: single leaves of OTHER layers
        this one computes with beside its own.  ``FunctionalNet`` hands
        them over under ``tag`` in ``params``: one leaf, one gradient, the
        sum of every use (``routed_experts``' ``route_norm``)."""
        return {}

    def apply(
        self,
        params: Params,
        inputs: Sequence[jnp.ndarray],
        *,
        train: bool = False,
        rng: Optional[jax.Array] = None,
        step: Optional[jnp.ndarray] = None,
    ) -> List[jnp.ndarray]:
        raise NotImplementedError

    # --- helpers --------------------------------------------------------
    def _check_arity(self, in_shapes: Sequence[Shape], n_in: int) -> None:
        if len(in_shapes) != n_in:
            raise ValueError(
                f"{self.type_name}: expected {n_in} input(s), got {len(in_shapes)}"
            )


class LossLayer(Layer):
    """Base of the self-loop loss layers.

    The reference loss layers transform their node in place on forward
    (e.g. softmax probabilities) and *inject* the gradient
    ``(transform(x) - y) * grad_scale / (batch_size * update_period)`` on
    backprop (``loss/loss_layer_base-inl.hpp:60-103``).  Functionally that
    is exactly the gradient of ``loss() = grad_scale * L(x, y) /
    (batch_size * update_period)`` for a suitable ``L``; each subclass
    defines ``L`` so that ``jax.grad`` reproduces the reference gradient
    bit-for-bit in expectation.
    """

    is_loss = True

    def __init__(self) -> None:
        super().__init__()
        self.target = "label"
        self.grad_scale = 1.0

    def set_param(self, name: str, val: str) -> None:
        if name == "target":
            self.target = val
        elif name == "grad_scale":
            self.grad_scale = float(val)
        else:
            super().set_param(name, val)

    def infer_shape(self, in_shapes: Sequence[Shape]) -> List[Shape]:
        self._check_arity(in_shapes, 1)
        return [tuple(in_shapes[0])]

    def apply(self, params, inputs, *, train=False, rng=None, step=None):
        # forward transform only; gradient comes from loss()
        return [self.transform(inputs[0])]

    # subclass API
    def transform(self, x: jnp.ndarray) -> jnp.ndarray:
        """Forward transform (prediction output), e.g. softmax probs."""
        return x

    def loss(self, x: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
        """Summed (not averaged) loss; the trainer scales by
        ``grad_scale / (batch_size * update_period)``."""
        raise NotImplementedError

    def loss_masked(
        self,
        x: jnp.ndarray,
        labels: jnp.ndarray,
        weight: Optional[jnp.ndarray] = None,
    ) -> jnp.ndarray:
        """``loss`` with an optional per-row weight vector ``(N,)``.

        The static-shape analog of the reference's ``AdjustBatchSize``
        (``neural_net-inl.hpp:266-277``): a short final train batch is
        zero-padded to the compiled batch size and the padded rows are
        masked out of the loss, so they contribute exactly zero gradient.
        Implemented generically by vmapping the subclass ``loss`` over
        rows — subclasses only ever define the summed form.
        """
        if weight is None:
            return self.loss(x, labels)
        per_row = jax.vmap(
            lambda xi, yi: self.loss(xi[None], yi[None])
        )(x, labels)
        return jnp.sum(per_row * weight.astype(per_row.dtype))


# ----------------------------------------------------------------------
# registry
# ----------------------------------------------------------------------
_REGISTRY: Dict[str, Callable[[], Layer]] = {}


def register(cls):
    """Class decorator: register a Layer under its ``type_name``."""
    assert cls.type_name, f"{cls} missing type_name"
    _REGISTRY[cls.type_name] = cls
    return cls


def create_layer(type_name: str) -> Layer:
    """Factory by config name.

    Parity: ``GetLayerType`` (layer.h:322-361) + ``CreateLayer_``
    (layer_impl-inl.hpp:36-76).  ``pairtest-A-B`` composes two layer types;
    ``shared[...]`` is resolved by the graph builder, not here.
    """
    if type_name.startswith("pairtest-"):
        from .pairtest import PairTestLayer

        rest = type_name[len("pairtest-"):]
        if "-" not in rest:
            raise ValueError(
                f'unknown layer type: "{type_name}" (pairtest needs '
                f"pairtest-<master>-<slave>)"
            )
        master_name, slave_name = rest.split("-", 1)
        return PairTestLayer(create_layer(master_name), create_layer(slave_name))
    if type_name == "torch" and type_name not in _REGISTRY:
        # plugin layer, loaded on demand (the reference gates its caffe
        # adapter behind CXXNET_USE_CAFFE_ADAPTOR the same way)
        from ..plugin import torch_adapter  # noqa: F401 - registers "torch"
    if type_name not in _REGISTRY:
        raise ValueError(f'unknown layer type: "{type_name}"')
    return _REGISTRY[type_name]()


def layer_types() -> List[str]:
    return sorted(_REGISTRY)
