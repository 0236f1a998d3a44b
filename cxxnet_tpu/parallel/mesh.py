"""Mesh construction from ``dev=`` config strings.

Grammar parity with the reference device parser
(``/root/reference/src/nnet/nnet_impl-inl.hpp:32-51``):

* ``dev=tpu`` / ``dev=gpu`` / ``dev=cpu`` — one device
* ``dev=tpu:0-3`` — devices 0..3 inclusive
* ``dev=tpu:0,2,5`` — explicit list

The platform word is advisory: confs written for the reference say
``gpu``; on a TPU host the same conf runs on TPU chips, and under the
CPU test harness on virtual CPU devices.  What is honored exactly is the
device *count and ordinals* — ``batch_size`` must divide by the data-axis
size, as in the reference (``nnet_impl-inl.hpp:146-151``).  Advisory is
not silent: ``MeshPlan.describe_devices`` names the platform, device
kind and ordinals actually bound (and the platform that was asked for,
when it differs), and the trainer prints it every time it builds a mesh.

The mesh is always 2-D ``('data', 'model')``; ``model=1`` gives pure data
parallelism (the reference's only strategy).  ``model_parallel=k`` in the
config splits the devices ``(n/k, k)`` for tensor-parallel layers.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def parse_device(dev: str) -> Tuple[str, List[int]]:
    """``"tpu:0-3"`` → ``("tpu", [0,1,2,3])``; bare platform → ``[0]``."""
    dev = dev.strip()
    if ":" not in dev:
        return dev, [0]
    plat, spec = dev.split(":", 1)
    ids: List[int] = []
    for part in spec.split(","):
        part = part.strip()
        if "-" in part:
            lo, hi = part.split("-", 1)
            if int(hi) < int(lo):
                raise ValueError(f"dev={dev!r}: reversed range {part!r}")
            ids.extend(range(int(lo), int(hi) + 1))
        elif part:
            ids.append(int(part))
    if not ids:
        raise ValueError(f"dev={dev!r}: empty device list")
    return plat, ids


@dataclasses.dataclass
class MeshPlan:
    """A resolved mesh plus the shardings the trainer needs."""

    mesh: Mesh
    n_data: int
    n_model: int
    # the platform word of the dev= string ("" when the caller handed
    # make_mesh its devices directly)
    requested: str = ""

    @property
    def n_devices(self) -> int:
        return self.n_data * self.n_model

    @property
    def platform(self) -> str:
        """The platform the mesh's programs actually run on."""
        return str(self.mesh.devices.flat[0].platform)

    def describe_devices(self) -> str:
        """One line of device truth: platform, device kind and ordinals
        bound — plus the platform ``dev=`` asked for when this process
        does not have it (the advisory fall-through, never silent)."""
        devs = list(self.mesh.devices.flat)
        line = (f"{self.platform} ({devs[0].device_kind}) "
                f"ordinals {[int(d.id) for d in devs]}")
        if self.requested and self.requested != self.platform:
            line += (f" — dev asked for {self.requested!r}, which this "
                     "process does not have")
        return line

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def data_sharding(self, axis: int = 0) -> NamedSharding:
        """Batch-major arrays: shard the batch dim over the data axis.

        ``axis=1`` covers step-stacked ``[K, B, ...]`` arrays fed to the
        device-side multi-step scan (NetTrainer.update_scan)."""
        spec = [None] * axis + ["data"]
        return NamedSharding(self.mesh, P(*spec))

    def param_sharding(self, shape: Sequence[int]) -> NamedSharding:
        """Tensor-parallel weight sharding over the ``model`` axis.

        The GSPMD recipe (SURVEY §2.8 TPU mapping): annotate each weight's
        output-feature dimension as sharded and let XLA partition the
        matmuls/convs and insert the collectives.  Layout convention:

        * fullc ``(nout, nin)`` → shard ``nout`` (dim 0)
        * conv HWIO ``(kh, kw, cin_g, cout)`` → shard ``cout`` (dim 3)
        * per-channel 1-D params (bias, prelu slope, BN gamma/beta) →
          shard the channel dim

        A dim that does not divide by the model-axis size is replicated —
        correctness never depends on the annotation, only placement.
        """
        if self.n_model == 1:
            return self.replicated()
        shape = tuple(shape)
        if not shape:
            return self.replicated()
        axis = 3 if len(shape) == 4 else 0
        if shape[axis] % self.n_model == 0:
            spec = [None] * len(shape)
            spec[axis] = "model"
            return NamedSharding(self.mesh, P(*spec))
        return self.replicated()

    def state_sharding(self, shape: Sequence[int]) -> NamedSharding:
        """Optimizer-state sharding: the ``update_on_server=1`` analog.

        The reference moved the SGD step onto the parameter server so each
        worker held no optimizer state (``nnet_ps_server.cpp:83-89``); the
        TPU-native equivalent is ZeRO-1: momentum/Adam state sharded over
        the data axis, each DP rank computing its slice of the update and
        GSPMD all-gathering the result (SURVEY §5 distributed backend
        mapping).  On top of any model-axis placement, the largest
        still-unsharded dim divisible by the data-axis size is sharded.
        """
        base = self.param_sharding(shape)
        if self.n_data == 1 or not shape:
            return base
        spec = list(base.spec) + [None] * (len(shape) - len(base.spec))
        best, best_size = None, 0
        for d, size in enumerate(shape):
            if spec[d] is None and size % self.n_data == 0 and size > best_size:
                best, best_size = d, size
        if best is None:
            return base
        spec[best] = "data"
        return NamedSharding(self.mesh, P(*spec))

    def fsdp_sharding(self, shape: Sequence[int]) -> NamedSharding:
        """ZeRO-3/FSDP parameter placement: the weights THEMSELVES live
        sharded over the data axis (largest divisible dim, on top of any
        model-axis tensor parallelism).

        Under ``jit`` GSPMD then materializes each layer's full weight
        just-in-time with an all-gather in forward/backward and
        reduce-scatters the gradients — per-device parameter memory drops
        ~n_data-fold, the classic FSDP recipe expressed purely as
        sharding annotations (no wrapper modules, no manual collectives).
        Same placement algorithm as ``state_sharding`` — ZeRO-3 is ZeRO-1
        applied to the params too.
        """
        return self.state_sharding(shape)

    def describe(self, zero: int = 0) -> str:
        """One-line layout summary shared by the CLI's train-start line
        and ``task=summary`` (one formatter, so logs and dashboards
        never disagree about the mesh shape)."""
        import jax

        return (f"data={self.n_data} model={self.n_model} zero={zero} "
                f"processes={jax.process_count()}")

    def check_batch(self, batch_size: int) -> None:
        if batch_size % self.n_data != 0:
            raise ValueError(
                f"batch_size={batch_size} must be divisible by the number of "
                f"data-parallel devices ({self.n_data}), as in the reference "
                f"(nnet_impl-inl.hpp:146-151)"
            )


def make_mesh(
    dev: str = "tpu",
    model_parallel: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> MeshPlan:
    """Build the ('data','model') mesh for a ``dev=`` string.

    Ordinals index into the available device list of the matching
    platform when present, else into ``jax.devices()`` (confs written for
    ``gpu`` run unchanged on TPU).
    """
    plat, ids = parse_device(dev)
    requested = "" if devices is not None else plat
    if devices is None:
        try:
            pool = jax.devices(plat)
        except RuntimeError:
            pool = jax.devices()
        if ":" not in dev.strip() and jax.process_count() > 1:
            # multi-process job, bare platform word: the mesh spans ALL
            # global devices (each process contributes its local chips —
            # the multi-host semantic; explicit ordinals remain global
            # indices for expert layouts)
            devices = list(pool)
        else:
            try:
                devices = [pool[i] for i in ids]
            except IndexError:
                raise ValueError(
                    f"dev={dev!r} requests device ordinals {ids} but only "
                    f"{len(pool)} devices are available"
                ) from None
    devices = list(devices)
    n = len(devices)
    if model_parallel < 1 or n % model_parallel != 0:
        raise ValueError(
            f"model_parallel={model_parallel} must divide the device count {n}"
        )
    n_model = model_parallel
    n_data = n // n_model
    arr = np.asarray(devices, dtype=object).reshape(n_data, n_model)
    return MeshPlan(mesh=Mesh(arr, ("data", "model")), n_data=n_data,
                    n_model=n_model, requested=requested)
