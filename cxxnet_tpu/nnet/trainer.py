"""NetTrainer: the INetTrainer equivalent, jit-compiled end to end.

Parity: ``INetTrainer`` (``/root/reference/src/nnet/nnet.h:18-92``) and
``CXXNetThreadTrainer`` (``/root/reference/src/nnet/nnet_impl-inl.hpp``):
``SetParam / InitModel / SaveModel / LoadModel / CopyModelFrom /
StartRound / Update(batch) / Evaluate / Predict / ExtractFeature /
SetWeight / GetWeight``.

TPU-first architecture: where the reference spawns one pthread + CUDA
stream per GPU and aggregates gradients through the mshadow-ps parameter
server, here the whole train step — forward, backward, gradient
accumulation, updater math — is ONE jitted function.  Data parallelism is
sharding the batch over a ``jax.sharding.Mesh`` (``parallel/``): XLA
inserts the ICI all-reduce that replaces push/pull, and its latency-hiding
scheduler overlaps it with backprop the way the reference's per-layer
AsyncUpdater priorities did.

Semantics preserved:
* ``update_period`` gradient accumulation with the reference's counters:
  ``epoch_counter`` (number of applied updates — the updaters' schedule
  clock) advances once per ``update_period`` micro-batches.
* checkpoint = net structure + epoch counter + weights; updater state is
  NOT saved by default (reference behavior — momentum restarts on
  resume); ``save_ustate = 1`` opts into exact resume (momentum/adam
  moments + the training RNG key ride along in the blob).
* ``CopyModelFrom`` copies name-matched layers only, resets the epoch.
* prediction output is argmax (multi-column) or the raw scalar.
"""

from __future__ import annotations

import io as _io
import json
import struct
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..io.data import DataBatch
from ..obs import device as obs_device
from ..parallel import MeshPlan, make_mesh
from ..parallel.distributed import fetch_array, fetch_local_rows
from ..updater import Updater, create_updater
from ..utils import checkpoint as ckpt
from ..utils import metric_device
from ..utils.checkpoint import MODEL_MAGIC, DivergenceError  # noqa: F401
from ..utils.metric import MetricSet
from ..utils.profiler import pipeline_stats, stage
from .graph import NetGraph
from .net import FunctionalNet

# what a scanned step folds into its own key for the train metrics'
# tie-break draws: no layer's index (net.forward folds those), so the
# draws share no key with the step and consume nothing of its stream
_METRIC_FOLD = 0x6D657472


class NetTrainer:
    def __init__(self) -> None:
        self.cfg: List[Tuple[str, str]] = []
        self.net: Optional[FunctionalNet] = None
        self.graph: Optional[NetGraph] = None
        self.params = None
        self.ustates = None
        self.updaters: Dict[Tuple[str, str], Updater] = {}
        self.epoch_counter = 0
        self.sample_counter = 0
        self.round = 0
        self.batch_size = 0
        self.update_period = 1
        self.eval_train = 1
        self.silent = 0
        self.seed = 0
        self.dev = "tpu"
        self.model_parallel = 1
        self.update_on_server = 0
        self.zero = 0
        self.det_reduce = 0
        # async data-parallel (parallel/async_ps, doc/parallel.md
        # "Async data-parallel"): per-group overlapped gradient
        # exchange + bounded-staleness updates
        self.async_overlap = 0
        self.async_groups = 0       # 0 = auto parameter-count buckets
        self.staleness = 0          # bounded staleness (aggregates)
        self.async_resync_period = 1  # hard re-sync barrier period
        self._async = None          # lazily built AsyncStepper
        self.save_ustate = 0
        self.divergence_policy = ""  # "" off | "abort" | "rollback"
        self.inject_nan_step = -1  # fault-injection hook (tests only)
        # finite loss-spike gate (integrity plane, doc/robustness.md):
        # a finite loss > ratio * rolling-median trips DivergenceError
        self.divergence_loss_ratio = 0.0   # 0 = off; else must be > 1
        self.inject_spike_step = -1   # fault-injection hook (tests only)
        self.inject_shadow_mismatch = 0  # one-shot shadow-audit hook
        self._loss_window: List[float] = []  # recent finite losses
        # quantized inference (doc/performance.md "Quantized inference"):
        # quant_scheme is set when the params pytree holds reduced-
        # precision kernels (int8 codes + scales, or bf16 casts) — the
        # trainer is then INFERENCE-ONLY; _quant_requested records the
        # conf's `quant` key, applied after init/load when the loaded
        # artifact is not already quantized
        self.quant_scheme = ""
        self.quant_plan = None
        self._quant_requested = ""
        self.mesh_plan: Optional[MeshPlan] = None
        self.aux = {}  # non-gradient layer state (BN running stats)
        # (params key, state leaf) -> the value count_layer_state last
        # read of a counter a layer keeps in its aux state
        self._aux_counted: Dict[Tuple[str, str], int] = {}
        self.metric = MetricSet()
        self.train_metric = MetricSet()
        self._grad_accum = None
        # (sums [K, n_metric], rows, first_epoch) of every chunk
        # update_scan dispatched whose train-metric sums train_metric
        # has not been given yet, oldest first (collect_scan_metrics)
        self._scan_sums: List[Tuple[jax.Array, int, int]] = []
        self._rng_key = None
        self._jit_cache: Dict[tuple, object] = {}

    # ------------------------------------------------------------------
    def set_param(self, name: str, val: str) -> None:
        if name == "batch_size":
            self.batch_size = int(val)
        elif name == "update_period":
            self.update_period = int(val)
        elif name == "eval_train":
            self.eval_train = int(val)
        elif name == "silent":
            self.silent = int(val)
        elif name == "seed":
            self.seed = int(val)
        elif name == "dev":
            self.dev = val
        elif name == "model_parallel":
            self.model_parallel = int(val)
        elif name == "update_on_server":
            # reference: SGD runs on the PS (nnet_ps_server.cpp); here the
            # optimizer state is ZeRO-1-sharded over the data axis instead
            self.update_on_server = int(val)
        elif name == "det_reduce":
            # pin the cross-replica gradient-reduction ORDER (elastic
            # pods, doc/parallel.md): the fused step's reduction is
            # re-expressed with shard_map — per-shard partial gradients
            # all-gathered and folded in fixed shard order — so the
            # summed bits depend only on the data-axis size, never on
            # the collectives implementation or process layout
            if int(val) not in (0, 1):
                raise ValueError(f"det_reduce={val}: must be 0 or 1")
            self.det_reduce = int(val)
        elif name == "async_overlap":
            # overlapped per-group gradient exchange (the mshadow-ps
            # async heritage, parallel/async_ps): the fused step splits
            # into per-shard backward + one async collective per
            # gradient-exchange group, applies overlapping exchanges
            if int(val) not in (0, 1):
                raise ValueError(f"async_overlap={val}: must be 0 or 1")
            self.async_overlap = int(val)
        elif name == "async_groups":
            if int(val) < 0:
                raise ValueError(
                    f"async_groups={val}: must be >= 0 (0 = auto)")
            self.async_groups = int(val)
        elif name == "staleness":
            # bounded staleness: slow replicas apply k-step-old reduced
            # aggregates instead of blocking; 0 = synchronous semantics
            # (bitwise — the parity suite pins it)
            if int(val) < 0:
                raise ValueError(f"staleness={val}: must be >= 0")
            self.staleness = int(val)
        elif name == "async_resync_period":
            if int(val) < 1:
                raise ValueError(
                    f"async_resync_period={val}: must be >= 1")
            self.async_resync_period = int(val)
        elif name == "compile_cache_dir":
            # persistent XLA compilation cache: restarts/reloads reuse
            # compiled programs instead of re-jitting (utils/compile_cache)
            from ..utils import compile_cache

            compile_cache.enable(val, silent=bool(self.silent))
        elif name == "save_ustate":
            # opt-in exact resume: checkpoint updater state (momentum /
            # adam moments) too.  Default 0 keeps reference parity —
            # "Updater state is NOT checkpointed; resume restarts
            # momentum from zero" (SURVEY §5 checkpoint notes)
            self.save_ustate = int(val)
        elif name == "divergence_policy":
            # NaN/Inf loss guard: "" disables (no per-step host sync),
            # abort|rollback enable the check; the response lives in the
            # task driver (cli.py) which catches DivergenceError
            if val not in ("", "off", "abort", "rollback"):
                raise ValueError(
                    f"divergence_policy={val!r}: must be abort or rollback"
                )
            self.divergence_policy = "" if val == "off" else val
        elif name == "inject_nan_step":
            # fault-injection harness: treat the loss at this epoch as
            # NaN (one transient blow-up) so recovery paths are testable
            self.inject_nan_step = int(val)
        elif name == "divergence_loss_ratio":
            # finite loss-spike gate (doc/robustness.md): with
            # divergence_policy set, a FINITE loss exceeding
            # ratio * rolling-median of recent losses trips the same
            # DivergenceError path NaN does — the PR-13 lesson that a
            # blow-up can stay finite for many rounds.  0 disables.
            r = float(val)
            if r and r <= 1.0:
                raise ValueError(
                    f"divergence_loss_ratio={val}: must be > 1 "
                    "(or 0 to disable)")
            self.divergence_loss_ratio = r
        elif name == "inject_spike_step":
            # fault-injection harness: scale the loss at this epoch to
            # a finite spike (one-shot), testing the loss-ratio gate
            self.inject_spike_step = int(val)
        elif name == "inject_shadow_mismatch":
            # fault-injection harness: perturb the shadow executable's
            # next comparison (one-shot), testing the shadow-audit path
            self.inject_shadow_mismatch = int(val)
        elif name == "kernel_lib":
            # on-chip kernel library selector (ops/kernels/): validate
            # here so a typo fails at conf parse, then flow the value to
            # the net via cfg -> graph defcfg like every other key
            from ..ops import kernels as _klib

            _klib.parse_mode(val)
        elif name == "quant":
            # inference-time weight precision: "" / 0 off, int8 (per-
            # channel scales + bf16 fallback) or bf16 (straight cast).
            # A pre-exported .quant.model artifact wins over this key;
            # on a plain checkpoint the quantization happens at load,
            # UNGATED (use task=export_quant for the gated artifact).
            if val in ("", "0", "off", "none"):
                self._quant_requested = ""
            elif val in ("int8", "bf16"):
                self._quant_requested = val
            else:
                raise ValueError(
                    f"quant={val!r}: supported schemes are int8 and "
                    "bf16 (0/off disables)")
        elif name in ("zero", "fsdp", "shard_weight_update"):
            # zero = 1: optimizer state sharded over the data axis
            # (update_on_server's modern spelling); zero = 3 / fsdp = 1:
            # params themselves sharded too (MeshPlan.fsdp_sharding).
            # shard_weight_update = 1 is the conf-level name for the
            # ZeRO-1 cross-replica weight-update sharding (arXiv
            # 2004.13336): reduce-scatter gradients, each replica
            # updates its 1/N shard, gather the new weights.
            # ZeRO-2 has no distinct GSPMD expression here: gradients
            # are transient inside the fused step, so 2 would silently
            # equal 1 — reject it rather than mislead.
            if name == "fsdp":
                if int(val) not in (0, 1):
                    raise ValueError(f"fsdp={val}: must be 0 or 1")
                z = 3 if int(val) else 0
            elif name == "shard_weight_update":
                if int(val) not in (0, 1):
                    raise ValueError(
                        f"shard_weight_update={val}: must be 0 or 1")
                z = 1 if int(val) else 0
            else:
                z = int(val)
            if z not in (0, 1, 3):
                raise ValueError(
                    f"{name}={val}: supported levels are 0, 1 "
                    "(state sharding) and 3 (FSDP param sharding)"
                )
            self.zero = z
        if self.metric.try_add_from_config(name, val):
            self.train_metric.try_add_from_config(name, val)
        self.cfg.append((name, val))

    def set_params(self, entries: Sequence[Tuple[str, str]]) -> None:
        for n, v in entries:
            if v == "default":
                continue
            self.set_param(n, v)

    # ------------------------------------------------------------------
    def _build_net(self, graph: Optional[NetGraph] = None) -> None:
        if graph is None:
            graph = NetGraph()
        graph.configure(self.cfg)
        self.graph = graph
        self._jit_cache.clear()  # drop closures over any previous net/mesh
        self._async = None       # async programs close over the old net
        self.net = FunctionalNet(graph)
        if self.net.batch_size:
            self.batch_size = self.net.batch_size
        else:
            self.net.batch_size = self.batch_size
        self.update_period = max(self.update_period, self.net.update_period)
        self.net.update_period = self.update_period

    def _build_updaters(self) -> None:
        assert self.net is not None and self.graph is not None
        self.updaters = {}
        ustates = {}
        for i, spec in enumerate(self.graph.layers):
            key = self.net.param_key[i]
            if spec.type_name == "shared" or key not in self.params:
                continue
            ustates[key] = {}
            for tag, w in self.params[key].items():
                up = create_updater(self.graph.updater_type, tag)
                for n, v in self.graph.defcfg:
                    up.set_param(n, v)
                for n, v in self.graph.layercfg[i]:
                    up.set_param(n, v)
                self.updaters[(key, tag)] = up
                ustates[key][tag] = up.init_state(w)
        self.ustates = ustates

    def _bind_mesh_to_layers(self) -> None:
        """Hand the mesh plan to layers that run their own collectives
        (ring attention's shard_map needs the Mesh object)."""
        for lay in self.net.layer_objs:
            if hasattr(lay, "bind_mesh"):
                lay.bind_mesh(self.mesh_plan)

    def _check_metric_nodes(self) -> None:
        """Fail fast on a bad ``metric[field,node]`` node name — the
        reference checks at InitModel (nnet_impl-inl.hpp:369-370), not at
        the first evaluation."""
        for mset in (self.metric, self.train_metric):
            for node in mset.nodes:
                if node is None:
                    continue
                try:
                    self.graph.node_index_of(node)
                except (KeyError, ValueError) as e:
                    raise ValueError(
                        f"metric[...,{node}]: cannot find node name "
                        f"{node!r} in the net graph"
                    ) from e

    def init_model(self) -> None:
        self._build_net()
        self._check_metric_nodes()
        self._build_mesh()
        self._bind_mesh_to_layers()
        self._rng_key = jax.random.PRNGKey(self.seed)
        self._rng_key, sub = jax.random.split(self._rng_key)
        self.params = self.net.init_params(sub, self.batch_size)
        self.aux = self.net.init_aux(self.batch_size)
        self._aux_counted = {}
        self._validate_det_reduce()
        self._build_updaters()
        self.epoch_counter = 0
        self.sample_counter = 0
        self._grad_accum = None
        self._maybe_quantize()
        self._place_state()

    def _build_mesh(self) -> None:
        """dev=tpu:0-3 → ('data','model') mesh; the mshadow-ps replacement."""
        self.mesh_plan = make_mesh(self.dev, self.model_parallel)
        if self.batch_size:
            self.mesh_plan.check_batch(self.batch_size)
        if not self.silent:
            print(f"devices: {self.mesh_plan.describe_devices()}", flush=True)
        if self.net is not None:
            # bind the platform the programs will actually run on (NOT
            # the process default backend — dev=cpu on a TPU host must
            # read as cpu): auto branch-embed and the kernel library's
            # interpret switch key on it
            self.net.exec_backend = self.mesh_plan.platform

    def _sh(self):
        """(replicated, data-sharded, per-extra) shardings for the mesh."""
        plan = self.mesh_plan
        if plan is None:
            self._build_mesh()
            plan = self.mesh_plan
        rep, dsh = plan.replicated(), plan.data_sharding()
        return rep, dsh, (dsh,) * self._n_extras()

    def _param_sh(self):
        """Sharding pytrees for (params, ustates): tensor-parallel weight
        placement over the mesh's model axis (pure DP → all replicated);
        ``zero = 1`` (or the reference-named ``update_on_server = 1``)
        additionally ZeRO-1-shards the updater state over the data axis;
        ``zero = 3`` / ``fsdp = 1`` shards the params themselves
        (MeshPlan.fsdp_sharding) — GSPMD inserts the per-layer
        all-gathers and gradient reduce-scatters."""
        plan = self.mesh_plan
        spec = lambda v: plan.param_sharding(np.shape(v))  # noqa: E731
        sspec = lambda v: plan.state_sharding(np.shape(v))  # noqa: E731
        fspec = lambda v: plan.fsdp_sharding(np.shape(v))  # noqa: E731
        if self.zero >= 3:
            psh = jax.tree_util.tree_map(fspec, self.params)
        else:
            psh = jax.tree_util.tree_map(spec, self.params)
        if self.update_on_server or self.zero >= 1:
            ush = jax.tree_util.tree_map(sspec, self.ustates)
        else:
            ush = jax.tree_util.tree_map(spec, self.ustates)
        return psh, ush

    def _place_state(self) -> None:
        """Explicitly place params / updater state / aux onto their mesh
        shardings (one ``jax.device_put`` per pytree).

        Called at the end of ``init_model`` / ``load_model`` /
        ``copy_model_from`` so the train state LIVES in its SPMD layout
        from step 0 rather than only after the first donated step
        resharded it: ZeRO-sharded runs get their ~1/N per-device
        params+state footprint immediately (the memory headroom is
        available for the first compile, which is when XLA sizes its
        temporary buffers), donation in the fused step is alias-clean
        (inputs already match ``in_shardings`` — no hidden copy), and a
        checkpoint written on one mesh re-shards onto the CURRENT mesh
        at load (resume on a different device count just works).
        Placement only — bitwise no-op on the training math."""
        if self.params is None or self.mesh_plan is None:
            self._export_state_bytes()
            return
        if self.mesh_plan.n_devices > 1:
            psh, ush = self._param_sh()
            self.params = jax.device_put(self.params, psh)
            if self.ustates:
                self.ustates = jax.device_put(self.ustates, ush)
            rep = self.mesh_plan.replicated()
            if self.aux:
                self.aux = jax.device_put(
                    self.aux,
                    jax.tree_util.tree_map(lambda _: rep, self.aux),
                )
            # the rng key too: the scanned step hands its carried key
            # back mesh-replicated, and jax's tracing cache keys on the
            # mesh an argument lives on — a key that starts on one
            # device makes the SECOND scan call retrace and recompile
            # the whole program (30.7 s for GoogLeNet on four v5e
            # chips, CHANGES.md PR 21)
            self._rng_key = jax.device_put(self._rng_key, rep)
        self._export_state_bytes()

    def state_shard_bytes(self):
        """Per-device addressable bytes of params + updater state, plus
        the replicated-equivalent total.

        Returns ``(per_device, total)`` where ``per_device`` maps
        ``"platform:id"`` to the bytes of train state RESIDENT on that
        device and ``total`` is what one full replica costs — the
        denominator of the ZeRO memory win (per-device ≈ total/N when
        every dim shards; unshardable leaves keep it slightly above).
        """
        per_device: Dict[str, float] = {}
        total = 0
        for tree in (self.params, self.ustates):
            for leaf in jax.tree_util.tree_leaves(tree or {}):
                nbytes = getattr(leaf, "nbytes", None)
                if nbytes is None:
                    nbytes = int(np.asarray(leaf).nbytes)
                total += int(nbytes)
                shards = getattr(leaf, "addressable_shards", None)
                if shards:
                    for s in shards:
                        dev = f"{s.device.platform}:{s.device.id}"
                        per_device[dev] = (
                            per_device.get(dev, 0) + int(s.data.nbytes)
                        )
                else:
                    per_device["host:0"] = (
                        per_device.get("host:0", 0) + int(nbytes)
                    )
        return per_device, total

    def _export_state_bytes(self) -> None:
        """Publish ``train_state_shard_bytes{device}`` (and the
        replicated-total gauge) so the ZeRO memory win is observable
        next to ``xla_device_memory_bytes`` — fail-open like the rest
        of the device plane."""
        try:
            per_device, total = self.state_shard_bytes()
            obs_device.set_train_state_bytes(per_device, total)
        except Exception:  # noqa: BLE001 - telemetry must never raise
            pass

    # ------------------------------------------------------------------
    # jitted step functions (built lazily, cached per (train, accum) kind)
    def _n_extras(self) -> int:
        return self.graph.extra_data_num if self.graph else 0

    @staticmethod
    def _apply_updates(updaters, params, ustates, grads, epoch,
                       gspec=None, kernels=None):
        """Per-tensor updater math over the param pytree (trace-time loop).

        ``gspec`` (shape → NamedSharding, set for ZeRO runs on a
        non-trivial mesh) pins each gradient to the updater state's
        data-axis sharding before the update math: the cross-replica
        gradient sum then lands sharded (reduce-scatter, or all-reduce
        + local slice where the backend lacks the fused pattern — this
        jaxlib's CPU partitioner does the latter), the updater applies
        shard-locally (each replica updates only its 1/N slice —
        momentum/Adam moments never materialize whole), and the
        program's replicated ``out_shardings`` on the new weights
        becomes the trailing all-gather — the arXiv 2004.13336
        weight-update-sharding dataflow, expressed purely as sharding
        annotations.  Placement only; the parity suites pin the math.
        """
        new_p = {}
        new_s = {}
        for key, tags in params.items():
            new_p[key] = {}
            new_s[key] = {}
            for tag, w in tags.items():
                up = updaters[(key, tag)]
                g = grads[key][tag]
                if gspec is not None:
                    g = jax.lax.with_sharding_constraint(
                        g, gspec(np.shape(w)))
                if (kernels is not None
                        and kernels.active("zero_update", w=w,
                                           updater=up)):
                    # the fused Pallas update step (ops/kernels/
                    # update_step.py): one VMEM pass over (w, g, m)
                    # instead of the op-by-op elementwise chain.  Same
                    # schedule spelling as SGDUpdater.apply; bit-equal
                    # to the stock lowering (tests/test_kernels.py).
                    from ..ops.kernels import update_step as _kup

                    p = up.param
                    w2, m2 = _kup.sgd_update(
                        w, g, ustates[key][tag]["m"],
                        p.learning_rate(epoch).astype(w.dtype),
                        p.momentum_at(epoch).astype(w.dtype),
                        wd=p.wd, clip=p.clip_gradient,
                        interpret=kernels.interpret)
                    new_p[key][tag] = w2
                    new_s[key][tag] = {"m": m2}
                    continue
                # a profiler trace names the update's operations by the
                # updater's type (the layers' carry l<index>_<name>)
                with jax.named_scope(f"update_{up.type_name}"):
                    w2, s2 = up.apply(w, g, ustates[key][tag], epoch)
                new_p[key][tag] = w2
                new_s[key][tag] = s2
        return new_p, new_s

    def _update_kernels(self):
        """The kernel library's bound selector for the UPDATE side of
        the step programs (``zero_update``), or None.  Gated to
        single-device meshes: a Pallas call inside a multi-device GSPMD
        program has no partitioning rule in this jaxlib, and the ZeRO
        sharded-update path relies exactly on those annotations — the
        stock elementwise chain stays the spelling there."""
        if self.net is None:
            return None
        plan = self.mesh_plan
        if plan is not None and plan.n_devices > 1:
            return None
        kb = self.net.bound_kernels()
        # bind only when the selector can ever fire (avoids a dead
        # closure arg re-tracing the step on verdict edits)
        return kb if kb.selector.mode != "off" else None

    def _grad_spec(self):
        """The gradient sharding hook for :meth:`_apply_updates`: the
        state sharding on ZeRO runs over a real mesh, else None (a
        1-device mesh must stay annotation-free — see ``_jit``)."""
        plan = self.mesh_plan
        if (plan is None or plan.n_devices <= 1
                or not (self.update_on_server or self.zero >= 1)):
            return None
        return lambda shape: plan.state_sharding(shape)

    def _det_active(self) -> bool:
        """Is the pinned-order (shard_map) reduction in effect?  On a
        1-device mesh there is no cross-replica reduction to pin, so
        the key is a documented no-op there."""
        return bool(self.det_reduce and self.mesh_plan is not None
                    and self.mesh_plan.n_devices > 1)

    @property
    def fence_at_round_end(self) -> bool:
        """Does :meth:`update` leave its fence to
        :meth:`async_round_end`?  True with the async stepper in effect:
        a caller that syncs after every update undoes the overlap."""
        return self._async_active()

    def _async_active(self) -> bool:
        """Is the overlapped per-group exchange (``async_overlap = 1``)
        in effect?  Same 1-device no-op contract as ``det_reduce`` —
        with no cross-replica exchange there is nothing to overlap, and
        ``staleness`` has no collective to absorb."""
        return bool(self.async_overlap and self.mesh_plan is not None
                    and self.mesh_plan.n_devices > 1
                    and not self.quant_scheme)

    def _row_separable_problems(self) -> list:
        """Constraints shared by every shard_map step re-expression
        (``det_reduce`` and ``async_overlap``): the forward runs per
        data shard, so only row-separable math qualifies — pure data
        parallelism (no model axis), replicated state (no ZeRO
        annotations inside the manual region), no extra-data nodes, no
        cross-batch aux state (BN running stats would silently become
        per-shard statistics), the fused single-update path, and no
        stochastic layers (the replicated per-shard rng would correlate
        noise masks across shards)."""
        problems = []
        if self.mesh_plan.n_model != 1:
            problems.append(f"model_parallel={self.mesh_plan.n_model} "
                            "(needs pure data parallelism)")
        if self.zero or self.update_on_server:
            problems.append(f"zero={self.zero} (needs replicated state)")
        if self.update_period != 1:
            problems.append(f"update_period={self.update_period} "
                            "(needs the fused single-update step)")
        if self._n_extras():
            problems.append("extra data nodes")
        if self.aux:
            problems.append("aux (batch-norm style) layer state — "
                            "per-shard batch statistics would diverge")
        stochastic = sorted({
            spec.type_name for spec in self.graph.layers
            if spec.type_name in ("dropout", "insanity",
                                  "insanity_max_pooling")
        })
        if stochastic:
            # the shard_map region replicates the rng across shards, so
            # every shard would draw the SAME noise pattern for its
            # rows — silently different stochasticity than the global
            # draw of the default step, varying with mesh size
            problems.append(
                f"stochastic layers {stochastic} (per-shard rng would "
                "correlate noise masks across data shards)")
        return problems

    def _validate_det_reduce(self) -> None:
        """``det_reduce = 1`` constraints, checked at model build time
        (see :meth:`_row_separable_problems`) — and the async-overlap
        twin, which shares the identical shard_map contract."""
        if self._det_active():
            problems = self._row_separable_problems()
            if problems:
                raise ValueError(
                    "det_reduce=1 is incompatible with: "
                    + "; ".join(problems)
                    + " (doc/parallel.md 'Determinism contract')")
        self._validate_async()

    def _validate_async(self) -> None:
        """``async_overlap = 1`` constraints (doc/parallel.md "Async
        data-parallel"): the same row-separable shard_map contract as
        ``det_reduce``, plus the async-only key coherence checks."""
        if self.staleness and not self.async_overlap:
            raise ValueError(
                f"staleness={self.staleness} requires async_overlap=1 "
                "(the synchronous step has no aggregate buffer to "
                "delay; doc/parallel.md 'Async data-parallel')")
        if not self._async_active():
            return
        problems = self._row_separable_problems()
        if problems:
            raise ValueError(
                "async_overlap=1 is incompatible with: "
                + "; ".join(problems)
                + " (doc/parallel.md 'Async data-parallel')")

    def _shard_grad_fn(self):
        """The per-shard summed-loss gradient closure: grad of THIS
        data shard's rows' summed loss, plus the per-shard loss and
        out-node rows.  SHARED by the ``det_reduce`` fold step below
        and the async per-group exchange (``parallel/async_ps``) —
        the ``staleness = 0`` bitwise-parity contract depends on both
        re-expressions tracing the IDENTICAL backward, so there is
        exactly one copy of it."""
        net = self.net
        out_idx = net.out_node_index()

        def per_shard_grad(params, data, labels, mask, rng, epoch):
            def sum_loss(p):
                nodes, loss, _ = net.forward(
                    p, data, labels=labels, extras=(), train=True,
                    rng=rng, step=epoch, aux={}, return_aux=True,
                    sample_mask=mask,
                )
                return loss, nodes[out_idx].astype(jnp.float32)

            (loss, out), g = jax.value_and_grad(
                sum_loss, has_aux=True)(params)
            return g, loss, out

        return per_shard_grad

    def _det_grad_fn(self):
        """The shard_map re-expression of the step's cross-replica
        gradient reduction (SNIPPETS.md [3] is the pattern): each data
        shard computes the gradient of ITS rows' summed loss, the
        partials are all-gathered over the ``data`` axis, and the
        global gradient is an explicitly ORDERED fold over shard index
        — ``((g0 + g1) + g2) + ...`` unrolled at trace time — so the
        reduction order (and therefore every result bit) is pinned by
        the data-axis size alone, independent of the collectives
        implementation, process layout, or partitioner mood.  The loss
        layers already sum (not average) over rows, so the fold IS the
        global gradient with no renormalization."""
        plan = self.mesh_plan
        n = plan.n_data
        per_shard_grad = self._shard_grad_fn()
        from jax.sharding import PartitionSpec as P

        def per_shard(params, data, labels, mask, rng, epoch):
            g, loss, out = per_shard_grad(
                params, data, labels, mask, rng, epoch)

            def fold(x):
                parts = jax.lax.all_gather(x, "data")
                acc = parts[0]
                for i in range(1, n):
                    acc = acc + parts[i]
                return acc

            grads = jax.tree_util.tree_map(fold, g)
            return grads, fold(loss), out

        return jax.shard_map(
            per_shard, mesh=plan.mesh,
            in_specs=(P(), P("data"), P("data"), P("data"), P(), P()),
            out_specs=(P(), P(), P("data")),
            check_vma=False,
        )

    def _loss_and_out(self, params, aux, data, labels, mask, rng, epoch,
                      extras):
        """(loss, (out_node, new_aux)) with train=True — fused/fwd_train."""
        net = self.net
        nodes, loss, new_aux = net.forward(
            params, data, labels=labels, extras=extras,
            train=True, rng=rng, step=epoch, aux=aux, return_aux=True,
            sample_mask=mask,
        )
        # metrics consume the out node on host: always hand back f32
        return loss, (nodes[net.out_node_index()].astype(jnp.float32), new_aux)

    def _jit(self, fn, in_shardings, out_shardings, donate_argnums=(),
             kind="program", data_arg=None):
        """jit with shardings only when the mesh is non-trivial.

        On a single-device mesh the NamedSharding annotations are pure
        constraint noise — measured on the v5e (transformer LM b8
        T=2048): sharding-annotated scan steps ran ~30x slower than the
        same program without annotations (layout constraints defeat
        XLA's scan buffer aliasing/fusion), so 1-device jits drop them.

        Every program is wrapped for device telemetry
        (``obs/device.py``): the first call per argument-shape
        signature records the program's cold-call time as
        ``xla_program_compile_seconds{kind,bucket}``, where
        ``bucket`` is the leading dim of argument ``data_arg``.  A
        straight pass-through when ``device_telemetry = 0``.
        """
        plan = self.mesh_plan
        if plan is not None and plan.n_devices > 1:
            jf = jax.jit(fn, in_shardings=in_shardings,
                         out_shardings=out_shardings,
                         donate_argnums=donate_argnums)
        else:
            jf = jax.jit(fn, donate_argnums=donate_argnums)
        return obs_device.instrument(jf, kind, data_arg=data_arg)

    def _fused_step_fn(self):
        """fwd + bwd + updater math as ONE donated SPMD program.

        Used when ``update_period == 1`` (the common case): XLA sees the
        whole step, fuses update math into backprop epilogues, and
        overlaps the data-parallel gradient all-reduce with backprop —
        the reference needed AsyncUpdater priorities for this
        (``async_updater-inl.hpp:94-127``); here it is the latency-hiding
        scheduler's job.
        """
        if "fused" not in self._jit_cache:
            updaters = dict(self.updaters)
            rep, dsh, ex = self._sh()
            psh, ush = self._param_sh()
            loss_and_out = self._loss_and_out
            apply_updates = self._apply_updates
            gspec = self._grad_spec()
            ukern = self._update_kernels()
            det_grad = self._det_grad_fn() if self._det_active() else None

            def step(params, ustates, aux, data, labels, mask, rng, epoch,
                     extras):
                if det_grad is not None:
                    grads, loss, out = det_grad(params, data, labels,
                                                mask, rng, epoch)
                    new_aux = aux
                else:
                    (loss, (out, new_aux)), grads = jax.value_and_grad(
                        lambda p: loss_and_out(
                            p, aux, data, labels, mask, rng, epoch, extras
                        ),
                        has_aux=True,
                    )(params)
                new_p, new_s = apply_updates(updaters, params, ustates,
                                             grads, epoch, gspec=gspec,
                                             kernels=ukern)
                return new_p, new_s, new_aux, loss, out

            self._jit_cache["fused"] = self._jit(
                step,
                (psh, ush, rep, dsh, dsh, dsh, rep, rep, ex),
                (psh, ush, rep, rep, dsh),
                donate_argnums=(0, 1, 2),
                kind="train_fused", data_arg=3,
            )
        return self._jit_cache["fused"]

    def _scan_step_fn(self, n_steps: int, per_step_data: bool,
                      with_out: bool):
        """K fused train steps as ONE device program (``lax.scan``).

        TPU-first: host dispatch cost is per-*program*, not per-step,
        so per-batch dispatch (the reference's ``Update(batch)`` loop,
        ``cxxnet_main.cpp:170-185``) pays it K times where one scanned
        program pays it once.  Scanning the fused step K times on
        device keeps identical per-step semantics: same updater math,
        same epoch advance per step, a fresh folded RNG per step.

        ``per_step_data=False`` closes over ONE staged batch reused every
        step (synthetic/benchmark mode); otherwise ``xs`` is the
        ``[K, B, ...]`` step-stacked data/labels.

        ``with_out`` (``eval_train``): each step reduces its out node to
        the train metrics' sums (``utils/metric_device.py``) and ``ys``
        is ``(losses [K], sums [K, n_metric])`` — no ``[K, B, ...]``
        array leaves the program.  ``rec@n``'s tie-break is drawn from
        the step's key folded with a constant, so the training stream is
        the one ``with_out=False`` consumes.
        """
        mset = self.train_metric
        key = ("scan", n_steps, per_step_data,
               mset.signature() if with_out else None)
        if key not in self._jit_cache:
            updaters = dict(self.updaters)
            rep, dsh, _ = self._sh()
            sdsh = self.mesh_plan.data_sharding(axis=1)
            psh, ush = self._param_sh()
            loss_and_out = self._loss_and_out
            apply_updates = self._apply_updates
            gspec = self._grad_spec()
            ukern = self._update_kernels()
            det_grad = self._det_grad_fn() if self._det_active() else None
            label_ranges = self._label_ranges()

            def one_step(params, ustates, aux, data, labels, rng, epoch):
                if det_grad is not None:
                    mask = jnp.ones((data.shape[0],), jnp.float32)
                    grads, loss, out = det_grad(params, data, labels,
                                                mask, rng, epoch)
                    new_aux = aux
                else:
                    (loss, (out, new_aux)), grads = jax.value_and_grad(
                        lambda p: loss_and_out(
                            p, aux, data, labels, None, rng, epoch, ()
                        ),
                        has_aux=True,
                    )(params)
                new_p, new_s = apply_updates(
                    updaters, params, ustates, grads, epoch, gspec=gspec,
                    kernels=ukern
                )
                return new_p, new_s, new_aux, loss, out

            def step(params, ustates, aux, data, labels, rng, epoch):
                def body(carry, xs):
                    p, s, a, k, e = carry
                    k, sub = jax.random.split(k)
                    d, l = xs if per_step_data else (data, labels)
                    p, s, a, loss, out = one_step(p, s, a, d, l, sub, e)
                    if not with_out:
                        return (p, s, a, k, e + 1), loss
                    sums = metric_device.set_sums(
                        mset, out, l, label_ranges,
                        jax.random.fold_in(sub, _METRIC_FOLD))
                    return (p, s, a, k, e + 1), (loss, sums)

                carry, ys = jax.lax.scan(
                    body, (params, ustates, aux, rng, epoch),
                    (data, labels) if per_step_data else None,
                    length=None if per_step_data else n_steps,
                )
                return carry + (ys,)

            data_sh = (sdsh, sdsh) if per_step_data else (dsh, dsh)

            ys_sh = (rep, rep) if with_out else rep
            self._jit_cache[key] = self._jit(
                step,
                (psh, ush, rep) + data_sh + (rep, rep),
                (psh, ush, rep, rep, rep, ys_sh),
                donate_argnums=(0, 1, 2),
                kind="train_scan", data_arg=3,
            )
        return self._jit_cache[key]

    def scan_refusal(self) -> Optional[str]:
        """Why a chunk cannot go through :meth:`update_scan` and has to
        take :meth:`update` batch by batch, or None when it can: the one
        rule the round loop asks and ``update_scan`` raises."""
        if self.update_period != 1:
            return "update_scan requires update_period == 1"
        if self.fence_at_round_end:
            return ("update_scan is the fused multi-step program — it "
                    "cannot interleave the per-group async exchange; use "
                    "update() (scan_steps=1) with async_overlap=1")
        if self._n_extras():
            return ("update_scan does not support extra_data nodes; use "
                    "update()")
        # node-bound train metrics need the per-step node forwards only
        # update() provides (irrelevant when eval_train is off — train
        # metrics never run then)
        if self.eval_train and self.train_metric.need_nodes():
            return ("update_scan cannot score node-bound train metrics "
                    "(metric[field,node] with eval_train); use update()")
        return None

    def update_scan(self, data, labels, n_steps: Optional[int] = None,
                    sync: bool = True, check_steps: bool = True):
        """Run K train steps in ONE dispatched device program.

        Two modes, both of full ``batch_size`` batches, where
        :meth:`scan_refusal` is None (use :meth:`update` otherwise):

        * ``data`` of shape ``[K, B, ...]`` — each scan step consumes its
          own micro-batch (the round loop's chunks);
        * ``data`` of shape ``[B, ...]`` with ``n_steps=K`` — the same
          staged batch is reused every step (synthetic benchmark mode).

        Returns the per-step f32 losses, shape ``[K]``, and nothing else
        — a host ``np.ndarray`` when ``sync=True``, a ``jax.Array``
        otherwise.  With ``eval_train`` the program also returns each
        step's sums of the train metrics, ``[K, n_metric]``
        (``_scan_step_fn``): no output row is fetched.  The sums stay
        on the device, pending inside the trainer, until
        :meth:`collect_scan_metrics` adds them to ``train_metric``.

        There is one path: dispatch, then collect.  ``sync=True`` does
        both at once — it blocks on the losses and collects every
        pending chunk, oldest first.  ``sync=False`` returns WITHOUT
        draining the dispatch queue: the caller overlaps host work (the
        next chunk's decode, copy and upload) with the device scan,
        fences the returned losses later and calls
        :meth:`collect_scan_metrics` there, once a chunk — the
        two-stage ThreadBuffer overlap
        (``iter_thread_imbin_x-inl.hpp:203-354``) in its TPU form, and
        what ``train_loop.RoundLoop`` does whatever ``eval_train`` says.
        """
        assert self.net is not None, "init_model/load_model first"
        self._check_trainable()
        refusal = self.scan_refusal()
        if refusal is not None:
            raise ValueError(refusal)
        in_ndim = len(self.net.input_node_shape(self.batch_size))
        data_arr = data if hasattr(data, "ndim") else np.asarray(data)
        per_step = data_arr.ndim == in_ndim + 1
        if per_step:
            k = int(data_arr.shape[0])
            if n_steps is not None and n_steps != k:
                raise ValueError(
                    f"n_steps={n_steps} != leading data axis {k}"
                )
        else:
            if n_steps is None:
                raise ValueError(
                    "single-batch mode needs n_steps (or pass [K,B,...])"
                )
            k = int(n_steps)
        if jax.process_count() > 1:
            # multi-host: each process feeds its LOCAL [K, B/nproc, ...]
            # stack; the global step-stacks are assembled over the batch
            # axis (the DCN-spanning analog of _to_device).  K must match
            # on every process (the iterators' equal-steps contract) —
            # verified with a cheap allgather so a mismatched tail chunk
            # fails fast instead of deadlocking the SPMD collectives.
            local = self.batch_size // jax.process_count()
            got = data_arr.shape[1] if per_step else data_arr.shape[0]
            if got != local:
                raise ValueError(
                    f"distributed update_scan: each process must feed "
                    f"batch_size/process_count = {local} rows, got {got}"
                )
            if check_steps:
                # fail fast instead of deadlocking; collective, so it
                # costs a cross-host rendezvous per call — a caller whose
                # iterators already guarantee equal K (the CLI's
                # equal-steps contract) passes check_steps=False to keep
                # the async overlap unbroken
                from jax.experimental import multihost_utils

                ks = np.asarray(
                    multihost_utils.process_allgather(
                        np.asarray([k], np.int32)
                    )
                ).reshape(-1)
                if not (ks == k).all():
                    raise ValueError(
                        f"distributed update_scan: step counts differ "
                        f"across processes "
                        f"({sorted(set(int(v) for v in ks))}); every "
                        "process must scan the same K"
                    )
        with_out = bool(self.eval_train and len(self.train_metric))
        first_epoch = self.epoch_counter
        rows = k * self.batch_size
        # host stages of the chunk, on the caller's thread; with the
        # round loop's next / copy / stack they tile its period
        # (doc/observability.md).  h2d is the ENQUEUE: the transfer's
        # tail ends inside device_wait; where it is exposed (a round's
        # first chunk) the round loop bills it in ``run_exposed``
        fed = data_arr.shape[0] * (data_arr.shape[1] if per_step else 1)
        with stage("h2d", rows=fed, step=first_epoch):
            data_dev = self._stage_scan(data, per_step)
            labels_dev = self._stage_scan(labels, per_step)
        with stage("dispatch", rows=rows, step=first_epoch):
            fn = self._scan_step_fn(k, per_step, with_out)
            step0 = jnp.asarray(first_epoch, jnp.int32)
            (self.params, self.ustates, self.aux, self._rng_key, _end,
             ys) = fn(
                self.params, self.ustates, self.aux, data_dev, labels_dev,
                self._next_rng(), step0,
            )
            del data_dev, labels_dev
        self.epoch_counter += k
        losses, sums = ys if with_out else (ys, None)
        if self.divergence_policy:
            # guard fetches the per-step losses — with sync=False this
            # serializes the async overlap (the cost of the check)
            with stage("device_wait", step=first_epoch):
                self._guard_loss(losses, first_epoch, k)
        if with_out:
            self._scan_sums.append((sums, rows, first_epoch))
        if not sync:
            return losses  # async: device array, queue not drained
        with stage("device_wait", rows=rows, step=first_epoch):
            losses_np = jax.device_get(losses)
        self.collect_scan_metrics(all_pending=True)
        return losses_np

    def collect_scan_metrics(self, all_pending: bool = False) -> None:
        """Add to ``train_metric`` the sums of the oldest chunk
        :meth:`update_scan` dispatched and nobody collected yet — of
        every such chunk, oldest first, with ``all_pending`` — so the
        accumulators see the chunks in the order they were trained.
        Nothing to do where none is pending (``eval_train = 0``).  The
        caller has fenced the chunk (its losses are ready, and the sums
        with them: one program wrote both), so the fetch is K x
        n_metric numbers coming down.  Under ``jax.distributed`` they
        come back replicated: every process adds the GLOBAL sums and
        ``cnt_inst`` counts the global batch."""
        while self._scan_sums:
            sums, rows, first_epoch = self._scan_sums.pop(0)
            with stage("device_wait", step=first_epoch):
                sums_np = jax.device_get(sums)
            with stage("metric", rows=rows, step=first_epoch):
                # a step scores every instance of its out node: the
                # global batch, times the positions of a (N, T, V) node
                out_shape = self.net.node_shapes[self.net.out_node_index()]
                self.train_metric.add_sums(
                    sums_np, int(np.prod(out_shape[:-1])))
                stats = pipeline_stats()
                stats.count("metric_rows", rows)
                stats.count("metric_rows_device", rows)
            if not all_pending:
                return

    def _stage_scan(self, x, per_step: bool):
        """Host stack → device array for update_scan; multi-process runs
        assemble the global array from per-process shards ([K, B, ...]
        step-stacks shard on batch axis 1; one staged batch is exactly
        the _to_device case).  The caller bills the ``h2d`` stage."""
        if not per_step:
            return self._place(x)
        if jax.process_count() == 1:
            return jnp.asarray(x)
        return jax.make_array_from_process_local_data(
            self.mesh_plan.data_sharding(axis=1), np.asarray(x)
        )

    def _grad_fn(self):
        if "grad" not in self._jit_cache:
            net = self.net

            def loss_fn(params, aux, data, labels, mask, rng, step, extras):
                _, loss, new_aux = net.forward(
                    params, data, labels=labels, extras=extras,
                    train=True, rng=rng, step=step, aux=aux, return_aux=True,
                    sample_mask=mask,
                )
                return loss, new_aux

            rep, dsh, ex = self._sh()
            psh, _ = self._param_sh()
            self._jit_cache["grad"] = self._jit(
                jax.value_and_grad(loss_fn, has_aux=True),
                (psh, rep, dsh, dsh, dsh, rep, rep, ex),
                ((rep, rep), psh),
                kind="train_grad", data_arg=2,
            )
        return self._jit_cache["grad"]

    def _fwd_train_fn(self):
        """value_and_grad + output node (for eval_train metrics)."""
        if "fwd_train" not in self._jit_cache:
            loss_and_out = self._loss_and_out

            def f(params, aux, data, labels, mask, rng, step, extras):
                (loss, (out, new_aux)), grads = jax.value_and_grad(
                    lambda p: loss_and_out(
                        p, aux, data, labels, mask, rng, step, extras
                    ),
                    has_aux=True,
                )(params)
                return loss, out, new_aux, grads

            rep, dsh, ex = self._sh()
            psh, _ = self._param_sh()
            self._jit_cache["fwd_train"] = self._jit(
                f,
                (psh, rep, dsh, dsh, dsh, rep, rep, ex),
                (rep, dsh, rep, psh),
                kind="train_fwd", data_arg=2,
            )
        return self._jit_cache["fwd_train"]

    def _eval_fn(self):
        if "eval" not in self._jit_cache:
            net = self.net
            out_idx = net.out_node_index()

            def f(params, aux, data, extras):
                nodes, _ = net.forward(
                    params, data, extras=extras, train=False, aux=aux
                )
                return nodes[out_idx].astype(jnp.float32)

            rep, dsh, ex = self._sh()
            psh, _ = self._param_sh()
            self._jit_cache["eval"] = self._jit(
                f, (psh, rep, dsh, ex), dsh, kind="eval", data_arg=2
            )
        return self._jit_cache["eval"]

    def _node_fn(self, node_id: int):
        key = ("node", node_id)
        if key not in self._jit_cache:
            net = self.net

            def f(params, aux, data, extras):
                nodes, _ = net.forward(
                    params, data, extras=extras, train=False, aux=aux
                )
                return nodes[node_id].astype(jnp.float32)

            rep, dsh, ex = self._sh()
            psh, _ = self._param_sh()
            self._jit_cache[key] = self._jit(
                f, (psh, rep, dsh, ex), dsh, kind="extract", data_arg=2
            )
        return self._jit_cache[key]

    def _apply_fn(self):
        if "apply" not in self._jit_cache:
            updaters = dict(self.updaters)
            apply_updates = self._apply_updates
            gspec = self._grad_spec()
            ukern = self._update_kernels()

            def f(params, ustates, grads, epoch):
                return apply_updates(updaters, params, ustates, grads,
                                     epoch, gspec=gspec, kernels=ukern)

            rep = self._sh()[0]
            psh, ush = self._param_sh()
            self._jit_cache["apply"] = self._jit(
                f,
                (psh, ush, psh, rep),
                (psh, ush),
                kind="update_apply",
            )
        return self._jit_cache["apply"]

    # ------------------------------------------------------------------
    def _guard_loss(self, losses, first_epoch: int, n_steps: int = 1) -> None:
        """NaN/Inf divergence guard (active when ``divergence_policy`` is
        set): fetch the step's loss(es), raise :class:`DivergenceError`
        on any non-finite value.  Each call forces a device sync, so the
        guard trades the async dispatch overlap for blow-up detection —
        that is why it is opt-in.

        ``inject_nan_step`` (fault-injection harness) makes the loss at
        that epoch read as NaN once, so recovery paths are testable
        without waiting for a real numeric blow-up."""
        arr = np.asarray(jax.device_get(losses), np.float64).reshape(-1)
        inj = self.inject_nan_step
        if inj >= 0 and first_epoch <= inj < first_epoch + n_steps:
            self.inject_nan_step = -1  # one-shot: a transient fault
            arr = arr.copy()
            arr[min(inj - first_epoch, max(arr.size - 1, 0))] = np.nan
        inj = self.inject_spike_step
        if inj >= 0 and first_epoch <= inj < first_epoch + n_steps:
            self.inject_spike_step = -1  # one-shot: a transient spike
            arr = arr.copy()
            i = min(inj - first_epoch, max(arr.size - 1, 0))
            # finite but far beyond any plausible ratio gate
            arr[i] = max(abs(arr[i]), 1.0) * 1e6
        finite = np.isfinite(arr)
        if not finite.all():
            bad = int(np.flatnonzero(~finite)[0])
            epoch = first_epoch + min(bad, n_steps - 1)
            raise DivergenceError(
                f"divergence guard: non-finite loss {arr[bad]!r} at update "
                f"{epoch} (round {self.round}, policy "
                f"{self.divergence_policy or 'abort'})",
                loss=arr, epoch=epoch,
            )
        self._guard_loss_ratio(arr, first_epoch)

    _SPIKE_WINDOW = 32   # rolling finite-loss history length
    _SPIKE_MIN_SAMPLES = 8   # gate stays disarmed until this many

    def _guard_loss_ratio(self, arr: np.ndarray, first_epoch: int) -> None:
        """Finite loss-spike gate (``divergence_loss_ratio``): a loss
        exceeding ratio x the rolling median of recent finite losses is
        a divergence verdict even though every value is finite — the
        PR-13 staleness blow-up stayed finite for whole rounds.  The
        spike itself is NOT admitted into the history (a genuine
        blow-up must not drag the median up and re-legitimize itself);
        the window rides the trainer, so a divergence rollback (which
        rebuilds the trainer) restarts it cleanly disarmed."""
        ratio = self.divergence_loss_ratio
        if not ratio:
            return
        hist = self._loss_window
        for i, v in enumerate(arr):
            v = float(v)
            if len(hist) >= self._SPIKE_MIN_SAMPLES:
                med = float(np.median(hist))
                if abs(v) > ratio * max(abs(med), 1e-12):
                    epoch = first_epoch + i
                    raise DivergenceError(
                        f"divergence guard: finite loss spike {v:g} > "
                        f"{ratio:g} x rolling median {med:g} at update "
                        f"{epoch} (round {self.round}, policy "
                        f"{self.divergence_policy or 'abort'})",
                        loss=arr, epoch=epoch,
                    )
            hist.append(v)
            if len(hist) > self._SPIKE_WINDOW:
                del hist[0]

    def weights_finite(self) -> bool:
        """True when every parameter tensor is free of NaN/Inf — the
        divergence-rollback sanity check: a CRC-valid checkpoint can
        still carry a baked-in blow-up (the last update of the round it
        captured went non-finite AFTER its loss was measured).
        COLLECTIVE in multi-process runs (``fetch_array`` allgathers),
        so every process computes the identical verdict."""
        for slots in self.params.values():
            for w in slots.values():
                if not np.isfinite(fetch_array(w)).all():
                    return False
        return True

    def scale_learning_rate(self, factor: float) -> None:
        """Multiply every updater's base learning rate by ``factor``
        (divergence-rollback backoff).  Clears the jit cache — compiled
        steps bake the schedule constants in."""
        for up in self.updaters.values():
            up.param.base_lr *= factor
        self._jit_cache.clear()
        self._async = None  # async programs bake the schedule in too

    # ------------------------------------------------------------------
    # async data-parallel (parallel/async_ps, doc/parallel.md)
    def _async_stepper(self):
        """The lazily built :class:`~cxxnet_tpu.parallel.async_ps.step.
        AsyncStepper` driving the overlapped per-group exchange; rebuilt
        whenever the net/mesh/jit cache is (programs close over both)."""
        if self._async is None:
            from ..parallel.async_ps import AsyncStepper

            self._async = AsyncStepper(self)
        return self._async

    def async_round_end(self, round_: int) -> bool:
        """Round-boundary fence for async mode — and, every
        ``async_resync_period`` rounds, the hard re-sync barrier
        (staleness buffers drained first).  No-op when async mode is
        off or no async step ran yet.  Returns True on a resync."""
        if self._async is None or not self._async_active():
            return False
        return self._async.round_end(round_)

    def async_abandon(self, generation: Optional[int] = None,
                      reason: str = "rebuild") -> int:
        """Elastic rebuild hook: discard every pending (in-flight)
        gradient aggregate and move the async updater to a new
        membership generation, so an aggregate reduced by a dead
        generation's collectives is never applied to the rebuilt
        mesh's weights.  Returns the number of aggregates dropped."""
        if self._async is None:
            return 0
        return self._async.updater.reset_staleness(
            generation=generation, reason=reason)

    def async_snapshot(self) -> Optional[dict]:
        """Pipeline telemetry block (pending depths, pushes/applies,
        overlap fraction) — ``None`` outside async mode."""
        if self._async is None:
            return None
        return self._async.snapshot()

    def _layer_counters(self):
        """(params key, state leaf, counter name) of every counter a
        layer keeps in its aux state: a layer type lists them as
        ``aux_counters = {leaf: name}`` (``layers/moe.py``)."""
        for i, lay in enumerate(self.net.layer_objs):
            key = self.net.param_key[i]
            if key in self.aux:
                for leaf, name in getattr(lay, "aux_counters", {}).items():
                    yield key, leaf, name

    def count_layer_state(self) -> None:
        """Add to the round's ``PipelineStats`` counters what the
        layers counted inside the step programs since this was last
        called (``routed_experts``: ``expert_pairs``,
        ``expert_pairs_max``, ``expert_pairs_dropped``, summed over the
        layers).  The counts ride the programs' ``aux`` state as
        wrapping uint32; this is their one fetch, a few scalars, made by
        the CLI once a round after the round's last fence — no program
        is in flight and no chunk's period holds it."""
        found = list(self._layer_counters())
        if not found:
            return
        now = jax.device_get({(k, l): self.aux[k][l] for k, l, _ in found})
        stats = pipeline_stats()
        for key, leaf, name in found:
            val = int(now[(key, leaf)])
            last = self._aux_counted.get((key, leaf), 0)
            self._aux_counted[(key, leaf)] = val
            stats.count(name, (val - last) % (1 << 32))

    def start_round(self, round_: int) -> None:
        self.round = round_
        # integrity-plane chaos site (doc/robustness.md "Integrity
        # plane"): a `bitflip` armed here flips a real bit in a live
        # train-state tensor on THIS process — the injected silent data
        # corruption the fingerprint vote must catch and quarantine
        from ..utils.faults import fault_point

        fault_point("device.state", self)

    def inject_bitflip(self, rng) -> dict:
        """Flip one bit of one element of one live parameter tensor —
        the ``device.state:bitflip`` fault payload hook.  Deterministic
        in ``rng`` (the spec's ``fault_seed``-derived stream): leaf
        choice over the sorted param tree, then element, then bit, so a
        chaos schedule replays to the same flipped bit.  The flip is
        applied to exactly ONE addressable replica copy of the chosen
        element (an rng-chosen local device — a single device-memory
        fault), via per-device rewrite + reassembly under the original
        sharding — a real in-memory corruption, not a simulated
        verdict, and a strict minority the replica vote can name."""
        assert self.params is not None, "init_model/load_model first"
        leaves = [(f"{k}/{t}", k, t)
                  for k in sorted(self.params)
                  for t in sorted(self.params[k])]
        name, key, tag = leaves[rng.randrange(len(leaves))]
        arr = self.params[key][tag]
        shape = tuple(int(d) for d in arr.shape)
        n = int(np.prod(shape)) if shape else 1
        elem = rng.randrange(n)
        itembits = np.dtype(arr.dtype).itemsize * 8
        bit = rng.randrange(min(itembits, 32))
        shards = getattr(arr, "addressable_shards", None)
        if not shards:
            flat = np.asarray(arr).reshape(-1)
            word = flat[elem:elem + 1].copy().view(
                f"u{flat.dtype.itemsize}")
            word ^= word.dtype.type(1 << bit)
            flat = flat.copy()
            flat[elem] = word.view(flat.dtype)[0]
            self.params[key][tag] = jnp.asarray(flat.reshape(shape))
        else:
            coord = np.unravel_index(elem, shape) if shape else ()
            ordered = sorted(shards, key=lambda s: s.device.id)
            holders = []  # (position, local coordinate) of replicas
            for pos, s in enumerate(ordered):
                inside = True
                lcoord = []
                for d, sl in enumerate(s.index):
                    start = sl.start or 0
                    stop = sl.stop if sl.stop is not None else shape[d]
                    if not (start <= coord[d] < stop):
                        inside = False
                        break
                    lcoord.append(coord[d] - start)
                if inside:
                    holders.append((pos, tuple(lcoord)))
            hit_pos, hit_coord = holders[rng.randrange(len(holders))]
            pieces = []
            hit_device = ordered[hit_pos].device
            for pos, s in enumerate(ordered):
                local = np.asarray(s.data)
                if pos == hit_pos:
                    local = local.copy()
                    word = local[hit_coord].reshape(1).view(
                        f"u{local.dtype.itemsize}")
                    word ^= word.dtype.type(1 << bit)
                    local[hit_coord] = word.view(local.dtype)[0]
                pieces.append(jax.device_put(local, s.device))
            self.params[key][tag] = (
                jax.make_array_from_single_device_arrays(
                    shape, arr.sharding, pieces))
        detail = {
            "tensor": name, "elem": int(elem), "bit": int(bit),
            "process": jax.process_index(),
            "device": (hit_device.id if shards else None),
        }
        if not self.silent:
            print(f"[faults] bitflip injected: tensor={name} "
                  f"elem={elem} bit={bit} device={detail['device']} "
                  f"process={detail['process']}", flush=True)
        return detail

    def sync(self) -> None:
        """Block until all dispatched device work is done (step timing).

        Instrumented as the ``mesh.replica`` fault site: a ``hang``
        here models a peer wedged inside a collective (the elastic
        deadline must surface :class:`ReplicaLossError` in bounded
        time), an ``ioerror`` models the abrupt connection-reset a
        SIGKILLed peer produces — reproducible in-process, no real
        process needs to die (doc/robustness.md)."""
        from ..utils.faults import fault_point

        fault_point("mesh.replica")
        if self.params is not None:
            jax.block_until_ready(self.params)

    def check_weight_sync(self, tol: float = 0.0) -> float:
        """Cross-process weight-consistency check — the reference's
        ``test_on_server = 1`` discipline (each worker pulls the server
        copy and compares to its local weights,
        ``/root/reference/src/updater/async_updater-inl.hpp:148-153``)
        re-expressed for SPMD: there is no server copy, so each process
        fingerprints the locally addressable shard of every replicated
        parameter (float64 sum + sum of squares per leaf) and the
        fingerprints are allgathered across the process group.  Replicas
        that drifted (a bad collective, host memory fault, divergent
        dispatch order) produce differing rows.

        Parameters sharded across devices (model parallel / ZeRO-3) get
        the same guard at shard granularity: each device's shard is
        fingerprinted together with the *logical slice* of the global
        array it holds (``Shard.index``), and every replica of the same
        slice — wherever it lives in the mesh — must agree bit-exactly.
        Slices with a single replica have nothing to compare and
        contribute nothing, so a pure-TP axis is quiet while TP x DP
        (the common case) checks the DP replicas of every TP shard.

        Returns the max abs fingerprint deviation across replicas
        (0.0 single-process single-device); raises RuntimeError when it
        exceeds ``tol``.
        """
        assert self.params is not None, "init_model/load_model first"
        if jax.process_count() == 1 and len(jax.local_devices()) == 1:
            return 0.0  # nothing to compare; skip the host transfers

        def _slice_key(index) -> tuple:
            return tuple(
                (s.start, s.stop, s.step) if isinstance(s, slice) else s
                for s in index
            )

        def _check_groups(keys, fps, where: str) -> float:
            groups: dict = {}
            for k, fpv in zip(keys, fps):
                groups.setdefault(k, []).append(fpv)
            worst = 0.0
            for k, g in groups.items():
                if len(g) < 2:
                    continue
                g = np.asarray(g, np.float64)
                d = float(np.abs(g - g[0]).max())
                worst = max(worst, d)
                if d > tol:
                    name, idx = k
                    raise RuntimeError(
                        f"weight-sync check failed: parameter {name} "
                        f"slice {idx} differs across {where} replicas "
                        f"by {d:g} (tol {tol:g}) — sharded weights have "
                        "diverged"
                    )
            return worst

        rows = []
        shard_rows: list = []   # per (sharded leaf, local device) fingerprints
        shard_keys: list = []   # matching (leaf, slice) group keys
        shard_leaves: list = []  # (name, sharding, shape) in traversal order
        for key in sorted(self.params):
            for tag in sorted(self.params[key]):
                arr = self.params[key][tag]
                sh = getattr(arr, "sharding", None)
                if sh is not None and not sh.is_fully_replicated:
                    for s in sorted(getattr(arr, "addressable_shards", []),
                                    key=lambda s: s.device.id):
                        local = np.asarray(s.data, dtype=np.float64)
                        shard_rows.append([local.sum(), (local * local).sum()])
                        shard_keys.append((f"{key}/{tag}",
                                           _slice_key(s.index)))
                    shard_leaves.append((f"{key}/{tag}", sh, arr.shape))
                    continue
                shards = getattr(arr, "addressable_shards", None)
                if not shards:
                    local = np.asarray(arr, dtype=np.float64)
                    rows.append([local.sum(), (local * local).sum()])
                    continue
                # every LOCAL device holds a full replica: fingerprint
                # each and require intra-process equality too (a single
                # corrupted on-device replica must not hide behind its
                # healthy neighbours)
                fps = []
                for s in shards:
                    local = np.asarray(s.data, dtype=np.float64)
                    fps.append([local.sum(), (local * local).sum()])
                intra = float(
                    np.abs(np.asarray(fps) - np.asarray(fps[0])).max()
                )
                if intra > tol:
                    raise RuntimeError(
                        f"weight-sync check failed: parameter {key}/{tag} "
                        f"differs across LOCAL devices by {intra:g} "
                        f"(tol {tol:g}) — an on-device replica is corrupt"
                    )
                rows.append(fps[0])

        # sharded leaves, intra-process: local replicas of the same slice
        dev_sharded = _check_groups(shard_keys, shard_rows, "local-device")

        fp = np.asarray(rows, np.float64).reshape(-1)
        if jax.process_count() == 1:
            return dev_sharded

        # sharded leaves, cross-process: every process holds the same
        # number of shard rows (uniform local device counts over one
        # mesh), so the fingerprints allgather as a dense block; the
        # matching keys are recomputed per peer from the sharding's
        # global device->slice map (devices_indices_map is deterministic
        # and identical on every process).
        from jax.experimental import multihost_utils

        if shard_rows:
            sfp = np.ascontiguousarray(
                np.asarray(shard_rows, np.float64).reshape(-1)
            ).view(np.uint32)
            all_sfp = np.asarray(
                multihost_utils.process_allgather(sfp)
            ).view(np.float64).reshape(-1, 2)
            all_keys = []
            for p in range(jax.process_count()):
                for name, sh, shape in shard_leaves:
                    imap = sh.devices_indices_map(shape)
                    for d in sorted(
                        (d for d in imap if d.process_index == p),
                        key=lambda d: d.id,
                    ):
                        all_keys.append((name, _slice_key(imap[d])))
            assert len(all_keys) == all_sfp.shape[0], (
                "shard fingerprint/key count mismatch across processes"
            )
            dev_sharded = max(
                dev_sharded,
                _check_groups(all_keys, list(all_sfp), "cross-process"),
            )

        # gather the f64 fingerprints as uint32 words: process_allgather
        # round-trips through jax.device_put, which (x64 mode off — the
        # repo default) would silently truncate float64 to float32 and
        # let sub-f32-resolution drift pass the tol=0 bit-exactness check
        words = np.ascontiguousarray(fp).view(np.uint32)
        all_words = np.asarray(multihost_utils.process_allgather(words))
        all_fp = all_words.view(np.float64).reshape(
            jax.process_count(), -1
        )
        dev = float(np.abs(all_fp - all_fp[0]).max()) if fp.size else 0.0
        if dev > tol:
            raise RuntimeError(
                f"weight-sync check failed: max fingerprint deviation "
                f"{dev:g} across {jax.process_count()} processes "
                f"(tol {tol:g}) — replicated weights have diverged"
            )
        return max(dev, dev_sharded)

    # ------------------------------------------------------------------
    # shadow-step audit (integrity plane, doc/robustness.md)
    def _shadow_fn(self, which: str):
        """One of the TWO independently traced grad executables: same
        python function, two separate ``jax.jit`` objects, so jax
        traces and XLA compiles each from scratch.  A deterministic
        miscompile that lowers the traces differently (the PR-9 GSPMD
        concat class), or a core that computes the same executable
        differently across runs, breaks the bitwise A/B compare."""
        key = ("shadow", which)
        if key not in self._jit_cache:
            loss_and_out = self._loss_and_out

            def f(params, aux, data, labels, mask, rng, step, extras):
                (loss, (_out, _new_aux)), grads = jax.value_and_grad(
                    lambda p: loss_and_out(
                        p, aux, data, labels, mask, rng, step, extras
                    ),
                    has_aux=True,
                )(params)
                return loss, grads

            rep, dsh, ex = self._sh()
            psh, _ = self._param_sh()
            self._jit_cache[key] = self._jit(
                f, (psh, rep, dsh, dsh, dsh, rep, rep, ex), (rep, psh),
                kind=f"shadow_{which}", data_arg=2,
            )
        return self._jit_cache[key]

    @staticmethod
    def _local_bytes(x) -> bytes:
        """Concatenated bytes of the locally addressable data of ``x``
        in device-id order — the unit of the bitwise A/B compare (works
        for replicated, ZeRO-sharded, and host arrays alike)."""
        shards = getattr(x, "addressable_shards", None)
        if not shards:
            return np.ascontiguousarray(np.asarray(x)).tobytes()
        return b"".join(
            np.ascontiguousarray(np.asarray(s.data)).tobytes()
            for s in sorted(shards, key=lambda s: s.device.id))

    def shadow_step(self, round_: int):
        """Re-execute a sampled grad step through two independently
        traced executables on identical probe inputs and compare loss +
        every gradient leaf bitwise.  COLLECTIVE on a multi-process
        mesh (both executions are SPMD programs; every rank must call
        at the same round).  Returns None when the executions agree, a
        ``{"tensor", "detail"}`` mismatch record otherwise.  Skipped
        (returns None) for nets with extra input nodes — the probe
        generator only commits the primary input."""
        assert self.net is not None, "init_model/load_model first"
        if self._n_extras():
            return None
        in_shape = self.net.input_node_shape(self.batch_size)
        local_rows = self.batch_size // max(jax.process_count(), 1)
        rng_np = np.random.RandomState(
            (0x5AD0 ^ (round_ * 2654435761)) & 0x7FFFFFFF)
        data_np = rng_np.random_sample(
            (local_rows,) + tuple(in_shape[1:])).astype(np.float32)
        label_np = np.zeros((local_rows, 1), np.float32)
        mask_np = np.ones(local_rows, np.float32)
        data, labels, mask, extras = self._transfer_batch(
            data_np, label_np, mask_np, ())
        rng = jax.random.PRNGKey(round_ & 0x7FFFFFFF)
        step = jnp.asarray(self.epoch_counter, jnp.int32)
        args_a = (self.params, self.aux, data, labels, mask, rng, step,
                  extras)
        loss_a, grads_a = self._shadow_fn("a")(*args_a)
        # the second executable runs on a DIFFERENT device where one is
        # free (trivial mesh + >1 local device): a per-core fault then
        # shows up as A-vs-B instead of reproducing on both legs
        dev_b = None
        plan = self.mesh_plan
        if ((plan is None or plan.n_devices == 1)
                and len(jax.local_devices()) > 1):
            dev_b = jax.local_devices()[1]
        if dev_b is not None:
            args_b = jax.device_put(args_a, dev_b)
        else:
            args_b = args_a
        loss_b, grads_b = self._shadow_fn("b")(*args_b)
        la, lb = self._local_bytes(loss_a), self._local_bytes(loss_b)
        if self.inject_shadow_mismatch:
            self.inject_shadow_mismatch = 0  # one-shot
            lb = bytes([lb[0] ^ 0x10]) + lb[1:]
        if la != lb:
            return {"tensor": "loss",
                    "detail": (f"shadow loss mismatch at round {round_}: "
                               f"{la.hex()} vs {lb.hex()}")}
        for key in sorted(grads_a):
            for tag in sorted(grads_a[key]):
                if (self._local_bytes(grads_a[key][tag])
                        != self._local_bytes(grads_b[key][tag])):
                    return {"tensor": f"{key}/{tag}",
                            "detail": ("shadow grad mismatch at round "
                                       f"{round_}: {key}/{tag}")}
        return None

    def _next_rng(self) -> jax.Array:
        self._rng_key, sub = jax.random.split(self._rng_key)
        return sub

    def _h2d_sharding(self):
        """The explicit H2D placement for batch-major host arrays: the
        mesh's data sharding (``jax.device_put`` target), or None when
        no mesh exists yet (fall back to ``jnp.asarray``)."""
        plan = self.mesh_plan
        return plan.data_sharding() if plan is not None else None

    def _to_device(self, x: np.ndarray,
                   count_rows: bool = False) -> jax.Array:
        """Batch-major host array → (possibly multi-process) global array.

        Single process: explicit sharding-aware ``jax.device_put`` onto
        the mesh's data axis (replacing the former plain
        ``jnp.asarray`` — the exact site of the bisected jaxlib
        ``batched_device_put`` flake), so the array arrives already
        placed where jit's in_shardings want it.  ``device_put`` may
        ALIAS host memory (CPU zero-copy) and iterator buffers are
        reused by ``next()``, so the source is copied first.
        Multi-process (jax.distributed job): this process holds only its
        shard of the global batch; assemble the global array over the
        data axis (the DCN-spanning-mesh analog of the reference's
        per-worker data sharding, SURVEY §2.8).

        Timed as the ``h2d`` pipeline stage (dispatch + host-side copy;
        the device-side completion overlaps async and is billed to
        ``device_wait`` at the next fence).  ``count_rows`` is set only
        for THE data tensor of a batch — labels/mask/extras bill their
        time but no rows, so the stage's rows/sec stays the true batch
        rate instead of 3-4x it.
        """
        rows = (x.shape[0] if count_rows and getattr(x, "ndim", 0) else 0)
        with stage("h2d", rows=rows, step=self.epoch_counter):
            return self._place(x)

    def _place(self, x: np.ndarray) -> jax.Array:
        """:meth:`_to_device` without the bill."""
        if jax.process_count() == 1:
            sh = self._h2d_sharding()
            if sh is None:
                return jnp.asarray(x)
            return jax.device_put(np.array(x, copy=True), sh)
        return jax.make_array_from_process_local_data(
            self.mesh_plan.data_sharding(), np.asarray(x)
        )

    def _transfer_batch(self, data_np, label_np, mask_np, extras_np):
        """One sharding-aware H2D for a whole train batch.

        Single-process with a mesh: ONE batched ``jax.device_put`` of
        the (data, labels, mask, extras) pytree onto the data sharding
        — one dispatch instead of four.  Other configurations fall back
        to per-array :meth:`_to_device`.  Returns ``(data, labels, mask,
        extras)`` device arrays; billed to the ``h2d`` stage with the
        batch's row count."""
        sh = self._h2d_sharding()
        if jax.process_count() != 1 or sh is None:
            data = self._to_device(data_np, count_rows=True)
            labels = self._to_device(label_np)
            mask = self._to_device(mask_np)
            extras = tuple(self._to_device(e) for e in extras_np)
            return data, labels, mask, extras
        with stage("h2d", rows=data_np.shape[0], step=self.epoch_counter):
            # device_put may alias host memory (CPU zero-copy) and the
            # iterator reuses its buffers: copy, as jnp.asarray did
            leaves = tuple(np.array(a, copy=True) for a in
                           (data_np, label_np, mask_np) + tuple(extras_np))
            placed = jax.device_put(leaves, sh)
        return placed[0], placed[1], placed[2], tuple(placed[3:])

    def _pad_train_batch(self, batch: DataBatch):
        """Zero-pad a short final train batch to the compiled batch size.

        The static-shape AdjustBatchSize (``neural_net-inl.hpp:266-277``):
        XLA programs are compiled for one batch shape, so instead of
        re-jitting for every tail size, pad up and hand the step a 0/1
        sample mask that zeroes the padded rows' loss contribution.  Two
        sources of dead rows are masked:

        * a hand-fed short batch (wrapper API) — padded up here;
        * the IO chain's full-size final batch whose trailing
          ``num_batch_padd`` rows are filler (``io/batch.py`` with
          ``round_batch=0``) — already full-size, only masked.

        Returns ``(data, label, extras, mask, n_real)``.
        """
        n = batch.data.shape[0]
        bs = self.batch_size or n
        if jax.process_count() > 1:
            # multi-process: update() receives this process's shard of the
            # global batch (see _to_device); padding must happen upstream
            local = bs // jax.process_count()
            if n != local:
                raise ValueError(
                    f"distributed run: each process must feed exactly "
                    f"batch_size/process_count = {local} rows, got {n}; "
                    "use round_batch=1 in the data iterator"
                )
            # this process's iterator pads its own tail (round_batch=0):
            # mask those filler rows here exactly like the single-process
            # branch; per-process masks concatenate into the global mask
            n_real = n - int(batch.num_batch_padd or 0)
            mask = np.ones(local, np.float32)
            if n_real < n:
                mask[n_real:] = 0.0
            return (batch.data, batch.label, tuple(batch.extra_data),
                    mask, n_real)
        if n == bs:
            n_real = n - int(batch.num_batch_padd or 0)
            mask = np.ones(bs, np.float32)
            if n_real < n:
                mask[n_real:] = 0.0
            return (batch.data, batch.label, tuple(batch.extra_data),
                    mask, n_real)
        if n > bs:
            raise ValueError(
                f"train batch of {n} rows exceeds batch_size={bs}"
            )
        pad = bs - n

        def _pad(a):
            a = np.asarray(a)
            return np.concatenate(
                [a, np.zeros((pad,) + a.shape[1:], a.dtype)], axis=0
            )

        mask = np.concatenate(
            [np.ones(n, np.float32), np.zeros(pad, np.float32)]
        )
        return (_pad(batch.data), _pad(batch.label),
                tuple(_pad(e) for e in batch.extra_data), mask, n)

    def _node_pred_cache(self, data, extras, n_real):
        """Eval-mode forwards for the train metric's node-bound entries,
        run on the CURRENT (pre-update) weights — call before the fused
        step, which donates the param buffers.  Every metric then scores
        the same weight version.  Deliberate divergence from the
        reference: its eval_req snapshots come from the TRAIN forward
        (dropout noise included, nnet_impl-inl.hpp:363-372); here the
        node forward runs eval-mode, so on stochastic nets a node-bound
        metric and the default metric can differ even on the out node."""
        cache = {}
        for node in self.train_metric.nodes:
            if node is not None and node not in cache:
                fn = self._metric_node_fn(node)
                cache[node] = fetch_local_rows(
                    fn(self.params, self.aux, data, extras)
                )[:n_real]
        return cache

    def _score_train_batch(self, out, batch, n_real, node_cache):
        """eval_train on the per-batch path: fetch the step's output and
        score it on the host — the step's own output for default
        entries, the precomputed node forwards for ``metric[field,node]``
        entries (no extra compute otherwise)."""
        preds = fetch_local_rows(out)[:n_real]
        if node_cache:
            cache = {None: preds, **node_cache}
            preds = [cache[node] for node in self.train_metric.nodes]
        self.train_metric.add_eval(
            preds, np.asarray(batch.label)[:n_real], self._label_ranges())
        pipeline_stats().count("metric_rows", n_real)

    def _maybe_quantize(self) -> None:
        """Apply the conf's ``quant`` scheme to freshly built f32 params
        (no-op when unrequested or already quantized).  This is the
        UNGATED on-load path — serving processes have no held-out data
        to gate on; the event makes that visible."""
        if not self._quant_requested or self.quant_scheme:
            return
        from . import quant as nquant
        from ..obs import events as obs_events

        plan = nquant.build_plan(self, self._quant_requested)
        if not plan:
            return
        nquant.apply_plan(self, plan, self._quant_requested)
        obs_events.emit(
            "quant.on_load", scheme=self.quant_scheme,
            layers=len(plan), gated=False)

    def _check_trainable(self) -> None:
        if self.quant_scheme:
            raise ValueError(
                f"this trainer serves a quantized model "
                f"({self.quant_scheme}) and is inference-only — "
                "gradients through int8 codes are meaningless; train "
                "on the f32 checkpoint and re-export")

    def update(self, batch: DataBatch) -> None:
        """One micro-batch: fwd/bwd + (every update_period-th call) update."""
        assert self.net is not None, "init_model/load_model first"
        self._check_trainable()
        data_np, label_np, extras_np, mask_np, n_real = (
            self._pad_train_batch(batch)
        )
        data, labels, mask, extras = self._transfer_batch(
            data_np, label_np, mask_np, extras_np
        )
        step = jnp.asarray(self.epoch_counter, jnp.int32)
        node_cache = {}
        if self.eval_train and self.train_metric.need_nodes():
            node_cache = self._node_pred_cache(data, extras, n_real)
        if self._async_active():
            # overlapped per-group exchange (parallel/async_ps): the
            # host never blocks here — fences belong to
            # async_round_end (and the opt-in divergence guard / train
            # metrics below, which fetch and therefore sync)
            stepper = self._async_stepper()
            losses, out = stepper.step(
                data, labels, mask, self._next_rng(), self.epoch_counter)
            if self.divergence_policy or self.eval_train:
                # these fetches fence the pipeline every step — billed
                # against the round's overlap fraction so the gauge
                # cannot report a fully-overlapped round that is
                # effectively synchronous
                t0 = time.perf_counter()
                if self.divergence_policy:
                    self._guard_loss(losses, self.epoch_counter)
                if self.eval_train:
                    self._score_train_batch(out, batch, n_real, node_cache)
                stepper.add_blocked(time.perf_counter() - t0)
            self.epoch_counter += 1
            return
        if self.update_period == 1:
            # fused SPMD fast path: fwd+bwd+update in one donated program
            (self.params, self.ustates, self.aux, loss, out) = (
                self._fused_step_fn()(
                    self.params, self.ustates, self.aux, data, labels,
                    mask, self._next_rng(), step, extras,
                )
            )
            if self.divergence_policy:
                self._guard_loss(loss, self.epoch_counter)
            if self.eval_train:
                self._score_train_batch(out, batch, n_real, node_cache)
            self.epoch_counter += 1
            return
        if self.eval_train:
            loss, out, self.aux, grads = self._fwd_train_fn()(
                self.params, self.aux, data, labels, mask,
                self._next_rng(), step, extras,
            )
        else:
            (loss, self.aux), grads = self._grad_fn()(
                self.params, self.aux, data, labels, mask,
                self._next_rng(), step, extras,
            )
        if self.divergence_policy:
            # accumulation path: catch the blow-up per micro-batch,
            # BEFORE the bad gradient is folded into the accumulator
            # (and, as on the fused path, before the train metric sees
            # the NaN prediction — logloss refuses one on its own)
            self._guard_loss(loss, self.epoch_counter)
        if self.eval_train:
            self._score_train_batch(out, batch, n_real, node_cache)
        if self._grad_accum is None:
            self._grad_accum = grads
        else:
            self._grad_accum = jax.tree_util.tree_map(
                jnp.add, self._grad_accum, grads
            )
        self.sample_counter += 1
        if self.sample_counter >= self.update_period:
            self.params, self.ustates = self._apply_fn()(
                self.params,
                self.ustates,
                self._grad_accum,
                jnp.asarray(self.epoch_counter, jnp.int32),
            )
            self._grad_accum = None
            self.sample_counter = 0
            self.epoch_counter += 1

    def update_all(self, data: np.ndarray, labels: np.ndarray) -> None:
        """numpy-in convenience (wrapper API ``CXNNetUpdateBatch``)."""
        self.update(DataBatch(data=np.asarray(data), label=np.asarray(labels)))

    # ------------------------------------------------------------------
    def _label_ranges(self) -> Dict[str, Tuple[int, int]]:
        g = self.graph
        return {name: g.label_range[i] for name, i in g.label_name_map.items()}

    def _run_sharded(self, fn, data: np.ndarray, extras=()) -> np.ndarray:
        """Call a data-sharded jit, zero-padding a partial final batch to a
        multiple of the data-axis size (the XLA-static-shapes analog of the
        reference's AdjustBatchSize, SURVEY §7 hard part (f)) and trimming
        the result."""
        n = data.shape[0]
        nd = self.mesh_plan.n_data if self.mesh_plan else 1
        pad = (-n) % nd
        if pad:
            data = np.concatenate([data, np.zeros((pad,) + data.shape[1:],
                                                  data.dtype)], axis=0)
            extras = tuple(
                np.concatenate([e, np.zeros((pad,) + e.shape[1:], e.dtype)], 0)
                for e in extras
            )
        out = fetch_local_rows(
            fn(self.params, self.aux, self._to_device(data, count_rows=True),
               tuple(self._to_device(e) for e in extras))
        )
        return out[:n] if pad else out

    def _metric_node_fn(self, node):
        """Forward fn for one metric's node selector (None = final out) —
        the per-metric ``eval_req`` binding, nnet_impl-inl.hpp:363-372."""
        if node is None:
            return self._eval_fn()
        return self._node_fn(self.graph.node_index_of(node))

    def evaluate(self, iter_eval, data_name: str) -> str:
        """Round-end evaluation; format parity ``\\tname-metric:value``.

        Multi-process: every process evaluates its own (sharded) rows
        and the metric counters are summed across the job before
        printing, so the line reports the GLOBAL metric on each rank."""
        ret = ""
        if self.eval_train:
            # a caller that left scanned chunks uncollected still prints
            # every row it trained
            self.collect_scan_metrics(all_pending=True)
            self.train_metric.reduce_across_processes()
            ret += self.train_metric.print("train")
            self.train_metric.clear()
        if iter_eval is None:
            return ret
        if len(self.metric) == 0:
            return ret
        self.metric.clear()
        fns = [self._metric_node_fn(n) for n in self.metric.nodes]
        iter_eval.before_first()
        while iter_eval.next():
            batch = iter_eval.value()
            data = np.asarray(batch.data)
            extras = tuple(batch.extra_data)
            n = batch.batch_size - batch.num_batch_padd
            outs, preds = {}, []
            for fn in fns:
                if id(fn) not in outs:
                    outs[id(fn)] = self._run_sharded(fn, data, extras)[:n]
                preds.append(outs[id(fn)])
            self.metric.add_eval(preds, batch.label[:n], self._label_ranges())
        self.metric.reduce_across_processes()
        ret += self.metric.print(data_name)
        return ret

    def predict_fn(self, node_id: Optional[int] = None):
        """The PURE, shape-stable inference function — the compiled
        primitive the serving subsystem caches (``serve/cache.py``):
        ``f(params, aux, data, extras) -> f32 out rows`` with eval-mode
        forward semantics and no trainer state captured mutably (params
        and aux are explicit arguments, so a hot-swapped model is just a
        different first argument).  XLA specializes per input shape;
        callers that control the batch shape (power-of-two buckets)
        control the compile count.  ``node_id`` selects a feature node
        (``resolve_feature_node``); ``None`` is the final output."""
        return self._eval_fn() if node_id is None else self._node_fn(node_id)

    def resolve_feature_node(self, node_name: str) -> int:
        """``top[-k]`` / node-name → node index (ExtractFeature rules)."""
        g = self.graph
        if node_name.startswith("top[-"):
            offset = int(node_name[len("top[-"):-1])
            nnode = g.num_nodes
            if not (1 <= offset <= nnode):
                raise ValueError("ExtractFeature: offset out of node range")
            return nnode - offset
        return g.node_index_of(node_name)

    @staticmethod
    def predict_from_scores(out: np.ndarray) -> np.ndarray:
        """Raw out-node rows → per-instance predictions: argmax
        (multi-column), the raw scalar (1-column), or the per-position
        ``(N, T)`` argmax id matrix for sequence models."""
        if out.ndim == 3:
            return out.argmax(axis=-1).astype(np.float32)
        out2d = out.reshape(out.shape[0], -1)
        if out2d.shape[1] == 1:
            return out2d[:, 0]
        return out2d.argmax(axis=1).astype(np.float32)

    def predict(self, batch: DataBatch) -> np.ndarray:
        """Per-instance prediction: argmax, or raw value for 1-col output.

        Sequence models (``(N, T, V)`` out node) predict per position —
        the result is the ``(N, T)`` argmax id matrix."""
        out = self._run_sharded(
            self._eval_fn(), np.asarray(batch.data), tuple(batch.extra_data)
        )
        return self.predict_from_scores(out)

    def extract_feature(self, batch: DataBatch, node_name: str) -> np.ndarray:
        node_id = self.resolve_feature_node(node_name)
        return self._run_sharded(
            self._node_fn(node_id), np.asarray(batch.data),
            tuple(batch.extra_data),
        )

    # ------------------------------------------------------------------
    # weight access (wrapper API parity: 2-D views, visitor tag scheme)
    def get_weight(self, layer_name: str, tag: str) -> np.ndarray:
        i = self.graph.layer_index_of(layer_name)
        key = self.net.param_key[i]
        if key not in self.params or tag not in self.params[key]:
            return np.zeros((0, 0), np.float32)
        w = fetch_array(self.params[key][tag])
        return self._to_2d(w, self.graph.layers[i].type_name, tag)

    def set_weight(self, weight: np.ndarray, layer_name: str, tag: str) -> None:
        if tag not in ("wmat", "bias"):
            raise ValueError("tag must be wmat or bias")
        i = self.graph.layer_index_of(layer_name)
        key = self.net.param_key[i]
        cur = fetch_array(self.params[key][tag])
        new = self._from_2d(np.asarray(weight, np.float32), cur.shape,
                            self.graph.layers[i].type_name, tag)
        plan = self.mesh_plan
        if plan is not None and plan.n_devices > 1:
            # keep the leaf on its SPMD placement (a hand-set weight
            # must not silently break the sharded-state invariant)
            spec = (plan.fsdp_sharding if self.zero >= 3
                    else plan.param_sharding)(new.shape)
            self.params[key][tag] = jax.device_put(new, spec)
        else:
            self.params[key][tag] = jnp.asarray(new)

    @staticmethod
    def _to_2d(w: np.ndarray, type_name: str, tag: str) -> np.ndarray:
        """Flatten to the reference visitor's 2-D view: conv wmat becomes
        (cout, cin_g*kh*kw) in (cin, kh, kw) minor order (the
        unpack_patch2col layout); everything else row-major."""
        if type_name == "conv" and tag == "wmat" and w.ndim == 4:
            kh, kw, ci, co = w.shape
            return w.transpose(3, 2, 0, 1).reshape(co, ci * kh * kw)
        if w.ndim == 1:
            return w[None, :]
        return w.reshape(w.shape[0], -1)

    @staticmethod
    def _from_2d(w2: np.ndarray, shape, type_name: str, tag: str) -> np.ndarray:
        if type_name == "conv" and tag == "wmat" and len(shape) == 4:
            kh, kw, ci, co = shape
            return w2.reshape(co, ci, kh, kw).transpose(2, 3, 1, 0)
        return w2.reshape(shape)

    # ------------------------------------------------------------------
    # checkpointing: magic | json header | npz params
    #
    # npz cannot represent ml_dtypes natively (bfloat16 round-trips as
    # raw void bytes), so bfloat16 leaves — the quantized artifacts' 2x
    # fallback kernels — are stored as uint16 words under a "~bf16"
    # name suffix and re-viewed at read time.
    _BF16_SUFFIX = "~bf16"

    @classmethod
    def _read_model_file(cls, path: str):
        """Parse a checkpoint → (header, params, aux, ustates) where
        params/aux are ``{key: {tag: ndarray}}`` and ustates (present
        only for ``save_ustate=1`` checkpoints) is
        ``{key: {tag: {slot: ndarray}}}``."""
        with open(path, "rb") as f:
            magic = f.read(8)
            if magic != MODEL_MAGIC:
                raise ValueError(f"{path}: not a cxxnet-tpu model file")
            (hlen,) = struct.unpack("<I", f.read(4))
            header = json.loads(f.read(hlen).decode("utf-8"))
            blob = f.read()
        npz = np.load(_io.BytesIO(blob))
        params: Dict[str, dict] = {}
        aux: Dict[str, dict] = {}
        ust: Dict[str, dict] = {}
        for k in npz.files:
            arr = npz[k]
            if k.endswith(cls._BF16_SUFFIX):
                import ml_dtypes

                k = k[:-len(cls._BF16_SUFFIX)]
                arr = arr.view(ml_dtypes.bfloat16)
            key, tag = k.rsplit("/", 1)
            if key.startswith("ust:"):
                tagname, slot = tag.split("@", 1)
                ust.setdefault(key[4:], {}).setdefault(tagname, {})[
                    slot
                ] = arr
            elif key.startswith("aux:"):
                aux.setdefault(key[4:], {})[tag] = arr
            else:
                params.setdefault(key, {})[tag] = arr
        return header, params, aux, ust

    def checkpoint_bytes(self) -> bytes:
        """Serialize the full checkpoint to one byte string.

        COLLECTIVE in multi-process runs: assembling sharded arrays
        (``fetch_array``) allgathers across the job, so EVERY process
        must call this even when only rank 0 writes the file (the
        driver's discipline — ``cli.py::_save_model``)."""
        if self._async is not None:
            # checkpoints are SYNCHRONOUS states: apply every pending
            # staleness aggregate first (every process drains the same
            # buffer contents, so the collective apply order agrees),
            # then PullWait every group — the serializer below reads
            # the weights on host — then serialize; a resumed run
            # restarts the pipeline
            up = self._async.updater
            up.drain()
            for gid in range(len(up.groups)):
                up.pull_wait(gid)
        header = {
            "structure": json.loads(self.graph.structure_to_json()),
            "epoch_counter": self.epoch_counter,
        }
        if self.quant_scheme:
            # quantized artifact: load_model restores the scheme/plan so
            # the served programs (and the bucket-cache key) know what
            # precision they run — see nnet/quant.py
            header["quant"] = {
                "scheme": self.quant_scheme,
                "scales_dtype": "float32",
                "layers": dict(self.quant_plan or {}),
            }
        if self.save_ustate and self._rng_key is not None:
            # exact resume includes the training rng stream (dropout /
            # insanity noise), not just optimizer state; the impl name is
            # recorded so a process with a different jax_default_prng_impl
            # reconstructs the same stream rather than silently diverging
            header["rng_key"] = np.asarray(
                jax.random.key_data(self._rng_key)
            ).tolist()
            header["rng_impl"] = str(
                jax.config.jax_default_prng_impl
            )
        hjson = json.dumps(header).encode("utf-8")
        buf = _io.BytesIO()
        flat = {}

        def _store(name: str, w) -> None:
            arr = fetch_array(w)
            if arr.dtype.name == "bfloat16":
                # npz-safe spelling: uint16 words + name suffix (see
                # _read_model_file)
                flat[name + self._BF16_SUFFIX] = arr.view(np.uint16)
            else:
                flat[name] = arr

        for key, tags in self.params.items():
            for tag, w in tags.items():
                _store(f"{key}/{tag}", w)
        for key, tags in self.aux.items():
            for tag, w in tags.items():
                _store(f"aux:{key}/{tag}", w)
        if self.save_ustate:
            for key, tags in self.ustates.items():
                for tag, slots in tags.items():
                    for slot, w in slots.items():
                        _store(f"ust:{key}/{tag}@{slot}", w)
        np.savez(buf, **flat)
        out = _io.BytesIO()
        out.write(MODEL_MAGIC)
        out.write(struct.pack("<I", len(hjson)))
        out.write(hjson)
        out.write(buf.getvalue())
        return out.getvalue()

    def net_fp(self) -> str:
        """Fingerprint of the current net structure (manifest field)."""
        return ckpt.net_fingerprint(self.graph.structure_to_json())

    def mesh_manifest(self) -> Optional[dict]:
        """The SPMD layout that writes checkpoints (manifest ``mesh``
        field) — informational, since the payload is always gathered
        full arrays and load re-shards onto the current mesh."""
        if self.mesh_plan is None:
            return None
        return {
            "n_data": self.mesh_plan.n_data,
            "n_model": self.mesh_plan.n_model,
            "zero": self.zero,
            "processes": jax.process_count(),
        }

    def save_model(self, path: str, round_: Optional[int] = None,
                   manifest: bool = True) -> None:
        """Atomic checkpoint write (temp + fsync + rename) plus a sidecar
        manifest carrying CRC32 / size / round / net fingerprint, so a
        kill mid-write can never leave a loadable-looking truncation."""
        blob = self.checkpoint_bytes()
        if manifest:
            quant = None
            if self.quant_scheme:
                plan = self.quant_plan or {}
                quant = {
                    "scheme": self.quant_scheme,
                    "scales_dtype": "float32",
                    "int8_layers": sum(1 for v in plan.values()
                                       if v == "int8"),
                    "bf16_layers": sum(1 for v in plan.values()
                                       if v == "bf16"),
                }
            ckpt.write_checkpoint(
                path, blob,
                round_=self.round if round_ is None else round_,
                net_fp=self.net_fp(),
                save_ustate=self.save_ustate,
                mesh=self.mesh_manifest(),
                quant=quant,
            )
        else:
            ckpt.atomic_write_bytes(path, blob)

    def load_model(self, path: str) -> None:
        if not any(n == "netconfig" for n, _ in self.cfg):
            raise ValueError(
                "load_model: set the model conf first (checkpoints store "
                "the net STRUCTURE; layer settings come from the conf — "
                "reference parity: pred.conf carries the full netconfig "
                "section).  Net(cfg=conf_text) / set_params(...) before "
                "load_model."
            )
        header, raw, raw_aux, raw_ust = self._read_model_file(path)
        graph = NetGraph.structure_from_json(json.dumps(header["structure"]))
        self._build_net(graph)
        self._check_metric_nodes()
        self._build_mesh()
        self._bind_mesh_to_layers()
        self.epoch_counter = int(header["epoch_counter"])
        self.sample_counter = 0
        self._grad_accum = None  # drop any half-window from before load
        if "rng_key" in header:
            self._rng_key = jax.random.wrap_key_data(
                jnp.asarray(header["rng_key"], jnp.uint32),
                impl=header.get(
                    "rng_impl", str(jax.config.jax_default_prng_impl)
                ),
            )
        else:
            self._rng_key = jax.random.PRNGKey(self.seed + 1)
        self.params = {
            key: {tag: jnp.asarray(w) for tag, w in tags.items()}
            for key, tags in raw.items()
        }
        q = header.get("quant")
        if q:
            # pre-exported quantized artifact (nnet/quant.py): the codes
            # / scales / bf16 kernels loaded verbatim above ARE the
            # serving params; record the scheme for dispatch + identity
            self.quant_scheme = str(q.get("scheme", "int8"))
            self.quant_plan = dict(q.get("layers") or {})
        else:
            self.quant_scheme = ""
            self.quant_plan = None
        self.aux = self.net.init_aux(self.batch_size)
        for key, tags in raw_aux.items():
            if key in self.aux:
                self.aux[key] = {t: jnp.asarray(w) for t, w in tags.items()}
        # what a checkpoint's counters had counted is not this run's
        self._aux_counted = {
            (key, leaf): int(raw_aux[key][leaf])
            for key, leaf, _ in self._layer_counters()
            if leaf in raw_aux.get(key, {})}
        self.net.infer_shapes(self.batch_size)
        self._validate_det_reduce()
        self._build_updaters()
        # exact resume (save_ustate=1 checkpoints): restore momentum /
        # adam moments where shapes match the rebuilt updaters
        for key, tags in raw_ust.items():
            if key not in self.ustates:
                continue
            for tag, slots in tags.items():
                cur = self.ustates[key].get(tag)
                if cur is None:
                    continue
                if set(slots) == set(cur) and all(
                    slots[sl].shape == cur[sl].shape for sl in slots
                ):
                    self.ustates[key][tag] = {
                        sl: jnp.asarray(w) for sl, w in slots.items()
                    }
        # a conf-level quant key on a PLAIN checkpoint: quantize now
        # (ungated — doc/performance.md); a quantized artifact wins
        self._maybe_quantize()
        # checkpoints hold GATHERED (full) arrays — re-shard onto the
        # CURRENT mesh, whatever mesh (or process count) wrote them
        self._place_state()

    def copy_model_from(self, path: str) -> None:
        """Finetune: fresh init, then copy name-matched layers' weights
        (nnet_impl-inl.hpp:101-134); epoch restarts at 0."""
        self.init_model()
        header, old_params, _old_aux, _old_ust = self._read_model_file(path)
        old = NetGraph.structure_from_json(json.dumps(header["structure"]))
        old_keys = {}
        for i, spec in enumerate(old.layers):
            if spec.name:
                tagk = spec.name if spec.name else spec.type_name
                old_keys[spec.name] = f"l{i}_{tagk}"
        for j, spec in enumerate(self.graph.layers):
            if not spec.name or spec.name not in old_keys:
                continue
            okey = old_keys[spec.name]
            nkey = self.net.param_key[j]
            if okey in old_params and nkey in self.params:
                src = old_params[okey]
                dst = self.params[nkey]
                if all(tag in src and src[tag].shape == np.asarray(dst[tag]).shape
                       for tag in dst):
                    if not self.silent:
                        print(f"Copying layer {spec.name}")
                    for tag in dst:
                        dst[tag] = jnp.asarray(src[tag])
        self.epoch_counter = 0
        self._place_state()  # copied leaves land on the mesh shardings
