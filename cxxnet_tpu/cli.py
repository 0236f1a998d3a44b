"""Task driver: ``python -m cxxnet_tpu <config.conf> [name=val ...]``.

Parity: ``CXXNetLearnTask`` (``/root/reference/src/cxxnet_main.cpp``):
tasks ``train`` / ``pred`` / ``extract`` / ``finetune``; round loop with
per-round evaluation lines ``[round]\\tname-metric:value`` on stderr;
``%04d.model`` checkpoints every ``save_model`` rounds in ``model_dir``;
``continue=1`` resumes from the newest checkpoint; ``model_in`` loads a
model (inferring ``start_counter`` from its filename); ``test_io=1``
pulls batches without updating (IO throughput dry-run); ``print_step``
progress lines; ``max_round`` caps rounds this invocation.

New scope beyond the reference: ``task = serve`` runs the online
inference server (``serve/`` subsystem, doc/serving.md) — dynamic
micro-batching over an HTTP JSON endpoint with hot model reload.
"""

from __future__ import annotations

import os
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from . import config as cfgmod
from .io.data import DataIter, create_iterator
from .nnet.trainer import NetTrainer


class LearnTask:
    def __init__(self) -> None:
        self.task = "train"
        self.net_trainer: Optional[NetTrainer] = None
        self.itr_train: Optional[DataIter] = None
        self.itr_pred: Optional[DataIter] = None
        self.itr_evals: List[DataIter] = []
        self.eval_names: List[str] = []
        self.name_model_dir = "models"
        self.num_round = 10
        self.max_round = 1 << 30
        self.test_io = 0
        self.test_on_server = 0
        self.silent = 0
        self.start_counter = 0
        self.continue_training = 0
        self.save_period = 1
        self.keep_latest = 0  # retention: 0 keeps every checkpoint
        self.divergence_policy = ""  # "" off | abort | rollback
        self.divergence_lr_backoff = 0.5
        self.divergence_max_retries = 3
        # integrity plane (cxxnet_tpu/integrity/, doc/robustness.md
        # "Integrity plane"): fingerprint-vote cadence, shadow-step
        # audit, serve golden-canary probe committed at save
        self.integrity_every = 0     # rounds between votes; 0 = off
        self.integrity_shadow = 0    # 1: shadow-step audit at cadence
        self.integrity_probe = 0     # 1: commit probe block at save
        self._integrity = None       # IntegrityPlane, built in run()
        self._integrity_rollback_before = None  # quarantine bound
        self.name_model_in = "NULL"
        self.name_pred = "pred.txt"
        self.print_step = 100
        self.extract_node_name = ""
        self.output_format = 1
        self.scan_steps = 1
        self._loop = None  # train_loop.RoundLoop, made by task_train
        self.gen_prompt = ""
        self.gen_prompt_file = ""
        self.gen_len = 256
        self.gen_temp = 0.0
        self.gen_topk = 0
        self.gen_topp = 0.0
        self.gen_cache = 1
        self.serve_host = "127.0.0.1"
        self.serve_port = 9090
        # serving fleet (doc/serving.md "Serving fleet"): replicas >= 2
        # turns task=serve into a supervised multi-process fleet behind
        # one routing front-end; fleet_* / canary_* keys are parsed by
        # serve.fleet.FleetOptions from the raw cfg stream
        self.replicas = 1
        self.serve_max_batch = 0  # 0: the trainer's batch_size
        self.batch_timeout_ms = 2.0
        self.queue_limit = 128
        self.serve_reload_period = 0.0  # seconds; 0 disables hot reload
        self.serve_deadline_ms = 0.0  # default per-request deadline
        self.wire = "binary"  # accept binary x-cxb frames (json = refuse)
        self.drain_timeout_s = 5.0  # SIGTERM: flush in-flight this long
        self.reload_breaker_threshold = 3
        self.reload_breaker_cooldown_s = 30.0
        self.watchdog_timeout_s = 600.0  # serve batcher stall guard
        # disaggregated input-data service (task=data_service,
        # io/dataservice/, doc/io.md "Data service"): a shared decode/
        # augment fleet member serving CXD1 batch streams
        self.data_service_host = "127.0.0.1"
        self.data_service_port = 0  # 0 picks an ephemeral port
        self.data_service_http_port = 0
        self.data_service_max_sessions = 64
        self.data_service_cache_mb = 256.0
        self.data_service_window = 4
        self.data_service_ready_file = ""
        self.telemetry = 0  # per-round JSONL records (doc/observability.md)
        self.telemetry_path = "telemetry.jsonl"
        # seconds of set-up by phase, stamped where each happens and
        # carried in every telemetry record (doc/observability.md)
        self._setup: Dict[str, float] = {}
        # self-tuning knob controller (cxxnet_tpu/tune/,
        # doc/performance.md): tune_* keys are parsed by
        # tune.options_from_cfg from the raw cfg stream
        self.controller = 0
        # closed-loop continuous training (task=serve_train,
        # doc/continuous_training.md).  The loop_*/publish_*/feedback_*
        # defaults live in ONE table shared with the per-tenant parser
        # (loop/tenant.py TenantOptions) so task=serve_train and
        # task=loop_fleet can never drift apart on the same conf.
        from .loop.tenant import TenantOptions

        for _key, _default in TenantOptions.DEFAULTS.items():
            setattr(self, _key, _default)
        self.loop_dir = "loop"
        self.loop_cycle_period_s = 2.0
        self.loop_max_cycles = 0  # stop fine-tuning after N trained cycles
        self.capture_predict = 0  # log /predict inputs+predictions too
        # multi-tenant loops (task=loop_fleet, loop/tenant.py): keys
        # inside a 'tenant = <name>' .. 'tenant = end' section bind to
        # that tenant, not to the driver
        self._in_tenant_section = False
        # quantized inference (task=export_quant / quant= at serve
        # time; doc/performance.md "Quantized inference")
        self.quant = "int8"  # export scheme (serve reads the raw key)
        self.quant_min_agreement = 0.99
        self.quant_calib_batches = 0  # 0 = the whole eval set
        self.quant_out = ""  # artifact path override
        self.quant_report = ""  # also write the verdict JSON here
        # elastic pod (doc/parallel.md "Elastic pod"): parsed once in
        # run() from the elastic_* / collective_timeout_s keys
        self.elastic_opts = None
        self.elastic_member = None
        self._elastic_joined = False
        self._elastic_left = False
        self._elastic_drop_done = False
        self._elastic_rebuilds = 0
        self._elastic_consec_recoveries = 0
        self._elastic_attempted_gen = 0
        self._elastic_last_rebuild_s = 0.0
        self.conf_path = ""
        self.cli_overrides: List[str] = []
        self.cfg: List[tuple] = []

    # ------------------------------------------------------------------
    def set_param(self, name: str, val: str) -> None:
        # tenant sections pass through untouched: a tenant's model_dir
        # (or any other key) must never clobber the driver's globals —
        # loop/tenant.py re-splits them from the raw stream
        if name == "tenant":
            self._in_tenant_section = val != "end"
            self.cfg.append((name, val))
            return
        if self._in_tenant_section:
            self.cfg.append((name, val))
            return
        if val == "default":
            return
        if name == "print_step":
            self.print_step = int(val)
        elif name == "continue":
            self.continue_training = int(val)
        elif name == "save_model":
            self.save_period = int(val)
        elif name == "keep_latest":
            self.keep_latest = int(val)
        elif name == "divergence_policy":
            self.divergence_policy = "" if val == "off" else val
        elif name == "divergence_lr_backoff":
            self.divergence_lr_backoff = float(val)
        elif name == "divergence_max_retries":
            self.divergence_max_retries = int(val)
        elif name == "integrity_every":
            self.integrity_every = int(val)
        elif name == "integrity_shadow":
            self.integrity_shadow = int(val)
        elif name == "integrity_probe":
            self.integrity_probe = int(val)
        elif name == "start_counter":
            self.start_counter = int(val)
        elif name == "model_in":
            self.name_model_in = val
        elif name == "model_dir":
            self.name_model_dir = val
        elif name == "num_round":
            self.num_round = int(val)
        elif name == "max_round":
            self.max_round = int(val)
        elif name == "silent":
            self.silent = int(val)
        elif name == "task":
            self.task = val
        elif name == "test_io":
            self.test_io = int(val)
        elif name == "test_on_server":
            # per-round cross-process weight-divergence check
            # (reference async_updater-inl.hpp:148-153 discipline)
            self.test_on_server = int(val)
        elif name == "extract_node_name":
            self.extract_node_name = val
        elif name == "output_format":
            self.output_format = 1 if val == "txt" else 0
        elif name == "scan_steps":
            self.scan_steps = int(val)
        elif name == "gen_prompt":
            self.gen_prompt = val
        elif name == "gen_prompt_file":
            self.gen_prompt_file = val  # read lazily in task_generate
        elif name == "gen_len":
            self.gen_len = int(val)
        elif name == "gen_temp":
            self.gen_temp = float(val)
        elif name == "gen_topk":
            self.gen_topk = int(val)
        elif name == "gen_topp":
            self.gen_topp = float(val)
        elif name == "gen_cache":
            self.gen_cache = int(val)
        elif name == "serve_host":
            self.serve_host = val
        elif name == "serve_port":
            self.serve_port = int(val)
        elif name == "replicas":
            self.replicas = int(val)
        elif name == "max_batch_size":
            self.serve_max_batch = int(val)
        elif name == "batch_timeout_ms":
            self.batch_timeout_ms = float(val)
        elif name == "queue_limit":
            self.queue_limit = int(val)
        elif name == "serve_reload_period":
            self.serve_reload_period = float(val)
        elif name == "serve_deadline_ms":
            self.serve_deadline_ms = float(val)
        elif name == "wire":
            # data-plane wire formats the engine accepts (the raw key
            # also reaches serve.Engine through self.cfg)
            if val not in ("binary", "json"):
                raise ValueError(
                    f"wire must be binary or json, got {val!r}")
            self.wire = val
        elif name == "drain_timeout_s":
            self.drain_timeout_s = float(val)
        elif name == "reload_breaker_threshold":
            self.reload_breaker_threshold = int(val)
        elif name == "reload_breaker_cooldown_s":
            self.reload_breaker_cooldown_s = float(val)
        elif name == "watchdog_timeout_s":
            self.watchdog_timeout_s = float(val)
        elif name == "controller":
            self.controller = int(val)
        elif name == "telemetry":
            self.telemetry = int(val)
        elif name == "telemetry_path":
            self.telemetry_path = val
        elif name == "loop_dir":
            self.loop_dir = val
        elif name == "loop_rounds_per_cycle":
            self.loop_rounds_per_cycle = int(val)
        elif name == "loop_replay_ratio":
            self.loop_replay_ratio = float(val)
        elif name == "loop_min_records":
            self.loop_min_records = int(val)
        elif name == "loop_max_records":
            self.loop_max_records = int(val)
        elif name == "loop_cycle_period_s":
            self.loop_cycle_period_s = float(val)
        elif name == "loop_max_cycles":
            self.loop_max_cycles = int(val)
        elif name == "publish_min_delta":
            self.publish_min_delta = float(val)
        elif name == "publish_metric":
            self.publish_metric = val
        elif name == "publish_slice_floor":
            self.publish_slice_floor = float(val)
        elif name == "publish_slice_min_count":
            self.publish_slice_min_count = int(val)
        elif name == "publish_source_field":
            self.publish_source_field = int(val)
        elif name == "capture_predict":
            self.capture_predict = int(val)
        elif name == "feedback_page_bytes":
            self.feedback_page_bytes = int(val)
        elif name == "feedback_rotate_bytes":
            self.feedback_rotate_bytes = int(val)
        elif name == "feedback_retain_shards":
            self.feedback_retain_shards = int(val)
        elif name == "feedback_retain_bytes":
            self.feedback_retain_bytes = int(val)
        elif name == "data_service_host":
            self.data_service_host = val
        elif name == "data_service_port":
            self.data_service_port = int(val)
        elif name == "data_service_http_port":
            self.data_service_http_port = int(val)
        elif name == "data_service_max_sessions":
            self.data_service_max_sessions = int(val)
        elif name == "data_service_cache_mb":
            self.data_service_cache_mb = float(val)
        elif name == "data_service_window":
            self.data_service_window = int(val)
        elif name == "data_service_ready_file":
            self.data_service_ready_file = val
        elif name == "quant":
            self.quant = "" if val in ("0", "off", "none") else val
        elif name == "quant_min_agreement":
            self.quant_min_agreement = float(val)
        elif name == "quant_calib_batches":
            self.quant_calib_batches = int(val)
        elif name == "quant_out":
            self.quant_out = val
        elif name == "quant_report":
            self.quant_report = val
        self.cfg.append((name, val))

    # ------------------------------------------------------------------
    def run(self, argv: List[str]) -> int:
        if len(argv) < 1:
            print("Usage: <config> [name=val ...]")
            return 0
        # the fleet supervisor re-launches this exact invocation per
        # replica (conf + overrides, fleet keys pinned) — keep the raw
        # argv around for serve.fleet.cli_spawn_fn
        t_run = time.perf_counter()
        self.conf_path = argv[0]
        self.cli_overrides = list(argv[1:])
        for name, val in cfgmod.parse_file(argv[0]):
            self.set_param(name, val)
        for name, val in cfgmod.parse_cli_overrides(argv[1:]):
            self.set_param(name, val)
        # join the multi-process job (if any) before any JAX backend use;
        # the distributed-PS replacement (SURVEY §2.8): bigger mesh, same
        # SPMD program, collectives over ICI/DCN
        from .parallel import maybe_init_distributed
        from .parallel.elastic import ElasticOptions

        self.elastic_opts = ElasticOptions.from_cfg(self.cfg)
        maybe_init_distributed(self.cfg)
        # arm the chaos harness (no-op without fault_inject keys); the
        # instrumented sites live in io/, utils/checkpoint.py and serve/
        from .utils import compile_cache, faults

        faults.configure(self.cfg)
        # observability (doc/observability.md): host-span tracing
        # (trace_dir/trace_steps) and the structured event log
        # (event_log*) — both default off; the metrics registry needs
        # no arming, layers write into it unconditionally
        from . import obs

        obs.configure(self.cfg)
        # persistent XLA compile cache, on for every task and enabled
        # before ANY jit of this run (utils/compile_cache.py says where)
        compile_cache.configure(self.cfg, silent=bool(self.silent))
        if self.task not in ("train", "finetune", "pred", "pred_raw",
                             "extract", "generate", "summary", "serve",
                             "serve_train", "loop_fleet",
                             "export_quant", "data_service"):
            raise ValueError(f"unknown task {self.task!r}")
        if self.elastic_opts.join:
            # a rejoining process has no mesh yet: admission, backend
            # init, model load and iterators all happen inside
            # task_train (_elastic_join_setup) once the coordinator
            # grows the pod
            if self.task != "train":
                raise ValueError("elastic_join=1 only supports task=train")
        else:
            t_init = time.perf_counter()
            self._setup["conf_s"] = t_init - t_run
            self.init()
            self._setup["model_s"] = (
                time.perf_counter() - t_init
                - self._setup.get("iterators_s", 0.0))
        if not self.silent:
            print("initializing end, start working")
        if self.task == "export_quant":
            return self.task_export_quant()
        if self.task in ("train", "finetune"):
            self.task_train()
        elif self.task in ("pred", "pred_raw"):
            self.task_predict(raw=self.task == "pred_raw")
        elif self.task == "extract":
            self.task_extract()
        elif self.task == "generate":
            self.task_generate()
        elif self.task == "summary":
            self.task_summary()
        elif self.task == "serve":
            self.task_serve()
        elif self.task == "serve_train":
            self.task_serve_train()
        elif self.task == "loop_fleet":
            self.task_loop_fleet()
        elif self.task == "data_service":
            self.task_data_service()
        else:
            raise ValueError(f"unknown task {self.task!r}")
        return 0

    # ------------------------------------------------------------------
    def _create_trainer(self) -> NetTrainer:
        tr = NetTrainer()
        tr.set_params(self.cfg)
        return tr

    def init(self) -> None:
        if self.task == "serve":
            # the serving engine owns model discovery/validation and
            # needs no data iterators — see task_serve
            return
        if self.task == "data_service":
            # the server builds the conf's data chain itself (inside
            # BatchPlant) and has no model — see task_data_service
            return
        if self.task == "export_quant":
            # the exporter loads its own trainers (f32 reference +
            # candidate); the driver only supplies the held-out eval
            # iterator the agreement gate scores on
            if self.name_model_in == "NULL":
                raise ValueError(
                    "task=export_quant needs model_in (the trained f32 "
                    "checkpoint to quantize)")
            from .parallel.distributed import process_info

            if process_info()[1] > 1:
                raise ValueError("task=export_quant is single-process")
            self._create_iterators()
            return
        if self.task == "serve_train":
            # the engine owns the model; the continuous loop needs the
            # conf's data section (replay mixing) and eval section (the
            # publish gate) but no driver-level trainer
            from .parallel.distributed import process_info

            if process_info()[1] > 1:
                raise ValueError(
                    "task=serve_train is single-process (the trainer "
                    "rides beside the serving engine)")
            self._create_iterators()
            return
        if self.task == "loop_fleet":
            # every tenant builds its OWN engine + iterators from its
            # effective config (loop/tenant.py) — the driver only
            # validates the process shape here
            from .parallel.distributed import process_info

            if process_info()[1] > 1:
                raise ValueError(
                    "task=loop_fleet is single-process (N tenants "
                    "share this process's device pool)")
            return
        if self.task == "train" and self.continue_training:
            if self._sync_latest_model():
                print(f"Init: Continue training from round {self.start_counter}")
                self._create_iterators()
                return
            raise FileNotFoundError(
                "Init: cannot find models for continue training; "
                "specify model_in instead"
            )
        self.continue_training = 0
        if self.name_model_in == "NULL":
            if self.task not in ("train", "summary"):
                raise ValueError("must specify model_in if not training")
            self.net_trainer = self._create_trainer()
            self.net_trainer.init_model()
        elif self.task == "finetune":
            self.net_trainer = self._create_trainer()
            self.net_trainer.copy_model_from(self.name_model_in)
        else:
            self._load_model()
        self._create_iterators()

    def _net_fingerprint(self) -> Optional[str]:
        """Fingerprint of the conf's netconfig (manifest cross-check on
        resume); None when the conf has no parseable netconfig."""
        from .nnet.graph import NetGraph
        from .utils import checkpoint as ckpt

        try:
            g = NetGraph()
            g.configure(self.cfg)
            return ckpt.net_fingerprint(g.structure_to_json())
        except Exception:
            return None

    def _locate_agreed_checkpoint(self, before=None):
        """THE distributed resume/rollback discovery protocol — one copy
        so every caller issues the identical collective sequence (a
        divergent copy would deadlock multi-process runs).

        Collective: finds the newest locally-valid checkpoint, agrees on
        the newest round EVERY process holds (``agree_on_round``),
        validates the agreed round when it is older than the local
        newest (``find_latest_valid`` only vouched for the newest —
        consensus must not launder a corrupt/pruned file past the
        integrity checks), and agrees on the usable/unusable verdict
        (``any_process_flag`` — a lone local abort would strand the
        peers at their next collective).

        Returns ``(round_, path, reason)``: ``round_ == -1`` when no
        process has any valid checkpoint; ``reason`` is not None (or
        path unusable on a peer, reason None with path set) when the
        agreed round failed validation somewhere — the caller decides
        raise vs bail."""
        from .parallel.distributed import agree_on_round, any_process_flag
        from .utils import checkpoint as ckpt

        net_fp = self._net_fingerprint()
        found = ckpt.find_latest_valid(
            self.name_model_dir, net_fp=net_fp, silent=bool(self.silent),
            before=before,
        )
        local_round = found[0] if found else -1
        round_ = agree_on_round(local_round)
        if round_ < 0:
            return -1, None, None
        if round_ == local_round:
            path, reason = found[1], None
        else:
            if not self.silent:
                print(f"resume: agreed on round {round_} across processes "
                      f"(local newest was {local_round})")
            path = os.path.join(self.name_model_dir, f"{round_:04d}.model")
            reason = ckpt.validate_checkpoint(path, net_fp=net_fp)
        if any_process_flag(reason is not None):
            return round_, path, reason or "unusable on a peer process"
        return round_, path, None

    def _load_trainer(self, path: str) -> NetTrainer:
        """Fresh trainer with ``path`` loaded, retrying transient I/O
        under the unified :class:`RetryPolicy` (``retry_*`` config keys
        — the same policy the serving engine uses)."""
        from .utils.faults import RetryPolicy

        tr = self._create_trainer()
        RetryPolicy.from_cfg(self.cfg).run(
            lambda: tr.load_model(path),
            what=f"loading {path}", silent=bool(self.silent))
        return tr

    def _sync_latest_model(self) -> bool:
        """Resume from the newest VALID checkpoint in ``model_dir``.

        Globs all ``NNNN.model`` files (the old consecutive scan stopped
        at the first gap, so ``save_model > 1`` or ``keep_latest``
        pruning made resume find nothing), validates each against its
        manifest (CRC32 + size + net fingerprint), and falls back past
        corrupt/truncated ones — a kill mid-write never bricks resume.
        Multi-process runs agree on the newest round EVERY process can
        see before anyone loads.

        An integrity quarantine sets ``_integrity_rollback_before``
        (exclusive bound): the newest checkpoints may carry state the
        corrupt rank's gradients already poisoned, so survivors must
        resume from the last FINGERPRINT-VERIFIED round, not the newest
        round on disk — the poisoned rounds are re-trained and their
        checkpoints overwritten."""
        from .utils import checkpoint as ckpt

        bound, self._integrity_rollback_before = (
            self._integrity_rollback_before, None)
        round_, path, reason = self._locate_agreed_checkpoint(before=bound)
        if round_ < 0:
            return False
        if reason is not None:
            raise ckpt.CheckpointError(
                f"resume: processes agreed on round {round_} but "
                f"{path} is unusable: {reason}"
            )
        self.net_trainer = self._load_trainer(path)
        self.start_counter = round_ + 1
        from .obs import emit as obs_emit

        obs_emit("checkpoint.restore", round=round_, path=path)
        return True

    def _load_model(self) -> None:
        base = os.path.basename(self.name_model_in)
        stem = base.split(".")[0]
        if stem.isdigit():
            self.start_counter = int(stem)
        else:
            print(
                "WARNING: cannot infer start_counter from model name; "
                "set it in the config if needed"
            )
        self.net_trainer = self._create_trainer()
        self.net_trainer.load_model(self.name_model_in)
        self.start_counter += 1

    def _probe_block(self) -> Optional[dict]:
        """The golden-canary ``probe`` manifest block
        (``integrity_probe = 1``, doc/robustness.md "Integrity plane"):
        the deterministic probe-batch spec, plus — on single-process
        runs — the CRC of this trainer's scores for it.  Multi-process
        runs commit the spec only (scoring is a different SPMD program
        per mesh; the engine records its own golden at load)."""
        if not self.integrity_probe or self.net_trainer is None:
            return None
        import jax

        from .integrity import canary

        tr = self.net_trainer
        rows = max(1, min(int(tr.batch_size) or 8, 8))
        shape = tuple(tr.net.input_node_shape(tr.batch_size))[1:]
        seed = 0xC0FFEE ^ int(tr.seed or 0)
        crc = None
        if jax.process_count() == 1 and not tr.quant_scheme:
            probe = canary.probe_batch(seed, rows, shape)
            scores = tr._run_sharded(tr._eval_fn(), probe)
            crc = canary.scores_crc(scores)
        return canary.make_probe_block(seed, rows, shape, crc,
                                       jax.default_backend())

    def _save_model(self, force: bool = False) -> bool:
        """Checkpoint the current state as ``NNNN.model`` + manifest.

        Fault-tolerant write discipline: serialize (COLLECTIVE — every
        process assembles sharded state), then rank 0 alone writes
        atomically with retry/backoff, applies ``keep_latest`` retention,
        and everyone re-synchronizes at a barrier so no process reads a
        checkpoint before it is durable.  ``force=True`` (preemption
        snapshot) bypasses the ``save_model`` period gate — though
        ``save_model = 0`` (checkpointing disabled) stays disabled.
        Returns True when a checkpoint was written."""
        from .parallel.distributed import (
            any_process_flag, barrier, is_primary, process_info,
        )
        from .utils import checkpoint as ckpt

        round_ = self.start_counter
        path = os.path.join(self.name_model_dir, f"{round_:04d}.model")
        self.start_counter += 1
        if self.save_period == 0 or (
                not force and self.start_counter % self.save_period != 0):
            return False
        blob = self.net_trainer.checkpoint_bytes()
        probe = self._probe_block()
        err = None
        if is_primary():
            try:
                os.makedirs(self.name_model_dir, exist_ok=True)
                ckpt.write_checkpoint(
                    path, blob, round_=round_,
                    net_fp=self.net_trainer.net_fp(),
                    save_ustate=self.net_trainer.save_ustate,
                    retry=True, silent=bool(self.silent),
                    mesh=self.net_trainer.mesh_manifest(),
                    probe=probe,
                )
                if self.keep_latest > 0:
                    ckpt.apply_retention(
                        self.name_model_dir, self.keep_latest,
                        silent=bool(self.silent),
                    )
            except Exception as exc:  # noqa: BLE001 - relayed collectively
                err = exc
        if process_info()[1] > 1:
            # success/failure must be exchanged BEFORE the barrier — a
            # raise on rank 0 alone would strand the other ranks in it
            if any_process_flag(err is not None):
                if err is not None:
                    raise err
                raise ckpt.CheckpointError(
                    f"checkpoint {path} failed to write on the primary "
                    "process"
                )
            barrier("ckpt_save")
        elif err is not None:
            raise err
        return True

    def _create_iterators(self) -> None:
        t0 = time.perf_counter()
        split = cfgmod.split_sections(self.cfg)
        for sec in split.sections:
            if sec.kind == "data" and self.task not in ("pred", "pred_raw",
                                                        "generate",
                                                        "summary",
                                                        "export_quant"):
                if self.itr_train is not None:
                    raise ValueError("can only have one data section")
                self.itr_train = create_iterator(sec.entries)
            elif sec.kind == "eval" and self.task not in ("pred", "pred_raw",
                                                          "generate",
                                                          "summary"):
                self.itr_evals.append(create_iterator(sec.entries))
                self.eval_names.append(sec.tag)
            elif sec.kind == "pred":
                self.name_pred = sec.tag
                if self.task in ("pred", "pred_raw", "extract"):
                    if self.itr_pred is not None:
                        raise ValueError("can only have one pred section")
                    self.itr_pred = create_iterator(sec.entries)
        from .parallel.distributed import process_info

        pid, nproc = process_info()
        for it in [self.itr_train, self.itr_pred, *self.itr_evals]:
            if it is not None:
                for n, v in split.global_entries:
                    it.set_param(n, v)
                if nproc > 1 and (it is self.itr_train
                                  or it in self.itr_evals):
                    # multi-process contract (trainer._pad_train_batch):
                    # each process feeds batch_size/nproc LOCAL rows of
                    # its own data shard; batch_size in the conf is
                    # GLOBAL.  Shard + shrink the iterator here so dist
                    # confs run unchanged on any process count.  Eval
                    # iterators shard too (cross-process metric reduction
                    # reassembles the global number — trainer.evaluate);
                    # an eval chain that can't shard still works, every
                    # process just scores the full set redundantly.
                    gbs = self.net_trainer.batch_size
                    if gbs % nproc != 0:
                        raise ValueError(
                            f"batch_size={gbs} must divide by the "
                            f"process count ({nproc})"
                        )
                    if not it.supports_dist_shard():
                        if it is self.itr_train:
                            raise ValueError(
                                "multi-process training needs a train "
                                "iterator that honors dist_num_worker "
                                "(mnist/imgbin/img/csv/synthetic); this "
                                "chain would silently feed every process "
                                "identical data"
                            )
                    else:
                        it.set_param("batch_size", str(gbs // nproc))
                        it.set_param("dist_num_worker", str(nproc))
                        it.set_param("dist_worker_rank", str(pid))
                it.init()
        self._setup["iterators_s"] = (self._setup.get("iterators_s", 0.0)
                                      + time.perf_counter() - t0)

    # ------------------------------------------------------------------
    # self-tuning controller (cxxnet_tpu/tune/): ``controller = 1``
    # arms a background KnobController for the task's live knobs
    def _start_controller(self, knobs, objective, on_tick=None,
                          name="tune"):
        from .tune import KnobController, options_from_cfg

        opts = options_from_cfg(self.cfg)
        ctrl = KnobController(
            objective, knobs,
            period_s=opts.period_s, band=opts.band,
            measure_ticks=opts.measure_ticks,
            settle_ticks=opts.settle_ticks,
            cooldown_ticks=opts.cooldown_ticks,
            name=name, on_tick=on_tick,
        )
        ctrl.start()
        if not self.silent:
            print(f"controller: tuning {[k.name for k in knobs]} "
                  f"every {opts.period_s:g}s (band {opts.band:g})",
                  flush=True)
        return ctrl

    def _start_train_controller(self):
        """``controller = 1`` for train tasks: tune the decode pool
        (workers + in-flight window) against the rate of rows the train
        loop actually dispatches.  None when the conf did not opt in or
        the chain has no parallel decode stage."""
        if not self.controller or self.itr_train is None:
            return None
        from .tune import find_pipeline, options_from_cfg, pipeline_knobs

        opts = options_from_cfg(self.cfg)
        knobs = []
        if opts.wants("pipeline"):
            pipe = find_pipeline(self.itr_train)
            if pipe is not None:
                knobs.extend(pipeline_knobs(pipe))
        if not knobs:
            if not self.silent:
                print("controller=1: no tunable pipeline stage in this "
                      "iterator chain; controller idle", flush=True)
            return None
        bs = float(self.net_trainer.batch_size or 1)
        return self._start_controller(
            knobs,
            objective=lambda: float(self._loop.global_step) * bs,
            name="train",
        )

    def _start_serve_controller(self, engine):
        """``controller = 1`` for serve tasks: tune the micro-batcher
        (coalescing limit + batch window) against executed batch rows,
        with the speculative bucket prewarm riding every tick."""
        if not self.controller:
            return None
        from .tune import batcher_knobs, options_from_cfg

        opts = options_from_cfg(self.cfg)
        knobs = batcher_knobs(engine) if opts.wants("batcher") else []
        if not knobs:
            return None
        return self._start_controller(
            knobs,
            objective=lambda: float(engine.stats.batch_rows),
            on_tick=engine.prewarm_buckets,
            name="serve",
        )

    def _print_mesh_summary(self) -> None:
        """One line of SPMD layout truth at train start: mesh shape,
        ZeRO level, and the measured per-device train-state bytes vs
        the replicated footprint — the memory headroom the sharded
        weight update bought, stated where an operator reads logs
        (the same numbers live as ``train_state_shard_bytes{device}``
        in ``/metricsz``)."""
        tr = self.net_trainer
        if self.silent or tr is None or tr.mesh_plan is None:
            return
        plan = tr.mesh_plan
        if plan.n_devices <= 1:
            return
        try:
            per_device, total = tr.state_shard_bytes()
            worst = max(per_device.values()) if per_device else total
        except Exception:  # noqa: BLE001 - a log line must never abort
            return
        print(
            f"mesh: {plan.describe(zero=tr.zero)}"
            f" | train state {total / 1e6:.2f} MB replicated -> "
            f"{worst / 1e6:.2f} MB/device "
            f"({worst / total if total else 1:.2%} of a full copy)",
            flush=True,
        )

    # ------------------------------------------------------------------
    # elastic pod (doc/parallel.md "Elastic pod"): survive replica loss
    # and resize the mesh mid-run, inside ONE CLI invocation
    def _elastic_setup(self) -> None:
        """Arm the peer-liveness layer: rank 0 hosts the membership
        coordinator; every rank heartbeats it.  No-op unless
        ``elastic = 1`` on a real multi-process job."""
        opts = self.elastic_opts
        if not opts.elastic:
            return
        from .parallel import elastic as par_elastic
        from .parallel.distributed import distributed_spec, process_info

        spec = distributed_spec(self.cfg)
        if spec is None or process_info()[1] == 1:
            return  # single process: nothing to monitor
        coord, num, pid = spec
        addr = opts.resolve_coordinator(coord)
        self.elastic_member = par_elastic.ElasticMember(
            addr, pid, opts, host_coordinator=(pid == 0), num=num,
            jax_host=coord.rsplit(":", 1)[0],
        ).start()
        if not self.silent:
            print(f"elastic: liveness monitor armed (coordinator "
                  f"{self.elastic_member.addr}, heartbeat "
                  f"{opts.heartbeat_s:g}s, replica timeout "
                  f"{opts.timeout_s:g}s, collective deadline "
                  f"{opts.collective_timeout_s:g}s)", flush=True)

    def _elastic_join_setup(self) -> None:
        """``elastic_join = 1``: this process has NO mesh yet.  Announce
        to the coordinator, wait for a grow generation to assign a rank
        (``elastic_rejoin_s`` bounds the wait), join the re-init
        rendezvous, load the consensus checkpoint and shard the
        iterators — then fall straight into the round loop beside the
        survivors."""
        opts = self.elastic_opts
        from .parallel import elastic as par_elastic
        from .parallel.distributed import init_distributed

        if not opts.coordinator:
            raise ValueError(
                "elastic_join=1 needs elastic_coordinator=host:port "
                "(the running job's membership coordinator)")
        m = par_elastic.ElasticMember(opts.coordinator, -1, opts)
        print(f"elastic: waiting to join the mesh via {opts.coordinator}"
              f" (up to {opts.rejoin_s:g}s"
              + (f", at round {opts.join_at}" if opts.join_at else "")
              + ")", flush=True)
        plan = m.join()
        if plan.rank is None:
            raise RuntimeError("elastic join: admitted without a rank")
        print(f"elastic: admitted as rank {plan.rank}/{plan.num} "
              f"(generation {plan.generation})", flush=True)
        self._set_cfg_entries({
            "dist_coordinator": plan.jax_coordinator,
            "dist_num_proc": str(plan.num),
            "dist_proc_id": str(plan.rank),
        })
        # heartbeat BEFORE the blocking rendezvous: the coordinator
        # registered this member at plan time, and a slow survivor
        # teardown must not get the joiner evicted mid-admission
        m.rank = plan.rank
        m.generation = plan.generation
        m.start()
        init_distributed(plan.jax_coordinator, plan.num, plan.rank,
                         resilient=True)
        m.ack_generation(plan, rank=plan.rank)
        self.elastic_member = m
        self.continue_training = 1
        if not self._sync_latest_model():
            raise FileNotFoundError(
                "elastic join: no checkpoint in model_dir to sync from")
        self._create_iterators()
        self._elastic_joined = True

    def _elastic_guard(self, fn, what: str):
        """Collective deadline: a dead peer surfaces as
        ``ReplicaLossError`` within ``collective_timeout_s`` instead of
        hanging this rank inside a collective forever."""
        if self.elastic_member is None:
            return fn()
        from .parallel import elastic as par_elastic

        return par_elastic.guarded_call(
            fn, self.elastic_member,
            timeout_s=self.elastic_opts.collective_timeout_s, what=what)

    def _elastic_recover(self, exc: BaseException) -> bool:
        """Classify a round/checkpoint failure: replica loss → rebuild
        onto the survivors and return True (the caller re-enters the
        loop); anything else → False (the error propagates)."""
        if self.elastic_member is None:
            return False
        from .parallel import elastic as par_elastic

        loss = par_elastic.classify_failure(
            exc, self.elastic_member,
            confirm_s=self.elastic_opts.timeout_s + 2.0)
        if loss is None:
            return False
        min_gen = 0
        while True:
            if loss.fatal:
                print(f"elastic: unrecoverable replica loss: {loss}",
                      flush=True)
                return False
            # a persistent NON-replica error misclassified as loss
            # would otherwise rebuild forever (the caller refunds the
            # round budget) — bound consecutive recoveries; a
            # completed round resets the counter
            self._elastic_consec_recoveries += 1
            if self._elastic_consec_recoveries > 5:
                print("elastic: giving up after "
                      f"{self._elastic_consec_recoveries - 1} "
                      "consecutive rebuilds without completing a "
                      "round", flush=True)
                return False
            print(f"elastic: replica loss detected ({loss})", flush=True)
            try:
                self._elastic_rebuild("replica_lost",
                                      min_generation=min_gen)
                return True
            except par_elastic.ReplicaLossError as again:
                # a SECOND replica died during the rebuild's own
                # collectives: wait for the next generation and retry
                # (survivors above quorum must not give up)
                loss = again
            except Exception as again:  # noqa: BLE001 - classify
                loss = par_elastic.classify_failure(
                    again, self.elastic_member,
                    confirm_s=self.elastic_opts.timeout_s + 2.0)
                if loss is None:
                    raise
            min_gen = self._elastic_attempted_gen + 1

    def _elastic_boundary(self) -> bool:
        """Planned mesh transitions at the round boundary (the
        consensus checkpoint for this boundary is already durable).
        Returns True when the mesh changed (rebuilt, or this rank
        left)."""
        m = self.elastic_member
        opts = self.elastic_opts
        m.poll_now()  # synchronous beat: every rank reads the same state
        plan = m.pending_plan()
        if plan is not None and plan.at_round is None:
            # a replica died while this rank sat at the boundary (its
            # own collectives all completed) — rebuild without waiting
            # to trip over the corpse inside the next round
            print(f"elastic: replica loss at round boundary "
                  f"(generation {plan.generation})", flush=True)
            self._elastic_rebuild("replica_lost", plan=plan)
            return True
        r = self.start_counter
        if (opts.drop_at and r >= opts.drop_at
                and not self._elastic_drop_done):
            # >= + latch: a boundary whose RPC failed retries at the
            # next round instead of silently skipping the drop forever
            plan = m.plan_shrink(r)
            self._elastic_drop_done = True
            if plan.rank is None:
                self._elastic_left = True
                return True
            self._elastic_rebuild(plan.reason, plan=plan)
            return True
        g = m.grow_round()
        if g is not None and r >= g:
            plan = m.plan_grow(r)
            if plan is None:
                return False  # every waiter abandoned the join
            self._elastic_rebuild("grow", plan=plan)
            return True
        return False

    def _await_plan(self, min_generation: int = 0):
        """Block (briefly) until the coordinator's generation plan for a
        detected loss arrives over the heartbeat channel.
        ``min_generation`` skips a stale plan from a rebuild attempt
        that itself died (a second loss mid-rebuild)."""
        import time as _time

        m = self.elastic_member
        deadline = _time.monotonic() + self.elastic_opts.timeout_s * 2 + 5
        while _time.monotonic() < deadline:
            p = m.pending_plan()
            if p is not None and p.generation >= min_generation:
                return p
            try:
                m.poll_now()
            except (OSError, ValueError, RuntimeError):
                pass
            _time.sleep(0.1)
        return None

    def _set_cfg_entries(self, updates: dict) -> None:
        """Rewrite config entries in place (the rebuilt generation's
        dist_* identity) so anything re-reading the cfg stream agrees
        with the live mesh."""
        out, seen = [], set()
        for n, v in self.cfg:
            if n in updates:
                if n in seen:
                    continue  # collapse duplicates to one entry
                out.append((n, updates[n]))
                seen.add(n)
            else:
                out.append((n, v))
        for n, v in updates.items():
            if n not in seen:
                out.append((n, v))
        self.cfg = out

    def _elastic_rebuild(self, reason: str, plan=None,
                         min_generation: int = 0) -> None:
        """Checkpoint-consensus rebuild onto the new process set, inside
        this CLI invocation: tear down the distributed backend, re-init
        on the plan's fresh coordinator, re-load the agreed round via
        the PR-1 consensus machinery (state re-places onto the CURRENT
        mesh through the eager ``_place_state`` + PR-9 cross-mesh
        reshard), and re-shard the iterators; ``start_counter`` rewinds
        to the consensus round + 1, and the deterministic augmentation
        stream (``RecordRNG`` + ``dist_shard = block``) makes the
        resumed stream exact."""
        import gc

        from .obs import emit as obs_emit
        from .parallel import elastic as par_elastic
        from .parallel.distributed import (
            init_distributed, shutdown_distributed,
        )
        from .utils import checkpoint as ckpt

        m = self.elastic_member
        t0 = time.time()
        par_elastic.set_rebuilding(True)
        try:
            plan = plan or self._await_plan(min_generation)
            if plan is None:
                raise par_elastic.ReplicaLossError(
                    "elastic: replica loss with no generation plan "
                    "(membership coordinator unreachable?)", fatal=True)
            if plan.abort:
                raise par_elastic.ReplicaLossError(
                    f"elastic: cannot continue: {plan.abort}", fatal=True)
            self._elastic_attempted_gen = plan.generation
            obs_emit("mesh.rebuild_start", reason=reason,
                     generation=plan.generation, num=plan.num,
                     rank=plan.rank, round=self.start_counter)
            print(f"elastic: {reason} -> rebuilding the mesh as "
                  f"{plan.num} process(es), this rank becomes "
                  f"{plan.rank} (generation {plan.generation})",
                  flush=True)
            # a guarded worker may still be wedged in a dead collective:
            # give it a grace to error out before the backend dies under it
            t = par_elastic.guarded_call.last_thread
            if t is not None and t.is_alive():
                t.join(timeout=min(
                    self.elastic_opts.collective_timeout_s, 10.0))
                if t.is_alive():
                    obs_emit("mesh.guard_thread_abandoned", what=reason)
            # drop every reference into the old backend before it dies
            for it in [self.itr_train, *self.itr_evals]:
                if it is not None:
                    try:
                        it.close()
                    except Exception:  # noqa: BLE001 - teardown
                        pass
            self.itr_train = None
            self.itr_evals = []
            self.eval_names = []
            if self.net_trainer is not None:
                # async data-parallel: in-flight aggregates were reduced
                # by the DEAD generation's collectives — generation-stamp
                # them out so nothing stale can ever be applied (the
                # rebuilt trainer reloads a drained checkpoint anyway;
                # this guards the window until it does, and the event
                # makes the discard auditable)
                try:
                    self.net_trainer.async_abandon(
                        generation=plan.generation, reason="rebuild")
                except Exception:  # noqa: BLE001 - teardown best-effort
                    pass
            self.net_trainer = None
            gc.collect()
            # zero-RPC teardown: a shutdown barrier can never complete
            # with a dead peer, and its failure broadcast would kill
            # the surviving clients — abandon, don't negotiate
            shutdown_distributed(graceful=False)
            self._set_cfg_entries({
                "dist_coordinator": plan.jax_coordinator,
                "dist_num_proc": str(plan.num),
                "dist_proc_id": str(plan.rank),
            })
            # the rebuild rendezvous: initialize blocks until every
            # member of the new generation connects
            init_distributed(plan.jax_coordinator, plan.num, plan.rank,
                             resilient=True)
            m.ack_generation(plan, rank=plan.rank)
            # round consensus + reload on the NEW process set; the
            # iterator re-shards for the new rank/process count
            if not self._sync_latest_model():
                raise ckpt.CheckpointError(
                    "elastic: no valid checkpoint any survivor can "
                    "load — cannot rebuild")
            self._create_iterators()
            dt = time.time() - t0
            self._elastic_rebuilds += 1
            self._elastic_last_rebuild_s = dt
            try:
                from .obs.registry import registry as obs_registry

                obs_registry().counter(
                    "mesh_rebuilds_total",
                    "Elastic mesh rebuilds by trigger.",
                    labelnames=("reason",),
                ).labels(reason=reason).inc()
                obs_registry().gauge(
                    "mesh_rebuild_seconds",
                    "Wall time of the last elastic mesh rebuild.",
                ).set(dt)
            except Exception:  # noqa: BLE001 - telemetry never aborts
                pass
            obs_emit("mesh.rebuild_done", reason=reason,
                     generation=plan.generation, num=plan.num,
                     rank=plan.rank, wall_s=round(dt, 3),
                     resume_round=self.start_counter)
            self._print_mesh_summary()
            print(f"elastic: rebuilt in {dt:.2f}s; resuming at round "
                  f"{self.start_counter} on {plan.num} process(es)",
                  flush=True)
        finally:
            par_elastic.set_rebuilding(False)

    def _elastic_quiet_teardown(self) -> None:
        """End-of-task teardown for elastic runs: drop the resilient
        coordination client BEFORE interpreter exit destructs the
        in-process coordination service — a client that outlives its
        service sees the socket close as a fatal error and aborts the
        process.  Zero-RPC (graceful=False); rank 0 lingers briefly so
        every peer's client is gone before its exit closes the live
        service's socket."""
        from .parallel.distributed import (
            distributed_initialized, shutdown_distributed,
        )

        m, self.elastic_member = self.elastic_member, None
        if m is None:
            return
        try:
            m.close()
        except Exception:  # noqa: BLE001 - teardown best-effort
            pass
        if distributed_initialized():
            shutdown_distributed(graceful=False)
        if m.coordinator is not None:  # this process is rank 0
            time.sleep(2.0)

    def task_train(self) -> None:
        from .integrity.plane import IntegrityError
        from .parallel.distributed import any_process_flag, process_info
        from .utils.checkpoint import DivergenceError, PreemptionHandler

        self._train_start = time.time()
        self._train_t0 = time.perf_counter()  # to the first fence: set-up
        if self.elastic_opts is None:  # task_train without run()
            from .parallel.elastic import ElasticOptions

            self.elastic_opts = ElasticOptions.from_cfg(self.cfg)
        if self.elastic_opts.join:
            self._elastic_join_setup()
        else:
            self._elastic_setup()
        if self._elastic_joined:
            # a rejoined process enters the round loop directly: the
            # survivors are already mid-loop, so any extra collective
            # here (initial eval / checkpoint) would deadlock the mesh
            pass
        elif self.continue_training == 0 and self.name_model_in == "NULL":
            self._elastic_guard(self._save_model, what="initial checkpoint")
        else:
            for it, nm in zip(self.itr_evals, self.eval_names):
                sys.stderr.write(self.net_trainer.evaluate(it, nm))
            sys.stderr.write("\n")
            sys.stderr.flush()
        if self.itr_train is None:
            return
        if self.test_io:
            print("start I/O test")
        from .obs import emit as obs_emit
        from .obs import trace as obs_trace
        from .utils.profiler import StepTimer, TraceController

        timer = StepTimer()
        tracer = TraceController()
        tracer.configure(self.cfg)
        self._print_mesh_summary()
        obs_emit("train.start", task=self.task, round=self.start_counter,
                 num_round=self.num_round)
        from .train_loop import RoundLoop

        self._loop = RoundLoop(self.scan_steps, test_io=bool(self.test_io))
        self._divergence_retries = 0
        self._lr_scale = 1.0
        # integrity plane (doc/robustness.md "Integrity plane"): one
        # driver per task, surviving trainer rebuilds; the state loaded
        # or initialized before the loop is taken as the clean baseline
        if self.integrity_every > 0 and self._integrity is None:
            from .integrity import IntegrityPlane

            self._integrity = IntegrityPlane(
                self.integrity_every, self.integrity_shadow)
            self._integrity.last_clean_round = self.start_counter - 1
        # SIGTERM/SIGINT → finish the current step, snapshot, exit clean.
        # Single-process runs stop at the next BATCH boundary; multi-
        # process runs stop at the next ROUND boundary (the per-batch
        # check would need a per-batch collective to keep the SPMD
        # programs aligned) — the flag is agreed across processes so one
        # preempted worker stops the whole job consistently.
        self._preempt = PreemptionHandler().install()
        preempted = False
        tuner = self._start_train_controller()
        try:
            cc = self.max_round
            while self.start_counter <= self.num_round and cc > 0:
                cc -= 1
                if self.elastic_member is not None:
                    self.elastic_member.report_round(self.start_counter)
                try:
                    completed = self._elastic_guard(
                        lambda: self._train_one_round(timer, tracer),
                        what=f"train round {self.start_counter}",
                    )
                except DivergenceError as e:
                    if self._handle_divergence(e):
                        cc += 1  # the aborted attempt keeps its budget
                        continue
                    tracer.close()
                    raise
                except Exception as e:  # noqa: BLE001 - replica loss?
                    if self._elastic_recover(e):
                        cc += 1  # the aborted round is re-run
                        continue
                    raise
                self._divergence_retries = 0
                self._elastic_consec_recoveries = 0  # a round completed
                if not completed:  # preempted mid-round (single-process)
                    snapshotted = self._save_model(force=True)
                    preempted = True
                    break
                # integrity plane: fingerprint vote (+ shadow audit) at
                # the round boundary, BEFORE the consensus checkpoint —
                # state that failed the vote is never made durable, so
                # with integrity_every=1 no poisoned round is ever
                # resumable
                if (self._integrity is not None
                        and self._integrity.due(self.start_counter - 1)):
                    try:
                        self._elastic_guard(
                            lambda: self._integrity.check_round(
                                self.net_trainer,
                                self.start_counter - 1),
                            what="integrity check")
                    except IntegrityError as e:
                        if self._handle_integrity(e):
                            cc += 1  # the re-run rounds keep the budget
                            continue
                        tracer.close()
                        raise
                    except Exception as e:  # noqa: BLE001 - replica loss?
                        if self._elastic_recover(e):
                            continue  # the round completed; only re-sync
                        raise
                # boundary preemption check (collective in multi-process
                # runs): force the snapshot past the save_model period
                # gate so the preempted state is never lost
                try:
                    stop = (
                        self._preempt.requested
                        if process_info()[1] == 1
                        else self._elastic_guard(
                            lambda: any_process_flag(
                                self._preempt.requested),
                            what="preemption sync"))
                    snapshotted = self._elastic_guard(
                        lambda: self._save_model(force=stop),
                        what="checkpoint save")
                except Exception as e:  # noqa: BLE001 - replica loss?
                    if self._elastic_recover(e):
                        continue  # the round completed; only re-sync
                    raise
                if stop:
                    preempted = True
                    break
                # planned mesh transitions land at the round boundary,
                # AFTER the consensus checkpoint is durable.  A
                # transient coordinator RPC failure must not kill a
                # survivor — skip this boundary and retry at the next
                # (the drop/grow latches re-fire until handled)
                if (self.elastic_member is not None
                        and self.start_counter <= self.num_round):
                    try:
                        changed = self._elastic_boundary()
                    except (OSError, ValueError, RuntimeError) as e:
                        obs_emit("mesh.boundary_rpc_failed",
                                 round=self.start_counter, error=str(e))
                        changed = False
                    if changed:
                        if self._elastic_left:
                            break  # this rank left (planned shrink)
                        continue  # rebuilt onto the new mesh
        finally:
            self._loop.close()  # the last boundary: no round bills it
            if tuner is not None:
                tuner.stop()
            self._preempt.uninstall()
            if self.elastic_member is not None:
                self._elastic_quiet_teardown()
        tracer.close()
        obs_trace.tracer().flush_window(self._loop.global_step)
        if self._elastic_left:
            obs_emit("mesh.left", round=self.start_counter)
            print(
                f"elastic: this rank left the mesh at round "
                f"{self.start_counter} (planned shrink); exiting clean",
                flush=True,
            )
            return
        if preempted:
            last = self.start_counter - 1
            obs_emit("train.preempted", round=last,
                     snapshotted=snapshotted)
            if snapshotted:
                print(
                    f"preemption: state saved through round {last} "
                    f"({last:04d}.model); resume with continue=1",
                    flush=True,
                )
            else:
                print("preemption: exiting (checkpointing disabled, "
                      "save_model=0)", flush=True)
            return
        obs_emit("train.end", rounds=self.start_counter - 1,
                 elapsed_s=time.time() - self._train_start)
        if not self.silent:
            print(f"\nupdating end, "
                  f"{int(time.time() - self._train_start)} sec in all")

    def _handle_divergence(self, e) -> bool:
        """Respond to a non-finite loss per ``divergence_policy``.

        ``rollback``: reload the newest valid checkpoint, optionally back
        off the learning rate (``divergence_lr_backoff``), and retry —
        up to ``divergence_max_retries`` consecutive failures.  Returns
        True when training should continue; False aborts (the default
        ``abort`` policy: stop rather than train on corrupt weights)."""
        from .obs import emit as obs_emit

        obs_emit("divergence.trip", error=str(e),
                 policy=self.divergence_policy or "abort",
                 retries=self._divergence_retries)
        print(f"DIVERGENCE: {e}", flush=True)
        if self.divergence_policy != "rollback":
            return False
        if self._divergence_retries >= self.divergence_max_retries:
            print(
                f"divergence: giving up after "
                f"{self._divergence_retries} consecutive rollbacks",
                flush=True,
            )
            return False
        # the injected fault (fault-injection harness) is one-shot: drop
        # it from the cfg so the rebuilt trainer doesn't re-arm it
        self.cfg = [(n, v) for n, v in self.cfg
                    if n not in ("inject_nan_step", "inject_spike_step")]
        bound = None  # exclusive upper round bound while falling back
        while True:
            round_, path, reason = self._locate_agreed_checkpoint(
                before=bound)
            if round_ < 0:
                print("divergence: no valid checkpoint to roll back to",
                      flush=True)
                return False
            if reason is not None:
                print(f"divergence: agreed rollback target round {round_} "
                      f"is unusable: {reason}", flush=True)
                return False
            tr = self._load_trainer(path)
            if tr.weights_finite():  # collective — identical verdict
                break
            # CRC-valid but numerically poisoned: the blow-up happened in
            # the LAST update of the round this checkpoint captured (its
            # losses were measured pre-update, all finite) — exclude it
            # and fall back further
            print(f"divergence: checkpoint {path} carries non-finite "
                  "weights; falling back past it", flush=True)
            bound = round_
        self._divergence_retries += 1
        if self.divergence_lr_backoff != 1.0:
            self._lr_scale *= self.divergence_lr_backoff
            tr.scale_learning_rate(self._lr_scale)
        if self.net_trainer is not None:
            # async data-parallel: the discarded trainer may hold
            # pending staleness aggregates — count + event-log the
            # discard (same auditability as the elastic-rebuild path)
            # so the staleness gauges don't misreport dead work
            try:
                self.net_trainer.async_abandon(reason="rollback")
            except Exception:  # noqa: BLE001 - teardown best-effort
                pass
        self.net_trainer = tr
        self.start_counter = round_ + 1
        obs_emit("divergence.rollback", round=round_, path=path,
                 lr_scale=self._lr_scale,
                 retry=self._divergence_retries)
        print(
            f"divergence: rolled back to round {round_} ({path}), "
            f"lr scale now {self._lr_scale:g} "
            f"(retry {self._divergence_retries}/"
            f"{self.divergence_max_retries})",
            flush=True,
        )
        return True

    def _handle_integrity(self, e) -> bool:
        """Quarantine response to an integrity verdict
        (doc/robustness.md "Integrity plane").  The vote ran on the
        full allgathered digest matrix, so every rank holds the
        IDENTICAL verdict without another collective: the corrupt rank
        self-quarantines (``integrity.quarantine`` event, hard exit 41
        — it must never contribute another gradient), the survivors
        evict it through the elastic coordinator (idempotent per
        (rank, round) verdict) and rebuild onto the last
        fingerprint-VERIFIED round — state the corrupt rank's
        gradients touched after the flip is discarded with it.
        Returns True when this surviving rank rebuilt and the round
        loop should continue; False aborts the run (no elastic mesh
        to quarantine within, or no rank was named)."""
        from .obs import emit as obs_emit
        from .parallel.distributed import process_info

        rank, num = process_info()
        round_ = self.start_counter - 1
        print(f"INTEGRITY: {e}", flush=True)
        if e.rank is None or num == 1 or self.elastic_member is None:
            # ambiguous vote (2-way tie / 2-replica group), a
            # single-process run, or no elastic membership: there is
            # no healthy majority to rebuild onto — stopping beats
            # training on silently corrupt state
            return False
        last_clean = self._integrity.last_clean_round
        obs_emit("integrity.quarantine", kind=e.kind, rank=e.rank,
                 tensor=e.tensor, round=round_,
                 last_clean_round=last_clean, self_evict=e.rank == rank)
        if e.rank == rank:
            # self-quarantine: leave the coordination plane quietly and
            # hard-exit with the distinct quarantine code (41) — the
            # supervisor must not relaunch onto the same device, and a
            # plain exit would let resilient-client destructors abort
            # with a misleading status (_hard_exit_if_resilient)
            print(f"integrity: this rank ({rank}) was named corrupt — "
                  "self-quarantining (exit 41)", flush=True)
            self._elastic_quiet_teardown()
            sys.stdout.flush()
            sys.stderr.flush()
            os._exit(41)
        # survivor: rebuild rolls back PAST every unverified round —
        # _sync_latest_model consumes the bound (exclusive)
        self._integrity_rollback_before = (
            None if last_clean is None else last_clean + 1)
        try:
            plan = self.elastic_member.plan_evict(e.rank, round_)
        except (OSError, ValueError, RuntimeError) as err:
            print(f"integrity: evict RPC failed: {err}", flush=True)
            return False
        if plan.rank is None:
            print("integrity: eviction plan dropped this rank too — "
                  "aborting", flush=True)
            return False
        self._elastic_rebuild("integrity_evict", plan=plan)
        return True

    def _train_one_round(self, timer, tracer) -> bool:
        """Run one training round; returns False when a preemption
        request stopped the round early (single-process only — see
        task_train), True when the round ran to completion.  The
        round's entry and exit; what lies between the rewind and the
        last fence is ``train_loop.RoundLoop``, which owns the spans
        ``train.round`` (to the last fence) and ``train.boundary``
        (from there to the next round's ``begin``): a round that
        raises closes them unbilled."""
        try:
            return self._round(timer, tracer)
        except BaseException:
            self._loop.close()
            raise

    def _round(self, timer, tracer) -> bool:
        if not self.silent:
            print(f"update round {self.start_counter - 1}", flush=True)
        from .obs import trace as obs_trace
        from .parallel.distributed import process_info
        from .utils.profiler import pipeline_stats, stage

        trainer = self.net_trainer
        loop = self._loop
        trainer.start_round(self.start_counter)
        pipeline_stats().reset()  # per-round stage breakdown
        # the boundary behind the last round ends here, billed to this
        # one; the round's span, its first chunk's period and its head
        # start
        loop.begin(trainer, self.start_counter)
        with stage("next", step=trainer.epoch_counter):
            self.itr_train.before_first()
            # anchor the augmentation epoch to the ROUND counter (after
            # the rewind, overriding the process-local epoch count): a
            # resumed run's round r then draws the identical stream an
            # uninterrupted run drew at round r (io/augment.py
            # `augment_epoch`)
            self.itr_train.set_param("augment_epoch",
                                     str(self.start_counter))
        timer.clear()
        check_preempt = process_info()[1] == 1

        def on_batch(n_batches: int) -> bool:
            if (self.print_step > 0 and n_batches % self.print_step == 0
                    and not self.silent):
                elapsed = int(time.time() - self._train_start)
                print(f"round {self.start_counter - 1:8d}:"
                      f"[{n_batches:8d}] {elapsed} sec elapsed", flush=True)
            return check_preempt and self._preempt.requested

        sample_counter, preempted = loop.run(
            self.itr_train, timer, (tracer, obs_trace), on_batch)
        if loop.first_fence_at is not None:
            self._setup.setdefault(
                "first_fence_s", loop.first_fence_at - self._train_t0)
        if self.test_io == 0 and trainer.fence_at_round_end:
            # async data-parallel (doc/parallel.md "Async
            # data-parallel"): the round-boundary fence (and, on resync
            # rounds, the hard barrier draining the staleness buffers);
            # billed as one device_wait lap so the round timing stays
            # honest
            wait = stage("device_wait",
                         rows=sample_counter * trainer.batch_size,
                         step=trainer.epoch_counter).begin()
            trainer.async_round_end(self.start_counter)
            timer.add(wait.end(), 0)
        if preempted:
            return False
        if self.test_io == 0:
            # what the layers counted inside the step programs, into the
            # round's counters: after the last fence, in no chunk's period
            trainer.count_layer_state()
        chunks = loop.chunks
        stage_line = pipeline_stats().report()
        if not self.silent and stage_line:
            # per-stage host-pipeline breakdown (decode/augment/batch/
            # h2d/device_wait) — prints in test_io dry-runs too, where
            # it IS the measurement
            if chunks.allocated + chunks.recycled:
                stage_line += (f" | chunk blocks {chunks.allocated} new "
                               f"{chunks.recycled} recycled")
            print(
                f"round {self.start_counter - 1:8d} pipeline: "
                + stage_line,
                flush=True,
            )
        if self.test_io == 0:
            if not self.silent and timer.count:
                print(
                    f"round {self.start_counter - 1:8d}: "
                    + timer.report(trainer.batch_size),
                    flush=True,
                )
            sys.stderr.write(f"[{self.start_counter}]")
            eval_text = ""
            if not self.itr_evals:
                eval_text += trainer.evaluate(None, "train")
            for it, nm in zip(self.itr_evals, self.eval_names):
                eval_text += trainer.evaluate(it, nm)
            sys.stderr.write(eval_text)
            sys.stderr.write("\n")
            sys.stderr.flush()
            self._write_telemetry(timer, eval_text, sample_counter)
            if self.test_on_server:
                dev = trainer.check_weight_sync()
                sys.stderr.write(
                    f"[{self.start_counter}]\tweight-sync:"
                    f"max_dev={dev:g} ok\n"
                )
                sys.stderr.flush()
        return True

    def _write_telemetry(self, timer, eval_text: str,
                         n_batches: int) -> None:
        """Append one per-round JSONL record to ``telemetry_path``
        (``telemetry = 1``; doc/observability.md).  The record carries
        what the human-facing round lines print — eval metrics, step
        timing, samples/sec, learning rate, per-stage pipeline timers —
        as one machine-parseable object.  Never raises: a full disk
        must not abort training (failures are event-logged once)."""
        if not self.telemetry:
            return
        import json
        import re

        from .obs import device as obs_device
        from .obs import events as obs_events
        from .obs import log_exception_once
        from .utils import diskio
        from .utils.profiler import pipeline_stats

        metrics = {
            m.group(1): float(m.group(2))
            for m in re.finditer(
                r"(\S+?):([-+]?[0-9]*\.?[0-9]+(?:[eE][-+]?[0-9]+)?)",
                eval_text or "",
            )
        }
        lr = None
        try:
            up = next(iter(self.net_trainer.updaters.values()))
            lr = float(up.param.base_lr)
        except (StopIteration, AttributeError):
            pass
        record = {
            "ts": time.time(),
            "round": self.start_counter - 1,
            "steps": timer.count,
            "batches": n_batches,
            "elapsed_s": time.time() - self._train_start,
            "lr": lr,
            "eval": metrics,
            "step": timer.summary(self.net_trainer.batch_size),
            "stages": pipeline_stats().snapshot(),
            # what the round's iterators counted that is no stage's
            # time (io/tokens.py: tokens, docs, docs_cut)
            "counters": pipeline_stats().counters(),
            # host blocks the scanned path's chunk assembler mapped anew
            # and took back from its free list this round (io/chunk.py)
            "chunks": {"allocated": self._loop.chunks.allocated,
                       "recycled": self._loop.chunks.recycled},
            # device plane (doc/observability.md): programs compiled so
            # far, cumulative XLA compile seconds, sampled step fences —
            # lifetime totals: deltas are computable between records
            "device": obs_device.summary(),
            # set-up by phase, lifetime too: conf parse and arming, the
            # iterators' init, trainer + model init or load, and
            # task_train's entry to the first fence
            "setup": {k: round(v, 6) for k, v in self._setup.items()},
        }
        async_snap = self.net_trainer.async_snapshot()
        if async_snap is not None:
            # async data-parallel pipeline block: pending aggregate
            # depths, push/apply/drop totals, last overlap fraction
            record["async"] = async_snap
        if self.elastic_member is not None or self._elastic_rebuilds:
            from .parallel.distributed import process_info as _pinfo

            record["elastic"] = {
                "rebuilds": self._elastic_rebuilds,
                "last_rebuild_s": round(self._elastic_last_rebuild_s, 3),
                "processes": _pinfo()[1],
                "generation": (self.elastic_member.generation
                               if self.elastic_member is not None
                               else None),
            }
        if self._integrity is not None:
            # integrity plane: check cadence/count and the newest
            # fingerprint-verified round (the quarantine rollback bound)
            record["integrity"] = self._integrity.snapshot()
        try:
            line = json.dumps(record, separators=(",", ":")) + "\n"
            diskio.append_bytes(self.telemetry_path,
                                line.encode("utf-8"), site="obs.append")
        except (OSError, ValueError, TypeError) as e:
            # degrade-don't-crash: a round record is droppable; training
            # and serving keep going, the drop is counted and the first
            # failure logged (disk-full additionally bumps
            # disk_full_total inside diskio → the paging alert)
            import errno as _errno
            reason = ("disk" if getattr(e, "errno", None) == _errno.ENOSPC
                      else "io")
            obs_events.record_drop("telemetry", reason)
            log_exception_once("cli.telemetry", e, kind="telemetry.error",
                               path=self.telemetry_path)

    def task_predict(self, raw: bool = False) -> None:
        """``task=pred``: one argmax/value per line.  ``task=pred_raw``:
        the full output row (softmax probabilities) space-separated —
        the submission-file input (reference ``CXXNetPredRaw``,
        ``wrapper/cxxnet_wrapper.h:150``; kaggle_bowl make_submission
        expects a trailing separator, kept for format parity)."""
        if self.itr_pred is None:
            raise ValueError("must specify a pred iterator to generate predictions")
        print("start predicting...")
        t0 = time.perf_counter()
        nrow = 0
        with open(self.name_pred, "w", encoding="utf-8") as fo:
            self.itr_pred.before_first()
            while self.itr_pred.next():
                # stream per batch: each batch's rows are formatted and
                # flushed as soon as they land, so memory stays O(batch)
                # no matter how large the prediction set is
                batch = self.itr_pred.value()
                n = batch.batch_size - batch.num_batch_padd
                if raw:
                    rows = self.net_trainer.extract_feature(batch, "top[-1]")
                    rows = rows.reshape(rows.shape[0], -1)
                    for r in rows[:n]:
                        fo.write(" ".join(f"{v:g}" for v in r) + " \n")
                else:
                    preds = self.net_trainer.predict(batch)
                    for v in preds[:n]:
                        if np.ndim(v):  # sequence models: (T,) ids/row
                            fo.write(
                                " ".join(f"{t:g}" for t in v) + "\n"
                            )
                        else:
                            fo.write(f"{v:g}\n")
                fo.flush()
                nrow += n
        dt = time.perf_counter() - t0
        rate = nrow / dt if dt > 0 else 0.0
        print(f"finished prediction, write into {self.name_pred} "
              f"({nrow} rows, {rate:.1f} rows/sec)")

    def task_serve_fleet(self) -> None:
        """``task=serve`` with ``replicas >= 2``: the serving fleet
        (doc/serving.md "Serving fleet").

        Launches ``replicas`` single-engine ``task=serve`` child
        processes (each re-reading this conf with the fleet keys
        pinned), supervises them (healthz probing, SLOW/GONE
        classification, restart-with-backoff, eject-from-rotation of
        wedged replicas), and runs the routing front-end on
        ``serve_host:serve_port`` — priority-classed admission control
        (batch sheds first), least-loaded dispatch with failover, and
        deadline budgets split between route and execute.  With
        ``serve_reload_period > 0`` new rounds in ``model_dir`` roll
        out one replica at a time behind a fleet-level circuit
        breaker; with ``canary = int8`` the fleet runs a rolling int8
        canary that promotes or rolls back through the publish
        pointer, with ``/alertz`` as the rollback trigger."""
        import signal as _signal
        import threading

        from .serve.fleet import FleetOptions, ServingFleet, cli_spawn_fn

        opts = FleetOptions.from_cfg(self.cfg)
        model_dir = (self.name_model_dir
                     if self.name_model_in == "NULL" else None)
        log_dir = opts.log_dir or (
            os.path.join(model_dir, "fleet_logs") if model_dir
            else "fleet_logs")
        spawn = cli_spawn_fn(self.conf_path, self.cli_overrides,
                             host=self.serve_host, opts=opts,
                             log_dir=log_dir)
        fleet = ServingFleet(
            opts, spawn_fn=spawn, host=self.serve_host,
            port=self.serve_port, model_dir=model_dir,
            default_deadline_ms=self.serve_deadline_ms,
            reload_period_s=self.serve_reload_period,
            silent=bool(self.silent),
        )
        httpd_box = {}

        def _stop(signum, frame):
            print(f"fleet: shutdown requested, draining (up to "
                  f"{self.drain_timeout_s:g}s)", flush=True)
            h = httpd_box.get("httpd")
            if h is not None:
                threading.Thread(target=h.shutdown, daemon=True).start()
            else:
                # still booting replicas: abort startup — the raise
                # lands in the main thread inside fleet.start(), the
                # finally below reaps the spawned children
                raise SystemExit(0)

        prev = {s: _signal.signal(s, _stop)
                for s in (_signal.SIGTERM, _signal.SIGINT)}
        try:
            httpd = fleet.start()
            httpd_box["httpd"] = httpd
            h = fleet.healthz()
            print(f"fleet: serving {h['rotation']}/{opts.replicas} "
                  f"replica(s) (round {h['round']}) on "
                  f"http://{self.serve_host}:{httpd.server_port}",
                  flush=True)
            httpd.serve_forever(poll_interval=0.2)
        finally:
            for s, p in prev.items():
                _signal.signal(s, p)
            fleet.close(self.drain_timeout_s)
        print("fleet: shutdown complete", flush=True)

    def task_serve(self) -> None:
        """``task=serve``: run the online inference server (doc/serving.md).

        Loads ``model_in`` (or the newest valid checkpoint in
        ``model_dir``) into a :class:`~cxxnet_tpu.serve.Engine` and
        serves ``/predict`` / ``/extract`` / ``/healthz`` / ``/statsz``
        on ``serve_host:serve_port`` (``serve_port = 0`` picks an
        ephemeral port, printed on startup).  SIGTERM/SIGINT drain
        gracefully: the server stops accepting, in-flight requests get
        up to ``drain_timeout_s`` to finish, queued ones are failed
        with 503, then the process exits 0.

        ``replicas >= 2`` routes to :meth:`task_serve_fleet` instead —
        N supervised engine subprocesses behind one front door."""
        import signal as _signal
        import threading

        from .serve import Engine
        from .serve.server import serve_forever

        if self.replicas > 1:
            return self.task_serve_fleet()

        model_in = (None if self.name_model_in == "NULL"
                    else self.name_model_in)
        engine = Engine(
            cfg=self.cfg,
            model_in=model_in,
            model_dir=None if model_in else self.name_model_dir,
            max_batch_size=self.serve_max_batch,
            batch_timeout_ms=self.batch_timeout_ms,
            queue_limit=self.queue_limit,
            default_deadline_ms=self.serve_deadline_ms,
            silent=bool(self.silent),
            reload_breaker_threshold=self.reload_breaker_threshold,
            reload_breaker_cooldown_s=self.reload_breaker_cooldown_s,
            watchdog_timeout_s=self.watchdog_timeout_s,
        )
        httpd_box = {}

        def _ready(httpd):
            httpd_box["httpd"] = httpd
            h = engine.healthz()
            print(f"serving model round {h['round']} "
                  f"(fp {h['net_fp']}) on "
                  f"http://{httpd.server_address[0]}:{httpd.server_port}",
                  flush=True)

        def _stop(signum, frame):
            print(f"serve: shutdown requested, draining in-flight "
                  f"requests (up to {self.drain_timeout_s:g}s)", flush=True)
            h = httpd_box.get("httpd")
            if h is not None:
                # shutdown() blocks until serve_forever returns — must
                # not run on the thread stuck inside serve_forever
                threading.Thread(target=h.shutdown, daemon=True).start()

        prev = {s: _signal.signal(s, _stop)
                for s in (_signal.SIGTERM, _signal.SIGINT)}
        tuner = self._start_serve_controller(engine)
        try:
            serve_forever(
                engine,
                host=self.serve_host,
                port=self.serve_port,
                reload_period_s=self.serve_reload_period,
                drain_timeout_s=self.drain_timeout_s,
                verbose=not self.silent,
                ready_fn=_ready,
            )
        finally:
            for s, p in prev.items():
                _signal.signal(s, p)
            if tuner is not None:
                tuner.stop()
            engine.close()
        print("serve: shutdown complete", flush=True)

    def task_data_service(self) -> None:
        """``task=data_service``: run the shared decode/augment server
        (doc/io.md "Data service").

        Hosts the conf's ``data`` section iterator chain behind the
        ``CXD1`` batch protocol on ``data_service_host:
        data_service_port`` (0 picks an ephemeral port; the bound
        address lands in ``data_service_ready_file`` for discovery) and
        a ``/healthz``/``/statsz``/``/metricsz`` HTTP sidecar on
        ``data_service_http_port``.  SIGTERM/SIGINT stop both planes
        and close the chain."""
        import signal as _signal
        import threading

        from .io.dataservice.server import DataServiceServer

        split = cfgmod.split_sections(self.cfg)
        data_secs = split.find("data")
        if not data_secs:
            raise ValueError(
                "task=data_service needs a 'data = train ... iter = "
                "end' section (the chain this server deals)")
        if len(data_secs) > 1:
            raise ValueError("task=data_service serves exactly one "
                             "data section")
        server = DataServiceServer(
            data_secs[0].entries,
            split.global_entries,
            host=self.data_service_host,
            port=self.data_service_port,
            http_port=self.data_service_http_port,
            max_sessions=self.data_service_max_sessions,
            cache_bytes=int(self.data_service_cache_mb * (1 << 20)),
            window=self.data_service_window,
            ready_file=self.data_service_ready_file,
            silent=bool(self.silent),
        )

        def _stop(signum, frame):
            print("data_service: shutdown requested", flush=True)
            # shutdown() joins serve_forever loops — never run it on
            # the thread blocked inside serve_forever
            threading.Thread(target=server.shutdown, daemon=True).start()

        prev = {s: _signal.signal(s, _stop)
                for s in (_signal.SIGTERM, _signal.SIGINT)}
        try:
            server.serve_forever()
        finally:
            for s, p in prev.items():
                _signal.signal(s, p)
            server.close()
        print("data_service: shutdown complete", flush=True)

    def task_serve_train(self) -> None:
        """``task=serve_train``: the closed loop — serve, collect
        feedback, fine-tune, publish behind the eval gate
        (doc/continuous_training.md).

        The serving engine and HTTP front-end run exactly as
        ``task=serve`` (plus a ``POST /feedback`` route and, with
        ``capture_predict = 1``, prediction capture); a daemon thread
        runs the :class:`~cxxnet_tpu.loop.ContinuousLoop` — tail the
        feedback log, fine-tune ``loop_rounds_per_cycle`` rounds mixed
        with ``loop_replay_ratio`` base-iterator rows, and hand the
        candidate to the eval-gated publisher.  Published checkpoints
        land in ``model_dir`` and hot-reload immediately.
        ``loop_max_cycles > 0`` stops fine-tuning after that many
        trained cycles (serving continues).  Shutdown is the same
        graceful drain as ``task=serve``."""
        import signal as _signal
        import threading

        from .loop import ContinuousLoop, FeedbackWriter
        from .serve import Engine
        from .serve.server import serve_forever

        if self.replicas > 1:
            raise ValueError(
                "task=serve_train is single-replica (the fine-tune loop "
                "rides beside one engine); run the fleet with task=serve "
                "and a separate serve_train process if both are needed")
        if not self.itr_evals:
            raise ValueError(
                "task=serve_train needs an eval section — the publish "
                "gate scores candidates on held-out data")
        if any(n == "quant" and v not in ("", "0", "off", "none")
               for n, v in self.cfg):
            raise ValueError(
                "task=serve_train cannot serve a quantized model: the "
                "fine-tune loop trains on the served weights, and "
                "quantized trainers are inference-only — serve the f32 "
                "checkpoints and run task=export_quant offline")
        engine = Engine(
            cfg=self.cfg,
            model_dir=self.name_model_dir,
            max_batch_size=self.serve_max_batch,
            batch_timeout_ms=self.batch_timeout_ms,
            queue_limit=self.queue_limit,
            default_deadline_ms=self.serve_deadline_ms,
            silent=bool(self.silent),
            reload_breaker_threshold=self.reload_breaker_threshold,
            reload_breaker_cooldown_s=self.reload_breaker_cooldown_s,
            watchdog_timeout_s=self.watchdog_timeout_s,
        )
        feedback = FeedbackWriter(
            os.path.join(self.loop_dir, "feedback"),
            page_bytes=self.feedback_page_bytes,
            rotate_bytes=self.feedback_rotate_bytes,
        )
        retention = None
        if self.feedback_retain_shards >= 0:
            from .loop.retention import RetentionOptions, Sweeper

            retention = Sweeper(
                feedback.dir,
                RetentionOptions(self.feedback_retain_shards,
                                 self.feedback_retain_bytes),
                silent=bool(self.silent))
        loop = ContinuousLoop(
            engine,
            self.cfg,
            feedback_dir=feedback.dir,
            base_iter=self.itr_train,
            eval_iter=self.itr_evals[0],
            eval_name=self.eval_names[0] if self.eval_names else "eval",
            rounds_per_cycle=self.loop_rounds_per_cycle,
            replay_ratio=self.loop_replay_ratio,
            min_records=self.loop_min_records,
            max_records_per_cycle=self.loop_max_records,
            cycle_period_s=self.loop_cycle_period_s,
            publish_min_delta=self.publish_min_delta,
            publish_metric=self.publish_metric,
            publish_slice_floor=(self.publish_slice_floor
                                 if self.publish_slice_floor >= 0
                                 else None),
            publish_slice_min_count=self.publish_slice_min_count,
            publish_source_field=(self.publish_source_field
                                  if self.publish_source_field >= 0
                                  else None),
            feedback_writer=feedback,
            retention=retention,
            silent=bool(self.silent),
        )
        loop_thread = threading.Thread(
            target=loop.run, kwargs={"max_cycles": self.loop_max_cycles},
            name="cxxnet-serve-train-loop", daemon=True,
        )
        httpd_box = {}

        def _ready(httpd):
            httpd_box["httpd"] = httpd
            h = engine.healthz()
            print(f"serve_train: serving model round {h['round']} "
                  f"(fp {h['net_fp']}) on "
                  f"http://{httpd.server_address[0]}:{httpd.server_port}; "
                  f"feedback log at {feedback.dir}",
                  flush=True)
            loop_thread.start()

        def _stop(signum, frame):
            print(f"serve_train: shutdown requested, draining (up to "
                  f"{self.drain_timeout_s:g}s)", flush=True)
            loop.stop()
            h = httpd_box.get("httpd")
            if h is not None:
                threading.Thread(target=h.shutdown, daemon=True).start()

        prev = {s: _signal.signal(s, _stop)
                for s in (_signal.SIGTERM, _signal.SIGINT)}
        tuner = self._start_serve_controller(engine)
        try:
            serve_forever(
                engine,
                host=self.serve_host,
                port=self.serve_port,
                reload_period_s=self.serve_reload_period,
                drain_timeout_s=self.drain_timeout_s,
                verbose=not self.silent,
                ready_fn=_ready,
                feedback=feedback,
                capture_predict=bool(self.capture_predict),
            )
        finally:
            for s, p in prev.items():
                _signal.signal(s, p)
            if tuner is not None:
                tuner.stop()
            loop.stop()
            if loop_thread.is_alive():
                loop_thread.join(timeout=max(self.drain_timeout_s, 5.0))
            engine.close()
            feedback.close()
        print("serve_train: shutdown complete", flush=True)

    def task_loop_fleet(self) -> None:
        """``task=loop_fleet``: multi-tenant continuous learning
        (doc/continuous_training.md "Multi-tenant loops").

        Hosts one serving engine + feedback log + fine-tune loop per
        ``[tenant:<name>]`` conf section, all sharing this process's
        device pool.  One HTTP front door dispatches by the request's
        ``model`` field (``serve/router.ModelRouter``); a scheduler
        thread round-robins the tenants' fine-tune cycles under the
        SLO-constrained arbiter — while any ``alert=`` rule fires
        (e.g. the serve plane's p99 bound), ALL tune cycles shed.
        Gates are per-slice when ``publish_slice_floor >= 0``; consumed
        feedback shards compact when ``feedback_retain_shards >= 0``.
        Shutdown drains like ``task=serve``."""
        import signal as _signal
        import threading

        from .loop.tenant import TenantManager
        from .serve import Engine
        from .serve.server import serve_forever
        from .tune import options_from_cfg

        if self.replicas > 1:
            raise ValueError(
                "task=loop_fleet is single-replica per tenant engine; "
                "front a replica fleet with task=serve separately")
        if any(n == "quant" and v not in ("", "0", "off", "none")
               for n, v in self.cfg):
            raise ValueError(
                "task=loop_fleet cannot serve quantized models: the "
                "fine-tune loops train on the served weights")
        shared_cfg, tenant_secs = cfgmod.split_tenant_sections(self.cfg)
        if not tenant_secs:
            raise ValueError(
                "task=loop_fleet needs at least one tenant section "
                "(tenant = <name> .. tenant = end)")
        if not cfgmod.split_sections(shared_cfg).find("eval"):
            raise ValueError(
                "task=loop_fleet needs an eval section — every "
                "tenant's publish gate scores on held-out data")

        def engine_factory(tenant_cfg, model_dir):
            return Engine(
                cfg=tenant_cfg,
                model_dir=model_dir,
                max_batch_size=self.serve_max_batch,
                batch_timeout_ms=self.batch_timeout_ms,
                queue_limit=self.queue_limit,
                default_deadline_ms=self.serve_deadline_ms,
                silent=bool(self.silent),
                reload_breaker_threshold=self.reload_breaker_threshold,
                reload_breaker_cooldown_s=self.reload_breaker_cooldown_s,
                watchdog_timeout_s=self.watchdog_timeout_s,
            )

        def make_iters(tenant_cfg):
            # a tenant's iterators come from the SHARED data/eval
            # sections with the tenant's own overrides applied last
            # (e.g. seed_data) — fresh instances per tenant, iterator
            # state is never shared
            tsplit = cfgmod.split_sections(tenant_cfg)
            data = tsplit.find("data")
            evals = tsplit.find("eval")
            base = create_iterator(data[0].entries) if data else None
            ev = create_iterator(evals[0].entries)
            for it in (base, ev):
                if it is None:
                    continue
                for n, v in tsplit.global_entries:
                    it.set_param(n, v)
                it.init()
            return base, ev, evals[0].tag or "eval"

        manager = TenantManager(
            shared_cfg, tenant_secs,
            engine_factory=engine_factory,
            make_iters=make_iters,
            loop_dir=self.loop_dir,
            period_s=self.loop_cycle_period_s,
            # the fleet-wide arbiter reads the SHARED stream: a tune_*
            # key inside a tenant section must never retune the shared
            # controller (the same isolation set_param enforces)
            tune_opts=options_from_cfg(shared_cfg),
            silent=bool(self.silent),
        )
        router = manager.router()
        httpd_box = {}

        def _ready(httpd):
            httpd_box["httpd"] = httpd
            names = ", ".join(t.name for t in manager.tenants)
            print(f"loop_fleet: serving {len(manager.tenants)} "
                  f"tenant(s) [{names}] on "
                  f"http://{httpd.server_address[0]}:{httpd.server_port}",
                  flush=True)
            manager.start()

        def _stop(signum, frame):
            # signal only — joining the scheduler here would stall the
            # accept loop for up to a whole fine-tune cycle and eat the
            # drain window; close() in the finally block does the join
            print(f"loop_fleet: shutdown requested, draining (up to "
                  f"{self.drain_timeout_s:g}s)", flush=True)
            manager.request_stop()
            h = httpd_box.get("httpd")
            if h is not None:
                threading.Thread(target=h.shutdown, daemon=True).start()

        prev = {s: _signal.signal(s, _stop)
                for s in (_signal.SIGTERM, _signal.SIGINT)}
        try:
            serve_forever(
                manager.tenants[0].engine,
                host=self.serve_host,
                port=self.serve_port,
                reload_period_s=self.serve_reload_period,
                drain_timeout_s=self.drain_timeout_s,
                verbose=not self.silent,
                ready_fn=_ready,
                capture_predict=bool(self.capture_predict),
                router=router,
            )
        finally:
            for s, p in prev.items():
                _signal.signal(s, p)
            manager.close()
        print("loop_fleet: shutdown complete", flush=True)

    def task_export_quant(self) -> int:
        """``task=export_quant``: post-training quantized export with
        the accuracy gate (doc/performance.md "Quantized inference").

        Quantizes ``model_in`` per ``quant`` (default int8), gates it
        on top-1 agreement with the f32 model over the conf's eval
        section (``quant_min_agreement`` / ``quant_calib_batches``),
        falling individual layers back to bf16 until the gate passes,
        and writes ``<round>.quant.model`` + manifest beside the
        source.  Prints one JSON verdict line; exit 0 on publish, 3 on
        reject (nothing written — the f32 artifact keeps serving)."""
        import json

        from .nnet import quant as nquant

        eval_iter = self.itr_evals[0] if self.itr_evals else None
        verdict = nquant.export_quantized(
            self.cfg,
            self.name_model_in,
            eval_iter=eval_iter,
            scheme=self.quant or "int8",
            min_agreement=self.quant_min_agreement,
            calib_batches=self.quant_calib_batches,
            out_path=self.quant_out or None,
            silent=bool(self.silent),
        )
        line = json.dumps(verdict, separators=(",", ":"))
        print(line, flush=True)
        if self.quant_report:
            from .utils.checkpoint import atomic_write_bytes
            atomic_write_bytes(self.quant_report,
                               (line + "\n").encode("utf-8"))
        return 0 if verdict["ok"] else 3

    def task_summary(self) -> None:
        """``task=summary``: per-layer table — type, name, output node
        shapes, parameter counts — plus totals.  Works on a bare conf
        (no data files needed; batch column shows the conf batch)."""
        import jax

        tr = self.net_trainer
        g = tr.graph
        shapes = tr.net.node_shapes
        total = 0
        print(f"{'#':>3} {'layer':22s} {'type':18s} {'out shape':20s} "
              f"{'params':>12}")
        for i, spec in enumerate(g.layers):
            key = tr.net.param_key[i]
            n_par = 0
            if spec.type_name != "shared" and key in tr.params:
                n_par = int(sum(
                    np.prod(np.shape(w))
                    for w in jax.tree_util.tree_leaves(tr.params[key])
                ))
                total += n_par
            out = shapes[spec.nindex_out[0]] if spec.nindex_out else ()
            name = spec.name or ""
            print(f"{i:>3} {name:22s} {spec.type_name:18s} "
                  f"{str(tuple(out)):20s} {n_par:>12,}")
        print(f"{'':66s}{'-' * 12}")
        print(f"total parameters: {total:,} "
              f"({total * 4 / 1e6:.1f} MB f32)")
        if tr.mesh_plan is not None:
            print(f"mesh: {tr.mesh_plan.describe(zero=tr.zero)}")

    def task_generate(self) -> None:
        """``task=generate``: autoregressive byte sampling from a trained
        language model (``nnet/generate.py``; doc/tasks.md).  KV-cache
        incremental decoding by default (``gen_cache = 1``), sliding
        window otherwise or as the fallback."""
        from .nnet.generate import generate

        prompt = self.gen_prompt
        if self.gen_prompt_file:
            with open(self.gen_prompt_file, "rb") as f:
                prompt = f.read().decode("utf-8", "replace")
        text = generate(
            self.net_trainer, prompt, self.gen_len, self.gen_temp,
            cache=bool(self.gen_cache), topk=self.gen_topk,
            topp=self.gen_topp, silent=bool(self.silent),
        )
        with open(self.name_pred, "w", encoding="utf-8") as fo:
            fo.write(text)
        if not self.silent:
            print(f"generated {len(text.encode())} bytes -> {self.name_pred}")
            print(text)

    def task_extract(self) -> None:
        if self.itr_pred is None:
            raise ValueError("must specify a pred iterator for feature extraction")
        if not self.extract_node_name:
            raise ValueError("extract_node_name must be specified in task extract")
        print("start predicting...")
        nrow = 0
        dshape = None
        meta_path = self.name_pred + ".meta"
        mode = "w" if self.output_format else "wb"
        with open(self.name_pred, mode) as fo:
            self.itr_pred.before_first()
            while self.itr_pred.next():
                batch = self.itr_pred.value()
                feats = self.net_trainer.extract_feature(batch, self.extract_node_name)
                n = batch.batch_size - batch.num_batch_padd
                feats = feats[:n]
                nrow += n
                flat = feats.reshape(feats.shape[0], -1)
                if self.output_format:
                    for row in flat:
                        fo.write(" ".join(f"{v:g}" for v in row) + " \n")
                else:
                    flat.astype("<f4").tofile(fo)
                if n:
                    dshape = feats.shape[1:]
        with open(meta_path, "w", encoding="utf-8") as fm:
            shp = list(dshape) if dshape else []
            while len(shp) < 3:
                shp.append(1)
            fm.write(f"{nrow},{shp[0]},{shp[1]},{shp[2]}\n")
        print(f"finished prediction, write into {self.name_pred}")


def _hard_exit_if_resilient(rc: int) -> None:
    """Elastic runs built coordination clients whose error-poll threads
    cannot be stopped from Python; interpreter-exit destructor order
    (leaked generation services vs zombie pollers) would abort the
    process AFTER all real work succeeded.  Flush and hard-exit
    instead — every artifact this process writes (checkpoints,
    manifests, telemetry, events) is flushed/fsynced at write time."""
    from .parallel.distributed import resilient_client_used

    if resilient_client_used():
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(rc)


def main(argv: Optional[List[str]] = None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    try:
        rc = LearnTask().run(argv)
    except SystemExit:
        raise
    except BaseException:
        import traceback

        from .parallel.distributed import resilient_client_used

        if resilient_client_used():
            traceback.print_exc()
            _hard_exit_if_resilient(1)
        raise
    _hard_exit_if_resilient(rc)
    return rc
