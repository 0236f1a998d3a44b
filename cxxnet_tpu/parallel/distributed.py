"""Multi-process (multi-host) runtime: the distributed-PS replacement.

Parity target: the reference's distributed mode — ps-lite workers/servers
launched from ``mpi.conf`` with ``param_server = dist`` and data sharded by
``PS_RANK`` (SURVEY §2.7/§2.8, ``/root/reference/src/nnet/nnet_impl-inl.hpp:
376-390``, ``iter_thread_imbin_x-inl.hpp:108-139``).

TPU-native design: there are no parameter servers.  Every process joins one
`jax.distributed` job (GRPC coordination), the device mesh spans all
processes' chips, and gradient exchange is XLA collectives over ICI within a
host/pod and DCN across hosts — the same SPMD program as single-host, just a
bigger mesh.  The reference's ``update_on_server`` maps to sharded optimizer
state (params/updater state sharded over the mesh instead of replicated).

Config keys (set on every process, e.g. by a launcher):

* ``dist_coordinator = host:port`` — process-0 address
  (``jax.distributed.initialize`` coordinator)
* ``dist_num_proc`` — number of processes in the job
* ``dist_proc_id`` — this process's rank

or the corresponding environment variables ``CXN_COORDINATOR`` /
``CXN_NUM_PROC`` / ``CXN_PROC_ID`` (the env route mirrors the reference's
``PS_RANK`` convention).  When none are present this is a no-op single-process
run.  The data iterators independently honor ``dist_num_worker`` /
``dist_worker_rank`` / ``PS_RANK`` for shard-per-worker reading; a launcher
normally sets both groups from the same rank.
"""

from __future__ import annotations

import os
from typing import List, Optional, Sequence, Tuple

import jax

ConfigEntry = Tuple[str, str]

_initialized = False
_resilient_used = False


def resilient_client_used() -> bool:
    """Did this process ever build the resilient (elastic) coordination
    client?  Its error-poll thread cannot be stopped from Python, so
    interpreter-exit destructor order can trip it into a LOG(FATAL)
    abort — the CLI hard-exits (``os._exit``) after a clean flush
    instead of running destructors when this is set."""
    return _resilient_used


def distributed_spec(
    cfg: Sequence[ConfigEntry],
) -> Optional[Tuple[str, int, int]]:
    """Extract (coordinator, num_proc, proc_id) from config or env."""
    coord = os.environ.get("CXN_COORDINATOR")
    num = os.environ.get("CXN_NUM_PROC")
    pid = os.environ.get("CXN_PROC_ID", os.environ.get("PS_RANK"))
    for name, val in cfg:
        if name == "dist_coordinator":
            coord = val
        elif name == "dist_num_proc":
            num = val
        elif name == "dist_proc_id":
            pid = val
    if coord is None and num is None:
        return None
    if coord is None or num is None or pid is None:
        raise ValueError(
            "distributed run needs all of dist_coordinator, dist_num_proc, "
            "dist_proc_id (or CXN_COORDINATOR/CXN_NUM_PROC/CXN_PROC_ID)"
        )
    return coord, int(num), int(pid)


def maybe_init_distributed(cfg: Sequence[ConfigEntry]) -> bool:
    """Join the jax.distributed job if the config asks for one.

    Idempotent; returns True when running multi-process.  Must be called
    before any other JAX API touches the backend.  ``elastic = 1`` confs
    join through the RESILIENT client (non-fatal heartbeat callbacks,
    no shutdown-on-destruction) so a peer death is an error this
    process handles instead of a ``LOG(FATAL)`` that kills it — the
    precondition for the elastic rebuild (doc/parallel.md).
    """
    global _initialized
    spec = distributed_spec(cfg)
    if spec is None:
        return False
    if _initialized:
        return True
    coord, num, pid = spec
    from .elastic import ElasticOptions

    # last-entry-wins, same as every other config key — a CLI override
    # elastic=0 must yield the stock client, not a liveness-blind one
    # with no elastic layer armed on top
    opts = ElasticOptions.from_cfg(cfg)
    init_distributed(coord, num, pid,
                     resilient=opts.elastic or opts.join)
    return True


def init_distributed(coordinator: str, num: int, pid: int,
                     resilient: bool = False,
                     init_timeout: int = 120) -> None:
    """Join (or re-join) a jax.distributed job with explicit arguments.

    ``resilient=True`` builds the coordination-service client by hand
    (same wire protocol) with the changes that make replica loss
    survivable.  The stock client LOG(FATAL)s — terminates this
    process — when the service broadcasts a peer's death, and the
    Python-level ``missed_heartbeat_callback`` escape hatch is unusable
    in this jaxlib (nanobind cannot convert the ``absl::Status``
    argument; invoking it throws ``std::bad_cast`` on whatever thread
    polls).  So the resilient client makes the coordination service
    LIVENESS-BLIND instead: heartbeats so slow that no eviction — and
    therefore no fatal broadcast — ever fires within a training run.
    Failure detection belongs entirely to the elastic layer
    (``parallel/elastic.py``: sub-second application heartbeats + the
    collective deadline) and the gloo data plane (a SIGKILLed peer
    resets its TCP pairs, erroring collectives in milliseconds).
    ``shutdown_on_destruction=False`` plus short client/service
    shutdown timeouts make teardown abandonable: the handles are
    dropped (and their poll threads die) before any late barrier
    failure can be broadcast back.  Re-init after
    :func:`shutdown_distributed` is the elastic-rebuild rendezvous:
    connect blocks until all ``num`` processes arrive."""
    global _initialized
    _enable_cpu_collectives()
    if not resilient:
        jax.distributed.initialize(
            coordinator_address=coordinator, num_processes=num,
            process_id=pid,
        )
        _initialized = True
        return
    from jax._src import distributed as jdist
    from jax._src.lib import xla_extension as xe

    from ..obs import emit as obs_emit

    gs = jdist.global_state
    if gs.client is not None or gs.service is not None:
        raise RuntimeError(
            "init_distributed: a distributed client is already live; "
            "call shutdown_distributed() first")
    if pid == 0:
        port = coordinator.rsplit(":", 1)[1]
        gs.service = xe.get_distributed_runtime_service(
            f"[::]:{port}", num, heartbeat_interval=600,
            max_missing_heartbeats=6, shutdown_timeout=8)
    gs.client = xe.get_distributed_runtime_client(
        coordinator, pid, init_timeout=init_timeout, shutdown_timeout=5,
        heartbeat_interval=600, max_missing_heartbeats=6,
        shutdown_on_destruction=False, use_compression=True)
    obs_emit("mesh.dist_init", coordinator=coordinator, num=num,
             rank=pid, resilient=True)
    gs.client.connect()
    gs.process_id = pid
    gs.num_processes = num
    gs.coordinator_address = coordinator
    _initialized = True
    global _resilient_used
    _resilient_used = True


#: coordination services deliberately kept alive after an elastic
#: teardown: stopping (or destructing) one closes its gRPC socket, and
#: every peer whose old client is still polling it would see the
#: closure as a fatal error and LOG(FATAL).  One tiny idle server per
#: mesh generation is the price of not letting teardown order kill
#: survivors.
_leaked_services: list = []


def shutdown_distributed(timeout_s: float = 10.0,
                         graceful: bool = True) -> bool:
    """Tear down the jax.distributed runtime so it is safe to
    re-initialize IN THIS PROCESS (the elastic rebuild, and the
    re-init regression test).

    ``graceful=True`` (every peer known alive — the regression test,
    planned same-membership teardowns): client disconnect and service
    stop each run on a deadline thread; a step that cannot complete is
    ABANDONED after ``timeout_s``.

    ``graceful=False`` (the elastic rebuild): NO coordination-service
    RPC is issued at all.  A shutdown RPC would start the service-side
    shutdown barrier, the dead peer can never join it, and the barrier
    failure would be broadcast to the surviving peers' still-live
    clients — which treat any poll error as fatal and terminate.  So
    the client handle is simply dropped (its destructor cancels the
    poll thread without RPC — ``shutdown_on_destruction=False``) and
    the service object is intentionally LEAKED (see
    ``_leaked_services``).

    Live backends are dropped afterwards — compiled programs and
    device buffers of the old mesh die with them — and the next
    backend use builds a fresh client against the new distributed
    state.  Returns True when every step completed cleanly."""
    import threading as _threading

    from jax._src import distributed as jdist

    from ..obs import emit as obs_emit

    global _initialized
    gs = jdist.global_state
    client, service = gs.client, gs.service
    gs.client = None
    gs.service = None
    gs.preemption_sync_manager = None
    gs.process_id, gs.num_processes = 0, 1
    gs.coordinator_address = None
    clean = True
    if not graceful:
        if service is not None:
            _leaked_services.append(service)
        if client is not None or service is not None:
            obs_emit("mesh.dist_teardown", graceful=False,
                     leaked_services=len(_leaked_services))
        del client  # destructor cancels the poll thread, no RPC
    else:
        for name, obj in (("client", client), ("service", service)):
            if obj is None:
                continue
            box: dict = {}

            def _run(o=obj, n=name) -> None:
                try:
                    o.shutdown()
                    box[n] = True
                except Exception as e:  # noqa: BLE001 - not fatal
                    box[n] = e

            t = _threading.Thread(target=_run, daemon=True,
                                  name=f"cxxnet-dist-shutdown-{name}")
            t.start()
            t.join(timeout=timeout_s)
            if t.is_alive() or box.get(name) is not True:
                clean = False
                obs_emit("mesh.dist_shutdown_abandoned", what=name,
                         error=(None if t.is_alive()
                                else str(box.get(name))),
                         timed_out=t.is_alive())
    jax.clear_caches()
    from jax._src import api as _api

    _api.clear_backends()
    _initialized = False
    return clean


def distributed_initialized() -> bool:
    return _initialized


def _enable_cpu_collectives() -> None:
    """Arm cross-process CPU collectives (gloo) BEFORE the backend exists.

    The CPU PJRT client is built per-process with a collectives
    implementation baked in; the default (``none``) rejects any SPMD
    program whose mesh spans processes ("Multiprocess computations
    aren't implemented on the CPU backend") — which is exactly the shape
    of a multi-host mesh trainer rehearsed on CPU (a 2-process x
    2-device 2x2 data x model mesh).  Selecting the gloo TCP
    implementation here makes the CPU backend a faithful miniature of
    the TPU pod: one jit program, partitions on every process, XLA
    collectives across them.  No-op when another platform is primary
    (TPU/GPU ignore it)."""
    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def process_info() -> Tuple[int, int]:
    """(process_id, process_count) — (0, 1) for single-process runs."""
    try:
        return jax.process_index(), jax.process_count()
    except RuntimeError:
        return 0, 1


def is_primary() -> bool:
    """True on the process that owns checkpoint writes (rank 0)."""
    return process_info()[0] == 0


def agree_on_value(val: int, reduce: str = "min") -> int:
    """Cross-process integer agreement (allgather + min/max reduce).

    Single-process runs return ``val`` unchanged.  Used by the
    checkpoint subsystem so every process resumes from the SAME round
    (``min`` — a round every process can see) and so a preemption signal
    delivered to any one process stops the whole job (``max``)."""
    import numpy as np

    _, count = process_info()
    if count == 1:
        return int(val)
    from jax.experimental import multihost_utils

    vals = np.asarray(
        multihost_utils.process_allgather(np.asarray([val], np.int64))
    ).reshape(-1)
    return int(vals.min() if reduce == "min" else vals.max())


def agree_on_round(local_round: int) -> int:
    """Resume-round consensus: the newest round EVERY process holds a
    valid checkpoint for (-1 when any process has none)."""
    return agree_on_value(local_round, reduce="min")


def any_process_flag(flag: bool) -> bool:
    """True when the flag is set on ANY process (collective)."""
    return bool(agree_on_value(int(bool(flag)), reduce="max"))


def barrier(name: str = "cxxnet_barrier") -> None:
    """Block until every process reaches this point (no-op single-proc).

    Used after rank-0 checkpoint writes so no process races ahead and
    reads (or prunes) a checkpoint before it is fully durable."""
    _, count = process_info()
    if count == 1:
        return
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(name)


def fetch_array(x) -> "np.ndarray":
    """Global jax.Array → full host ndarray, multi-process safe.

    Replicated arrays (params) read from the local shard; sharded arrays
    are allgathered across processes first.
    """
    import numpy as np

    if not hasattr(x, "sharding") or jax.process_count() == 1:
        return np.asarray(x)
    if x.sharding.is_fully_replicated:
        return np.asarray(x.addressable_shards[0].data)
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(x, tiled=True))


def fetch_local_rows(x, axis: int = 0) -> "np.ndarray":
    """Global array → this process's rows along ``axis`` (device order).

    ``axis=0`` for batch-major arrays; ``axis=1`` for ``[K, B, ...]``
    scan step-stacks sharded over the batch axis."""
    import numpy as np

    if not hasattr(x, "sharding") or jax.process_count() == 1:
        return np.asarray(x)
    # one shard per row range: replication (e.g. over the model axis) puts
    # identical row blocks on several local devices — keep the first each
    by_start = {}
    for s in x.addressable_shards:
        start = s.index[axis].start or 0
        if start not in by_start:
            by_start[start] = s
    return np.concatenate(
        [np.asarray(by_start[k].data) for k in sorted(by_start)], axis=axis
    )


def global_batch_parts(n: int) -> List[int]:
    """Deterministic split of a global batch over processes (equal shards)."""
    _, count = process_info()
    if n % count != 0:
        raise ValueError(
            f"global batch {n} must divide process count {count}"
        )
    return [n // count] * count
