"""Test harness configuration.

Tests run on CPU with 8 virtual XLA devices so the multi-chip sharding paths
(data parallelism over a ``jax.sharding.Mesh``) can be exercised without TPU
hardware.  Must be set before jax is imported anywhere.
"""

import os

# Tests never touch an accelerator: hard-override whatever the shell
# exports (setdefault would keep it).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# The CLI and the serve engine turn the persistent compile cache on by
# default (utils/compile_cache.py).  The suite runs with JAX's own
# switch off — in this process and in every subprocess that inherits
# the environment — so tests neither write into <checkout>/.jax_cache
# nor load XLA:CPU executables from it (each load logs a page of
# machine-feature noise).  The cache tests re-enable it for their own
# subprocesses.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.RandomState(42)


@pytest.fixture
def split(request, monkeypatch):
    """The flash kernels compute a block in parts, those above the
    diagonal or beyond the window left out (``ops/flash._tiles``), only
    where a part is whole lane tiles; ``split = True`` (an indirect
    parameter) lets the tests' blocks of 16 and 32 split too."""
    from cxxnet_tpu.ops import flash

    if request.param:
        monkeypatch.setattr(flash, "_LANES", 8)
        _retraced(flash, request)
    return request.param


def _retraced(flash, request):
    """The kernels' two jitted calls keep their traces by shapes and
    settings, not by the module's constants: a test that patches one
    starts and ends with none kept."""
    def drop():
        flash._forward.clear_cache()
        flash._backward.clear_cache()
    drop()
    request.addfinalizer(drop)


@pytest.fixture
def two_kernels(request, monkeypatch):
    """The backward of the flash kernels is ONE kernel where a key-value
    head's row of ``dk`` and ``dv`` fits its VMEM budget
    (``ops/flash._ROW_VMEM``); ``two_kernels = True`` (an indirect
    parameter) leaves it no room, so ``flash_dq`` + ``flash_dkv`` run —
    as for a row too long to fit."""
    from cxxnet_tpu.ops import flash

    if request.param:
        monkeypatch.setattr(flash, "_ROW_VMEM", 0)
        _retraced(flash, request)
    return request.param


@pytest.fixture(scope="module")
def ref(request):
    """The plain reference of the token-model family whose own tests
    the module holds (its ``FAMILY``, a row of ``tests/families.py``)."""
    import families

    return families.reference(request.module.FAMILY)


@pytest.fixture(scope="session")
def one_chip():
    """The first chip of a DESCRIBED ``v5e:2x2``, for the
    ``test_v5e_<family>.py`` files (``tests/v5e.py``): nothing runs on
    it.  Describing it loads the TPU's library, which one process at a
    time may do unless ``ALLOW_MULTIPLE_LIBTPU_LOAD`` is set (the second
    fails on ``/tmp/libtpu_lockfile``) — and xdist's workers each take a
    family's file."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps it away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


#: The files that take longest, in the order they are handed out (PR
#: 45's junit record; ROADMAP D0): ``--dist loadfile`` gives a file to one
#: worker, and a long file that starts last is the run's tail.  xdist
#: gives each of its six workers the next file of this order and one more
#: to hold behind it: the whole-step compiles for a described chip use
#: every core they find, so no more than two run at a time (places 0 and
#: 6, 1 and 7 are one worker's).  The short files fill up behind these.
LONGEST_FIRST = (
    "test_v5e_joyai", "test_v5e_trinity", "test_flash_window",
    "test_bench_harness", "test_bench_nemotron_h", "test_flash",
    "test_v5e_nemotron", "test_v5e_granite", "test_families",
    "test_accuracy", "test_bench_joyai_llm_flash", "test_bench_qwen3_next",
    "test_v5e_qwen3_next", "test_v5e_smallthinker", "test_qwen3_next_layers",
    "test_nemotron_layers", "test_layers", "test_cli",
    "test_bench_trinity_mini", "test_bench_smallthinker",
    "test_fault_tolerance", "test_joyai_layers", "test_bench_granite",
    "test_afmoe_layers", "test_models", "test_granite_hybrid",
    "test_branch_embed", "test_ops", "test_moe_dispatch", "test_trainer",
    # PR 49's, behind everything the seed's order placed: a seventh
    # whole-step compile beside the other six would make three at a time
    "test_v5e_ling", "test_ling_layers", "test_bench_ling",
)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-process / long-running tests"
    )
    config.addinivalue_line(
        "markers", "chaos: fault-injection chaos suite (tools/chaos_run.sh)"
    )
    # xdist's loadfile hands files out by their NUMBER of cases, the most
    # first, unless told not to: a family's one whole-step compile (one
    # case, minutes) would start last.  Collection order it is, with the
    # long files first (``LONGEST_FIRST``).
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(items):
    rank = {name: i for i, name in enumerate(LONGEST_FIRST)}
    items.sort(key=lambda item: rank.get(
        item.module.__name__ if item.module else "", len(rank)))


@pytest.fixture(autouse=True)
def _disarm_faults():
    """No test leaks an armed fault spec (or a thread blocked at a hang
    site) into the next one: reset() also releases in-progress hangs."""
    yield
    from cxxnet_tpu.utils import faults

    faults.reset()


# ----------------------------------------------------------------------
# daemon-thread leak accounting.  The multi-file tier-1 flake (see
# CHANGES.md, PR 7) had ~24 leaked daemon threads alive at crash time;
# this guard bounds that suspect: every module gets a grace period to
# join the threads it started, the survivors are accounted, and a module
# that leaks more than CXXNET_THREAD_LEAK_LIMIT (default 12) fails
# loudly with their names instead of letting the leak compound silently
# across the suite.
_THREAD_LEAKS = {}  # module name -> [thread names] (session accounting)


@pytest.fixture(autouse=True, scope="module")
def _thread_leak_guard(request):
    import threading
    import time

    # object identity, not ident: thread idents are recycled by the OS,
    # so an ident set would mistake a fresh thread for a finished one
    before = set(threading.enumerate())
    yield
    deadline = time.monotonic() + 3.0

    def _leaked():
        return [
            t for t in threading.enumerate()
            if t.is_alive() and t not in before
            and t is not threading.current_thread()
        ]

    new = _leaked()
    while new and time.monotonic() < deadline:
        for t in new:  # join what exits on its own (close() in flight)
            t.join(timeout=0.2)
        new = _leaked()
    if not new:
        return
    names = sorted(t.name for t in new)
    _THREAD_LEAKS[request.module.__name__] = names
    limit = int(os.environ.get("CXXNET_THREAD_LEAK_LIMIT", "12"))
    if len(new) > limit:
        pytest.fail(
            f"{request.module.__name__} leaked {len(new)} daemon "
            f"threads (> limit {limit}): {names} — close your "
            "iterators/engines/evaluators (CXXNET_THREAD_LEAK_LIMIT "
            "overrides)", pytrace=False,
        )


def pytest_terminal_summary(terminalreporter):
    if _THREAD_LEAKS:
        total = sum(len(v) for v in _THREAD_LEAKS.values())
        terminalreporter.write_sep(
            "-", f"daemon-thread leak accounting: {total} leaked")
        for mod, names in sorted(_THREAD_LEAKS.items()):
            terminalreporter.write_line(f"  {mod}: {len(names)} {names}")


def build_from_shapes(tr):
    """The trainer's net built, and its parameters as shapes: every
    layer's ``infer_shape`` and ``init_params`` runs, and no ImageNet-size
    net's weights are drawn to read a node's shape."""
    import jax

    tr._build_net()
    return jax.eval_shape(lambda k: tr.net.init_params(k, tr.batch_size),
                          jax.random.PRNGKey(0))


def run_cli(args, cwd, timeout=300, module=True):
    """Shared subprocess harness for driving the CLI (or a tool script,
    module=False with args[0] an absolute script path) in tests.

    The package is not installed, so PYTHONPATH points the child at
    this checkout; JAX_PLATFORMS=cpu keeps it off any accelerator.
    """
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo
    cmd = ([sys.executable, "-m", "cxxnet_tpu", *args] if module
           else [sys.executable, *args])
    return subprocess.run(
        cmd, capture_output=True, text=True, cwd=cwd, env=env,
        timeout=timeout,
    )
