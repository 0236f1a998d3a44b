"""Persistent XLA compilation cache: the one place that decides where.

Every jitted program in this framework — the fused train step, the
``update_scan`` body, eval/predict programs, the serving engine's
shape-bucket cache entries — is compiled from scratch on process start;
the GoogLeNet scan step alone is minutes of XLA time on a chip.  JAX's
persistent cache keys executables by (HLO, compile options, backend)
and reloads them on later runs, so warm restarts skip XLA entirely.

Every entry point (the CLI, ``serve.Engine``, ``bench.py``,
``chip_smoke.py``, the ``tools/`` drivers) calls :func:`enable` before
its first jit.  The directory is resolved in this order:

1. ``JAX_COMPILATION_CACHE_DIR`` in the environment — JAX reads it
   itself, so nothing here touches the directory setting: whoever
   launches the process (a scheduler that mounts a shared cache) owns
   the placement, and a conf key cannot override it;
2. the ``compile_cache_dir`` conf key;
3. ``<checkout>/.jax_cache`` — a fixed path, because the path is part
   of what makes a later run find the entries again.

The thresholds are dropped to zero: this framework's programs are few
and large, so caching everything is strictly better than re-jitting.
The directory is shared safely between concurrent processes (JAX
writes entries atomically), and a stale entry is just a miss.
``JAX_ENABLE_COMPILATION_CACHE=false`` (JAX's own switch) turns the
cache off whatever the directory — the test suite runs that way.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence, Tuple

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)

_enabled_dir: Optional[str] = None


def enabled_dir() -> Optional[str]:
    """The directory the cache was pointed at, or None."""
    return _enabled_dir


def resolve(conf_dir: str = "") -> str:
    """The cache directory for this process (see the module docstring
    for the precedence)."""
    path = os.environ.get(ENV_DIR) or conf_dir or DEFAULT_DIR
    return os.path.abspath(os.path.expanduser(path))


def enable(conf_dir: str = "", silent: bool = True) -> str:
    """Turn the persistent cache on at :func:`resolve`'s directory
    (created if missing) and return it.  Idempotent.  Call before the
    programs it should serve are compiled."""
    global _enabled_dir
    path = resolve(conf_dir)
    if _enabled_dir == path:
        return path
    os.makedirs(path, exist_ok=True)
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    if not os.environ.get(ENV_DIR):
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program no matter how small or fast to compile — the
    # program count here is tiny and restart latency is what is bought
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # jax decides ONCE, at its first compile, whether a cache is in
    # use; a library caller that already compiled something would
    # otherwise never see the directory take effect
    cc.reset_cache()
    _enabled_dir = path
    if not silent:
        print(f"compile cache: persistent XLA cache at {path}", flush=True)
    return path


def configure(cfg: Sequence[Tuple[str, str]], silent: bool = True) -> str:
    """:func:`enable` with the ``compile_cache_dir`` key of an ordered
    config stream (last one wins) as the conf-level directory."""
    conf_dir = ""
    for name, val in cfg or ():
        if name == "compile_cache_dir":
            conf_dir = val
    return enable(conf_dir, silent=silent)
