"""On-chip kernel library: registry, capability probes, verdict gating.

The TPP/cuDNN lesson (arXiv 2104.05755, arXiv 1410.0759): a SMALL
library of well-chosen fused primitives beats op-by-op lowering — but
only where measured.  This package holds the repo's Pallas block
kernels and the discipline that decides when they run:

* ``conv_block``   — fused conv+bias(+relu) GEMM for the sibling-1x1
  groups ``nnet/net.py`` already assembles (``conv_block.py``);
* ``int8_gemm``    — quantized GEMM with the per-channel rescale (+bias,
  optional relu) inside the kernel epilogue (``int8_gemm.py``);
* ``zero_update``  — the fused shard-local sgd update step for
  ``_apply_updates`` (``update_step.py``).

Every kernel registers a **capability probe** (backend/dtype/shape —
"can this launch at all") and an **interpret-mode reference**: the
identical kernel body run under ``interpret=True`` on CPU, pinned
bit-equal to the stock XLA lowering by tests/test_kernels.py.  Whether
a capable kernel actually RUNS is the selector's call:

``kernel_lib = auto | off | <name[,name...]>``

* ``off`` (also ``0``/empty) — stock lowering everywhere;
* an explicit name list — those kernels pinned ON wherever their probe
  passes (on non-TPU backends they execute in interpret mode: exact,
  slow — the parity/test spelling);
* ``auto`` (the default, also ``-1``) — follow the RECORDED per-backend
  verdicts in ``verdicts.json``, the same way ``conv_branch_embed=-1``
  follows its measured CPU reject: a kernel runs only where a committed
  ``promote`` verdict from the bisect A/B (``tools/kernel_ab.py``)
  says it pays.  CPU rejects are recorded (Pallas on CPU is emulation);
  no TPU verdict is recorded yet (ROADMAP S9) — until a chip A/B
  commits a promote, ``auto`` means stock, so adopting a kernel is
  always a measured decision, never faith.
"""

from __future__ import annotations

import json
import os
import threading
from typing import Callable, Dict, NamedTuple, Optional

__all__ = [
    "KERNELS", "KernelSpec", "KernelSelector", "BoundKernels",
    "parse_mode", "verdicts_path", "load_verdicts", "record_verdict",
    "reload_verdicts",
]


class KernelSpec(NamedTuple):
    name: str
    doc: str
    probe: Callable[..., Optional[str]]  # None = capable, str = reason


def _specs() -> Dict[str, KernelSpec]:
    from . import conv_block, int8_gemm, update_step

    return {
        "conv_block": KernelSpec(
            "conv_block",
            "fused conv+bias(+relu) GEMM for sibling-1x1 groups",
            conv_block.probe),
        "int8_gemm": KernelSpec(
            "int8_gemm",
            "int8 GEMM, per-channel rescale in the kernel epilogue",
            int8_gemm.probe),
        "zero_update": KernelSpec(
            "zero_update",
            "fused shard-local sgd update step",
            update_step.probe),
    }


KERNELS: Dict[str, KernelSpec] = _specs()

# ----------------------------------------------------------------------
# recorded per-backend verdicts (the committed promotion state)
_VERDICTS_LOCK = threading.Lock()
_VERDICTS: Optional[dict] = None


def verdicts_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "verdicts.json")


def load_verdicts() -> dict:
    """``{kernel: {backend: {"verdict": promote|reject, ...}}}`` from
    the committed file; cached (``reload_verdicts`` drops the cache —
    tests and ``kernel_ab --record`` use it)."""
    global _VERDICTS
    with _VERDICTS_LOCK:
        if _VERDICTS is None:
            try:
                with open(verdicts_path(), "r", encoding="utf-8") as f:
                    _VERDICTS = json.load(f)
            except (OSError, ValueError):
                _VERDICTS = {}
        return _VERDICTS


def reload_verdicts() -> None:
    global _VERDICTS
    with _VERDICTS_LOCK:
        _VERDICTS = None


def record_verdict(kernel: str, backend: str, verdict: str,
                   path: str = "", **extra) -> dict:
    """Append/overwrite one (kernel, backend) verdict in the committed
    file (``tools/kernel_ab.py --record``).  Returns the full doc."""
    if kernel not in KERNELS:
        raise ValueError(f"unknown kernel {kernel!r}")
    if verdict not in ("promote", "reject"):
        raise ValueError(f"verdict must be promote/reject, got {verdict!r}")
    path = path or verdicts_path()
    try:
        with open(path, "r", encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError):
        doc = {}
    doc.setdefault(kernel, {})[backend] = {"verdict": verdict, **extra}
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    if os.path.abspath(path) == verdicts_path():
        reload_verdicts()
    return doc


# ----------------------------------------------------------------------
# the conf-keyed selector
def parse_mode(val: str) -> str:
    """Validate a ``kernel_lib`` conf value; returns the canonical
    spelling (``auto`` / ``off`` / comma name list).  Raises on unknown
    kernel names — a conf typo must fail at build, not silently serve
    the stock path."""
    v = (val or "").strip()
    if v in ("auto", "-1"):
        return "auto"
    if v in ("off", "0", "", "none"):
        return "off"
    names = [s.strip() for s in v.split(",") if s.strip()]
    bad = [s for s in names if s not in KERNELS]
    if bad or not names:
        raise ValueError(
            f"kernel_lib={val!r}: expected auto, off, or a comma list "
            f"of {sorted(KERNELS)}"
            + (f" (unknown: {bad})" if bad else ""))
    return ",".join(sorted(set(names)))


class KernelSelector:
    """Decides, per (kernel, backend), whether the Pallas path runs."""

    def __init__(self, mode: str = "auto",
                 verdicts: Optional[dict] = None) -> None:
        self.mode = parse_mode(mode)
        self._verdicts = verdicts

    def _verdict(self, name: str, backend: str) -> str:
        v = (self._verdicts if self._verdicts is not None
             else load_verdicts())
        return ((v.get(name) or {}).get(backend) or {}).get("verdict", "")

    def active(self, name: str, backend: str) -> bool:
        if name not in KERNELS:
            raise ValueError(f"unknown kernel {name!r}")
        if self.mode == "off":
            return False
        if self.mode == "auto":
            # follow the recorded promotion state: no verdict = stock
            # (promotion requires the measured A/B, never default-on)
            return self._verdict(name, backend) == "promote"
        return name in self.mode.split(",")

    def fingerprint(self, backend: str) -> str:
        """Cache-key component (``serve/cache.py``): the names this
        selector activates on ``backend``, '' when none — the stock
        program's key is unchanged from the pre-kernel era."""
        names = [n for n in sorted(KERNELS) if self.active(n, backend)]
        return "+".join(names)

    def bind(self, backend: str) -> "BoundKernels":
        if not backend:
            # no backend must never read as "cpu": that would run a
            # kernel under the Pallas interpreter on a chip
            raise ValueError("KernelSelector.bind needs the backend the "
                             "programs run on")
        return BoundKernels(self, backend)


class BoundKernels:
    """A selector fixed to one backend — what dispatch sites consume.
    ``interpret`` is True off-TPU: the identical kernel body runs under
    the Pallas interpreter (exact, slow — the parity spelling)."""

    __slots__ = ("selector", "backend", "interpret")

    def __init__(self, selector: KernelSelector, backend: str) -> None:
        self.selector = selector
        self.backend = backend
        self.interpret = backend != "tpu"

    def active(self, name: str, **probe_kw) -> bool:
        """Selected AND capable; publishes the decision as the
        ``kernel_selected{name,backend}`` gauge."""
        on = self.selector.active(name, self.backend)
        if on and probe_kw:
            on = KERNELS[name].probe(self.backend, **probe_kw) is None
        from ...obs import device as obs_device

        obs_device.mark_kernel_selected(name, self.backend, on)
        return on

    def fingerprint(self) -> str:
        return self.selector.fingerprint(self.backend)
