"""Shared scaffold for the step-time bisection tools
(googlenet/resnet/vgg): the bench preamble (a TPU or exit 2, compile
cache on) and the bench-harness timing loop, so bisect numbers stay
comparable to ``bench.py`` numbers.  A variant that fails fails the
sweep."""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def run_bisect(variant_conf, default_names, batch: int = 128,
               scan_k: int = 30) -> None:
    """Time each requested variant with the bench harness."""
    import bench

    bench.require_accelerator()
    for name in sys.argv[1:] or default_names:
        bench._set_stage(f"bisect:{name}")
        bench._bench_imagenet_conf(
            f"bisect:{name}", name, variant_conf(name, batch),
            batch, scan_k,
        )
