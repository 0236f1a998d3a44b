"""Published peaks of the chips the benchmark may run on, keyed by
``jax.devices()[0].device_kind``.  A device that is not here is an
error, never a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of
    # HBM2e at 819 GB/s per chip
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}: add it "
            "to benchmarks/lib/peaks.py with its source")
    return PEAKS[device_kind]
