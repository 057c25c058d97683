"""Published per-chip peaks, keyed by `jax.Device.device_kind`.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16 and
16 GB of HBM at 819 GB/s per chip. A device kind that is not here is an
error, not a default.
"""
DEVICE_PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks_for(kind: str) -> dict:
    try:
        return DEVICE_PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}") from None
