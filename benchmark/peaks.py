"""Published peaks, keyed by `device_kind` as JAX reports it. A device that
is not here is an error, never a default."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e": 197 TFLOP/s '
                  'bf16, 16 GB HBM at 819 GB/s',
    },
}


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise LookupError(
            f"no published peak for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
