"""Published peaks of each accelerator the benchmark runs on.

Keyed by ``jax.Device.device_kind``. A kind that is not in the table is an
error, never a default: a utilization against a guessed peak is no number.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (Cloud TPU system
    # architecture): per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of
    # HBM at 819 GB/s.
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises ``KeyError`` for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known kinds: {sorted(PEAKS)}") from None
