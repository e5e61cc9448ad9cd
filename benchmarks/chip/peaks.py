"""Published peaks per chip, keyed by JAX's `device_kind`.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; have {sorted(PEAKS)}") from None


def least_time_s(ops: float, nbytes: float, ops_peak: float,
                 bytes_peak: float) -> tuple:
    """The roofline's least time for `ops` operations and `nbytes` bytes,
    and which of the two bounds it ("compute" or "memory")."""
    t_ops, t_mem = ops / ops_peak, nbytes / bytes_peak
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
