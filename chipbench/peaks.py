"""Published peaks of the chips the benchmark runs on, keyed by the
`device_kind` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}


def peaks(device_kind: str) -> dict:
    """The peaks of `device_kind`; an unknown chip is an error, not a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None


def least_seconds(ops: float, nbytes: float, device_kind: str) -> tuple[float, str]:
    """The least time `ops` operations over `nbytes` bytes can take on the
    chip, and which of the two bounds it: max(ops / int8 peak, bytes / HBM
    bandwidth). The int8 rate is the chip's highest, so no implementation
    computes faster."""
    p = peaks(device_kind)
    t_ops = ops / p["int8_ops_per_s"]
    t_bytes = nbytes / p["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")
