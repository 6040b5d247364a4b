"""Published per-chip peaks, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
819 GB/s of HBM bandwidth and 197 TFLOP/s in bf16 per chip. The compute
peak is the bf16 matrix-unit rate; the solves here run float32 vector
and sparse work, which is bound by memory long before either.
"""

from __future__ import annotations

SOURCE = 'Google Cloud documentation, "TPU v5e"'

#: device_kind -> peaks of one chip
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12},
    "TPU v5e": {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12},
}


def peaks(device_kind):
    """The peaks of ``device_kind``; a device missing from the table is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError("no published peaks for device kind %r (have: %s)"
                       % (device_kind, ", ".join(sorted(PEAKS)))) from None
