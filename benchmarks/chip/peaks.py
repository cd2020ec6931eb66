"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports. A device missing from the table is an error,
never a default.

Source: Google Cloud documentation, "TPU v5e"
(https://cloud.google.com/tpu/docs/v5e): per chip 197 TFLOP/s bf16,
393 TOP/s int8, 16 GiB HBM2 at 819 GB/s.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2**30,
        "source": "https://cloud.google.com/tpu/docs/v5e",
    },
}


class UnknownDevice(RuntimeError):
    pass


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
