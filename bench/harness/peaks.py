"""Published peaks of each accelerator the benchmark knows, by the
``device_kind`` JAX reports. A device that is not in the table is an
error: a roofline share against a guessed peak would mean nothing."""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": per chip, 197 TFLOP/s bf16,
    # 393 TOP/s int8, 16 GB of HBM at 819 GB/s.
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> dict:
    """The peaks row of ``device_kind``; raises for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
