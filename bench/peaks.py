"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB of HBM at 819 GB/s per chip.
A kind that is not in the table is an error: a share of a peak is never
taken against a guessed or default peak.
"""
from __future__ import annotations

PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2**30,
    },
}

SOURCE = 'Google Cloud documentation, "TPU v5e"'


def peak(device_kind: str) -> dict[str, float]:
    """The peak table's entry for ``device_kind``; raises ``KeyError``
    for a kind the table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
