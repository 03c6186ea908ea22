"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s, and
1,600 Gbit/s of chip-to-chip interconnect per chip. A kind that is not in
the table is an error, never a default.
"""

from __future__ import annotations

#: ``device_kind`` that JAX reports for a TPU v5e chip
V5E = "TPU v5 lite"

PEAKS: dict[str, dict[str, float]] = {
    V5E: {
        "flops": 197e12,       # FLOP/s, bf16 on the MXU (the highest rate)
        "hbm_bytes_s": 819e9,  # B/s
        "hbm_bytes": 16e9,     # B of device memory
        "ici_bytes_s": 200e9,  # B/s per chip, 1,600 Gbit/s
    },
}


def peaks(device_kind: str) -> dict[str, float]:
    """The peaks of ``device_kind``; raises ``KeyError`` for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)}); add them with their source"
                       ) from None


def least_seconds(flops: float, bytes_moved: float, device_kind: str) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    p = peaks(device_kind)
    return max(flops / p["flops"], bytes_moved / p["hbm_bytes_s"])
