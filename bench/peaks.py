"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16 and 819 GB/s HBM bandwidth per chip, 16 GB of HBM.
JAX reports a v5e chip as ``"TPU v5 lite"``; both spellings map to it.
A kind that is not in the table is an error, never a default.
"""

from __future__ import annotations

_V5E = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9, "hbm_bytes": 16e9,
        "source": "Google Cloud docs, TPU v5e: 197 TFLOP/s bf16, "
                  "819 GB/s HBM, 16 GB HBM per chip"}

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> dict:
    """The peak table entry for ``device_kind``; raises on an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
