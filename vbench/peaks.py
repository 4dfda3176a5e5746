"""Published peaks of each accelerator, keyed by JAX's `device_kind`.

A device that is not in the table is an error: a share of a peak is never
computed against a guessed peak.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,  # bf16
        "bytes_per_s": 819e9,  # HBM bandwidth
        "memory_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' (per chip)",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
