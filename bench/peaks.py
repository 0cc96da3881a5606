"""Published peaks of each accelerator the benchmark may run on, keyed by
the ``device_kind`` JAX reports.  A kind that is not in the table is an
error: a share of a guessed peak is no measurement."""

from __future__ import annotations

__all__ = ["PEAKS", "peaks_for"]

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,        # bf16 matrix units
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}") from None
