"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` string JAX reports. A device missing here is an error,
never a default: a roofline or MFU against a guessed peak is no number.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,       # FLOP/s per chip, dense bf16
        "int8_ops": 393e12,         # OP/s per chip
        "hbm_bytes_per_s": 819e9,   # HBM bandwidth per chip
        "hbm_bytes": 16e9,          # HBM capacity per chip
        "source": "Google Cloud documentation, 'TPU v5e' (system architecture page)",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"device_kind {device_kind!r} has no entry in "
                       f"perfbench/peaks.py; known: {sorted(PEAKS)}") from None
