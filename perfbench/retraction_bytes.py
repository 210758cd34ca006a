"""Bytes that the retraction of a training step must move, computed
from a configuration's sizes alone (``perfbench/configs/*.json`` keys).

Every spectral factor is read once and written once in fp32, whatever
the method does between: U (d x rank) and V (d_ff x rank) of each of
the MLP's three projections in every layer. A QR, a Cholesky-QR2 or a
Cayley retraction that moves more bytes than this reads lower against
the same floor.
"""
from __future__ import annotations

FP32_BYTES = 4


def retraction_bytes(cfg: dict) -> int:
    """``2 * 4 * L * 3 * (d + d_ff) * rank`` bytes a step; 0 without a
    spectral MLP."""
    sct = cfg["sct"]
    if not sct["spectral_mlp"]:
        return 0
    factor_elems = (cfg["hidden_size"] + cfg["intermediate_size"]) * sct["rank"]
    return 2 * FP32_BYTES * cfg["num_hidden_layers"] * 3 * factor_elems
