"""SpectralLinear: the paper's permanent truncated-SVD parameterization.

A weight matrix ``W (m, n)`` is stored as ``U (m, k)``, ``s (k,)``,
``V (n, k)`` with ``W = U @ diag(s) @ V.T``. The dense ``W`` is never
materialized — forward/backward flow through the three small factors
(paper Eq. 1–4).

Parameters live in plain dicts so they compose with pjit/shard_map and
our from-scratch optimizer without a module framework.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

# A spectral parameter group is a dict with exactly these keys. Code
# elsewhere (optimizer wrapper, sharding rules, retraction walker)
# recognizes spectral leaves by this structure.
SPECTRAL_KEYS = ("U", "s", "V")

# The spectral group type: {"U": (..., m, k), "s": (..., k),
# "V": (..., n, k)} with U, V column-orthonormal and an optional
# vmap-stacked layer/expert prefix "...". W = U @ diag(s) @ V.T is
# implied, never materialized.
SpectralParams = Dict[str, jax.Array]


def spectral_init(
    key: jax.Array,
    m: int,
    n: int,
    k: int,
    dtype: Any = jnp.float32,
    scale: float | None = None,
) -> SpectralParams:
    """Initialize spectral factors for from-scratch training.

    U, V get orthonormal columns (QR of Gaussian). The singular values
    decay geometrically and are scaled so the implied dense matrix has
    the same Frobenius norm as a LeCun-normal dense init:
    ``E||W||_F^2 = m * n * sigma^2`` with ``sigma^2 = 1/m`` (fan-in), and
    ``||U diag(s) V^T||_F^2 = ||s||_2^2``.
    """
    if k > min(m, n):
        raise ValueError(f"rank {k} exceeds min(m={m}, n={n})")
    ku, kv = jax.random.split(key)
    u0 = jax.random.normal(ku, (m, k), dtype=jnp.float32)
    v0 = jax.random.normal(kv, (n, k), dtype=jnp.float32)
    U, _ = jnp.linalg.qr(u0)
    V, _ = jnp.linalg.qr(v0)
    sigma = scale if scale is not None else 1.0 / math.sqrt(m)
    # geometric decay over the retained spectrum (condition ~ 100)
    decay = jnp.logspace(0.0, -2.0, k)
    s = decay * (sigma * math.sqrt(m * n) / jnp.linalg.norm(decay))
    return {
        "U": U.astype(dtype),
        "s": s.astype(dtype),
        "V": V.astype(dtype),
    }


def spectral_apply(params: SpectralParams, x: jax.Array) -> jax.Array:
    """Forward pass ``y = ((x @ U) * s) @ V.T`` — paper Eq. 2–4.

    Three small matmuls, O(b*k*(m+n)) FLOPs. No (m, n) tensor exists;
    autograd through this function yields factor-shaped gradients only.

    Mixed precision note: the factors cast to ``x.dtype`` at apply time,
    so the compute dtype is whatever the embedding cast chose
    (PrecisionPolicy.compute_dtype via cfg.dtype) while the stored
    masters keep their own dtype — the apply-time-cast contract.
    """
    h = spectral_project(params, x)             # (..., k)
    return h @ params["V"].T.astype(x.dtype)    # (..., n)   cost O(b k n)


def spectral_project(params: SpectralParams, x: jax.Array) -> jax.Array:
    """The rank-space half of :func:`spectral_apply`: ``(x @ U) * s``,
    (..., k), in ``x.dtype``."""
    h = x @ params["U"].astype(x.dtype)      # (..., k)   cost O(b m k)
    return h * params["s"].astype(h.dtype)   # (..., k)   cost O(b k)


def spectral_param_count(m: int, n: int, k: int) -> int:
    """Stored numbers for one rank-k spectral layer: ``k(m + n + 1)``
    (U is (m, k), V is (n, k), s is (k,)) — the paper's §3 storage
    analysis. Compare :func:`dense_param_count` for the ``m·n`` matrix
    the factors replace; the ratio is the layer's compression factor."""
    return k * (m + n + 1)


def dense_param_count(m: int, n: int) -> int:
    """Stored numbers for the dense ``(m, n)`` weight the spectral
    parameterization never materializes: ``m·n``. The denominator of
    every compression claim in the paper's Table 1."""
    return m * n


def is_spectral(params: Any) -> bool:
    """True if this pytree node is a spectral parameter group."""
    return (
        isinstance(params, dict)
        and set(params.keys()) >= set(SPECTRAL_KEYS)
        and all(hasattr(params[k], "ndim") for k in SPECTRAL_KEYS)
        and params["U"].ndim >= 2
        and params["s"].ndim == params["U"].ndim - 1
    )
