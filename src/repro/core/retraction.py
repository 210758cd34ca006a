"""Stiefel-manifold retractions for spectral factors.

Paper (Algorithm 1, lines 5-7): after each AdamW step,

    Q, R = qr(U);  U <- Q * sign(diag(R))

The sign correction makes the retraction continuous (QR is unique only up
to column signs; fixing diag(R) > 0 picks the branch closest to the
pre-update factor). ``qr_retract`` computes that Q by Cholesky-QR2 at full
fp32 precision and falls back to Householder QR where the factor is too
ill-conditioned for it.

Beyond-paper (DESIGN.md S2): CholeskyQR2 — the TPU/distributed-native
retraction. For a row-sharded U only the k x k Gram matrix is
all-reduced; compute is two matmuls + a tiny Cholesky instead of a
sequential Householder QR. Applied twice for fp32-grade orthogonality.
Cayley retraction is included as the paper's own cited alternative
[Li et al., 2020].

All retractions are vmappable over leading (layer / expert) axes.
"""
from __future__ import annotations

import collections
import math
from typing import Callable, Dict

import jax
import jax.numpy as jnp

# factor retractions by method, counted at trace time; a factor stacked
# over leading (layer / expert) axes counts once per slice
_COUNTS: collections.Counter = collections.Counter()


def retraction_counts() -> dict:
    """How many factors :func:`retract` has traced, by method, since the
    last :func:`reset_retraction_counts`."""
    return dict(_COUNTS)


def reset_retraction_counts() -> None:
    _COUNTS.clear()


def _sign_fix(Q: jax.Array, R: jax.Array) -> jax.Array:
    """Q * sign(diag(R)) with sign(0) := 1 for determinism."""
    d = jnp.diagonal(R, axis1=-2, axis2=-1)
    sign = jnp.where(d >= 0, 1.0, -1.0).astype(Q.dtype)
    return Q * sign[..., None, :]


def _gram(U: jax.Array, axis_name: str | None, precision) -> jax.Array:
    """U^T U, psum'd over row shards along ``axis_name``."""
    G = jnp.einsum("...mk,...ml->...kl", U, U, precision=precision)
    if axis_name is not None:
        G = jax.lax.psum(G, axis_name)
    return G


def _cholesky_solve(U: jax.Array, G: jax.Array) -> jax.Array:
    """U R^{-1} for the upper-triangular R with R^T R = G, U's Gram."""
    # G = R^T R with R upper-triangular  =>  chol(G) = R^T (lower)
    k = G.shape[-1]
    G = G + (1e-10 * jnp.trace(G, axis1=-2, axis2=-1)[..., None, None] / k
             ) * jnp.eye(k, dtype=G.dtype)
    L = jnp.linalg.cholesky(G)
    # Solve U_new L^T = U  =>  U_new = U L^{-T}
    return jax.lax.linalg.triangular_solve(
        L, U, left_side=False, lower=True, transpose_a=True
    )


def _cholesky_qr_once(U: jax.Array, axis_name: str | None,
                      precision=None) -> jax.Array:
    """One CholeskyQR pass: G = U^T U (psum over row shards), R = chol(G)^T,
    U <- U R^{-1}. Communication: k x k, independent of m."""
    return _cholesky_solve(U, _gram(U, axis_name, precision))


# Cholesky-QR's first pass is trusted when ||Q1^T Q1 - I||_F is below
# this: the Frobenius norm bounds the 2-norm, so cond(Q1) < sqrt(3), where
# the second pass is orthonormal to fp32 rounding (Yamamoto et al., 2015)
_QR_GUARD = 0.5


def _qr_retract(U: jax.Array) -> tuple[jax.Array, jax.Array]:
    """:func:`qr_retract`, and whether the stacked leaf fell back to
    Householder QR (an int32 0 or 1)."""
    orig_dtype = U.dtype
    Uf = U.astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    Q1 = _cholesky_qr_once(Uf, None, hi)
    G2 = _gram(Q1, None, hi)
    eye = jnp.eye(G2.shape[-1], dtype=G2.dtype)
    # one predicate for the whole stack, so that the cond runs one branch;
    # a NaN or inf from a failed Cholesky compares false and falls back
    ok = jnp.all(jnp.sum(jnp.square(G2 - eye), axis=(-2, -1)) < _QR_GUARD ** 2)
    Q = jax.lax.cond(ok, lambda: _cholesky_solve(Q1, G2),
                     lambda: _sign_fix(*jnp.linalg.qr(Uf)))
    return Q.astype(orig_dtype), (~ok).astype(jnp.int32)


def qr_retract(U: jax.Array) -> jax.Array:
    """Paper-faithful QR retraction with sign correction (Eq. 5):
    ``U (..., m, k) -> Q * sign(diag(R))`` where ``Q, R = qr(U)``. Maps
    a factor drifted off the Stiefel manifold by an optimizer step back
    to orthonormal columns; computed in fp32 regardless of storage
    dtype, broadcast over leading stacked axes.

    The Q is computed by Cholesky-QR2 with both Grams at full fp32
    precision: the same positive-diagonal-R factor as the sign-fixed
    QR, from matmuls instead of a sequential Householder QR. Where the
    first pass leaves Q1 too far from orthonormal for the second to
    repair (``||Q1^T Q1 - I||_F >= 0.5`` in any slice of the stack: a
    condition number of about 1e4 or more, a rank-deficient or non-finite
    factor), the whole stacked leaf takes Householder ``jnp.linalg.qr``
    with the sign fix instead."""
    return _qr_retract(U)[0]


def cholesky_qr2_retract(U: jax.Array, axis_name: str | None = None) -> jax.Array:
    """CholeskyQR2 retraction (beyond-paper, distribution-friendly).

    Two passes of CholeskyQR give orthogonality error O(eps) even for
    moderately ill-conditioned inputs (cond(U) <~ 1e4 in fp32). The
    column space equals QR's; the sign convention matches the sign-fixed
    QR (both produce the factor with positive-diagonal R).

    If ``axis_name`` is given, U is interpreted as row-sharded along that
    mapped axis (inside shard_map) and the Gram matrix is psum'd.

    Both Grams are formed at full fp32 precision: at default precision a
    TPU rounds the operands to bf16, which left factors 2e-4 to 5e-4 off
    the manifold after every step on a TPU v5e, where QR leaves ~1e-6.
    The Cholesky factorization and the triangular solve compile to
    full-precision products on their own.
    """
    orig_dtype = U.dtype
    Uf = U.astype(jnp.float32)
    Uf = _cholesky_qr_once(Uf, axis_name, jax.lax.Precision.HIGHEST)
    Uf = _cholesky_qr_once(Uf, axis_name, jax.lax.Precision.HIGHEST)
    return Uf.astype(orig_dtype)


def cayley_retract(U: jax.Array, tangent_scale: float = 1.0) -> jax.Array:
    """Cayley-transform retraction [Li et al., 2020], the paper's cited
    lower-cost alternative (S5). Projects the deviation of U from its own
    manifold point onto the tangent space and transports along a Cayley
    curve. For a point already near the manifold this acts as a
    corrective retraction like QR, at 2 solves of a k x k system when
    using the low-rank Woodbury form; here we use the full form for
    clarity (U is tall-skinny so the cost is still O(m k^2)).
    """
    orig_dtype = U.dtype
    Uf = U.astype(jnp.float32)
    # Nearest-manifold reference point via one CholeskyQR pass. Its Gram
    # stays at default precision, which a TPU rounds to bf16 (see
    # cholesky_qr2_retract).
    Q = _cholesky_qr_once(Uf, None)
    # Tangent direction Delta = U - Q at Q; skew part drives the Cayley map.
    D = (Uf - Q) * tangent_scale
    A = jnp.einsum("...mk,...ml->...kl", Q, D)
    A = A - jnp.swapaxes(A, -1, -2)  # skew-symmetric k x k
    k = A.shape[-1]
    eye = jnp.eye(k, dtype=Uf.dtype)
    # Cayley: Q_new = Q (I - A/2)^{-1} (I + A/2)
    lhs = eye - 0.5 * A
    rhs = eye + 0.5 * A
    M = jnp.linalg.solve(lhs, rhs)
    out = jnp.einsum("...mk,...kl->...ml", Q, M)
    return out.astype(orig_dtype)


RETRACTIONS: Dict[str, Callable[..., jax.Array]] = {
    "qr": qr_retract,
    "cholesky_qr2": cholesky_qr2_retract,
    "cayley": cayley_retract,
}


def retract(U: jax.Array, method: str = "qr", axis_name: str | None = None,
            **kwargs) -> jax.Array:
    """Dispatch a retraction by name.

    ``axis_name`` marks U as row-sharded along that mapped axis (inside
    shard_map). Only cholesky_qr2 can honour it (the Gram matrix is
    psum'd; communication is k x k). ``qr``/``cayley`` operate on the
    local shard only — silently accepting ``axis_name`` would QR each
    shard independently and return a factor that is *not* orthonormal
    globally, so those combinations raise instead of corrupting the
    manifold. Extra kwargs go to the method (e.g. ``tangent_scale`` for
    cayley). Each call counts ``prod(U.shape[:-2])`` factors under
    ``method`` (:func:`retraction_counts`)."""
    return retract_with_fallback(U, method, axis_name, **kwargs)[0]


def retract_with_fallback(U: jax.Array, method: str = "qr",
                          axis_name: str | None = None, **kwargs):
    """:func:`retract`, and for ``method="qr"`` whether the stacked leaf
    fell back to Householder QR (an int32 0 or 1 on device; None under
    any other method)."""
    fn = RETRACTIONS.get(method)
    if fn is None:
        raise ValueError(f"unknown retraction {method!r}; options {list(RETRACTIONS)}")
    if axis_name is not None and method != "cholesky_qr2":
        raise ValueError(
            f"retraction {method!r} cannot distribute over axis_name="
            f"{axis_name!r}: per-shard QR/Cayley of a row-sharded factor is "
            f"not globally orthonormal; use method='cholesky_qr2'")
    _COUNTS[method] += math.prod(U.shape[:-2])
    if method == "qr":
        return _qr_retract(U, **kwargs)
    if method == "cholesky_qr2":
        return fn(U, axis_name=axis_name, **kwargs), None
    return fn(U, **kwargs), None
