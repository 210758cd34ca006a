"""Stiefel manifold utilities: diagnostics and tangent-space projection."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def orthogonality_error(U: jax.Array) -> jax.Array:
    """max |U^T U - I| — the paper's reported 'Ortho. Error' metric
    (Table 2 reports < 2e-6 after retraction)."""
    Uf = U.astype(jnp.float32)
    # full fp32 products: at default precision a TPU rounds the operands
    # to bf16, which alone leaves ~1e-3 and hides the retraction's error
    G = jnp.einsum("...mk,...ml->...kl", Uf, Uf,
                   precision=jax.lax.Precision.HIGHEST)
    eye = jnp.eye(G.shape[-1], dtype=G.dtype)
    return jnp.max(jnp.abs(G - eye))


def project_tangent(U: jax.Array, G: jax.Array) -> jax.Array:
    """Project an ambient gradient G (m, k) onto the tangent space of the
    Stiefel manifold at U:  PT(G) = G - U sym(U^T G).

    The paper takes plain Euclidean AdamW steps and relies on retraction;
    Riemannian projection before the step is an optional beyond-paper
    mode (reduces the distance the retraction must correct).
    """
    UtG = jnp.einsum("...mk,...ml->...kl", U, G)
    sym = 0.5 * (UtG + jnp.swapaxes(UtG, -1, -2))
    return G - jnp.einsum("...mk,...kl->...ml", U, sym)


def frobenius_tail(s: jax.Array, k: int) -> jax.Array:
    """Optimal rank-k approximation error sqrt(sum_{i>k} sigma_i^2)
    (Eckart-Young), used by tests to validate truncation."""
    s_sorted = jnp.sort(s)[::-1]
    return jnp.sqrt(jnp.sum(s_sorted[k:] ** 2))
