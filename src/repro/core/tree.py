"""Pytree-wide retraction: walk a model parameter tree, retract every
spectral factor group, leave everything else untouched.

Spectral groups are dicts {"U": (..., m, k), "s": (..., k), "V": (..., n, k)}
— possibly with leading layer/expert axes (our models stack per-layer
params for lax.scan). Retractions broadcast over those axes natively.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.spectral import is_spectral
from repro.core.retraction import retract_with_fallback


def _walk(tree: Any, fn) -> Any:
    """Depth-first walk replacing spectral groups via fn(group)."""
    if is_spectral(tree):
        return fn(tree)
    if isinstance(tree, dict):
        return {k: _walk(v, fn) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return type(tree)(_walk(v, fn) for v in tree)
    return tree


def retract_tree(params: Any, method: str = "qr", axis_name: str | None = None) -> Any:
    """Apply Stiefel retraction to U and V of every spectral group in the
    tree (paper Algorithm 1, lines 5-7, over the whole model)."""
    return retract_tree_with_stats(params, method, axis_name)[0]


QR_FALLBACKS = "retraction/qr_fallbacks"


def retract_tree_with_stats(params: Any, method: str = "qr",
                            axis_name: str | None = None) -> Tuple[Any, Dict[str, jax.Array]]:
    """:func:`retract_tree`, and the retraction's step metrics: where
    ``qr`` retracted some factor, ``{"retraction/qr_fallbacks": n}`` with
    n the stacked U and V leaves that fell back to Householder QR (an
    int32 on device); else ``{}``."""
    fallbacks = []

    def _retract_group(g: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        out = dict(g)
        for name in ("U", "V"):
            out[name], fell = retract_with_fallback(g[name], method=method,
                                                    axis_name=axis_name)
            if fell is not None:
                fallbacks.append(fell)
        return out

    out = _walk(params, _retract_group)
    return out, ({QR_FALLBACKS: sum(fallbacks)} if fallbacks else {})


def skipped_retraction_stats(params: Any, method: str = "qr") -> Dict[str, jax.Array]:
    """The metrics :func:`retract_tree_with_stats` gives ``params`` under
    ``method``, for a step that retracts nothing: the same keys, 0."""
    groups = []
    _walk(params, lambda g: groups.append(g) or g)
    return {QR_FALLBACKS: jnp.zeros((), jnp.int32)} if method == "qr" and groups else {}


def spectral_leaf_mask(params: Any) -> Any:
    """Pytree of {"U","s","V"} bools marking spectral leaves — used by the
    optimizer for per-component learning-rate groups (paper S4.3's 'clear
    next step')."""

    def _mark(g):
        return {k: (k in ("U", "s", "V")) for k in g}

    def _walk_mask(tree):
        if is_spectral(tree):
            return _mark(tree)
        if isinstance(tree, dict):
            return {k: _walk_mask(v) for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            return type(tree)(_walk_mask(v) for v in tree)
        return False

    return _walk_mask(params)


def max_orthogonality_error(params: Any) -> jax.Array:
    """Max ortho error over all spectral factors in the tree (diagnostic,
    matches the paper's Table 2 'Ortho. Error' row)."""
    from repro.core.manifold import orthogonality_error

    errs = []

    def _collect(g):
        errs.append(orthogonality_error(g["U"]))
        errs.append(orthogonality_error(g["V"]))
        return g

    _walk(params, _collect)
    if not errs:
        return jnp.float32(0.0)
    return jnp.max(jnp.stack(errs))
