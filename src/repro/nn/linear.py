"""Linear layers: dense or spectral (SCT). One call site for both, so the
paper's technique is a config switch on every projection in the system.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.spectral import spectral_init, spectral_apply, is_spectral


def _is_q8_spectral(p) -> bool:
    # lazy import: serving.quantize owns the single definition of
    # "quantized"; a dict-valued U/w that is NOT a {"q8","scale"} tensor
    # falls through to the dense branch instead of misrouting here
    if not isinstance(p.get("U"), dict):
        return False
    from repro.serving.quantize import is_quantized_spectral

    return is_quantized_spectral(p)


def _is_q8_dense(p) -> bool:
    if not isinstance(p.get("w"), dict):
        return False
    from repro.serving.quantize import is_quantized

    return is_quantized(p["w"])


def init_linear(
    key: jax.Array,
    in_dim: int,
    out_dim: int,
    *,
    rank: Optional[int] = None,
    bias: bool = False,
    dtype: Any = jnp.float32,
    scale: float | None = None,
):
    """rank=None -> dense {'w': (in, out)[, 'b']}; rank=k -> spectral
    {'U': (in,k), 's': (k,), 'V': (out,k)[, 'b']} (paper Eq. 1)."""
    if rank is not None:
        k = min(rank, in_dim, out_dim)
        p = spectral_init(key, in_dim, out_dim, k, dtype=dtype, scale=scale)
    else:
        sigma = scale if scale is not None else in_dim ** -0.5
        w = jax.random.normal(key, (in_dim, out_dim), dtype=jnp.float32) * sigma
        p = {"w": w.astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((out_dim,), dtype=dtype)
    return p


def apply_linear(p, x: jax.Array) -> jax.Array:
    """Dispatch on the form of the parameters: spectral, int8 spectral,
    int8 dense or dense. The dense (m, n) matrix is never built in the
    spectral branch. Int8 groups (serving/quantize.py) are dequantized
    on the fly: int8 lives in HBM, the fp copy is a per-call transient."""
    if is_spectral(p):
        y = spectral_apply(p, x)
    elif _is_q8_spectral(p):                    # int8 spectral group
        from repro.serving.quantize import dequantize_int8

        y = spectral_apply(
            {"U": dequantize_int8(p["U"], x.dtype), "s": p["s"],
             "V": dequantize_int8(p["V"], x.dtype)}, x)
    elif _is_q8_dense(p):                       # int8 dense weight
        from repro.serving.quantize import dequantize_int8

        y = x @ dequantize_int8(p["w"], x.dtype)
    else:
        w = p["w"]
        y = x @ w.astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


def linear_out_dim(p) -> int:
    return p["V"].shape[-2] if is_spectral(p) else p["w"].shape[-1]
