"""MLP blocks: SwiGLU (llama/qwen/deepseek/granite/jamba) and GELU
(whisper). These are the layers the paper converts to spectral form
(gate_proj / up_proj / down_proj — S4.2)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.nn.linear import init_linear, apply_linear


def init_mlp(key, d_model: int, d_ff: int, *, rank=None, act: str = "swiglu",
             bias: bool = False, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    p = {
        "up": init_linear(ks[0], d_model, d_ff, rank=rank, bias=bias, dtype=dtype),
        "down": init_linear(ks[1], d_ff, d_model, rank=rank, bias=bias, dtype=dtype),
    }
    if act == "swiglu":
        p["gate"] = init_linear(ks[2], d_model, d_ff, rank=rank, bias=bias, dtype=dtype)
    return p


def apply_mlp(p, x: jax.Array, *, act: str = "swiglu") -> jax.Array:
    up = apply_linear(p["up"], x)
    if act == "swiglu":
        gate = apply_linear(p["gate"], x)
        h = jax.nn.silu(gate) * up
    elif act == "gelu":
        h = jax.nn.gelu(up)
    else:
        raise ValueError(act)
    return apply_linear(p["down"], h)
