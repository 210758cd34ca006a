"""MLP blocks: SwiGLU (llama/qwen/deepseek/granite/jamba) and GELU
(whisper). These are the layers the paper converts to spectral form
(gate_proj / up_proj / down_proj — S4.2).

A spectral SwiGLU MLP on one TPU device runs its d_ff-wide core (gate,
up, ``h`` and their gradients) in the fused kernel of
``kernels/fused_mlp.py``; every other MLP, and every MLP elsewhere, runs
the three projections through XLA."""
from __future__ import annotations

import collections
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.spectral import SPECTRAL_KEYS, is_spectral, spectral_project
from repro.kernels.fused_mlp import swiglu_down_bwd_pallas, swiglu_down_pallas
from repro.kernels.interpret import resolve_interpret
from repro.nn.linear import init_linear, apply_linear
from repro.sharding import rules

# Fused kernel blocks, (token block, d_ff chunk) of the forward and of the
# backward kernel: each the largest of these halved down to
# FUSED_MIN_BLOCK that divides the token count or d_ff. The fastest of
# tools/mlp_blocks.py's sweep on a v5e at smollm2's widths (PERF.md §6).
FUSED_BLOCKS_FWD = (2048, 1024)
FUSED_BLOCKS_BWD = (2048, 256)
FUSED_MIN_BLOCK = 128
# the backward kernel holds dV_g, dV_u and dU_d (3 x d_ff x k f32) in VMEM
# three times over: its accumulators and its double-buffered outputs
FUSED_MAX_RESIDENT_BYTES = 24 * 1024 * 1024

# apply_mlp calls by path ("fused", "xla"), counted at trace time
_PATH_COUNTS: collections.Counter = collections.Counter()


def mlp_path_counts() -> dict:
    """How many traced :func:`apply_mlp` calls took each path since the
    last :func:`reset_mlp_path_counts`."""
    return dict(_PATH_COUNTS)


def reset_mlp_path_counts() -> None:
    _PATH_COUNTS.clear()


def fused_kernel_backend() -> bool:
    """Whether the fused SwiGLU kernel can run here: it compiles for TPU
    only. Tests force it, together with interpret mode."""
    return jax.default_backend() == "tpu"


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


def _block(n: int, target: int) -> int:
    b = target
    while n % b:
        b //= 2
    return b


def _fits_fused(p, x, act: str) -> bool:
    """A SwiGLU MLP of three plain spectral projections (no bias, no int8
    groups) at a rank that is a multiple of 128, over an aligned token
    count, on one TPU device."""
    if act != "swiglu" or set(p) != {"gate", "up", "down"}:
        return False
    g, u, dn = p["gate"], p["up"], p["down"]
    if not all(set(q) == set(SPECTRAL_KEYS) and is_spectral(q) and q["U"].ndim == 2
               for q in (g, u, dn)):
        return False
    d_ff, k = g["V"].shape
    mesh = rules.current_mesh()
    return (
        u["V"].shape == (d_ff, k) and dn["U"].shape == (d_ff, k)
        and k % 128 == 0
        and x.dtype in (jnp.float32, jnp.bfloat16)
        and math.prod(x.shape[:-1]) % FUSED_MIN_BLOCK == 0
        and d_ff % FUSED_MIN_BLOCK == 0
        and 3 * d_ff * k * 4 <= FUSED_MAX_RESIDENT_BYTES
        and (mesh is None or mesh.size <= 1)
        and fused_kernel_backend()
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _swiglu_down(a_g, a_u, V_g, V_u, U_d, s_d, V_d, fwd_blocks, bwd_blocks, interpret):
    """``((silu(a_g @ V_g^T) * (a_u @ V_u^T)) @ U_d * s_d) @ V_d^T`` for
    a_g, a_u (M, k) in the compute dtype and the three factors' masters.
    The down projection's V side is inside so that the backward pass
    needs no forward kernel: it takes its z from the backward kernel."""
    return _swiglu_down_fwd(a_g, a_u, V_g, V_u, U_d, s_d, V_d,
                            fwd_blocks, bwd_blocks, interpret)[0]


def _swiglu_down_fwd(a_g, a_u, V_g, V_u, U_d, s_d, V_d, fwd_blocks, bwd_blocks, interpret):
    cd = a_g.dtype
    tm, tn = fwd_blocks
    z = swiglu_down_pallas(a_g, a_u, V_g.astype(cd), V_u.astype(cd), U_d.astype(cd),
                           tm=tm, tn=tn, interpret=interpret)
    y = (z * s_d.astype(cd)) @ V_d.T.astype(cd)
    # residuals are rank-space or the factors: nothing (M, d_ff), not z
    return y, (a_g, a_u, V_g, V_u, U_d, s_d, V_d)


def _swiglu_down_bwd(fwd_blocks, bwd_blocks, interpret, res, dy):
    a_g, a_u, V_g, V_u, U_d, s_d, V_d = res
    cd = a_g.dtype
    tm, tn = bwd_blocks
    sd = s_d.astype(cd)
    t = dy @ V_d.astype(cd)                      # d(z * s_d), (M, k)
    z, da_g, da_u, dV_g, dV_u, dU_d = swiglu_down_bwd_pallas(
        a_g, a_u, t * sd, V_g.astype(cd), V_u.astype(cd), U_d.astype(cd),
        tm=tm, tn=tn, interpret=interpret)
    ds_d = jnp.sum(t.astype(jnp.float32) * z.astype(jnp.float32), axis=0)
    dV_d = lax.dot_general(dy, z * sd, (((0,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)
    return (da_g, da_u, dV_g.astype(V_g.dtype), dV_u.astype(V_u.dtype),
            dU_d.astype(U_d.dtype), ds_d.astype(s_d.dtype), dV_d.astype(V_d.dtype))


_swiglu_down.defvjp(_swiglu_down_fwd, _swiglu_down_bwd)


def _mlp_fused(p, x):
    g, u, dn = p["gate"], p["up"], p["down"]
    lead, d = x.shape[:-1], x.shape[-1]
    x2 = x.reshape(-1, d)
    a_u = spectral_project(u, x2)
    a_g = spectral_project(g, x2)
    m, d_ff = x2.shape[0], g["V"].shape[0]
    blocks = [(_block(m, tm), _block(d_ff, tn)) for tm, tn in (FUSED_BLOCKS_FWD, FUSED_BLOCKS_BWD)]
    y = _swiglu_down(a_g, a_u, g["V"], u["V"], dn["U"], dn["s"], dn["V"],
                     *blocks, resolve_interpret(None))
    return y.reshape(*lead, y.shape[-1])


def apply_mlp(p, x: jax.Array, *, act: str = "swiglu") -> jax.Array:
    if _fits_fused(p, x, act):
        _PATH_COUNTS["fused"] += 1
        return _mlp_fused(p, x)
    _PATH_COUNTS["xla"] += 1
    up = apply_linear(p["up"], x)
    if act == "swiglu":
        gate = apply_linear(p["gate"], x)
        h = jax.nn.silu(gate) * up
    elif act == "gelu":
        h = jax.nn.gelu(up)
    else:
        raise ValueError(act)
    return apply_linear(p["down"], h)
