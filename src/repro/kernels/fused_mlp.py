"""Fused spectral SwiGLU core: z = (silu(a_g @ V_g^T) * (a_u @ V_u^T)) @ U_d.

A spectral SwiGLU MLP is three rank-k projections. Between the rank-space
products ``a_g = (x @ U_g) * s_g`` and ``a_u = (x @ U_u) * s_u`` (M, k)
and the down projection's ``(z * s_d) @ V_d^T`` lie the d_ff-wide
tensors: gate, up, ``h = silu(gate) * up`` and, in the backward pass,
``dh``, ``dgate`` and ``dup``. These kernels compute them chunk by chunk
in VMEM; nothing d_ff-wide is written to HBM.

Forward, grid (M/tm, d_ff/tn): step (i, j) forms gate and up for one
(tm, tn) chunk in f32, rounds ``h`` to the factors' dtype where it enters
the MXU, and adds ``h @ U_d[j]`` into an f32 (tm, k) accumulator; only z
is written.

Backward, one kernel on the same grid: step (i, j) recomputes gate and up
and ``dh = dz @ U_d[j]^T``, forms ``dgate`` and ``dup`` in f32, and adds
into
  - f32 (tm, k) accumulators over j: z (the down projection's gradients
    need it, and the forward kernel's output is not kept), da_g, da_u;
  - f32 (k, tn) slices over i of dV_g^T, dV_u^T and dU_d^T, held in
    VMEM scratch for the whole grid (3 x d_ff x k x 4 bytes); at the
    last token block each slice is transposed once into the (d_ff, k)
    outputs, which stay in VMEM and are written at the grid's end.
Every matmul is (rows, contraction) x (contraction, cols) or contracts
the minor dims of both; the rank-space operands come in both (M, k) and
(k, M) layouts so that no (tm, tn) tile is transposed.

MXU operands are the factors' dtype, accumulation is f32, silu and its
derivative are f32. f32 operands multiply at full precision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# contract the minor dims of both operands: a @ b^T
_NT = (((1,), (1,)), ((), ()))
VMEM_LIMIT_BYTES = 100 * 1024 * 1024


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    precision = lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return lax.dot_general(a, b, dims, precision=precision,
                           preferred_element_type=jnp.float32)


def _sigmoid(g):
    # one tanh on the EUP, where 1 / (1 + exp(-g)) takes an exp and a divide
    return 0.5 * jnp.tanh(0.5 * g) + 0.5


def _fwd_kernel(ag_ref, au_ref, vg_ref, vu_ref, ud_ref, z_ref, acc_ref):
    j = pl.program_id(1)
    g = _dot(ag_ref[...], vg_ref[...], _NT)          # (tm, tn) f32
    u = _dot(au_ref[...], vu_ref[...], _NT)
    h = (g * _sigmoid(g) * u).astype(ud_ref.dtype)
    part = _dot(h, ud_ref[...])                      # (tm, k)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = part

    @pl.when(j > 0)
    def _():
        acc_ref[...] += part

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        z_ref[...] = acc_ref[...].astype(z_ref.dtype)


def _bwd_kernel(ag_ref, au_ref, dz_ref, agt_ref, aut_ref, dzt_ref,
                vg_ref, vu_ref, ud_ref,
                z_ref, dag_ref, dau_ref, dvg_ref, dvu_ref, dud_ref,
                z_acc, dag_acc, dau_acc, dvgt_acc, dvut_acc, dudt_acc):
    i, j = pl.program_id(0), pl.program_id(1)
    cd = ud_ref.dtype
    vg, vu, ud = vg_ref[...], vu_ref[...], ud_ref[...]
    g = _dot(ag_ref[...], vg, _NT)                   # (tm, tn) f32
    u = _dot(au_ref[...], vu, _NT)
    dh = _dot(dz_ref[...], ud, _NT)
    sig = _sigmoid(g)
    silu = g * sig
    h = (silu * u).astype(cd)
    du = (dh * silu).astype(cd)
    dg = (dh * u * (sig * (1.0 + g * (1.0 - sig)))).astype(cd)

    rows = ((z_acc, _dot(h, ud)), (dag_acc, _dot(dg, vg)), (dau_acc, _dot(du, vu)))
    cols = ((dvgt_acc, _dot(agt_ref[...], dg)), (dvut_acc, _dot(aut_ref[...], du)),
            (dudt_acc, _dot(dzt_ref[...], h)))        # (k, tn) each

    @pl.when(j == 0)
    def _():
        for acc, part in rows:
            acc[...] = part

    @pl.when(j > 0)
    def _():
        for acc, part in rows:
            acc[...] += part

    @pl.when(i == 0)
    def _():
        for ref, part in cols:
            ref[j] = part

    @pl.when(i > 0)
    def _():
        for ref, part in cols:
            ref[j] += part

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        tn = dvg_ref.shape[0] // pl.num_programs(1)
        rows_j = pl.ds(pl.multiple_of(j * tn, tn), tn)
        for out, (acc, _) in zip((dvg_ref, dvu_ref, dud_ref), cols):
            out[rows_j, :] = acc[j].T

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        for out, (acc, _) in zip((z_ref, dag_ref, dau_ref), rows):
            out[...] = acc[...].astype(out.dtype)


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def swiglu_down_pallas(a_g, a_u, V_g, V_u, U_d, *, tm: int, tn: int,
                       interpret: bool = False):
    """a_g, a_u: (M, k); V_g, V_u, U_d: (d_ff, k), all one dtype ->
    z (M, k). Requires M % tm == 0 and d_ff % tn == 0."""
    M, k = a_g.shape
    d_ff = V_g.shape[0]
    assert M % tm == 0 and d_ff % tn == 0, (M, d_ff, tm, tn)
    rows = pl.BlockSpec((tm, k), lambda i, j: (i, 0))
    chunk = pl.BlockSpec((tn, k), lambda i, j: (j, 0))
    return pl.pallas_call(
        _fwd_kernel,
        grid=(M // tm, d_ff // tn),
        in_specs=[rows, rows, chunk, chunk, chunk],
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct((M, k), a_g.dtype),
        scratch_shapes=[pltpu.VMEM((tm, k), jnp.float32)],
        compiler_params=_params("parallel", "arbitrary"),
        interpret=interpret,
        name="sct_mlp_fwd",
    )(a_g, a_u, V_g, V_u, U_d)


def swiglu_down_bwd_pallas(a_g, a_u, dz, V_g, V_u, U_d, *, tm: int, tn: int,
                           interpret: bool = False):
    """The backward pass of :func:`swiglu_down_pallas` for the cotangent
    ``dz`` (M, k). Returns z and da_g, da_u (M, k) in the operands'
    dtype, and dV_g, dV_u, dU_d (d_ff, k) in f32."""
    M, k = a_g.shape
    d_ff = V_g.shape[0]
    assert M % tm == 0 and d_ff % tn == 0, (M, d_ff, tm, tn)
    nj = d_ff // tn
    rows = pl.BlockSpec((tm, k), lambda i, j: (i, 0))
    rows_t = pl.BlockSpec((k, tm), lambda i, j: (0, i))
    chunk = pl.BlockSpec((tn, k), lambda i, j: (j, 0))
    whole = pl.BlockSpec((d_ff, k), lambda i, j: (0, 0))
    row_out = jax.ShapeDtypeStruct((M, k), a_g.dtype)
    col_out = jax.ShapeDtypeStruct((d_ff, k), jnp.float32)
    return pl.pallas_call(
        _bwd_kernel,
        grid=(M // tm, nj),
        in_specs=[rows, rows, rows, rows_t, rows_t, rows_t, chunk, chunk, chunk],
        out_specs=[rows, rows, rows, whole, whole, whole],
        out_shape=[row_out] * 3 + [col_out] * 3,
        scratch_shapes=[pltpu.VMEM((tm, k), jnp.float32)] * 3
        + [pltpu.VMEM((nj, k, tn), jnp.float32)] * 3,
        compiler_params=_params("arbitrary", "arbitrary"),
        interpret=interpret,
        name="sct_mlp_bwd",
    )(a_g, a_u, dz, a_g.T, a_u.T, dz.T, V_g, V_u, U_d)
