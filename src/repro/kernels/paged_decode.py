"""Paged flash-decode Pallas TPU kernels: batched one-token attention
straight against the paged KV pools, walking each slot's block table
*inside* the kernel.

The jnp serving path (nn/attention.py ``apply_*_decode_paged``) gathers
every slot's pages into a contiguous ``(b, S, ...)`` view before
attending — a full logical-cache copy written to and re-read from HBM on
every decode step. Here the block table rides in as a scalar-prefetch
operand, so the BlockSpec index map resolves ``logical page j of slot i
-> physical page bt[i, j]`` while the grid walks pages: KV stream
page-by-page from the pool into VMEM and the gathered copy never exists
(the serving-side expression of the paper's never-materialize rule).

Two variants, matching the two attention families that page:

  * GQA  — q ``(b, kvh, rep, hd)`` against pools ``(P+1, page, kvh, hd)``;
    one program per (slot, page) covering every local kv head, online
    softmax over the page axis with per-position validity
    ``pos <= seq_lens[i]``.
  * MLA (absorbed) — q already absorbed into the latent space:
    ``q_lat (b, h, L)`` / ``q_rope (b, h, R)`` against latent pools
    ``(P+1, page, L)`` / ``(P+1, page, R)``; scores are the sum of both
    dot products and the page's ckv rows double as the values (the MLA
    trick — full K/V is never expanded).

Inactive slots follow the paged_append contract: their block tables
point at the null page (physical id P) and ``seq_lens == 0``, so the
kernel harmlessly attends over one null-page position; the engine
ignores those rows.

Interpret/compiled resolution is the shared ``SCT_INTERPRET`` switch
(kernels/interpret.py). The jnp references live in kernels/paged_ref.py
and tests/test_kernels_paged.py holds the differential suite.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.interpret import resolve_interpret

NEG_INF = -1e30

_TRUTHY = ("1", "true", "yes", "on")
_FALSY = ("0", "false", "no", "off")


def paged_kernel_enabled() -> bool:
    """Serving gate for the paged flash-decode kernels, read at trace
    time by nn/attention.py. ``SCT_PAGED_KERNEL=0`` falls back to the
    jnp gather-then-attend reference path (the differential oracle);
    default is the kernel (its interpret/compiled mode is then resolved
    by ``SCT_INTERPRET`` like every other kernel)."""
    env = os.environ.get("SCT_PAGED_KERNEL")
    if env is not None and env.strip():
        v = env.strip().lower()
        if v in _TRUTHY:
            return True
        if v in _FALSY:
            return False
        raise ValueError(
            f"SCT_PAGED_KERNEL={env!r}: expected one of {_TRUTHY + _FALSY}")
    return True


# ------------------------------------------------------------------ GQA --
#
# One program per (slot, logical page) covering every local kv head: the
# K/V block is the pool's whole (page, kvh, hd) slab, so its last two
# dimensions are the array's own and the TPU tiling rule holds for any
# head count (a per-head (1, page, 1, hd) block puts 1 of kvh in the
# second-minor position, which the compiler refuses unless kvh == 1).
# q rides as (b, rep, kvh, hd) so every in-kernel op is a broadcast
# over leading axes plus lane/leading-axis reductions: no transposes,
# no head slicing, and fp32 elementwise math on the VPU.


def _gqa_page_update(q_ref, k, v, valid, acc_ref, m_ref, l_ref, scale):
    """Online-softmax update of every (rep, kv head) row against one
    page. k/v: (page, kvh, hd) fp32; valid: (page, 1, kvh, 1)."""
    q = q_ref[0].astype(jnp.float32)                       # (rep, kvh, hd)
    s_ij = jnp.sum(q[None] * k[:, None], axis=-1,
                   keepdims=True) * scale                  # (page, rep, kvh, 1)
    s_ij = jnp.where(valid, s_ij, NEG_INF)

    m_prev = m_ref[...]                                    # (rep, kvh, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s_ij, axis=0))
    p = jnp.exp(s_ij - m_new[None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=0)
    acc_ref[...] = acc_ref[...] * alpha + jnp.sum(p * v[:, None], axis=0)
    m_ref[...] = m_new


def _gqa_init(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _gqa_valid(j, sl, page, kvh):
    pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (page, 1, kvh, 1), 0)
    return pos <= sl


def _gqa_kernel(bt_ref, sl_ref, q_ref, k_ref, v_ref, o_ref,
                acc_ref, m_ref, l_ref, *, page: int, n_pages: int,
                scale: float):
    i = pl.program_id(0)                      # slot
    j = pl.program_id(1)                      # logical page

    @pl.when(j == 0)
    def _init():
        _gqa_init(acc_ref, m_ref, l_ref)

    k = k_ref[0].astype(jnp.float32)                       # (page, kvh, hd)
    v = v_ref[0].astype(jnp.float32)
    _gqa_page_update(q_ref, k, v, _gqa_valid(j, sl_ref[i], page, k.shape[1]),
                     acc_ref, m_ref, l_ref, scale)

    @pl.when(j == n_pages - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def _gqa_call(kernel, q, k_pool, block_table, scalars, pools, *,
              interpret):
    """Shared pallas_call plumbing of both GQA variants: ``scalars`` are
    the scalar-prefetch operands after the block table, ``pools`` the
    (P+1, ...) operands whose leading axis the block table indexes (a
    per-page slab of each, all other axes whole)."""
    b, kvh, rep, hd = q.shape
    page = k_pool.shape[1]
    n_pages = block_table.shape[1]
    n_sp = 1 + len(scalars)

    def slot_map(i, j, *refs):
        return (i, 0, 0, 0)

    def page_map(ndim):
        def index(i, j, bt, *refs):
            return (bt[i, j],) + (0,) * (ndim - 1)
        return index

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_sp,
        grid=(b, n_pages),
        in_specs=[pl.BlockSpec((1, rep, kvh, hd), slot_map)]
        + [pl.BlockSpec((1, *x.shape[1:]), page_map(x.ndim)) for x in pools],
        out_specs=pl.BlockSpec((1, rep, kvh, hd), slot_map),
        scratch_shapes=[
            pltpu.VMEM((rep, kvh, hd), jnp.float32),   # acc
            pltpu.VMEM((rep, kvh, 1), jnp.float32),    # running max
            pltpu.VMEM((rep, kvh, 1), jnp.float32),    # running sum
        ],
    )
    out = pl.pallas_call(
        functools.partial(kernel, page=page, n_pages=n_pages,
                          scale=1.0 / (hd ** 0.5)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rep, kvh, hd), q.dtype),
        interpret=resolve_interpret(interpret),
    )(block_table, *scalars, jnp.swapaxes(q, 1, 2), *pools)
    return jnp.swapaxes(out, 1, 2)


def paged_gqa_decode_pallas(q, k_pool, v_pool, block_table, seq_lens, *,
                            interpret: bool | None = None):
    """q: (b, kvh, rep, hd) grouped one-token queries; k_pool/v_pool:
    (P+1, page, kvh, hd) shared pools (paged_append already ran — the
    new token sits at logical position seq_lens[i]); block_table:
    (b, n_pages) int32; seq_lens: (b,) int32. Returns (b, kvh, rep, hd)
    in q.dtype: softmax attention over logical positions
    ``pos <= seq_lens[i]``, matching gather + masked _sdpa."""
    return _gqa_call(_gqa_kernel, q, k_pool, block_table, (seq_lens,),
                     (k_pool, v_pool), interpret=interpret)


# ------------------------------------------------------- GQA cold-KV --

def _gqa_cold_kernel(bt_ref, sl_ref, cold_ref, q_ref, k_ref, v_ref,
                     kq_ref, ks_ref, vq_ref, vs_ref, o_ref,
                     acc_ref, m_ref, l_ref, *, page: int, n_pages: int,
                     scale: float):
    """GQA paged decode with per-page cold-KV substitution: pages whose
    physical id is flagged in ``cold_ref`` read their K/V from the int8
    shadow pool, dequantized in-register with the page's per-channel
    scale. Hot pages take exactly :func:`_gqa_kernel`'s path."""
    i = pl.program_id(0)                      # slot
    j = pl.program_id(1)                      # logical page

    @pl.when(j == 0)
    def _init():
        _gqa_init(acc_ref, m_ref, l_ref)

    is_cold = cold_ref[bt_ref[i, j]] != 0

    def tier(hot_ref, q8_ref, scale_ref):
        cold = (q8_ref[0].astype(jnp.float32)
                * scale_ref[0].astype(jnp.float32)[None])  # (page, kvh, hd)
        return jnp.where(is_cold, cold, hot_ref[0].astype(jnp.float32))

    k = tier(k_ref, kq_ref, ks_ref)
    v = tier(v_ref, vq_ref, vs_ref)
    _gqa_page_update(q_ref, k, v, _gqa_valid(j, sl_ref[i], page, k.shape[1]),
                     acc_ref, m_ref, l_ref, scale)

    @pl.when(j == n_pages - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def paged_gqa_decode_cold_pallas(q, k_pool, v_pool, k_q8, k_scale,
                                 v_q8, v_scale, block_table, seq_lens,
                                 cold_flags, *,
                                 interpret: bool | None = None):
    """Cold-aware :func:`paged_gqa_decode_pallas`: same contract plus the
    int8 shadow pools ``k_q8``/``v_q8`` (P+1, page, kvh, hd), per-page
    scales ``k_scale``/``v_scale`` (P+1, kvh, hd) — the token axis is
    the reduced one (serving/quantize.py ``quantize_kv_pages``) — and
    ``cold_flags`` (P+1,) int32, riding as a third scalar-prefetch
    operand so the flag lookup costs one SMEM read per page."""
    return _gqa_call(_gqa_cold_kernel, q, k_pool, block_table,
                     (seq_lens, cold_flags),
                     (k_pool, v_pool, k_q8, k_scale, v_q8, v_scale),
                     interpret=interpret)


# ------------------------------------------------------------------ MLA --

def _mla_kernel(bt_ref, sl_ref, ql_ref, qr_ref, ckv_ref, kr_ref, o_ref,
                acc_ref, m_ref, l_ref, *, page: int, n_pages: int,
                scale: float):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    ckv = ckv_ref[0].astype(jnp.float32)                   # (page, L)
    s_ij = (
        jax.lax.dot_general(
            ql_ref[0].astype(jnp.float32), ckv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        + jax.lax.dot_general(
            qr_ref[0].astype(jnp.float32), kr_ref[0].astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    ) * scale                                              # (h, page)
    pos = j * page + jax.lax.broadcasted_iota(jnp.int32, s_ij.shape, 1)
    s_ij = jnp.where(pos <= sl_ref[i], s_ij, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s_ij, axis=-1, keepdims=True))
    p = jnp.exp(s_ij - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    # the page's latent rows double as the values — no K/V expansion
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, ckv, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new

    @pl.when(j == n_pages - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def paged_mla_decode_pallas(q_lat, q_rope, ckv_pool, kr_pool, block_table,
                            seq_lens, *, scale: float,
                            interpret: bool | None = None):
    """Absorbed-MLA one-token decode against paged latent pools.

    q_lat: (b, h, L) — q_nope already absorbed through W_uk; q_rope:
    (b, h, R); ckv_pool: (P+1, page, L); kr_pool: (P+1, page, R);
    block_table: (b, n_pages); seq_lens: (b,). ``scale`` is the score
    scale 1/sqrt(qk_nope_dim + qk_rope_dim) — the *pre-absorption* head
    dim, so it is passed in rather than derived from L. Returns the
    latent context o_lat (b, h, L); the caller applies W_uv + W_o."""
    b, h, lat = q_lat.shape
    rope_d = q_rope.shape[-1]
    page = ckv_pool.shape[1]
    n_pages = block_table.shape[1]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_pages),
        in_specs=[
            pl.BlockSpec((1, h, lat), lambda i, j, bt, sl: (i, 0, 0)),
            pl.BlockSpec((1, h, rope_d), lambda i, j, bt, sl: (i, 0, 0)),
            pl.BlockSpec((1, page, lat), lambda i, j, bt, sl: (bt[i, j], 0, 0)),
            pl.BlockSpec((1, page, rope_d),
                         lambda i, j, bt, sl: (bt[i, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, lat), lambda i, j, bt, sl: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, lat), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_mla_kernel, page=page, n_pages=n_pages,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, lat), q_lat.dtype),
        interpret=resolve_interpret(interpret),
    )(block_table, seq_lens, q_lat, q_rope, ckv_pool, kr_pool)


# ------------------------------------------------------- MLA cold-KV --

def _mla_cold_kernel(bt_ref, sl_ref, cold_ref, ql_ref, qr_ref,
                     ckv_ref, kr_ref, cq_ref, cs_ref, rq_ref, rs_ref,
                     o_ref, acc_ref, m_ref, l_ref, *, page: int,
                     n_pages: int, scale: float):
    """Absorbed-MLA paged decode with cold-page substitution: flagged
    pages read latent/rope rows from the int8 shadow pools, dequantized
    in-register. The dequantized ckv rows double as the values, same as
    the hot path."""
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    is_cold = cold_ref[bt_ref[i, j]] != 0
    ckv_hot = ckv_ref[0].astype(jnp.float32)               # (page, L)
    ckv_cold = (cq_ref[0].astype(jnp.float32)
                * cs_ref[0].astype(jnp.float32))           # (1, L) row
    ckv = jnp.where(is_cold, ckv_cold, ckv_hot)
    kr_hot = kr_ref[0].astype(jnp.float32)                 # (page, R)
    kr_cold = (rq_ref[0].astype(jnp.float32)
               * rs_ref[0].astype(jnp.float32))
    kr = jnp.where(is_cold, kr_cold, kr_hot)
    s_ij = (
        jax.lax.dot_general(
            ql_ref[0].astype(jnp.float32), ckv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        + jax.lax.dot_general(
            qr_ref[0].astype(jnp.float32), kr,
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    ) * scale                                              # (h, page)
    pos = j * page + jax.lax.broadcasted_iota(jnp.int32, s_ij.shape, 1)
    s_ij = jnp.where(pos <= sl_ref[i], s_ij, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s_ij, axis=-1, keepdims=True))
    p = jnp.exp(s_ij - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, ckv, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new

    @pl.when(j == n_pages - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def paged_mla_decode_cold_pallas(q_lat, q_rope, ckv_pool, kr_pool,
                                 ckv_q8, ckv_scale, kr_q8, kr_scale,
                                 block_table, seq_lens, cold_flags, *,
                                 scale: float,
                                 interpret: bool | None = None):
    """Cold-aware :func:`paged_mla_decode_pallas`: adds the int8 latent
    shadow pools (P+1, page, L)/(P+1, page, R), their per-page scales
    (P+1, L)/(P+1, R), and the (P+1,) int32 ``cold_flags`` as a third
    scalar-prefetch operand. The scales enter the kernel as
    (P+1, 1, L)/(P+1, 1, R) views, so each page's scale block spans the
    array's own last two dimensions (the TPU tiling rule)."""
    b, h, lat = q_lat.shape
    rope_d = q_rope.shape[-1]
    page = ckv_pool.shape[1]
    n_pages = block_table.shape[1]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_pages),
        in_specs=[
            pl.BlockSpec((1, h, lat), lambda i, j, bt, sl, cold: (i, 0, 0)),
            pl.BlockSpec((1, h, rope_d),
                         lambda i, j, bt, sl, cold: (i, 0, 0)),
            pl.BlockSpec((1, page, lat),
                         lambda i, j, bt, sl, cold: (bt[i, j], 0, 0)),
            pl.BlockSpec((1, page, rope_d),
                         lambda i, j, bt, sl, cold: (bt[i, j], 0, 0)),
            pl.BlockSpec((1, page, lat),
                         lambda i, j, bt, sl, cold: (bt[i, j], 0, 0)),
            pl.BlockSpec((1, 1, lat),
                         lambda i, j, bt, sl, cold: (bt[i, j], 0, 0)),
            pl.BlockSpec((1, page, rope_d),
                         lambda i, j, bt, sl, cold: (bt[i, j], 0, 0)),
            pl.BlockSpec((1, 1, rope_d),
                         lambda i, j, bt, sl, cold: (bt[i, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, lat),
                               lambda i, j, bt, sl, cold: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, lat), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_mla_cold_kernel, page=page, n_pages=n_pages,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, lat), q_lat.dtype),
        interpret=resolve_interpret(interpret),
    )(block_table, seq_lens, cold_flags, q_lat, q_rope, ckv_pool, kr_pool,
      ckv_q8, ckv_scale[:, None], kr_q8, kr_scale[:, None])
