"""Pallas TPU flash-attention (forward) kernel.

Grid: (batch*kv_groups*rep, q_chunks). Each program streams kv chunks
for one (batch, head, q-chunk) tile with online softmax — scores/probs
never leave VMEM. Forward only, with no causal block skipping, and no
model path calls it: on TPU the training attention runs the bundled
splash-attention kernel (nn/attention.py ``_sdpa_fused``). The
roofline still substitutes this kernel's HBM traffic for the jnp
``_flash`` fallback's (roofline/hlo_cost.py, ``PALLAS_EQ`` scopes).

VMEM at cq=512, ck=1024, d=128, bf16 in / fp32 acc:
q 128K + k/v 2x256K + scores 2MB (f32) + acc 256K ~= 3 MB << 16 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.interpret import resolve_interpret

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            nk: int, cq: int, ck: int, causal: bool, scale: float):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    s_ij = jax.lax.dot_general(
        q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                              # (cq, ck)
    if causal:
        qpos = qi * cq + jax.lax.broadcasted_iota(jnp.int32, (cq, ck), 0)
        kpos = kj * ck + jax.lax.broadcasted_iota(jnp.int32, (cq, ck), 1)
        s_ij = jnp.where(qpos >= kpos, s_ij, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s_ij, axis=-1, keepdims=True))
    p = jnp.exp(s_ij - m_new)                              # (cq, ck)
    alpha = jnp.exp(m_prev - m_new)                        # (cq, 1)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new

    @pl.when(kj == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           cq: int = 512, ck: int = 1024,
                           interpret: bool | None = None):
    """q: (B, sq, d), k/v: (B, skv, d) with B = batch*heads folded.
    Returns (B, sq, d). Requires sq % cq == 0, skv % ck == 0."""
    B, sq, d = q.shape
    skv = k.shape[1]
    cq = min(cq, sq)
    ck = min(ck, skv)
    assert sq % cq == 0 and skv % ck == 0, (sq, skv, cq, ck)
    nq, nk = sq // cq, skv // ck
    scale = 1.0 / (d ** 0.5)

    return pl.pallas_call(
        functools.partial(_kernel, nk=nk, cq=cq, ck=ck, causal=causal, scale=scale),
        grid=(B, nq, nk),
        in_specs=[
            pl.BlockSpec((1, cq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, ck, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, ck, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, cq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((cq, d), jnp.float32),   # acc
            pltpu.VMEM((cq, 1), jnp.float32),   # running max
            pltpu.VMEM((cq, 1), jnp.float32),   # running sum
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
