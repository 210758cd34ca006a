"""Attention blocks: GQA (llama/qwen/granite/whisper/qwen2-vl) and MLA
(deepseek-v2/v3), each with a training path, a prefill path (fills the
cache) and a single-token decode path.

Projections can optionally be spectral (SCT) via ``rank`` — the paper
leaves attention dense (S5 'Attention layers'); we expose the extension
as a config flag and benchmark it separately.

Cache layouts (per layer, stacked with a leading L axis by the model):
  GQA: {"k": (b, S, kvh, hd), "v": (b, S, kvh, hd)}
  MLA: {"ckv": (b, S, kv_lora), "krope": (b, S, rope_dim)}   <- the MLA
       memory win: compressed latent is cached, not full K/V.
"""
from __future__ import annotations

import collections
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as splash, splash_attention_mask as splash_masks)

from repro.kernels.interpret import resolve_interpret
from repro.nn.linear import init_linear, apply_linear
from repro.nn.norms import init_rmsnorm, apply_rmsnorm
from repro.nn.rotary import apply_rope, apply_mrope
from repro.sharding import rules

NEG_INF = -1e30


def _tp_slice(a, tp_axis, n_local, axis):
    """This shard's contiguous block of ``n_local`` entries along
    ``axis`` under ``shard_map`` — block i of the mesh axis owns
    entries [i*n_local, (i+1)*n_local). Head blocks are contiguous per
    kv group (see the (kvh, rep) reshape in :func:`_sdpa`), so slicing
    q and k/v by the same shard index keeps GQA grouping congruent
    with the single-device layout."""
    idx = jax.lax.axis_index(tp_axis)
    return jax.lax.dynamic_slice_in_dim(a, idx * n_local, n_local, axis=axis)


FLASH_THRESHOLD = 2048  # direct softmax below this sequence length
# big chunks: few loop iterations => few HBM round-trips of the chunk
# intermediates in the jnp fallback, which still writes them to HBM
FLASH_Q_CHUNK = 2048
FLASH_KV_CHUNK = 4096

# Fused causal kernel (splash attention, forward and one dQ/dK/dV
# backward kernel): each block is the largest of these halved down to
# 128 that divides the sequence. Chosen on a v5e at 4 x 2048 x 32 heads
# x 64 against block_q 256/512, block_kv 512/2048 and the split dQ +
# dK/dV backward (PERF.md §5).
FUSED_MIN_SEQ = 128
FUSED_BLOCK_Q = 1024
FUSED_BLOCK_KV = 1024
FUSED_BLOCK_KV_COMPUTE = 512

# _sdpa calls by path ("fused", "chunked", "direct"), counted at trace time
_PATH_COUNTS: collections.Counter = collections.Counter()


def attention_path_counts() -> dict:
    """How many traced ``_sdpa`` calls took each path since the last
    :func:`reset_attention_path_counts`."""
    return dict(_PATH_COUNTS)


def reset_attention_path_counts() -> None:
    _PATH_COUNTS.clear()


def fused_kernel_backend() -> bool:
    """Whether the fused causal kernel can run here: it compiles for TPU
    only. Tests force it, together with interpret mode."""
    return jax.default_backend() == "tpu"


def _fused_block(s: int, target: int) -> int:
    b = target
    while s % b:
        b //= 2
    return b


def _fused_block_sizes(s: int):
    """Blocks for sequence ``s``. q, k and v enter the kernel with the
    sequence minor: a head dim of 64 minor would be padded to 128 lanes
    in HBM, and XLA would give the rope and projections feeding the
    kernel that padded layout too (PERF.md §5)."""
    bq = _fused_block(s, FUSED_BLOCK_Q)
    bkv = _fused_block(s, FUSED_BLOCK_KV)
    bkvc = _fused_block(bkv, FUSED_BLOCK_KV_COMPUTE)
    seq_minor = splash.QKVLayout.SEQ_MINOR
    return splash.BlockSizes(
        block_q=bq, block_kv=bkv, block_kv_compute=bkvc,
        block_q_dkv=bq, block_kv_dkv=bkv, block_kv_dkv_compute=bkvc,
        use_fused_bwd_kernel=True,
        q_layout=seq_minor, k_layout=seq_minor, v_layout=seq_minor)


@functools.lru_cache(maxsize=32)
def _fused_kernel(heads: int, s: int, interpret: bool, block_sizes):
    """The causal splash-attention kernel for ``heads`` query heads at
    sequence ``s``, built once per shape: the mask's block tables are
    host constants, made outside any trace."""
    mask = splash_masks.MultiHeadMask([splash_masks.CausalMask((s, s))] * heads)
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mha(mask, block_sizes=block_sizes, head_shards=1,
                                      q_seq_shards=1, interpret=interpret)


def _fits_fused(q, k, v, *, causal, q_offset, kv_len_mask) -> bool:
    """Causal self-attention at an aligned length on one TPU device.
    q: (b, sq, h, d); k/v: (b, skv, kvh, d)."""
    sq, d = q.shape[1], q.shape[-1]
    mesh = rules.current_mesh()
    return (
        causal
        and kv_len_mask is None
        and isinstance(q_offset, int) and q_offset == 0
        and sq == k.shape[1]
        and sq >= FUSED_MIN_SEQ and sq % FUSED_MIN_SEQ == 0
        and k.shape[-1] == d and v.shape[-1] == d     # MLA: dv != d
        and (mesh is None or mesh.size <= 1)
        and fused_kernel_backend()
    )


def _sdpa_fused(q, k, v):
    """Causal attention through the fused kernel, forward and backward:
    scores and probabilities stay in VMEM, kv blocks above the diagonal
    are skipped. Query head i reads kv head i // (h // kvh), as the
    grouped einsums do; K/V are never repeated. The kernel does not
    scale, so 1/sqrt(d) is folded into q (rounded once to q's dtype).
    q: (b, s, h, d); k/v: (b, s, kvh, d)."""
    b, s, h, d = q.shape
    kernel = _fused_kernel(h, s, resolve_interpret(None), _fused_block_sizes(s))
    qs = (q.astype(jnp.float32) * (1.0 / d ** 0.5)).astype(q.dtype)
    heads_first = lambda t: jnp.swapaxes(t, 1, 2)          # (b, heads, s, d)
    out = jax.vmap(kernel)(heads_first(qs), heads_first(k), heads_first(v))
    return jnp.swapaxes(out, 1, 2)


def _sdpa_direct(q, k, v, *, causal: bool, q_offset=0, kv_len_mask=None):
    """Reference O(s^2)-memory attention — short sequences and
    single-token decode (sq == 1). q: (b, sq, g, r, d) grouped;
    k/v: (b, skv, g, d)."""
    b, sq, g, r, d = q.shape
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(d))
    if causal:
        qpos = jnp.arange(sq) + q_offset
        kpos = jnp.arange(k.shape[1])
        mask = qpos[:, None] >= kpos[None, :]
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    if kv_len_mask is not None:
        scores = jnp.where(kv_len_mask[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bgrqk,bkgd->bqgrd", probs, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, causal):
    out, _, _ = _flash_fwd_impl(q, k, v, causal)
    return out


def _flash_vjp_fwd(q, k, v, causal):
    out, m, l = _flash_fwd_impl(q, k, v, causal)
    return out, (q, k, v, out, m, l)


def _flash_vjp_bwd(causal, res, dout):
    return _flash_bwd_impl(causal, res, dout)


def _flash_fwd_impl(q, k, v, causal):
    """Exact flash-style attention in pure jnp: lax.map over q chunks,
    lax.scan over kv chunks with online softmax. Peak live scores tensor
    is (b, g, r, cq, ck) instead of (b, g, r, s, s), but each chunk's
    scores still go through HBM. It runs wherever :func:`_sdpa` does not
    take the fused kernel above FLASH_THRESHOLD (non-causal, more than
    one device, not TPU); the 512-device dry-run partitions it.
    q: (b, sq, g, r, d); k/v: (b, skv, g, d).
    Returns (out, m, l) — the softmax stats the backward needs."""
    b, sq, g, r, d = q.shape
    skv = k.shape[1]
    dv = v.shape[-1]          # MLA: v_head_dim != qk head dim
    cq = min(FLASH_Q_CHUNK, sq)
    ck = min(FLASH_KV_CHUNK, skv)
    nq, nk = sq // cq, skv // ck
    scale = 1.0 / jnp.sqrt(jnp.float32(d))

    qc = q.reshape(b, nq, cq, g, r, d)
    kc = k.reshape(b, nk, ck, g, d)
    vc = v.reshape(b, nk, ck, g, dv)

    def per_q_chunk(qi):
        q_i = qc[:, qi]                                   # (b, cq, g, r, d)
        qpos = qi * cq + jnp.arange(cq)

        def kv_step(carry, kj):
            acc, m, l = carry
            k_j = kc[:, kj]
            v_j = vc[:, kj]
            s_ij = jnp.einsum("bqgrd,bkgd->bgrqk", q_i, k_j).astype(jnp.float32) * scale
            if causal:
                kpos = kj * ck + jnp.arange(ck)
                mask = qpos[:, None] >= kpos[None, :]
                s_ij = jnp.where(mask[None, None, None], s_ij, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s_ij, axis=-1))
            p = jnp.exp(s_ij - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bgrqk,bkgd->bgrqd", p.astype(q_i.dtype), v_j
            ).astype(jnp.float32)
            return (acc, m_new, l), None

        acc0 = jnp.zeros((b, g, r, cq, dv), jnp.float32)
        m0 = jnp.full((b, g, r, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, g, r, cq), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(kv_step, (acc0, m0, l0), jnp.arange(nk))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        # out: (b, g, r, cq, dv) -> (b, cq, g, r, dv); stats (b, g, r, cq)
        return jnp.moveaxis(out, 3, 1).astype(q.dtype), m, l

    outs, ms, ls = jax.lax.map(per_q_chunk, jnp.arange(nq))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, sq, g, r, dv)
    return out, ms, ls                                    # ms/ls: (nq, b, g, r, cq)


def _flash_bwd_impl(causal, res, dout):
    """Chunked flash backward (the standard recompute-p form — what the
    Pallas backward kernel implements on TPU):
      delta_i = rowsum(dO_i * O_i)
      p_ij    = exp(s_ij - m_i) / l_i
      dV_j   += p_ij^T dO_i
      ds_ij   = p_ij * (dO_i V_j^T - delta_i) * scale
      dQ_i   += ds_ij K_j ;  dK_j += ds_ij^T Q_i
    Never materializes an (s, s) tensor."""
    q, k, v, out, ms, ls = res
    b, sq, g, r, d = q.shape
    skv = k.shape[1]
    dv = v.shape[-1]
    cq = min(FLASH_Q_CHUNK, sq)
    ck = min(FLASH_KV_CHUNK, skv)
    nq, nk = sq // cq, skv // ck
    scale = 1.0 / jnp.sqrt(jnp.float32(d))

    qc = q.reshape(b, nq, cq, g, r, d)
    kc = k.reshape(b, nk, ck, g, d)
    vc = v.reshape(b, nk, ck, g, dv)
    doc = dout.reshape(b, nq, cq, g, r, dv)
    oc = out.reshape(b, nq, cq, g, r, dv)

    def per_q_chunk(carry, qi):
        dk_acc, dv_acc = carry                            # (b, skv, g, d/dv) f32
        q_i = qc[:, qi]
        do_i = doc[:, qi].astype(jnp.float32)
        o_i = oc[:, qi].astype(jnp.float32)
        m_i = ms[qi]                                      # (b, g, r, cq)
        l_i = jnp.maximum(ls[qi], 1e-30)
        delta = jnp.einsum("bqgrd,bqgrd->bgrq", do_i, o_i)  # (b, g, r, cq)
        qpos = qi * cq + jnp.arange(cq)

        def kv_step(inner, kj):
            dq_i, dk_acc, dv_acc = inner
            k_j = kc[:, kj]
            v_j = vc[:, kj]
            s_ij = jnp.einsum("bqgrd,bkgd->bgrqk", q_i, k_j).astype(jnp.float32) * scale
            if causal:
                kpos = kj * ck + jnp.arange(ck)
                mask = qpos[:, None] >= kpos[None, :]
                s_ij = jnp.where(mask[None, None, None], s_ij, NEG_INF)
            p = jnp.exp(s_ij - m_i[..., None]) / l_i[..., None]   # (b,g,r,cq,ck)
            pv = p.astype(v_j.dtype)
            dv_j = jnp.einsum("bgrqk,bqgrd->bkgd", pv, do_i.astype(v_j.dtype))
            dp = jnp.einsum("bqgrd,bkgd->bgrqk", do_i.astype(v_j.dtype), v_j).astype(jnp.float32)
            ds = p * (dp - delta[..., None]) * scale             # (b,g,r,cq,ck)
            dsq = ds.astype(q_i.dtype)
            dq_i = dq_i + jnp.einsum("bgrqk,bkgd->bqgrd", dsq, k_j).astype(jnp.float32)
            dk_j = jnp.einsum("bgrqk,bqgrd->bkgd", dsq, q_i)
            dk_acc = _acc_update(dk_acc, dk_j.astype(jnp.float32), kj, ck)
            dv_acc = _acc_update(dv_acc, dv_j.astype(jnp.float32), kj, ck)
            return (dq_i, dk_acc, dv_acc), None

        dq0 = jnp.zeros((b, cq, g, r, d), jnp.float32)
        (dq_i, dk_acc, dv_acc), _ = jax.lax.scan(
            kv_step, (dq0, dk_acc, dv_acc), jnp.arange(nk))
        return (dk_acc, dv_acc), dq_i

    dk0 = jnp.zeros((b, skv, g, d), jnp.float32)
    dv0 = jnp.zeros((b, skv, g, dv), jnp.float32)
    with jax.named_scope("PALLAS_EQ_flash_attention_bwd"):
        (dk, dvv), dqs = jax.lax.scan(per_q_chunk, (dk0, dv0), jnp.arange(nq))
    dq = jnp.moveaxis(dqs, 0, 1).reshape(b, sq, g, r, d).astype(q.dtype)
    return dq, dk.astype(k.dtype), dvv.astype(v.dtype)


def _acc_update(acc, delta, kj, ck):
    """acc[:, kj*ck:(kj+1)*ck] += delta, XLA-friendly."""
    cur = jax.lax.dynamic_slice_in_dim(acc, kj * ck, ck, axis=1)
    return jax.lax.dynamic_update_slice_in_dim(acc, cur + delta, kj * ck, axis=1)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _sdpa(q, k, v, *, causal: bool, q_offset=0, kv_len_mask=None):
    """q: (b, sq, h, d); k/v: (b, skv, kvh, d). GQA via grouped-head
    einsums — kv heads are never materialized repeated (a rep x HBM-
    traffic save over jnp.repeat). Softmax in fp32. causal uses absolute
    positions (q_offset for decode); kv_len_mask: (b, skv) valid slots.

    Three paths, chosen from what is seen here: the fused kernel for
    causal self-attention at an aligned length on one TPU device
    (:func:`_fits_fused`); the chunked jnp ``_flash`` above
    FLASH_THRESHOLD; else the direct softmax (decode, traced-offset
    prefill, masks, short or unaligned sequences).
    """
    b, sq, h, d = q.shape
    if _fits_fused(q, k, v, causal=causal, q_offset=q_offset, kv_len_mask=kv_len_mask):
        _PATH_COUNTS["fused"] += 1
        return _sdpa_fused(q, k, v)
    skv, kvh = k.shape[1], k.shape[2]
    rep = h // kvh
    qg = q.reshape(b, sq, kvh, rep, d)
    dv = v.shape[-1]
    use_flash = (
        kv_len_mask is None
        and sq == skv
        and sq > FLASH_THRESHOLD
        and sq % min(FLASH_Q_CHUNK, sq) == 0
        and skv % min(FLASH_KV_CHUNK, skv) == 0
        and isinstance(q_offset, int)   # traced offset (chunked prefill)
        and q_offset == 0               # => direct path, mask handles it
    )
    if use_flash:
        _PATH_COUNTS["chunked"] += 1
        # PALLAS_EQ marker: the roofline cost model substitutes the HBM
        # traffic of kernels/flash_attention.py for this jnp region's
        # (roofline/hlo_cost.py), though that kernel does not run here.
        with jax.named_scope("PALLAS_EQ_flash_attention"):
            out = _flash(qg, k, v, causal)
    else:
        _PATH_COUNTS["direct"] += 1
        out = _sdpa_direct(qg, k, v, causal=causal, q_offset=q_offset,
                           kv_len_mask=kv_len_mask)
    return out.reshape(b, sq, h, dv)


# ---------------------------------------------------------------- GQA ----

def init_gqa(key, cfg, dtype=jnp.float32):
    """cfg needs: d_model, n_heads, n_kv_heads, head_dim, qkv_bias,
    attn_rank (None => dense, the paper-faithful default)."""
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    r = cfg.attn_rank
    return {
        "wq": init_linear(kq, d, h * hd, rank=r, bias=cfg.qkv_bias, dtype=dtype),
        "wk": init_linear(kk, d, kvh * hd, rank=r, bias=cfg.qkv_bias, dtype=dtype),
        "wv": init_linear(kv, d, kvh * hd, rank=r, bias=cfg.qkv_bias, dtype=dtype),
        "wo": init_linear(ko, h * hd, d, rank=r, bias=False, dtype=dtype),
    }


def _gqa_qkv(p, x, cfg, positions):
    b, s, _ = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = apply_linear(p["wq"], x).reshape(b, s, h, hd)
    k = apply_linear(p["wk"], x).reshape(b, s, kvh, hd)
    v = apply_linear(p["wv"], x).reshape(b, s, kvh, hd)
    if cfg.rope == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    elif cfg.rope == "mrope":
        mpos = jnp.broadcast_to(positions[None], (3,) + positions.shape)
        q = apply_mrope(q, mpos, cfg.mrope_sections, cfg.rope_theta)
        k = apply_mrope(k, mpos, cfg.mrope_sections, cfg.rope_theta)
    return q, k, v


def apply_gqa(p, x, cfg, *, positions, causal=True):
    """Training / no-cache forward."""
    b, s, _ = x.shape
    q, k, v = _gqa_qkv(p, x, cfg, positions)
    o = _sdpa(q, k, v, causal=causal)
    return apply_linear(p["wo"], o.reshape(b, s, -1))


def gqa_init_cache(cfg, batch, max_seq, dtype=jnp.bfloat16):
    kvh, hd = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, max_seq, kvh, hd), dtype=dtype),
        "v": jnp.zeros((batch, max_seq, kvh, hd), dtype=dtype),
    }


def apply_gqa_prefill(p, x, cfg, *, positions, cache):
    """Fill cache[:, :s] and return outputs (causal)."""
    b, s, _ = x.shape
    q, k, v = _gqa_qkv(p, x, cfg, positions)
    cache = {
        "k": jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0)),
        "v": jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0)),
    }
    o = _sdpa(q, k, v, causal=True)
    return apply_linear(p["wo"], o.reshape(b, s, -1)), cache


def apply_gqa_decode(p, x, cfg, *, cache, cache_len):
    """One-token step. x: (b, 1, d); cache_len: scalar int32 (tokens
    already in cache). Attends over the full cache with a validity mask
    — S stays static so the step compiles once."""
    b, s, _ = x.shape
    positions = jnp.broadcast_to(cache_len[None, None], (b, s)).astype(jnp.int32)
    q, k, v = _gqa_qkv(p, x, cfg, positions)
    ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), cache_len, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), cache_len, axis=1)
    S = ck.shape[1]
    valid = (jnp.arange(S)[None, :] <= cache_len).astype(bool)
    valid = jnp.broadcast_to(valid, (b, S))
    # Decode-step attention computes in fp32 regardless of model dtype
    # (the step is memory-bound, so the upcast is free). All three decode
    # paths — this static oracle, the paged jnp branch, and the Pallas
    # flash-decode kernel (fp32 scratch) — then agree to fp32 epsilon
    # with a single output rounding, which is what keeps bf16 greedy
    # decode token-identical across them.
    o = _sdpa(q.astype(jnp.float32), ck.astype(jnp.float32),
              cv.astype(jnp.float32), causal=False,
              kv_len_mask=valid).astype(q.dtype)
    return apply_linear(p["wo"], o.reshape(b, s, -1)), {"k": ck, "v": cv}


def _gather_cold(cache, name, block_table, cold_flags):
    """Gather one paged pool leaf into the logical (b, S, *f) view with
    cold pages transparently substituted by their dequantized int8
    shadow rows (streaming cold-KV tier; serving/quantize.py
    ``quantize_kv_pages``). Returns fp32 when substitution is active so
    the jnp path sees the same dequantized values as the cold-aware
    Pallas kernels; without cold flags (or without shadow leaves in the
    cache) this is exactly ``paged_gather``."""
    from repro.serving.paged_cache import paged_gather

    pool = cache[name]
    g = paged_gather(pool, block_table)
    if cold_flags is None or name + "_q8" not in cache:
        return g
    b, n = block_table.shape
    page = pool.shape[1]
    q8 = paged_gather(cache[name + "_q8"], block_table)        # (b, S, *f)
    scale = jnp.take(cache[name + "_scale"], block_table, axis=0)
    deq = (q8.astype(jnp.float32).reshape(b, n, page, *pool.shape[2:])
           * scale[:, :, None].astype(jnp.float32)).reshape(g.shape)
    flag = jnp.take(cold_flags, block_table, axis=0) != 0      # (b, n)
    flag = jnp.repeat(flag, page, axis=1)                      # (b, S)
    flag = flag.reshape(flag.shape + (1,) * (g.ndim - 2))
    return jnp.where(flag, deq, g.astype(jnp.float32))


def apply_gqa_prefill_paged(p, x, cfg, *, cache, block_table, start,
                            tp_axis=None, tp_size=1, cold_flags=None):
    """Chunked prefill from a logical offset against a paged pool.

    x: (1, c, d) — one sequence's prompt tokens for absolute positions
    [start, start+c); cache: {"k"/"v": (P+1, page, kvh, hd)} shared
    pool; block_table: (1, n_pages); start: scalar int32 (data — one
    executable per chunk length serves every offset). The chunk's K/V
    is scattered into the sequence's pages, then attention runs over
    the gathered logical view: positions < start are the already-cached
    (possibly shared) prefix, positions ≥ start+c stay behind the
    causal mask. Row-for-row this matches a full static prefill
    restricted to the chunk's query positions.

    ``tp_axis`` runs the body tensor-parallel under ``shard_map``:
    projections are computed from replicated weights, this shard keeps
    its contiguous kv-head block (``cache`` is the pool *shard* with
    kvh/tp_size heads), attention runs per-shard, and the head outputs
    are all-gathered before the replicated wo — per-head math is
    untouched, so outputs are bit-identical to single-device."""
    from repro.serving.paged_cache import paged_write_slice

    b, c, _ = x.shape
    positions = jnp.broadcast_to(start + jnp.arange(c, dtype=jnp.int32)[None], (b, c))
    q, k, v = _gqa_qkv(p, x, cfg, positions)
    if tp_axis is not None:
        q = _tp_slice(q, tp_axis, cfg.n_heads // tp_size, 2)
        k = _tp_slice(k, tp_axis, cfg.n_kv_heads // tp_size, 2)
        v = _tp_slice(v, tp_axis, cfg.n_kv_heads // tp_size, 2)
    pk = paged_write_slice(cache["k"], block_table[0], start, k[0])
    pv = paged_write_slice(cache["v"], block_table[0], start, v[0])
    new_cache = dict(cache, k=pk, v=pv)     # shadow leaves ride through
    ck = _gather_cold(new_cache, "k", block_table, cold_flags)
    cv = _gather_cold(new_cache, "v", block_table, cold_flags)
    o = _sdpa(q, ck.astype(q.dtype), cv.astype(q.dtype), causal=True, q_offset=start)
    if tp_axis is not None:
        o = jax.lax.all_gather(o, tp_axis, axis=2, tiled=True)
    return apply_linear(p["wo"], o.reshape(b, c, -1)), new_cache


def apply_gqa_decode_paged(p, x, cfg, *, cache, block_table, seq_lens,
                           tp_axis=None, tp_size=1, cold_flags=None):
    """One-token step against a paged pool (serving/paged_cache.py).

    cache: {"k"/"v": (P+1, page, kvh, hd)} — this layer's shared pool;
    block_table: (b, n_pages) int32; seq_lens: (b,) int32 per-slot fill
    level (mixed lengths — the continuous-batching contract). The new
    token is appended into each slot's current page, then attention runs
    through the paged flash-decode kernel, which walks the block table
    inside the kernel (kernels/paged_decode.py — no gathered-KV copy).
    ``SCT_PAGED_KERNEL=0`` selects the jnp reference branch instead:
    gather into the logical view, then masked softmax — the oracle the
    differential suite (tests/test_kernels_paged.py) compares against;
    both match apply_gqa_decode row-for-row.

    ``tp_axis`` (under ``shard_map``): ``cache`` is this shard's pool
    slice holding kvh/tp_size kv heads; the matching contiguous q-head
    block attends per-shard (the paged kernel runs unchanged on the
    smaller head count) and head outputs are all-gathered before wo.
    Per-head attention math is identical to single-device, so greedy
    decode stays token-for-token identical at any tp_size that divides
    n_kv_heads."""
    from repro.kernels.paged_decode import (
        paged_gqa_decode_cold_pallas,
        paged_gqa_decode_pallas,
        paged_kernel_enabled,
    )
    from repro.serving.paged_cache import paged_append

    b, s, _ = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    positions = seq_lens[:, None].astype(jnp.int32)
    q, k, v = _gqa_qkv(p, x, cfg, positions)
    if tp_axis is not None:
        h, kvh = h // tp_size, kvh // tp_size
        q = _tp_slice(q, tp_axis, h, 2)
        k = _tp_slice(k, tp_axis, kvh, 2)
        v = _tp_slice(v, tp_axis, kvh, 2)
    pk = paged_append(cache["k"], block_table, seq_lens, k[:, 0])
    pv = paged_append(cache["v"], block_table, seq_lens, v[:, 0])
    new_cache = dict(cache, k=pk, v=pv)     # shadow leaves ride through
    if paged_kernel_enabled():
        qg = q[:, 0].reshape(b, kvh, h // kvh, hd)
        if cold_flags is not None and "k_q8" in cache:
            og = paged_gqa_decode_cold_pallas(
                qg, pk, pv, cache["k_q8"], cache["k_scale"],
                cache["v_q8"], cache["v_scale"],
                block_table, seq_lens, cold_flags)
        else:
            og = paged_gqa_decode_pallas(qg, pk, pv, block_table, seq_lens)
        o = og.reshape(b, s, h, hd)
    else:
        ck = _gather_cold(new_cache, "k", block_table, cold_flags)
        cv = _gather_cold(new_cache, "v", block_table, cold_flags)
        S = ck.shape[1]
        valid = jnp.arange(S)[None, :] <= seq_lens[:, None]
        # fp32 like the kernel branch and the static oracle (see
        # apply_gqa_decode) — one output rounding, bf16 token identity.
        o = _sdpa(q.astype(jnp.float32), ck.astype(jnp.float32),
                  cv.astype(jnp.float32), causal=False,
                  kv_len_mask=valid).astype(q.dtype)
    if tp_axis is not None:
        o = jax.lax.all_gather(o, tp_axis, axis=2, tiled=True)
    return apply_linear(p["wo"], o.reshape(b, s, -1)), new_cache


# ---------------------------------------------------------------- MLA ----

def init_mla(key, cfg, dtype=jnp.float32):
    """DeepSeek Multi-head Latent Attention. cfg needs: d_model, n_heads,
    q_lora_rank (0 => direct q proj), kv_lora_rank, qk_nope_dim,
    qk_rope_dim, v_head_dim."""
    keys = jax.random.split(key, 6)
    d, h = cfg.d_model, cfg.n_heads
    nope, rope_d, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    p = {}
    if cfg.q_lora_rank:
        p["wdq"] = init_linear(keys[0], d, cfg.q_lora_rank, dtype=dtype)
        p["q_norm"] = init_rmsnorm(cfg.q_lora_rank, dtype=dtype)
        p["wuq"] = init_linear(keys[1], cfg.q_lora_rank, h * (nope + rope_d), dtype=dtype)
    else:
        p["wq"] = init_linear(keys[1], d, h * (nope + rope_d), dtype=dtype)
    p["wdkv"] = init_linear(keys[2], d, cfg.kv_lora_rank + rope_d, dtype=dtype)
    p["kv_norm"] = init_rmsnorm(cfg.kv_lora_rank, dtype=dtype)
    p["wukv"] = init_linear(keys[3], cfg.kv_lora_rank, h * (nope + vd), dtype=dtype)
    p["wo"] = init_linear(keys[4], h * vd, d, dtype=dtype)
    return p


def _mla_q(p, x, cfg):
    b, s, _ = x.shape
    h, nope, rope_d = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    if cfg.q_lora_rank:
        cq = apply_rmsnorm(p["q_norm"], apply_linear(p["wdq"], x))
        q = apply_linear(p["wuq"], cq)
    else:
        q = apply_linear(p["wq"], x)
    q = q.reshape(b, s, h, nope + rope_d)
    return jnp.split(q, [nope], axis=-1)  # q_nope (b,s,h,nope), q_rope (b,s,h,rope)


def _mla_ckv(p, x, cfg, positions):
    """Compressed latent + shared rope key. Returns ckv (b,s,kv_lora),
    krope (b,s,rope_d) — exactly what the decode cache stores."""
    lat = apply_linear(p["wdkv"], x)
    ckv, krope = jnp.split(lat, [cfg.kv_lora_rank], axis=-1)
    ckv = apply_rmsnorm(p["kv_norm"], ckv)
    krope = apply_rope(krope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]
    return ckv, krope


def apply_mla(p, x, cfg, *, positions, causal=True):
    """Training/prefill form: expand full K/V from the latent."""
    b, s, _ = x.shape
    h, nope, rope_d, vd = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    q_nope, q_rope = _mla_q(p, x, cfg)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    ckv, krope = _mla_ckv(p, x, cfg, positions)
    kv = apply_linear(p["wukv"], ckv).reshape(b, s, h, nope + vd)
    k_nope, v = jnp.split(kv, [nope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(krope[:, :, None, :], (b, s, h, rope_d))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    o = _sdpa(q, k, v, causal=causal)
    return apply_linear(p["wo"], o.reshape(b, s, -1))


def mla_init_cache(cfg, batch, max_seq, dtype=jnp.bfloat16):
    return {
        "ckv": jnp.zeros((batch, max_seq, cfg.kv_lora_rank), dtype=dtype),
        "krope": jnp.zeros((batch, max_seq, cfg.qk_rope_dim), dtype=dtype),
    }


def apply_mla_prefill(p, x, cfg, *, positions, cache):
    b, s, _ = x.shape
    ckv, krope = _mla_ckv(p, x, cfg, positions)
    cache = {
        "ckv": jax.lax.dynamic_update_slice(cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, 0, 0)),
        "krope": jax.lax.dynamic_update_slice(cache["krope"], krope.astype(cache["krope"].dtype), (0, 0, 0)),
    }
    # reuse the training attention for outputs
    out = apply_mla(p, x, cfg, positions=positions, causal=True)
    return out, cache


def _split_wukv(p, cfg):
    """Split the (kv_lora, h*(nope+vd)) up-projection into per-head
    W_uk (h, kv_lora, nope) and W_uv (h, kv_lora, vd) for the absorbed
    decode path. Works for dense wukv (MLA up-proj is never spectral —
    it IS already a low-rank factor by design)."""
    h, nope, vd = cfg.n_heads, cfg.qk_nope_dim, cfg.v_head_dim
    w = p["wukv"]["w"]                                  # (kv_lora, h*(nope+vd))
    w = w.reshape(cfg.kv_lora_rank, h, nope + vd)
    return w[:, :, :nope], w[:, :, nope:]               # (kv_lora,h,nope), (kv_lora,h,vd)


def _mla_absorbed_attend(p, x, cfg, q_nope, q_rope, cckv, ckr, valid, *,
                         precise=False, tp_axis=None, tp_size=1):
    """Shared absorbed-decode attention: scores and values computed
    directly against the compressed latent view cckv (b, S, kv_lora) /
    ckr (b, S, rope_d) under a validity mask — no full K/V is ever
    materialized (the MLA idea, mirroring SCT's never-materialize
    rule). ``valid`` is (b, S) (same mask for every query — the decode
    case) or (b, s, S) (per-query causal mask — the chunked-prefill
    case). ``precise`` runs every einsum in fp32 with a single rounding
    back to x.dtype before wo — the decode paths use it so this oracle
    and the paged flash-decode kernel (fp32 scratch) agree to fp32
    epsilon and bf16 greedy decode stays token-identical.

    ``tp_axis`` (under ``shard_map``) shards the *query heads*: the
    latent view is tiny and stays replicated (the MLA memory win makes
    latent replication the cheap placement), each shard attends its
    contiguous head block with the matching W_uk/W_uv slices, and head
    outputs are all-gathered before wo. Per-head math is unchanged."""
    b, s, _ = x.shape
    h, nope, rope_d, vd = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    wuk, wuv = _split_wukv(p, cfg)
    if tp_axis is not None:
        h = h // tp_size
        q_nope = _tp_slice(q_nope, tp_axis, h, 2)
        q_rope = _tp_slice(q_rope, tp_axis, h, 2)
        wuk = _tp_slice(wuk, tp_axis, h, 1)
        wuv = _tp_slice(wuv, tp_axis, h, 1)
    ct = jnp.float32 if precise else x.dtype
    # absorb W_uk into q: q_lat (b,s,h,kv_lora)
    q_lat = jnp.einsum("bshn,lhn->bshl", q_nope.astype(ct), wuk.astype(ct))
    scores = (
        jnp.einsum("bshl,bSl->bhsS", q_lat, cckv.astype(ct))
        + jnp.einsum("bshr,bSr->bhsS", q_rope.astype(ct), ckr.astype(ct))
    ).astype(jnp.float32) / jnp.sqrt(jnp.float32(nope + rope_d))
    mask = valid[:, None, None, :] if valid.ndim == 2 else valid[:, None, :, :]
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(ct)
    o_lat = jnp.einsum("bhsS,bSl->bshl", probs, cckv.astype(ct))   # (b,s,h,kv_lora)
    o = jnp.einsum("bshl,lhv->bshv", o_lat, wuv.astype(ct))        # (b,s,h,vd)
    if tp_axis is not None:
        o = jax.lax.all_gather(o, tp_axis, axis=2, tiled=True)
    return apply_linear(p["wo"], o.astype(x.dtype).reshape(b, s, cfg.n_heads * vd))


def apply_mla_decode(p, x, cfg, *, cache, cache_len):
    """Absorbed single-token decode against the static latent cache."""
    b, s, _ = x.shape
    positions = jnp.broadcast_to(cache_len[None, None], (b, s)).astype(jnp.int32)
    q_nope, q_rope = _mla_q(p, x, cfg)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    ckv_new, krope_new = _mla_ckv(p, x, cfg, positions)
    cckv = jax.lax.dynamic_update_slice_in_dim(cache["ckv"], ckv_new.astype(cache["ckv"].dtype), cache_len, axis=1)
    ckr = jax.lax.dynamic_update_slice_in_dim(cache["krope"], krope_new.astype(cache["krope"].dtype), cache_len, axis=1)
    S = cckv.shape[1]
    valid = jnp.broadcast_to((jnp.arange(S)[None, :] <= cache_len), (b, S))
    out = _mla_absorbed_attend(p, x, cfg, q_nope, q_rope, cckv, ckr, valid,
                               precise=True)
    return out, {"ckv": cckv, "krope": ckr}


def apply_mla_prefill_paged(p, x, cfg, *, cache, block_table, start,
                            tp_axis=None, tp_size=1, cold_flags=None):
    """Chunked prefill from a logical offset against paged latent
    pools — the MLA twin of :func:`apply_gqa_prefill_paged`. The
    chunk's compressed latent/rope-key is scattered into the sequence's
    pages, then the absorbed attend runs over the gathered view under a
    per-query causal mask at absolute positions (cached prefix latents
    are already roped, so nothing is recomputed for shared pages).

    ``tp_axis`` shards query heads per-shard inside the absorbed
    attend; the latent pools are replicated (every shard scatters the
    same latent chunk into its copy, so the pools stay consistent)."""
    from repro.serving.paged_cache import paged_write_slice

    b, c, _ = x.shape
    positions = jnp.broadcast_to(start + jnp.arange(c, dtype=jnp.int32)[None], (b, c))
    q_nope, q_rope = _mla_q(p, x, cfg)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    ckv, krope = _mla_ckv(p, x, cfg, positions)
    pckv = paged_write_slice(cache["ckv"], block_table[0], start, ckv[0])
    pkr = paged_write_slice(cache["krope"], block_table[0], start, krope[0])
    new_cache = dict(cache, ckv=pckv, krope=pkr)
    cckv = _gather_cold(new_cache, "ckv", block_table, cold_flags)
    ckr = _gather_cold(new_cache, "krope", block_table, cold_flags)
    S = cckv.shape[1]
    valid = jnp.arange(S)[None, None, :] <= positions[:, :, None]      # (b, c, S)
    out = _mla_absorbed_attend(p, x, cfg, q_nope, q_rope, cckv, ckr, valid,
                               tp_axis=tp_axis, tp_size=tp_size)
    return out, new_cache


def apply_mla_decode_paged(p, x, cfg, *, cache, block_table, seq_lens,
                           tp_axis=None, tp_size=1, cold_flags=None):
    """Absorbed single-token decode against paged latent pools
    cache = {"ckv"/"krope": (P+1, page, ...)}; per-slot seq_lens.

    Default path is the absorbed-MLA paged flash-decode kernel
    (kernels/paged_decode.py): q_nope is absorbed through W_uk outside,
    the kernel walks the block table over the latent pools and returns
    the latent context o_lat, W_uv/W_o apply outside — full K/V is never
    expanded and no gathered latent copy exists. ``SCT_PAGED_KERNEL=0``
    selects the jnp reference branch (gather + _mla_absorbed_attend).

    ``tp_axis`` (under ``shard_map``) shards query heads; the latent
    pools are replicated (each shard appends the identical new latent
    to its copy). The paged kernel runs per-shard on its head block and
    head outputs are all-gathered before wo — greedy decode stays
    token-identical at any tp_size dividing n_heads."""
    from repro.kernels.paged_decode import (
        paged_kernel_enabled,
        paged_mla_decode_cold_pallas,
        paged_mla_decode_pallas,
    )
    from repro.serving.paged_cache import paged_append

    b, s, _ = x.shape
    positions = seq_lens[:, None].astype(jnp.int32)
    q_nope, q_rope = _mla_q(p, x, cfg)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    ckv_new, krope_new = _mla_ckv(p, x, cfg, positions)
    pckv = paged_append(cache["ckv"], block_table, seq_lens, ckv_new[:, 0])
    pkr = paged_append(cache["krope"], block_table, seq_lens, krope_new[:, 0])
    new_cache = dict(cache, ckv=pckv, krope=pkr)
    if paged_kernel_enabled():
        h, nope, rope_d, vd = (cfg.n_heads, cfg.qk_nope_dim,
                               cfg.qk_rope_dim, cfg.v_head_dim)
        wuk, wuv = _split_wukv(p, cfg)
        qn, qr = q_nope, q_rope
        if tp_axis is not None:
            h = h // tp_size
            qn = _tp_slice(qn, tp_axis, h, 2)
            qr = _tp_slice(qr, tp_axis, h, 2)
            wuk = _tp_slice(wuk, tp_axis, h, 1)
            wuv = _tp_slice(wuv, tp_axis, h, 1)
        # fp32 absorb/up-project around the fp32-scratch kernel, matching
        # _mla_absorbed_attend(precise=True) — one rounding before wo.
        q_lat = jnp.einsum("bshn,lhn->bshl", qn.astype(jnp.float32),
                           wuk.astype(jnp.float32))[:, 0]       # (b, h, L)
        qr_f32 = qr[:, 0].astype(jnp.float32)
        kscale = 1.0 / float(nope + rope_d) ** 0.5
        if cold_flags is not None and "ckv_q8" in cache:
            o_lat = paged_mla_decode_cold_pallas(
                q_lat, qr_f32, pckv, pkr,
                cache["ckv_q8"], cache["ckv_scale"],
                cache["krope_q8"], cache["krope_scale"],
                block_table, seq_lens, cold_flags, scale=kscale)
        else:
            o_lat = paged_mla_decode_pallas(
                q_lat, qr_f32, pckv, pkr, block_table, seq_lens,
                scale=kscale)
        o = jnp.einsum("bhl,lhv->bhv", o_lat, wuv.astype(jnp.float32))
        if tp_axis is not None:
            o = jax.lax.all_gather(o, tp_axis, axis=1, tiled=True)
        out = apply_linear(p["wo"],
                           o.astype(x.dtype).reshape(b, s, cfg.n_heads * vd))
    else:
        cckv = _gather_cold(new_cache, "ckv", block_table, cold_flags)
        ckr = _gather_cold(new_cache, "krope", block_table, cold_flags)
        S = cckv.shape[1]
        valid = jnp.arange(S)[None, :] <= seq_lens[:, None]
        out = _mla_absorbed_attend(p, x, cfg, q_nope, q_rope, cckv, ckr,
                                   valid, precise=True,
                                   tp_axis=tp_axis, tp_size=tp_size)
    return out, new_cache


# ----------------------------------------------------------- cross-attn --

def init_cross_attn(key, cfg, dtype=jnp.float32):
    """Whisper decoder cross-attention (no rope)."""
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, h, hd = cfg.d_model, cfg.n_heads, cfg.head_dim
    return {
        "wq": init_linear(kq, d, h * hd, bias=True, dtype=dtype),
        "wk": init_linear(kk, d, h * hd, bias=False, dtype=dtype),
        "wv": init_linear(kv, d, h * hd, bias=True, dtype=dtype),
        "wo": init_linear(ko, h * hd, d, bias=True, dtype=dtype),
    }


def apply_cross_attn(p, x, enc_out, cfg):
    b, s, _ = x.shape
    h, hd = cfg.n_heads, cfg.head_dim
    se = enc_out.shape[1]
    q = apply_linear(p["wq"], x).reshape(b, s, h, hd)
    k = apply_linear(p["wk"], enc_out).reshape(b, se, h, hd)
    v = apply_linear(p["wv"], enc_out).reshape(b, se, h, hd)
    o = _sdpa(q, k, v, causal=False)
    return apply_linear(p["wo"], o.reshape(b, s, -1))
