"""Serving paths for decoder-only families: cache/state construction,
prefill, and single-token decode. Caches are stacked along a leading
layer (or period) axis and scanned together with the layer params.

Cache construction is pluggable: the attention cache is either the
static dense ``(batch, max_seq)`` layout or a paged pool layout
(serving/paged_cache.py) where each layer holds a shared page pool and
sequences map logical positions through a block table. Recurrent
(mamba / xlstm) decode state is fixed-size per sequence, so both
layouts index it by slot; only the attention leaves change shape.
"""
from __future__ import annotations

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from repro.config.model_config import ModelConfig
from repro.models import blocks
from repro.nn import attention as attn
from repro.nn import mamba as mamba_mod
from repro.nn import xlstm as xlstm_mod
from repro.nn.embedding import apply_embedding, apply_lm_head

Params = Dict[str, Any]

# state-dict keys holding attention caches (layout-dependent leaves) vs
# recurrent per-slot state, and the axis the serving slot lives on after
# layer stacking — the serving engine scatters prefill state with these
ATTN_STATE_KEYS = ("cache", "dense_cache", "moe_cache", "attn_cache")

# Families whose *entire* decode state is paged attention KV. Only these
# support shared-prefix reuse and chunked (offset) prefill: a cached
# page fully determines the contribution of its tokens to any later
# query. Recurrent families (hybrid mamba, xlstm) carry slot-local
# recurrent state that a mid-prompt restart cannot reconstruct from
# pages, so they opt out — their prompts always prefill in one shot
# from position 0 and never share prefix pages (the only reuse that
# could be exact for them is a full-prompt state snapshot, which we
# deliberately do not cache). The serving engine consults this policy;
# tests assert the opt-out families still serve token-identically.
#
# moe_lm caveat: expert capacity is sized per forward (capacity_factor
# * tokens_in_this_forward / n_experts, nn/moe.py), so when capacity
# actually *binds*, a prompt prefilled as chunks can drop a different
# token set than the one-shot oracle prefill. That dependence on the
# forward's token count is pre-existing (batched decode already drops
# differently than the batch-1 oracle at tight capacity — see the
# fp32/capacity_factor pins in tests); the token-identity guarantee for
# MoE therefore holds in the capacity-unbound regime, same as for every
# other MoE serving path in this repo.
PREFIX_SHARING_FAMILIES = ("dense_lm", "moe_lm")


def supports_prefix_sharing(cfg: ModelConfig) -> bool:
    """Whether this family can prefill from an offset against paged KV
    (and therefore share prefix pages / chunk its prefill)."""
    return cfg.family in PREFIX_SHARING_FAMILIES


def recurrent_slot_axes(cfg: ModelConfig) -> Dict[str, int]:
    """state key -> axis of the serving slot (batch) in stacked leaves."""
    if cfg.family == "hybrid":
        return {"mamba": 2}         # (n_periods, n_mamba, batch, ...)
    if cfg.family == "ssm_lm":
        return {"mlstm": 2, "slstm": 1}
    return {}


# ======================================================================
# State specs / init
# ======================================================================

def _stack_specs(n: int, tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), tree)


def _attn_cache_spec(cfg: ModelConfig, batch: int, max_seq: int):
    if cfg.attention == "mla":
        return {
            "ckv": jax.ShapeDtypeStruct((batch, max_seq, cfg.kv_lora_rank), jnp.bfloat16),
            "krope": jax.ShapeDtypeStruct((batch, max_seq, cfg.qk_rope_dim), jnp.bfloat16),
        }
    return {
        "k": jax.ShapeDtypeStruct((batch, max_seq, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16),
        "v": jax.ShapeDtypeStruct((batch, max_seq, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16),
    }


def _attn_pool_spec(cfg: ModelConfig, pcfg, cold_kv: str = "none"):
    """Per-layer paged pool: (num_pages + 1 null page, page_size, *feat).

    ``cold_kv="int8"`` adds page-granular int8 *shadow* pools plus
    per-page scales (token axis reduced) for the streaming cold tier:
    the engine demotes cold pages into the shadow leaves and attention
    substitutes their dequantized rows for flagged pages. Shadow leaves
    ride in the same cache dict, so the layer scan, COW page copy, and
    TP sharding machinery see them as ordinary pool leaves."""
    P, pg = pcfg.num_pages + 1, pcfg.page_size
    if cfg.attention == "mla":
        spec = {
            "ckv": jax.ShapeDtypeStruct((P, pg, cfg.kv_lora_rank), jnp.bfloat16),
            "krope": jax.ShapeDtypeStruct((P, pg, cfg.qk_rope_dim), jnp.bfloat16),
        }
        if cold_kv == "int8":
            spec.update({
                "ckv_q8": jax.ShapeDtypeStruct((P, pg, cfg.kv_lora_rank), jnp.int8),
                "ckv_scale": jax.ShapeDtypeStruct((P, cfg.kv_lora_rank), jnp.float32),
                "krope_q8": jax.ShapeDtypeStruct((P, pg, cfg.qk_rope_dim), jnp.int8),
                "krope_scale": jax.ShapeDtypeStruct((P, cfg.qk_rope_dim), jnp.float32),
            })
        return spec
    spec = {
        "k": jax.ShapeDtypeStruct((P, pg, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16),
        "v": jax.ShapeDtypeStruct((P, pg, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16),
    }
    if cold_kv == "int8":
        spec.update({
            "k_q8": jax.ShapeDtypeStruct((P, pg, cfg.n_kv_heads, cfg.head_dim), jnp.int8),
            "k_scale": jax.ShapeDtypeStruct((P, cfg.n_kv_heads, cfg.head_dim), jnp.float32),
            "v_q8": jax.ShapeDtypeStruct((P, pg, cfg.n_kv_heads, cfg.head_dim), jnp.int8),
            "v_scale": jax.ShapeDtypeStruct((P, cfg.n_kv_heads, cfg.head_dim), jnp.float32),
        })
    return spec


def _mamba_state_spec(cfg, batch):
    di = cfg.mamba_expand * cfg.d_model
    return {
        "conv": jax.ShapeDtypeStruct((batch, cfg.mamba_d_conv - 1, di), jnp.bfloat16),
        "ssm": jax.ShapeDtypeStruct((batch, di, cfg.mamba_d_state), jnp.bfloat16),
    }


def _mlstm_state_spec(cfg, batch):
    di = 2 * cfg.d_model
    dh = di // cfg.n_heads
    h = cfg.n_heads
    return {
        "C": jax.ShapeDtypeStruct((batch, h, dh, dh), jnp.float32),
        "n": jax.ShapeDtypeStruct((batch, h, dh), jnp.float32),
        "m": jax.ShapeDtypeStruct((batch, h), jnp.float32),
    }


def _slstm_state_spec(cfg, batch):
    nh = cfg.n_heads
    dh = cfg.d_model // nh
    s = jax.ShapeDtypeStruct((batch, nh, dh), jnp.float32)
    return {"h": s, "c": s, "n": s, "m": s}


def _lm_state_specs(cfg: ModelConfig, batch: int, attn_spec: Callable[[], Any]):
    """Family state tree; ``attn_spec`` supplies the per-layer attention
    cache spec — the pluggable (static vs. paged) part."""
    if cfg.family == "dense_lm":
        return {"cache": _stack_specs(cfg.n_layers, attn_spec())}
    if cfg.family == "moe_lm":
        st = {}
        if cfg.first_dense_layers:
            st["dense_cache"] = _stack_specs(cfg.first_dense_layers, attn_spec())
        st["moe_cache"] = _stack_specs(cfg.n_layers - cfg.first_dense_layers, attn_spec())
        return st
    if cfg.family == "hybrid":
        n_periods = cfg.n_layers // cfg.attn_every
        n_mamba = cfg.attn_every - 1
        return {
            "attn_cache": _stack_specs(n_periods, attn_spec()),
            "mamba": _stack_specs(n_periods, _stack_specs(n_mamba, _mamba_state_spec(cfg, batch))),
        }
    if cfg.family == "ssm_lm":
        n_periods = cfg.n_layers // cfg.slstm_every
        n_m = cfg.slstm_every - 1
        return {
            "mlstm": _stack_specs(n_periods, _stack_specs(n_m, _mlstm_state_spec(cfg, batch))),
            "slstm": _stack_specs(n_periods, _slstm_state_spec(cfg, batch)),
        }
    raise ValueError(cfg.family)


def lm_state_specs(cfg: ModelConfig, batch: int, max_seq: int):
    """ShapeDtypeStruct tree of the static-cache decode state."""
    return _lm_state_specs(cfg, batch, lambda: _attn_cache_spec(cfg, batch, max_seq))


def lm_paged_state_specs(cfg: ModelConfig, pcfg, cold_kv: str = "none"):
    """Decode state with paged attention pools: recurrent leaves are
    slot-indexed by ``pcfg.max_slots``; attention leaves are shared page
    pools addressed through the engine's block tables. ``cold_kv``
    extends each layer's pools with the streaming int8 shadow tier."""
    return _lm_state_specs(cfg, pcfg.max_slots,
                           lambda: _attn_pool_spec(cfg, pcfg, cold_kv))


def lm_init_state(cfg: ModelConfig, batch: int, max_seq: int):
    """Zero-filled decode state (real allocation — for smoke tests and
    the serving example; the dry-run uses lm_state_specs instead)."""
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), lm_state_specs(cfg, batch, max_seq))


def lm_init_paged_state(cfg: ModelConfig, pcfg, cold_kv: str = "none"):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        lm_paged_state_specs(cfg, pcfg, cold_kv))


# ======================================================================
# Layer scans
# ======================================================================

def _scan_layers(params: Params, x: jax.Array, state, cfg: ModelConfig,
                 attend, mamba, mlstm, slstm):
    """Family-dispatched layer scan over the stacked params and their
    per-layer state, through the bodies of models/blocks.py. The mode's
    mixers each map ``(layer params, h, state) -> (out, new state)``:
    ``attend`` for attention (its state is the layer's KV cache),
    ``mamba``, ``mlstm`` and ``slstm`` for the recurrent mixers."""
    if cfg.family in ("dense_lm", "moe_lm"):
        def f(h, xs):
            layer_p, cache = xs
            h, cache, _ = blocks.dense_block(cfg, layer_p, h, attend, cache)
            return h, cache

        stacks = [("layers", "cache")] if cfg.family == "dense_lm" else (
            ([("dense_layers", "dense_cache")] if cfg.first_dense_layers else [])
            + [("moe_layers", "moe_cache")]
        )
        new_state = dict(state)
        for pk, ck in stacks:
            x, new_state[ck] = jax.lax.scan(f, x, (params[pk], state[ck]))
        return x, new_state
    if cfg.family == "hybrid":
        def f(h, xs):
            period_p, cache, mstates = xs
            h, cache, mstates, _ = blocks.hybrid_period(
                cfg, period_p, h, attend, mamba, cache, mstates)
            return h, (cache, mstates)

        x, (cache, mstates) = jax.lax.scan(
            f, x, (params["periods"], state["attn_cache"], state["mamba"]))
        return x, {"attn_cache": cache, "mamba": mstates}
    if cfg.family == "ssm_lm":
        def f(h, xs):
            period_p, mstates, sstate = xs
            h, mstates, sstate = blocks.xlstm_period(
                cfg, period_p, h, mlstm, slstm, mstates, sstate)
            return h, (mstates, sstate)

        x, (mstates, sstate) = jax.lax.scan(
            f, x, (params["periods"], state["mlstm"], state["slstm"]))
        return x, {"mlstm": mstates, "slstm": sstate}
    raise ValueError(cfg.family)


# ======================================================================
# Prefill
# ======================================================================

def prefill_lm(params: Params, tokens: jax.Array, cfg: ModelConfig, state):
    """Process the prompt, fill caches. Returns (last-token logits, state).

    Attention layers fill their caches; Mamba and mLSTM layers run their
    training scan and keep its exact final carry; sLSTM replays its cell
    scan for the final state.
    """
    b, s = tokens.shape
    dt = blocks.compute_dtype(cfg)
    x = apply_embedding(params["embed"], tokens, compute_dtype=dt)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    def attend(p, h, cache):
        if cfg.attention == "mla":
            return attn.apply_mla_prefill(p, h, cfg, positions=positions, cache=cache)
        return attn.apply_gqa_prefill(p, h, cfg, positions=positions, cache=cache)

    x, state = _scan_layers(
        params, x, state, cfg, attend,
        mamba=lambda p, h, st: _mamba_prefill(p, h, cfg, st),
        # the chunkwise mLSTM returns its outputs and the exact final state
        mlstm=lambda p, h, st: xlstm_mod.apply_mlstm_with_state(p, h, cfg),
        slstm=lambda p, h, st: _slstm_prefill(p, h, cfg))
    x = blocks.norm_apply(cfg, params["final_norm"], x[:, -1:, :])
    logits = apply_lm_head(params["embed"], x)
    return logits, state


def _mamba_prefill(p, x, cfg, state):
    """Training scan + exact final state (conv tail, final SSM carry)."""
    y, new = mamba_mod.apply_mamba(p, x, cfg, return_state=True)
    return y, {
        "conv": new["conv"].astype(state["conv"].dtype),
        "ssm": new["ssm"].astype(state["ssm"].dtype),
    }


def _slstm_prefill(p, x, cfg):
    b, s, d = x.shape
    y = xlstm_mod.apply_slstm(p, x, cfg)
    # final state via the same scan the forward uses
    from repro.nn.linear import apply_linear
    xg = apply_linear(p["wx"], x)
    state = xlstm_mod.slstm_init_state(cfg, b, dtype=jnp.float32)

    def step(st, xg_t):
        return xlstm_mod._slstm_cell(p, cfg, xg_t, st), None

    state, _ = jax.lax.scan(step, state, jnp.moveaxis(xg, 1, 0))
    return y, state


# ======================================================================
# Decode (single token)
# ======================================================================

def decode_step_lm(params: Params, tokens: jax.Array, state, cache_len: jax.Array,
                   cfg: ModelConfig):
    """tokens (b, 1) + state -> (logits (b, 1, vocab), new state).

    cache_len is the number of tokens already in the cache (static cache
    size, dynamic fill level) so the step compiles once and serves any
    position — the serving-loop contract.
    """
    def attn_decode(p, h, cache):
        if cfg.attention == "mla":
            return attn.apply_mla_decode(p, h, cfg, cache=cache, cache_len=cache_len)
        return attn.apply_gqa_decode(p, h, cfg, cache=cache, cache_len=cache_len)

    return _decode_step_body(params, tokens, state, cfg, attn_decode)


def decode_step_lm_paged(params: Params, tokens: jax.Array, state,
                         block_table: jax.Array, seq_lens: jax.Array,
                         cfg: ModelConfig, *, tp_axis=None, tp_size=1,
                         cold_flags=None):
    """One-token step against paged attention pools with per-slot fill
    levels — mixed request lengths in one compiled step, the
    continuous-batching contract. block_table: (slots, n_pages) int32;
    seq_lens: (slots,) int32. Recurrent state paths are shared with the
    static step (slot-indexed either way).

    ``tp_axis``/``tp_size`` run the attention heads tensor-parallel
    when the step executes under ``shard_map`` over a serve mesh
    (sharding/partition.py:serve_mesh): GQA KV pools arrive as per-shard
    kv-head slices, MLA latent pools replicated; everything else
    (params, tokens, block tables, logits) is replicated. Per-head math
    is unchanged, so greedy outputs stay token-identical."""
    def attn_decode(p, h, cache):
        if cfg.attention == "mla":
            return attn.apply_mla_decode_paged(
                p, h, cfg, cache=cache, block_table=block_table, seq_lens=seq_lens,
                tp_axis=tp_axis, tp_size=tp_size, cold_flags=cold_flags)
        return attn.apply_gqa_decode_paged(
            p, h, cfg, cache=cache, block_table=block_table, seq_lens=seq_lens,
            tp_axis=tp_axis, tp_size=tp_size, cold_flags=cold_flags)

    return _decode_step_body(params, tokens, state, cfg, attn_decode)


def prefill_chunk_lm_paged(params: Params, tokens: jax.Array, state,
                           block_table: jax.Array, start: jax.Array,
                           cfg: ModelConfig, *, tp_axis=None, tp_size=1,
                           cold_flags=None):
    """Chunked/offset prefill against the paged pools: tokens (1, c)
    occupy absolute positions [start, start+c) of one sequence whose
    pages are mapped in block_table (1, n_pages). Positions < start are
    already cached (a shared prefix, or earlier chunks of this prompt);
    the chunk's KV is written through the block table and attention
    runs causally at absolute positions. Returns (logits (1, c, vocab),
    new state). ``start`` is data — one executable per chunk length.

    Only :data:`PREFIX_SHARING_FAMILIES`; recurrent families raise (see
    the policy note on that constant)."""
    if cfg.family not in PREFIX_SHARING_FAMILIES:
        raise NotImplementedError(
            f"chunked/offset prefill needs pure paged-attention state; "
            f"family {cfg.family!r} carries recurrent state and opts out")

    def attn_chunk(p, h, cache):
        if cfg.attention == "mla":
            return attn.apply_mla_prefill_paged(
                p, h, cfg, cache=cache, block_table=block_table, start=start,
                tp_axis=tp_axis, tp_size=tp_size, cold_flags=cold_flags)
        return attn.apply_gqa_prefill_paged(
            p, h, cfg, cache=cache, block_table=block_table, start=start,
            tp_axis=tp_axis, tp_size=tp_size, cold_flags=cold_flags)

    return _decode_step_body(params, tokens, state, cfg, attn_chunk)


def _decode_step_body(params: Params, tokens: jax.Array, state, cfg: ModelConfig,
                      attn_decode):
    """The layer scan shared by the static and paged steps and the paged
    chunked prefill; ``attn_decode(layer_params, h, cache) -> (out,
    cache)`` is the layout-specific part."""
    dt = blocks.compute_dtype(cfg)
    x = apply_embedding(params["embed"], tokens, compute_dtype=dt)
    x, state = _scan_layers(
        params, x, state, cfg, attn_decode,
        mamba=lambda p, h, st: mamba_mod.apply_mamba_decode(p, h, cfg, state=st),
        mlstm=lambda p, h, st: xlstm_mod.apply_mlstm_decode(p, h, cfg, state=st),
        slstm=lambda p, h, st: xlstm_mod.apply_slstm_decode(p, h, cfg, state=st))
    x = blocks.norm_apply(cfg, params["final_norm"], x)
    logits = apply_lm_head(params["embed"], x)
    return logits, state
