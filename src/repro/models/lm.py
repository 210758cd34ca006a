"""Decoder-only language models, all four LM families:

  dense_lm  — llama3.2-1b, granite-3-2b, qwen1.5-{0.5b,4b}, qwen2-vl-72b
  moe_lm    — deepseek-v2-236b, deepseek-v3-671b (MLA + routed experts)
  hybrid    — jamba-v0.1-52b (mamba:attention 7:1, MoE every 2nd layer)
  ssm_lm    — xlstm-1.3b (mLSTM:sLSTM 7:1)

Homogeneous layer stacks are scanned (lax.scan over stacked params) with
optional remat; heterogeneous families scan over their repeat *period*
(jamba: 8 layers, xlstm: 8 blocks) so the HLO stays small at 32-80
layers. SCT spectral layers appear wherever the config says so; the
dense (m, n) matrices of converted layers never exist.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config.model_config import ModelConfig
from repro.core import scopes
from repro.nn import attention as attn
from repro.nn import mamba as mamba_mod
from repro.nn import xlstm as xlstm_mod
from repro.nn.embedding import init_embedding, apply_embedding, apply_lm_head
from repro.nn.mlp import init_mlp, apply_mlp
from repro.nn.moe import init_moe, apply_moe
from repro.nn.norms import (
    init_rmsnorm,
    apply_rmsnorm,
    init_layernorm,
    apply_layernorm,
)
from repro.sharding.rules import constrain_activation

Params = Dict[str, Any]


def _norm_init(cfg, dim=None):
    dim = dim or cfg.d_model
    return init_rmsnorm(dim) if cfg.norm == "rmsnorm" else init_layernorm(dim)


def _norm_apply(cfg, p, x):
    return apply_rmsnorm(p, x) if cfg.norm == "rmsnorm" else apply_layernorm(p, x)


def _compute_dtype(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# ======================================================================
# Per-layer init (one layer; stacking via vmap happens in init_lm)
# ======================================================================

def _init_attn(key, cfg):
    if cfg.attention == "mla":
        return attn.init_mla(key, cfg)
    return attn.init_gqa(key, cfg)


def _init_dense_layer(key, cfg):
    k1, k2 = jax.random.split(key)
    return {
        "attn_norm": _norm_init(cfg),
        "attn": _init_attn(k1, cfg),
        "mlp_norm": _norm_init(cfg),
        "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, rank=cfg.mlp_rank, act=cfg.act),
    }


def _init_moe_layer(key, cfg):
    k1, k2 = jax.random.split(key)
    return {
        "attn_norm": _norm_init(cfg),
        "attn": _init_attn(k1, cfg),
        "mlp_norm": _norm_init(cfg),
        "moe": init_moe(k2, cfg),
    }


def _init_hybrid_period(key, cfg):
    """One jamba period: attn_every layers; attention at attn_offset,
    mamba elsewhere; MoE on odd positions, dense MLP on even."""
    P = cfg.attn_every
    keys = jax.random.split(key, 2 * P)
    layers = {}
    for p in range(P):
        km, kf = keys[2 * p], keys[2 * p + 1]
        mixer = (
            {"attn": _init_attn(km, cfg)}
            if p == cfg.attn_offset
            else {"mamba": mamba_mod.init_mamba(km, cfg)}
        )
        is_moe = (p % cfg.moe_every) == (cfg.moe_every - 1) and cfg.n_experts > 0
        ff = {"moe": init_moe(kf, cfg)} if is_moe else {
            "mlp": init_mlp(kf, cfg.d_model, cfg.d_ff, rank=cfg.mlp_rank, act=cfg.act)
        }
        layers[f"p{p}"] = {
            "pre_norm": _norm_init(cfg),
            **mixer,
            "ff_norm": _norm_init(cfg),
            **ff,
        }
    return layers


def _init_xlstm_period(key, cfg):
    """One xlstm period: slstm_every blocks; sLSTM at slstm_offset."""
    P = cfg.slstm_every
    keys = jax.random.split(key, P)
    layers = {}
    for p in range(P):
        if p == cfg.slstm_offset:
            body = {"slstm": xlstm_mod.init_slstm(keys[p], cfg)}
        else:
            body = {"mlstm": xlstm_mod.init_mlstm(keys[p], cfg)}
        layers[f"p{p}"] = {"pre_norm": _norm_init(cfg), **body}
    return layers


# ======================================================================
# Model init
# ======================================================================

def init_lm(key, cfg: ModelConfig) -> Params:
    ke, kl, kd, kh = jax.random.split(key, 4)
    params: Params = {"embed": init_embedding(ke, cfg.vocab, cfg.d_model)}

    if cfg.family == "dense_lm":
        L = cfg.n_layers
        keys = jax.random.split(kl, L)
        params["layers"] = jax.vmap(lambda k: _init_dense_layer(k, cfg))(keys)
    elif cfg.family == "moe_lm":
        Ld = cfg.first_dense_layers
        Lm = cfg.n_layers - Ld
        if Ld:
            params["dense_layers"] = jax.vmap(lambda k: _init_dense_layer(k, cfg))(
                jax.random.split(kd, Ld)
            )
        params["moe_layers"] = jax.vmap(lambda k: _init_moe_layer(k, cfg))(
            jax.random.split(kl, Lm)
        )
    elif cfg.family == "hybrid":
        n_periods = cfg.n_layers // cfg.attn_every
        params["periods"] = jax.vmap(lambda k: _init_hybrid_period(k, cfg))(
            jax.random.split(kl, n_periods)
        )
    elif cfg.family == "ssm_lm":
        n_periods = cfg.n_layers // cfg.slstm_every
        params["periods"] = jax.vmap(lambda k: _init_xlstm_period(k, cfg))(
            jax.random.split(kl, n_periods)
        )
    else:
        raise ValueError(cfg.family)

    params["final_norm"] = _norm_init(cfg)
    return params


# ======================================================================
# Forward (training / no-cache)
# ======================================================================

def _attention_residual(cfg, p, x, positions):
    with jax.named_scope(scopes.ATTENTION):
        h = _norm_apply(cfg, p["attn_norm"], x)
        if cfg.attention == "mla":
            h = attn.apply_mla(p["attn"], h, cfg, positions=positions)
        else:
            h = attn.apply_gqa(p["attn"], h, cfg, positions=positions,
                               use_pallas=cfg.use_pallas)
        return x + h


def _dense_block(cfg, p, x, positions):
    x = _attention_residual(cfg, p, x, positions)
    with jax.named_scope(scopes.MLP):
        h = _norm_apply(cfg, p["mlp_norm"], x)
        h = apply_mlp(p["mlp"], h, act=cfg.act, use_pallas=cfg.use_pallas)
        return x + h


def _moe_block(cfg, p, x, positions):
    x = _attention_residual(cfg, p, x, positions)
    with jax.named_scope(scopes.MLP):
        h = _norm_apply(cfg, p["mlp_norm"], x)
        h, aux = apply_moe(p["moe"], h, cfg, capacity_factor=cfg.capacity_factor,
                           use_pallas=cfg.use_pallas)
        return x + h, aux


def _hybrid_period_fwd(cfg, pp, x, positions):
    aux_total = jnp.float32(0.0)
    for p in range(cfg.attn_every):
        lp = pp[f"p{p}"]
        if "attn" in lp:
            with jax.named_scope(scopes.ATTENTION):
                h = _norm_apply(cfg, lp["pre_norm"], x)
                h = attn.apply_gqa(lp["attn"], h, cfg, positions=positions,
                                   use_pallas=cfg.use_pallas)
                x = x + h
        else:
            h = _norm_apply(cfg, lp["pre_norm"], x)
            x = x + mamba_mod.apply_mamba(lp["mamba"], h, cfg)
        with jax.named_scope(scopes.MLP):
            h = _norm_apply(cfg, lp["ff_norm"], x)
            if "moe" in lp:
                h, aux = apply_moe(lp["moe"], h, cfg, capacity_factor=cfg.capacity_factor,
                                   use_pallas=cfg.use_pallas)
                aux_total = aux_total + aux
            else:
                h = apply_mlp(lp["mlp"], h, act=cfg.act, use_pallas=cfg.use_pallas)
            x = x + h
    return x, aux_total


def _xlstm_period_fwd(cfg, pp, x, positions):
    for p in range(cfg.slstm_every):
        lp = pp[f"p{p}"]
        h = _norm_apply(cfg, lp["pre_norm"], x)
        if "slstm" in lp:
            h = xlstm_mod.apply_slstm(lp["slstm"], h, cfg)
        else:
            h = xlstm_mod.apply_mlstm(lp["mlstm"], h, cfg)
        x = x + h
    return x


def _scan_stack(stacked_params, x, body, cfg):
    """lax.scan over the leading layer axis of stacked params, with
    optional remat of the body (activation recompute in backward)."""

    def f(carry, layer_p):
        return constrain_activation(body(layer_p, carry)), None

    if cfg.remat:
        f = jax.checkpoint(f, policy=jax.checkpoint_policies.nothing_saveable)
    out, _ = jax.lax.scan(f, x, stacked_params)
    return out


def forward_lm(params: Params, tokens: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """tokens (b, s) -> (logits (b, s, vocab) fp32-castable, aux_loss)."""
    b, s = tokens.shape
    dt = _compute_dtype(cfg)
    with jax.named_scope(scopes.EMBED):
        x = constrain_activation(apply_embedding(params["embed"], tokens, compute_dtype=dt))
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    aux = jnp.float32(0.0)

    if cfg.family == "dense_lm":
        x = _scan_stack(
            params["layers"], x,
            lambda p, h: _dense_block(cfg, p, h, positions), cfg,
        )
    elif cfg.family == "moe_lm":
        if "dense_layers" in params:
            x = _scan_stack(
                params["dense_layers"], x,
                lambda p, h: _dense_block(cfg, p, h, positions), cfg,
            )
        x, aux = _scan_moe(params["moe_layers"], x, cfg, positions)
    elif cfg.family == "hybrid":
        x, aux = _scan_hybrid(params["periods"], x, cfg, positions)
    elif cfg.family == "ssm_lm":
        x = _scan_stack(
            params["periods"], x,
            lambda p, h: _xlstm_period_fwd(cfg, p, h, positions), cfg,
        )
    else:
        raise ValueError(cfg.family)

    with jax.named_scope(scopes.HEAD):
        x = _norm_apply(cfg, params["final_norm"], x)
        logits = apply_lm_head(params["embed"], x)
    return logits, aux


def _scan_moe(stacked, x, cfg, positions):
    def f(carry, layer_p):
        h, aux = carry
        h, a = _moe_block(cfg, layer_p, h, positions)
        return (constrain_activation(h), aux + a), None

    if cfg.remat:
        f = jax.checkpoint(f, policy=jax.checkpoint_policies.nothing_saveable)
    (x, aux), _ = jax.lax.scan(f, (x, jnp.float32(0.0)), stacked)
    return x, aux


def _scan_hybrid(stacked, x, cfg, positions):
    def f(carry, period_p):
        h, aux = carry
        h, a = _hybrid_period_fwd(cfg, period_p, h, positions)
        return (constrain_activation(h), aux + a), None

    if cfg.remat:
        f = jax.checkpoint(f, policy=jax.checkpoint_policies.nothing_saveable)
    (x, aux), _ = jax.lax.scan(f, (x, jnp.float32(0.0)), stacked)
    return x, aux


# ======================================================================
# Loss
# ======================================================================

def cross_entropy(logits: jax.Array, labels: jax.Array, z_loss: float = 1e-4):
    """Stable CE in fp32. Works with a vocab-sharded logits tensor: the
    logsumexp reduction and the label gather lower to per-shard compute
    plus small collectives under GSPMD (no full-vocab gather)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    label_logit = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - label_logit
    loss = jnp.mean(nll)
    if z_loss:
        loss = loss + z_loss * jnp.mean(lse ** 2)
    return loss


def train_loss_lm(params: Params, batch: Dict[str, jax.Array], cfg: ModelConfig):
    logits, aux = forward_lm(params, batch["tokens"], cfg)
    with jax.named_scope(scopes.HEAD):
        loss = cross_entropy(logits, batch["labels"])
        total = loss + cfg.aux_loss_coef * aux
    return total, {"ce_loss": loss, "aux_loss": aux}
