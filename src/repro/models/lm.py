"""Decoder-only language models, all four LM families:

  dense_lm  — llama3.2-1b, granite-3-2b, qwen1.5-{0.5b,4b}, qwen2-vl-72b
  moe_lm    — deepseek-v2-236b, deepseek-v3-671b (MLA + routed experts)
  hybrid    — jamba-v0.1-52b (mamba:attention 7:1, MoE every 2nd layer)
  ssm_lm    — xlstm-1.3b (mLSTM:sLSTM 7:1)

Homogeneous layer stacks are scanned (lax.scan over stacked params) with
optional remat; heterogeneous families scan over their repeat *period*
(jamba: 8 layers, xlstm: 8 blocks) so the HLO stays small at 32-80
layers. The layer bodies live in models/blocks.py, shared with the
serving paths (models/decode.py); this module holds init, the training
forward and the loss. SCT spectral layers appear wherever the config
says so; the dense (m, n) matrices of converted layers never exist.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config.model_config import ModelConfig
from repro.core import scopes
from repro.models.blocks import (
    compute_dtype,
    dense_block,
    hybrid_period,
    norm_apply,
    remat_scan,
    xlstm_period,
)
from repro.nn import attention as attn
from repro.nn import mamba as mamba_mod
from repro.nn import xlstm as xlstm_mod
from repro.nn.embedding import init_embedding, apply_embedding, apply_lm_head
from repro.nn.mlp import init_mlp
from repro.nn.moe import init_moe
from repro.nn.norms import init_rmsnorm, init_layernorm
from repro.sharding.rules import constrain_activation

Params = Dict[str, Any]


def _norm_init(cfg, dim=None):
    dim = dim or cfg.d_model
    return init_rmsnorm(dim) if cfg.norm == "rmsnorm" else init_layernorm(dim)


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

def _stateless(fn):
    # a training mixer threads no state through the shared layer bodies
    return lambda p, h, state: (fn(p, h), state)


def forward_lm(params: Params, tokens: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """tokens (b, s) -> (logits (b, s, vocab) fp32-castable, aux_loss)."""
    b, s = tokens.shape
    dt = compute_dtype(cfg)
    with jax.named_scope(scopes.EMBED):
        x = constrain_activation(apply_embedding(params["embed"], tokens, compute_dtype=dt))
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    aux = jnp.float32(0.0)

    if cfg.attention == "mla":
        attend = _stateless(lambda p, h: attn.apply_mla(p, h, cfg, positions=positions))
    else:
        attend = _stateless(lambda p, h: attn.apply_gqa(p, h, cfg, positions=positions))

    def dense_layer(h, layer_p):
        h, _, _ = dense_block(cfg, layer_p, h, attend)
        return constrain_activation(h), None

    def moe_layer(carry, layer_p):
        h, aux = carry
        h, _, a = dense_block(cfg, layer_p, h, attend)
        return (constrain_activation(h), aux + a), None

    def hybrid_layer(carry, period_p):
        h, aux = carry
        h, _, _, a = hybrid_period(
            cfg, period_p, h, attend,
            _stateless(lambda p, h: mamba_mod.apply_mamba(p, h, cfg)))
        return (constrain_activation(h), aux + a), None

    def xlstm_layer(h, period_p):
        h, _, _ = xlstm_period(
            cfg, period_p, h,
            _stateless(lambda p, h: xlstm_mod.apply_mlstm(p, h, cfg)),
            _stateless(lambda p, h: xlstm_mod.apply_slstm(p, h, cfg)))
        return constrain_activation(h), None

    if cfg.family == "dense_lm":
        x, _ = remat_scan(dense_layer, x, params["layers"], cfg)
    elif cfg.family == "moe_lm":
        if "dense_layers" in params:
            x, _ = remat_scan(dense_layer, x, params["dense_layers"], cfg)
        (x, aux), _ = remat_scan(moe_layer, (x, aux), params["moe_layers"], cfg)
    elif cfg.family == "hybrid":
        (x, aux), _ = remat_scan(hybrid_layer, (x, aux), params["periods"], cfg)
    elif cfg.family == "ssm_lm":
        x, _ = remat_scan(xlstm_layer, x, params["periods"], cfg)
    else:
        raise ValueError(cfg.family)

    with jax.named_scope(scopes.HEAD):
        x = norm_apply(cfg, params["final_norm"], x)
        logits = apply_lm_head(params["embed"], x)
    return logits, aux


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
