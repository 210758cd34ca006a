"""Encoder-decoder model (whisper-medium backbone). The audio conv
frontend is a STUB per the assignment: ``input_specs`` supplies
precomputed frame embeddings (b, n_frames, d_model); everything after
that — encoder stack, decoder with self+cross attention, LM head — is
real. MLPs (enc + dec) are SCT-spectral when configured.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.config.model_config import ModelConfig
from repro.nn import attention as attn
from repro.nn.embedding import init_embedding, apply_embedding, apply_lm_head
from repro.nn.mlp import init_mlp, apply_mlp
from repro.nn.norms import init_layernorm, apply_layernorm
from repro.models.blocks import compute_dtype, remat_scan
from repro.models.lm import cross_entropy

Params = Dict[str, Any]


def _init_enc_layer(key, cfg):
    k1, k2 = jax.random.split(key)
    return {
        "attn_norm": init_layernorm(cfg.d_model),
        "attn": attn.init_gqa(k1, cfg),
        "mlp_norm": init_layernorm(cfg.d_model),
        "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, rank=cfg.mlp_rank, act="gelu", bias=True),
    }


def _init_dec_layer(key, cfg):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "attn_norm": init_layernorm(cfg.d_model),
        "attn": attn.init_gqa(k1, cfg),
        "xattn_norm": init_layernorm(cfg.d_model),
        "xattn": attn.init_cross_attn(k2, cfg),
        "mlp_norm": init_layernorm(cfg.d_model),
        "mlp": init_mlp(k3, cfg.d_model, cfg.d_ff, rank=cfg.mlp_rank, act="gelu", bias=True),
    }


def init_encdec(key, cfg: ModelConfig) -> Params:
    ke, kenc, kdec, kp1, kp2 = jax.random.split(key, 5)
    Le = cfg.n_encoder_layers or cfg.n_layers
    Ld = cfg.n_layers
    return {
        "embed": init_embedding(ke, cfg.vocab, cfg.d_model),
        "enc_pos": {"w": (jax.random.normal(kp1, (cfg.encoder_seq, cfg.d_model)) * 0.02)},
        "dec_pos": {"w": (jax.random.normal(kp2, (cfg.max_seq, cfg.d_model)) * 0.02)},
        "enc_layers": jax.vmap(lambda k: _init_enc_layer(k, cfg))(jax.random.split(kenc, Le)),
        "dec_layers": jax.vmap(lambda k: _init_dec_layer(k, cfg))(jax.random.split(kdec, Ld)),
        "enc_norm": init_layernorm(cfg.d_model),
        "final_norm": init_layernorm(cfg.d_model),
    }


def encode(params, frames: jax.Array, cfg: ModelConfig) -> jax.Array:
    """frames: (b, n_frames, d) precomputed embeddings (conv stub)."""
    dt = compute_dtype(cfg)
    s = frames.shape[1]
    x = frames.astype(dt) + params["enc_pos"]["w"][:s].astype(dt)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], frames.shape[:2])

    def f(carry, layer_p):
        h = apply_layernorm(layer_p["attn_norm"], carry)
        h = attn.apply_gqa(layer_p["attn"], h, cfg, positions=positions, causal=False)
        x2 = carry + h
        h = apply_layernorm(layer_p["mlp_norm"], x2)
        h = apply_mlp(layer_p["mlp"], h, act="gelu")
        return x2 + h, None

    x, _ = remat_scan(f, x, params["enc_layers"], cfg)
    return apply_layernorm(params["enc_norm"], x)


def _dec_block(cfg, layer_p, x, enc_out, attend, cache=None):
    """Self-attention, cross-attention over ``enc_out``, MLP; the
    mode's ``attend(attn_params, h, cache) -> (out, cache)``."""
    h = apply_layernorm(layer_p["attn_norm"], x)
    h, cache = attend(layer_p["attn"], h, cache)
    x = x + h
    h = apply_layernorm(layer_p["xattn_norm"], x)
    h = attn.apply_cross_attn(layer_p["xattn"], h, enc_out, cfg)
    x = x + h
    h = apply_layernorm(layer_p["mlp_norm"], x)
    h = apply_mlp(layer_p["mlp"], h, act="gelu")
    return x + h, cache


def decode_train(params, tokens, enc_out, cfg) -> jax.Array:
    dt = compute_dtype(cfg)
    b, s = tokens.shape
    x = apply_embedding(params["embed"], tokens, compute_dtype=dt)
    x = x + params["dec_pos"]["w"][:s].astype(dt)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    def attend(p, h, cache):
        return attn.apply_gqa(p, h, cfg, positions=positions), cache

    def f(carry, layer_p):
        out, _ = _dec_block(cfg, layer_p, carry, enc_out, attend)
        return out, None

    x, _ = remat_scan(f, x, params["dec_layers"], cfg)
    x = apply_layernorm(params["final_norm"], x)
    return apply_lm_head(params["embed"], x)


def train_loss_encdec(params, batch, cfg):
    enc_out = encode(params, batch["encoder_frames"], cfg)
    logits = decode_train(params, batch["tokens"], enc_out, cfg)
    loss = cross_entropy(logits, batch["labels"])
    return loss, {"ce_loss": loss, "aux_loss": jnp.float32(0.0)}


def encdec_state_specs(cfg, batch, max_seq):
    kvh, hd = cfg.n_kv_heads, cfg.head_dim
    spec = {
        "k": jax.ShapeDtypeStruct((cfg.n_layers, batch, max_seq, kvh, hd), jnp.bfloat16),
        "v": jax.ShapeDtypeStruct((cfg.n_layers, batch, max_seq, kvh, hd), jnp.bfloat16),
    }
    return {"cache": spec}


def prefill_encdec(params, tokens, cfg, state, encoder_frames):
    """Encode audio + run the decoder prompt, filling self-attn cache."""
    enc_out = encode(params, encoder_frames, cfg)
    dt = compute_dtype(cfg)
    b, s = tokens.shape
    x = apply_embedding(params["embed"], tokens, compute_dtype=dt)
    x = x + params["dec_pos"]["w"][:s].astype(dt)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    def attend(p, h, cache):
        return attn.apply_gqa_prefill(p, h, cfg, positions=positions, cache=cache)

    def f(carry, xs):
        layer_p, cache = xs
        return _dec_block(cfg, layer_p, carry, enc_out, attend, cache)

    x, new_cache = jax.lax.scan(f, x, (params["dec_layers"], state["cache"]))
    x = apply_layernorm(params["final_norm"], x[:, -1:, :])
    return apply_lm_head(params["embed"], x), {"cache": new_cache}


def decode_step_encdec(params, tokens, state, cache_len, cfg, encoder_out):
    dt = compute_dtype(cfg)
    b = tokens.shape[0]
    x = apply_embedding(params["embed"], tokens, compute_dtype=dt)
    pos_emb = jnp.take(params["dec_pos"]["w"].astype(dt), cache_len[None], axis=0)
    x = x + pos_emb[None]

    def attend(p, h, cache):
        return attn.apply_gqa_decode(p, h, cfg, cache=cache, cache_len=cache_len)

    def f(carry, xs):
        layer_p, cache = xs
        return _dec_block(cfg, layer_p, carry, encoder_out, attend, cache)

    x, new_cache = jax.lax.scan(f, x, (params["dec_layers"], state["cache"]))
    x = apply_layernorm(params["final_norm"], x)
    return apply_lm_head(params["embed"], x), {"cache": new_cache}
