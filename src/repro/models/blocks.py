"""One layer body per decoder-only family, shared by every mode.

  dense_block    — pre-norm attention, then MLP or MoE: dense_lm layers,
                   moe_lm's leading dense layers and its MoE layers
  hybrid_period  — one jamba period: attention or Mamba mixer per layer,
                   each followed by MLP or MoE
  xlstm_period   — one xlstm period: mLSTM or sLSTM per block

A body owns its norms, residual adds, MLP/MoE call and the train
step's named scopes. What differs by mode is the sequence mixer and the
state it threads, which the caller passes in as ``mixer(p, h, state) ->
(out, new state)``: training (models/lm.py) threads no state, the
static prefill and the decode steps (models/decode.py) thread a KV cache
or a recurrent state per layer. ``remat_scan`` is the one place the
training layer scans take their rematerialization policy.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import scopes
from repro.nn.mlp import apply_mlp
from repro.nn.moe import apply_moe
from repro.nn.norms import apply_rmsnorm, apply_layernorm


def norm_apply(cfg, p, x):
    return apply_rmsnorm(p, x) if cfg.norm == "rmsnorm" else apply_layernorm(p, x)


def compute_dtype(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def remat_scan(f, init, xs, cfg):
    """``lax.scan(f, init, xs)`` over a stack of layers; under
    ``cfg.remat`` every step is rematerialized with nothing saved, so
    backward recomputes the layer from its input carry."""
    if cfg.remat:
        f = jax.checkpoint(f, policy=jax.checkpoint_policies.nothing_saveable)
    return jax.lax.scan(f, init, xs)


def _feed_forward(cfg, p, x):
    """MLP or MoE of a layer whose params hold ``mlp`` or ``moe``;
    returns (out, MoE aux loss or None)."""
    if "moe" in p:
        return apply_moe(p["moe"], x, cfg, capacity_factor=cfg.capacity_factor)
    return apply_mlp(p["mlp"], x, act=cfg.act), None


def _layer_state(stacked, i):
    # the i-th layer's slice of a stacked recurrent state (None in training)
    return jax.tree.map(lambda t: t[i], stacked)


def _stack(states):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *states)


def dense_block(cfg, p, x, attend, cache=None):
    """Attention then MLP/MoE, each pre-norm with a residual add.
    ``attend(attn_params, h, cache) -> (out, cache)``. Returns (x, cache,
    MoE aux loss or None)."""
    with jax.named_scope(scopes.ATTENTION):
        h = norm_apply(cfg, p["attn_norm"], x)
        h, cache = attend(p["attn"], h, cache)
        x = x + h
    with jax.named_scope(scopes.MLP):
        h = norm_apply(cfg, p["mlp_norm"], x)
        h, aux = _feed_forward(cfg, p, h)
        return x + h, cache, aux


def hybrid_period(cfg, pp, x, attend, mamba, cache=None, mamba_states=None):
    """The ``cfg.attn_every`` layers of one jamba period: attention at
    ``cfg.attn_offset``, Mamba elsewhere, each followed by MLP or MoE.
    ``mamba(p, h, state) -> (out, state)`` gets its layer's slice of
    ``mamba_states`` (stacked over the period's Mamba layers). Returns
    (x, cache, new stacked Mamba states, summed MoE aux loss)."""
    aux_total = jnp.float32(0.0)
    new_m = []
    for i in range(cfg.attn_every):
        lp = pp[f"p{i}"]
        if "attn" in lp:
            with jax.named_scope(scopes.ATTENTION):
                h = norm_apply(cfg, lp["pre_norm"], x)
                h, cache = attend(lp["attn"], h, cache)
                x = x + h
        else:
            h = norm_apply(cfg, lp["pre_norm"], x)
            h, ms = mamba(lp["mamba"], h, _layer_state(mamba_states, len(new_m)))
            new_m.append(ms)
            x = x + h
        with jax.named_scope(scopes.MLP):
            h = norm_apply(cfg, lp["ff_norm"], x)
            h, aux = _feed_forward(cfg, lp, h)
            if aux is not None:
                aux_total = aux_total + aux
            x = x + h
    return x, cache, _stack(new_m), aux_total


def xlstm_period(cfg, pp, x, mlstm, slstm, mlstm_states=None, slstm_state=None):
    """The ``cfg.slstm_every`` blocks of one xlstm period: sLSTM at
    ``cfg.slstm_offset``, mLSTM elsewhere, each pre-norm with a residual
    add. ``mlstm``/``slstm(p, h, state) -> (out, state)``; the mLSTM
    states are stacked over the period's mLSTM blocks. Returns (x, new
    stacked mLSTM states, new sLSTM state)."""
    new_m = []
    for i in range(cfg.slstm_every):
        lp = pp[f"p{i}"]
        h = norm_apply(cfg, lp["pre_norm"], x)
        if "slstm" in lp:
            h, slstm_state = slstm(lp["slstm"], h, slstm_state)
        else:
            h, ms = mlstm(lp["mlstm"], h, _layer_state(mlstm_states, len(new_m)))
            new_m.append(ms)
        x = x + h
    return x, _stack(new_m), slstm_state
