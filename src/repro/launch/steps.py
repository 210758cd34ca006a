"""Step builders: the jit-able train_step / prefill_step / serve_step
closures plus their in/out shardings for a given (config, shape, mesh).

These are shared by the real launcher (train.py / serve.py), the
dry-run (dryrun.py lowers them with ShapeDtypeStructs), and the tests.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config.model_config import ModelConfig
from repro.config.shapes import ShapeSpec, input_specs
from repro.core.precision import effective_policy, scale_loss
from repro.models.model import init_model, train_loss, prefill, decode_step
from repro.optim import make_sct_optimizer, reporting_metrics_to, SCTOptimizer
from repro.sharding.rules import param_pspecs, set_current_mesh, constrain, dp_axes
from repro.sharding.partition import (
    batch_pspecs,
    named_shardings,
    batch_axes,
)


# ----------------------------------------------------------------------
# Train
# ----------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, optimizer: Optional[SCTOptimizer] = None,
                    microbatches: int = 1, telemetry: bool = False):
    """(state, batch) -> (state, metrics). Pure; jit elsewhere.

    microbatches > 1 scans over batch slices accumulating gradients —
    activation memory drops by the microbatch count while the gradient
    accumulator is only params-sized fp32, which SCT makes k(m+n+1)
    instead of mn (gradient accumulation is disproportionately cheap for
    spectral models — DESIGN.md S2).

    If the optimizer carries a PrecisionPolicy, its compute dtype
    overrides ``cfg.dtype`` for the forward (bf16 apply-time casts off
    the fp32 masters), and with loss scaling on, the loss is multiplied
    by the dynamic scale before differentiation — ``opt.apply`` unscales
    and skips overflowed steps. Metrics then report the *unscaled* loss
    plus ``loss_scale`` / ``overflow``.

    ``telemetry=True`` folds the spectral-rank summary (rank/telemetry.py:
    effective rank, energy capture, tail mass, Stiefel drift — all
    computed on the post-update factors inside the same jit) into the
    metrics dict under ``rank/*`` keys; dense models emit nothing.

    Where some spectral factor is retracted by ``qr``, the metrics hold
    ``retraction/qr_fallbacks``: the stacked U and V leaves whose
    Cholesky-QR2 guard sent them to Householder QR this step."""
    opt = optimizer or make_sct_optimizer(cfg)
    # always a concrete policy: the legacy precision mode resolves
    # to (cfg.dtype compute, fp32 accum, no scaling) instead of a None
    # sentinel branching every dtype decision below
    pol = effective_policy(cfg, opt.precision)
    cfg_eff = cfg.replace(dtype=pol.compute_dtype)
    accum_dtype = pol.accum_jnp

    def train_step(state, batch):
        params = state["params"]
        # scaling requires BOTH the policy and the state entry (a state
        # restored from a non-mixed checkpoint lacks it) — mirrored by
        # SCTOptimizer.apply, so scale and unscale always agree
        scaling = pol.loss_scaling and "loss_scale" in state
        scale = state["loss_scale"]["scale"] if scaling else None

        def loss_fn(params, batch):
            total, metrics = train_loss(params, batch, cfg_eff)
            total = scale_loss(total, state["loss_scale"] if scaling else None)
            return total, metrics

        if microbatches == 1:
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        else:
            from repro.sharding import rules as rules_mod

            def split(x):
                y = x.reshape(microbatches, x.shape[0] // microbatches, *x.shape[1:])
                if rules_mod._CURRENT_MESH is not None:
                    bt = rules_mod.dp_axes(rules_mod._CURRENT_MESH)
                    y = rules_mod.constrain(y, None, bt, *([None] * (y.ndim - 2)))
                return y

            mbatch = jax.tree.map(split, batch)

            def body(acc, mb):
                (l, met), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
                acc = jax.tree.map(lambda a, b: a + b.astype(accum_dtype), acc, g)
                return acc, (l, met)

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, accum_dtype), params)
            grads, (losses, mets) = jax.lax.scan(body, zeros, mbatch)
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            loss = jnp.mean(losses)
            metrics = jax.tree.map(lambda m: jnp.mean(m), mets)
        opt_metrics = {}
        with reporting_metrics_to(opt_metrics):
            new_state = opt.apply(state, grads)
        metrics = dict(metrics, **opt_metrics)
        if scaling:
            # report the unscaled loss (scale is a power of two: exact)
            metrics["loss"] = loss / scale
            metrics["loss_scale"] = scale
            metrics["overflow"] = (
                new_state["loss_scale"]["skipped"] > state["loss_scale"]["skipped"]
            ).astype(jnp.float32)
        else:
            metrics["loss"] = loss
        if telemetry:
            from repro.rank.telemetry import telemetry_summary

            metrics.update(telemetry_summary(new_state["params"]))
        return new_state, metrics

    return train_step


def default_microbatches(cfg: ModelConfig, shape: ShapeSpec, mesh) -> int:
    """Adaptive default: keep per-device-per-microbatch tokens at or
    under ~16k so transient activations fit v5e HBM alongside the SCT
    state. Divisibility-safe."""
    from repro.sharding.partition import batch_axes

    bt = batch_axes(shape.global_batch, mesh) or ()
    n_dp = 1
    for a in bt:
        n_dp *= mesh.shape[a]
    local_batch = shape.global_batch // max(n_dp, 1)
    tokens = local_batch * shape.seq_len
    mb = 1
    while tokens // mb > 16_384 and mb < local_batch and local_batch % (mb * 2) == 0:
        mb *= 2
    return mb


def abstract_train_state(cfg: ModelConfig, optimizer: Optional[SCTOptimizer] = None):
    """ShapeDtypeStruct tree of the full train state — no allocation.
    This is what the dry-run lowers against."""
    opt = optimizer or make_sct_optimizer(cfg)

    def build():
        params = init_model(jax.random.PRNGKey(0), cfg)
        return opt.init(params)

    return jax.eval_shape(build)


def train_shardings(cfg: ModelConfig, shape: ShapeSpec, mesh, state_like=None):
    """(state_shardings, batch_shardings) as NamedSharding trees.
    ``state_like`` may be abstract (dry-run) or a live resized state
    (rank/controller.py) — shardings key on structure, not values."""
    from repro.sharding.partition import state_shardings_for

    if state_like is None:
        state_like = abstract_train_state(cfg)
    bspec = batch_pspecs(cfg, shape, mesh)
    return state_shardings_for(state_like, mesh), named_shardings(bspec, mesh)


def lower_train_step(cfg: ModelConfig, shape: ShapeSpec, mesh,
                     optimizer: Optional[SCTOptimizer] = None, donate: bool = True,
                     microbatches: Optional[int] = None):
    """jit(train_step).lower(...) with full sharding annotations —
    the dry-run entry point for training shapes."""
    opt = optimizer or make_sct_optimizer(cfg)
    state_like = abstract_train_state(cfg, opt)
    state_sh, batch_sh = train_shardings(cfg, shape, mesh, state_like)
    if microbatches is None:
        microbatches = default_microbatches(cfg, shape, mesh)
    step_fn = make_train_step(cfg, opt, microbatches=microbatches)
    set_current_mesh(mesh)
    jitted = jax.jit(
        step_fn,
        in_shardings=(state_sh, batch_sh),
        out_shardings=(state_sh, None),
        donate_argnums=(0,) if donate else (),
    )
    batch_like = input_specs(cfg, shape)
    with mesh:
        lowered = jitted.lower(state_like, batch_like)
    return lowered


# ----------------------------------------------------------------------
# Serve (prefill / decode)
# ----------------------------------------------------------------------

def make_prefill_step(cfg: ModelConfig):
    if cfg.family == "encdec":
        def prefill_step(params, tokens, state, encoder_frames):
            return prefill(params, tokens, cfg, state, encoder_frames=encoder_frames)
    else:
        def prefill_step(params, tokens, state):
            return prefill(params, tokens, cfg, state)

    return prefill_step


def make_serve_step(cfg: ModelConfig):
    if cfg.family == "encdec":
        def serve_step(params, tokens, state, cache_len, encoder_out):
            return decode_step(params, tokens, state, cache_len, cfg, encoder_out=encoder_out)
    else:
        def serve_step(params, tokens, state, cache_len):
            return decode_step(params, tokens, state, cache_len, cfg)

    return serve_step


def abstract_params(cfg: ModelConfig):
    return jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))


def lower_serve_step(cfg: ModelConfig, shape: ShapeSpec, mesh):
    """Lower prefill (shape.kind == 'prefill') or single-token decode
    (shape.kind == 'decode') with sharding annotations."""
    params_like = abstract_params(cfg)
    n_model = mesh.shape.get("model", 1)
    n_data = mesh.shape.get("data", 1)
    p_sh = named_shardings(param_pspecs(params_like, n_model, n_data), mesh)
    specs = input_specs(cfg, shape)
    b_sh = named_shardings(batch_pspecs(cfg, shape, mesh), mesh)
    set_current_mesh(mesh)

    if shape.kind == "prefill":
        from repro.models.model import decode_state_specs
        from repro.sharding.partition import decode_state_pspecs

        state_like = decode_state_specs(cfg, batch=shape.global_batch, max_seq=shape.seq_len)
        bt = batch_axes(shape.global_batch, mesh)
        st_sh = named_shardings(decode_state_pspecs(cfg, shape, mesh, bt), mesh)
        fn = make_prefill_step(cfg)
        args = [params_like, specs["tokens"], state_like]
        in_sh = [p_sh, b_sh["tokens"], st_sh]
        if cfg.family == "encdec":
            args.append(specs["encoder_frames"])
            in_sh.append(b_sh["encoder_frames"])
        jitted = jax.jit(fn, in_shardings=tuple(in_sh), out_shardings=(None, st_sh))
        with mesh:
            return jitted.lower(*args)

    # decode
    fn = make_serve_step(cfg)
    st_sh = b_sh["state"]
    args = [params_like, specs["tokens"], specs["state"], specs["cache_len"]]
    in_sh = [p_sh, b_sh["tokens"], st_sh, b_sh["cache_len"]]
    if cfg.family == "encdec":
        args.append(specs["encoder_out"])
        in_sh.append(b_sh["encoder_out"])
    jitted = jax.jit(
        fn,
        in_shardings=tuple(in_sh),
        out_shardings=(None, st_sh),
        donate_argnums=(2,),
    )
    with mesh:
        return jitted.lower(*args)


def lower_step(cfg: ModelConfig, shape: ShapeSpec, mesh):
    from repro.sharding.rules import set_activation_seq_sharding

    # seq-parallel needs the attention head axis to divide the model
    # axis, else every layer's SP boundary resharding degenerates into
    # gathers (measured: qwen1.5-4b's 20 heads on a 16-way axis regressed
    # 6.2 -> 7.3 s; with this guard it keeps its baseline).
    n_model = mesh.shape.get("model", 1)
    sp = cfg.seq_parallel and cfg.n_heads % n_model == 0
    set_activation_seq_sharding("model" if sp else None)
    try:
        if shape.kind == "train":
            return lower_train_step(cfg, shape, mesh)
        return lower_serve_step(cfg, shape, mesh)
    finally:
        set_activation_seq_sharding(None)
