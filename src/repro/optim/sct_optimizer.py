"""The SCT training step as an optimizer wrapper: AdamW on all params,
then Stiefel retraction of every spectral U/V (paper Algorithm 1).

``retract_every`` > 1 is a beyond-paper optimization: orthogonality
drift per AdamW step is O(lr), so retracting every r steps keeps the
error bounded at O(r*lr) while cutting the retraction cost (40-50% of
the paper's 70B step time) by r. r=1 is the faithful default.

Mixed precision (core/precision.py): with a loss-scaling policy the
state carries a ``loss_scale`` entry, incoming gradients are *scaled*
(the step builder multiplied the loss), and ``apply`` unscales them,
checks finiteness, and wraps the AdamW-update + retraction in a
``lax.cond`` so an overflowed step leaves params, moments, and the
manifold untouched while the scale backs off. Master params are stored
in ``policy.param_dtype`` (fp32 for 'mixed' — the master U/s/V the
forward casts down from).
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Any, Optional, Union

import jax
import jax.numpy as jnp

from repro.core.precision import (
    PrecisionPolicy,
    all_finite,
    cast_tree,
    loss_scale_init,
    loss_scale_update,
    precision_policy,
    unscale_grads,
)
from repro.core import scopes
from repro.core.tree import retract_tree_with_stats, skipped_retraction_stats
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from repro.optim.clip import clip_by_global_norm
from repro.optim.schedule import ScheduleConfig, make_schedule

TrainState = dict  # {"params", "opt", "step"[, "loss_scale"]}

# where SCTOptimizer.apply reports its step metrics while a step is traced
_METRICS_SINK: contextvars.ContextVar = contextvars.ContextVar("sct_step_metrics",
                                                              default=None)


@contextlib.contextmanager
def reporting_metrics_to(sink: dict):
    """Within the block, :meth:`SCTOptimizer.apply` adds its step metrics
    (``core/tree.py:retract_tree_with_stats``; 0 on a step that retracts
    nothing) to ``sink``: arrays of the trace that calls ``apply``, so
    read them in that same trace."""
    token = _METRICS_SINK.set(sink)
    try:
        yield sink
    finally:
        _METRICS_SINK.reset(token)


@dataclasses.dataclass(frozen=True)
class SCTOptimizer:
    adamw: AdamWConfig
    schedule: ScheduleConfig
    retraction: str = "qr"
    retract_every: int = 1
    clip_norm: float = 1.0
    retract_axis_name: Optional[str] = None   # set inside shard_map
    precision: Optional[PrecisionPolicy] = None  # None -> legacy fp32 path

    def init(self, params: Any) -> TrainState:
        if self.precision is not None:
            params = cast_tree(params, self.precision.param_jnp)
        state = {
            "params": params,
            "opt": adamw_init(params, self.adamw.moment_dtype),
            "step": jnp.zeros((), jnp.int32),
        }
        if self.precision is not None and self.precision.loss_scaling:
            state["loss_scale"] = loss_scale_init(self.precision)
        return state

    # ------------------------------------------------------------------
    def _update(self, params: Any, opt: Any, step: jax.Array, grads: Any):
        """One AdamW step + (conditional) retraction, and the retraction's
        metrics (``core/tree.py:retract_tree_with_stats``). ``step`` is the
        pre-increment counter: the schedule reads it, the retraction
        cadence checks step+1 — both exactly as the fp32 path always did."""
        with jax.named_scope(scopes.OPTIMIZER):
            lr_t = make_schedule(self.schedule)(step)
            if self.clip_norm:
                grads, _ = clip_by_global_norm(grads, self.clip_norm)
            params, opt = adamw_update(params, grads, opt, self.adamw, lr_t)
        with jax.named_scope(scopes.RETRACTION):
            if self.retract_every == 1:
                params, stats = retract_tree_with_stats(params, self.retraction,
                                                        self.retract_axis_name)
            else:
                params, stats = jax.lax.cond(
                    (step + 1) % self.retract_every == 0,
                    lambda p: retract_tree_with_stats(p, self.retraction,
                                                      self.retract_axis_name),
                    lambda p: (p, skipped_retraction_stats(p, self.retraction)),
                    params,
                )
        return params, opt, stats

    def resize(self, key: jax.Array, state: TrainState, target) -> TrainState:
        """Resize every spectral group in the TrainState to ``target``
        (uniform int or ``{group_path: rank}`` mapping) — params and the
        Adam moments together, Stiefel feasibility preserved via this
        optimizer's own retraction (rank/resize.py). Host-side: the
        returned state has new shapes, so the caller must re-jit its
        step function (rank/controller.py owns that in the train loop)."""
        from repro.rank.resize import resize_train_state

        return resize_train_state(key, state, target, retraction=self.retraction)

    def apply(self, state: TrainState, grads: Any) -> TrainState:
        """One optimizer step; its metrics go to the sink of an enclosing
        :func:`reporting_metrics_to`."""
        state, metrics = self._apply(state, grads)
        sink = _METRICS_SINK.get()
        if sink is not None:
            sink.update(metrics)
        return state

    def _apply(self, state: TrainState, grads: Any):
        pol = self.precision
        # both the step builder (which scales the loss) and this unscale
        # path key on policy AND state, so a checkpoint written under a
        # different precision policy degrades to the unscaled path on
        # both sides instead of scaling on one side only
        if pol is None or not pol.loss_scaling or "loss_scale" not in state:
            params, opt, stats = self._update(state["params"], state["opt"],
                                              state["step"], grads)
            out = dict(state)
            out.update(params=params, opt=opt, step=state["step"] + 1)
            return out, stats

        # mixed path: grads arrive scaled by state["loss_scale"]["scale"]
        ls = state["loss_scale"]
        with jax.named_scope(scopes.OPTIMIZER):
            grads = unscale_grads(grads, ls)
            finite = all_finite(grads)
        params, opt, stats = jax.lax.cond(
            finite,
            lambda p, o, g: self._update(p, o, state["step"], g),
            lambda p, o, g: (p, o, skipped_retraction_stats(p, self.retraction)),
            state["params"], state["opt"], grads,
        )
        # the step counter advances even on a skip: the data stream and
        # LR schedule stay aligned with the global step
        out = dict(state)   # preserve any extra TrainState entries
        out.update(params=params, opt=opt, step=state["step"] + 1,
                   loss_scale=loss_scale_update(ls, finite, pol))
        return out, stats


def make_sct_optimizer(
    model_cfg=None,
    *,
    lr: float = 5e-4,
    warmup: int = 100,
    total_steps: int = 2000,
    clip_norm: float = 1.0,
    spectral_lr_scale: float = 1.0,
    dense_lr_scale: float = 1.0,
    weight_decay: float = 0.01,
    precision: Union[str, PrecisionPolicy, None] = None,
) -> SCTOptimizer:
    retraction = model_cfg.sct.retraction if model_cfg is not None else "qr"
    retract_every = model_cfg.sct.retract_every if model_cfg is not None else 1
    return SCTOptimizer(
        adamw=AdamWConfig(
            lr=lr,
            weight_decay=weight_decay,
            spectral_lr_scale=spectral_lr_scale,
            dense_lr_scale=dense_lr_scale,
        ),
        schedule=ScheduleConfig(peak_lr=lr, warmup_steps=warmup, total_steps=total_steps),
        retraction=retraction,
        retract_every=retract_every,
        clip_norm=clip_norm,
        precision=precision_policy(precision),
    )
