from repro.core.precision import (
    PrecisionPolicy,
    precision_policy,
    loss_scale_init,
    loss_scale_update,
    all_finite,
)
from repro.optim.adamw import adamw_init, adamw_update, AdamWConfig
from repro.optim.schedule import make_schedule, ScheduleConfig
from repro.optim.clip import global_norm, clip_by_global_norm
from repro.optim.sct_optimizer import (
    SCTOptimizer,
    make_sct_optimizer,
    reporting_metrics_to,
    TrainState,
)

__all__ = [
    "PrecisionPolicy",
    "precision_policy",
    "loss_scale_init",
    "loss_scale_update",
    "all_finite",
    "adamw_init",
    "adamw_update",
    "AdamWConfig",
    "make_schedule",
    "ScheduleConfig",
    "global_norm",
    "clip_by_global_norm",
    "SCTOptimizer",
    "reporting_metrics_to",
    "make_sct_optimizer",
    "TrainState",
]
