"""Trainer: the programmatic training facade over one :class:`RunSpec`.

Owns everything ``launch/train.py`` used to wire by hand — config
resolution, mesh construction, optimizer, the jitted train step (with
shardings on a mesh), the adaptive-rank controller, synthetic data, and
the fault-tolerant :class:`TrainLoop` — and exposes two ways to run:

  * :meth:`fit` — the production path: checkpoint/restart loop to
    ``spec.train.steps``, periodic async checkpoints whose sidecars
    embed the serialized RunSpec (self-describing snapshots);
  * :meth:`step` — one optimizer step at a time for notebooks, sweeps,
    and benchmarks that need per-step metrics; no checkpoint directory
    required.

``Trainer.resume(ckpt_dir)`` rebuilds a Trainer from the spec embedded
in the newest checkpoint — zero re-specified flags — and
``resume(ckpt_dir, **{"rank.schedule": "static:K"})`` is the explicit
cross-rank restore: the schedule fires at the restored boundary and the
controller resizes params + Adam moments before the first step.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.specs import RunSpec
from repro.checkpoint.manager import CheckpointManager
from repro.config.shapes import ShapeSpec
from repro.core import scopes
from repro.data.synthetic import SyntheticLMDataset
from repro.launch import steps as steps_mod
from repro.models.model import init_model
from repro.optim import make_sct_optimizer
from repro.rank import RankController
from repro.runtime.train_loop import TrainLoop, TrainLoopConfig
from repro.sharding.rules import set_current_mesh

__all__ = ["Trainer", "log_metrics"]


def log_metrics(step: int, metrics: Dict[str, float]) -> None:
    """The CLI's train-log line (loss, loss scale, rank telemetry) —
    the default ``metrics_cb`` for verbose runs."""
    line = f"step {step:6d}  loss {metrics['loss']:.4f}  ce {metrics['ce_loss']:.4f}"
    if "loss_scale" in metrics:
        line += f"  scale {metrics['loss_scale']:.0f}"
    if "rank/mean" in metrics:
        line += (f"  rank {metrics['rank/mean']:.0f}"
                 f" (eff {metrics['rank/eff_mean']:.1f},"
                 f" energy {metrics['rank/energy_top']:.3f},"
                 f" ortho {metrics['rank/ortho_max']:.1e})")
    print(line, flush=True)


class Trainer:
    """One training run, fully described by ``spec``.

    ``metrics_cb(step, {name: float})`` fires every ``log_every`` steps
    inside :meth:`fit` (pass :func:`log_metrics` for the CLI format);
    ``failure_hook`` is the chaos-drill injection point the loop already
    supports. Construction is cheap-ish (config + jit closure building,
    no weights); parameters materialize on the first :meth:`fit` /
    :meth:`step` / :meth:`save`.
    """

    def __init__(self, spec: RunSpec, *,
                 metrics_cb: Optional[Callable[[int, Dict], None]] = None,
                 failure_hook: Optional[Callable[[int], None]] = None):
        self.spec = spec
        self.cfg = spec.model.config()
        t = spec.train
        self.optimizer = make_sct_optimizer(
            self.cfg, lr=t.lr, warmup=t.warmup_steps, total_steps=t.steps,
            precision=spec.precision.mode)
        self.mesh = spec.sharding.mesh(self.cfg)
        if self.mesh is not None:
            set_current_mesh(self.mesh)
        self.rank_schedule = spec.rank.parsed()
        self.telemetry = t.telemetry or self.rank_schedule is not None
        self.shape = ShapeSpec("api", t.seq, t.batch, "train")
        self.metrics_cb = metrics_cb
        self.failure_hook = failure_hook

        step_fn = steps_mod.make_train_step(
            self.cfg, self.optimizer, microbatches=t.microbatches,
            telemetry=self.telemetry)
        if self.mesh is not None:
            state_sh, batch_sh = steps_mod.train_shardings(
                self.cfg, self.shape, self.mesh)
            self._step_fn = jax.jit(step_fn, in_shardings=(state_sh, batch_sh),
                                    out_shardings=(state_sh, None),
                                    donate_argnums=(0,))
            self.state_shardings = state_sh
        else:
            self._step_fn = jax.jit(step_fn, donate_argnums=(0,))
            self.state_shardings = None

        self.controller = None
        if self.rank_schedule is not None:
            self.controller = RankController(
                self.cfg, self.optimizer, self.rank_schedule, mesh=self.mesh,
                shape=self.shape, microbatches=t.microbatches, seed=t.seed)

        self.dataset = SyntheticLMDataset(vocab=self.cfg.vocab,
                                          seq_len=t.seq, seed=t.seed)
        self.manager: Optional[CheckpointManager] = None
        if spec.checkpoint.directory is not None:
            self.manager = CheckpointManager(
                spec.checkpoint.directory, keep=spec.checkpoint.keep,
                run_spec=spec.to_dict())
        self.loop: Optional[TrainLoop] = None
        self._state: Any = None
        self._step = 0
        self._batches = None

    # ---------------------------------------------------------------- data --
    def make_batch(self, step: int) -> Dict[str, jax.Array]:
        """The spec's synthetic batch for ``step`` — what :meth:`step`
        consumes by default; public so benchmarks can build the batch
        outside their timed region."""
        t, l = self.dataset.batch(step, self.spec.train.batch)
        batch = {"tokens": jnp.asarray(t), "labels": jnp.asarray(l)}
        if self.cfg.family == "encdec":
            from repro.data.vision_stub import audio_frame_stub

            batch["encoder_frames"] = jnp.asarray(audio_frame_stub(
                self.spec.train.batch, self.cfg.encoder_seq, self.cfg.d_model))
        return batch

    def _batch_iter(self, start_step: int):
        step = start_step
        while True:
            yield self.make_batch(step)
            step += 1

    def _init_state(self):
        def init():
            params = init_model(jax.random.PRNGKey(self.spec.train.seed),
                                self.cfg)
            return self.optimizer.init(params)

        if self.state_shardings is None:
            return init()
        # born sharded: built eagerly, the whole state would first sit
        # on one device, which a model sized for the mesh cannot fit
        return jax.jit(init, out_shardings=self.state_shardings)()

    # ----------------------------------------------------------------- fit --
    def fit(self) -> Any:
        """Run the fault-tolerant loop to ``spec.train.steps`` and return
        the final TrainState. Resumes automatically from the newest
        checkpoint under ``spec.checkpoint.directory`` (which is
        required here — the restart path is disk-backed; use
        :meth:`step` for checkpoint-free experimentation)."""
        if self.manager is None:
            raise ValueError(
                "Trainer.fit needs spec.checkpoint.directory (the "
                "fault-tolerant loop restarts from disk); set it via "
                "spec.replace(**{'checkpoint.directory': ...}) or drive "
                "the run with Trainer.step() instead")
        if self._state is not None:
            # the loop resumes from disk (fault tolerance); progress made
            # in-memory via step() must land there first or it would be
            # silently re-run from the last checkpoint
            latest = self.manager.list_steps()
            if self._step > (latest[-1] if latest else -1):
                self.manager.save(self._step, jax.device_get(self._state),
                                  block=True)
        self.loop = TrainLoop(
            step_fn=self._step_fn,
            batch_iter_factory=self._batch_iter,
            ckpt_dir=self.spec.checkpoint.directory,
            cfg=TrainLoopConfig(total_steps=self.spec.train.steps,
                                checkpoint_every=self.spec.checkpoint.every,
                                keep_checkpoints=self.spec.checkpoint.keep),
            init_state_fn=self._init_state,
            state_shardings=self.state_shardings,
            metrics_cb=self.metrics_cb,
            failure_hook=self.failure_hook,
            rank_controller=self.controller,
            checkpoint_manager=self.manager,
        )
        self._state = self.loop.run()
        # the achieved step comes from the state itself: a checkpoint
        # already past train.steps restores and runs zero steps, and
        # current_step/save() must reflect that, not the budget
        self._step = (int(np.asarray(self._state["step"]))
                      if isinstance(self._state, dict) and "step" in self._state
                      else self.spec.train.steps)
        # the loop may have swapped in a resized step_fn/shardings, and
        # step() may be used to keep going — keep the data stream aligned
        self._step_fn = self.loop.step_fn
        self.state_shardings = self.loop.state_shardings
        self._batches = self._batch_iter(self._step)
        return self._state

    # ---------------------------------------------------------------- step --
    def _ensure_state(self) -> None:
        if self._state is not None:
            return
        step, state = (self.manager.restore_latest(self.state_shardings)
                       if self.manager is not None else (None, None))
        if state is None:
            step, state = 0, self._init_state()
        if self.controller is not None:
            # resize-on-restore: same boundary consult the loop performs
            result = self.controller.maybe_resize(step, state)
            if result is not None:
                state, self._step_fn, self.state_shardings = result
        self._state, self._step = state, step
        self._batches = self._batch_iter(step)

    def step(self, batch: Optional[Dict[str, jax.Array]] = None) -> Dict[str, jax.Array]:
        """One optimizer step; returns the step's metrics (device
        arrays — ``float(...)`` them as needed). The first call restores
        the newest checkpoint when a directory is configured, else
        initializes from ``spec.train.seed``. ``batch`` defaults to the
        spec's synthetic stream at the current step index; rank
        schedules fire at the same step boundaries as in :meth:`fit`."""
        self._ensure_state()
        if batch is None:
            with jax.profiler.TraceAnnotation(scopes.BATCH):
                batch = next(self._batches)
        with jax.profiler.TraceAnnotation(scopes.DISPATCH):
            self._state, metrics = self._step_fn(self._state, batch)
        self._step += 1
        if self.controller is not None:
            with jax.profiler.TraceAnnotation(scopes.RANK_RESIZE):
                result = self.controller.maybe_resize(self._step, self._state, metrics)
            if result is not None:
                self._state, self._step_fn, self.state_shardings = result
        return metrics

    # ---------------------------------------------------------------- save --
    def save(self, block: bool = True) -> int:
        """Checkpoint the current state at the current step index (with
        the RunSpec embedded in the sidecar); returns the step saved."""
        if self.manager is None:
            raise ValueError("Trainer.save needs spec.checkpoint.directory")
        self._ensure_state()
        self.manager.save(self._step, jax.device_get(self._state), block=block)
        return self._step

    # -------------------------------------------------------------- resume --
    @classmethod
    def resume(cls, ckpt_dir: str, **overrides) -> "Trainer":
        """A Trainer rebuilt from the RunSpec embedded in the newest
        checkpoint under ``ckpt_dir`` — no flags re-specified; the next
        :meth:`fit`/:meth:`step` restores that snapshot. ``overrides``
        are :meth:`RunSpec.replace` arguments — the explicit cross-rank
        (``{"rank.schedule": "static:64"}``), cross-precision
        (``{"precision.mode": "mixed"}``), or extended-budget
        (``{"train.steps": 600}``) restore paths."""
        from repro.api.server import load_run_spec

        _, spec = load_run_spec(ckpt_dir)
        merged = {"checkpoint.directory": ckpt_dir}
        merged.update(overrides)
        return cls(spec.replace(**merged))

    # --------------------------------------------------------------- state --
    @property
    def state(self) -> Any:
        """The live TrainState (materializing it on first access)."""
        self._ensure_state()
        return self._state

    @property
    def params(self) -> Any:
        return self.state["params"]

    @property
    def current_step(self) -> int:
        """The global step of the live state (materializing it on first
        access, like :attr:`state` — a resumed trainer reports the
        checkpoint's step, not 0)."""
        self._ensure_state()
        return self._step
