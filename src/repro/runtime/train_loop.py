"""Fault-tolerant training loop.

Production behaviours, all exercised by tests on CPU:

  * checkpoint/restart: periodic async checkpoints with rotation; on
    (re)start the loop resumes from the newest complete checkpoint and
    regenerates the data stream deterministically from the step index —
    a restarted run is bit-identical to an uninterrupted one.
  * failure injection: ``failure_hook`` lets tests (and chaos drills)
    raise mid-run; the loop converts unhandled step failures into a
    clean checkpoint-backed restart up to ``max_restarts``. Errors the
    runtime raises (``jax.errors.JaxRuntimeError``: out of device
    memory, a refused compile, a lost device) re-raise at once — a
    restart would only meet them again.
  * straggler mitigation: per-step deadline; steps that exceed it are
    counted and surfaced (on real multi-host this feeds the
    reschedule/evict policy; here it is monitored + tested).
  * elastic scaling: ``CheckpointManager`` stores host arrays, so a
    restart may use a different mesh/DP width — resharding happens at
    load via the new mesh's NamedShardings.
  * adaptive rank: an optional ``rank_controller`` (rank/controller.py)
    is consulted at every step boundary; when its schedule fires, the
    loop swaps in the resized state, the re-jitted step function, and
    the regenerated sharding tree mid-run. Resize events are recorded
    in ``controller.resizes`` and counted here in ``rank_resizes``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Iterator, Optional

import jax
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.core import scopes


@dataclasses.dataclass
class TrainLoopConfig:
    total_steps: int = 100
    checkpoint_every: int = 20
    keep_checkpoints: int = 3
    step_deadline_s: Optional[float] = None   # straggler threshold
    max_restarts: int = 3
    log_every: int = 10


class TrainLoop:
    def __init__(
        self,
        step_fn: Callable,                      # jitted (state, batch) -> (state, metrics)
        batch_iter_factory: Callable[[int], Iterator],  # start_step -> iterator
        ckpt_dir: str,
        cfg: TrainLoopConfig,
        init_state_fn: Callable[[], Any],
        state_shardings: Any = None,
        metrics_cb: Optional[Callable[[int, Dict], None]] = None,
        failure_hook: Optional[Callable[[int], None]] = None,
        rank_controller: Optional[Any] = None,
        checkpoint_manager: Optional[CheckpointManager] = None,
    ):
        self.step_fn = step_fn
        self.batch_iter_factory = batch_iter_factory
        self.cfg = cfg
        # an injected manager wins — the API facade passes one carrying
        # the serialized RunSpec so every sidecar is self-describing
        self.mgr = checkpoint_manager or CheckpointManager(
            ckpt_dir, keep=cfg.keep_checkpoints)
        self.init_state_fn = init_state_fn
        self.state_shardings = state_shardings
        self.metrics_cb = metrics_cb
        self.failure_hook = failure_hook
        self.rank_controller = rank_controller
        self.straggler_steps = 0
        # per executed step, in order (a restart appends its replays):
        # host seconds to a ready loss, and that loss
        self.step_seconds: list = []
        self.losses: list = []
        self.restarts = 0
        self.rank_resizes = 0
        # mixed precision: overflow-skipped steps, mirrored from the
        # authoritative checkpointed counter state["loss_scale"]["skipped"]
        # when the run finishes
        self.overflow_steps = 0

    # ------------------------------------------------------------------
    def _start_state(self):
        step, state = self.mgr.restore_latest(self.state_shardings)
        if state is None:
            return 0, self.init_state_fn()
        return step, state

    def run(self) -> Any:
        attempt = 0
        while True:
            try:
                return self._run_once()
            except jax.errors.JaxRuntimeError:
                raise
            except Exception:  # noqa: BLE001 — any other step failure
                attempt += 1
                self.restarts += 1
                if attempt > self.cfg.max_restarts:
                    raise
                # flush any in-flight async checkpoint write before the
                # restart touches the checkpoint directory: a writer
                # still running would race the restarted attempt's
                # restore_latest/save. Writer errors are swallowed —
                # the restart path must not die on a failed background
                # save (the restore picks the newest *complete*
                # checkpoint either way).
                try:
                    self.mgr.wait()
                except Exception:  # noqa: BLE001 — writer error
                    pass
                # fall through: restart from the latest checkpoint

    def _apply_rank_decision(self, step: int, state, metrics=None):
        """Consult the rank controller at a step boundary; on a resize,
        swap in the new state, the re-jitted step_fn, and the
        regenerated shardings (stale old-shape executables are simply
        abandoned — jit keeps them cached but they are never called)."""
        if self.rank_controller is None:
            return state
        with jax.profiler.TraceAnnotation(scopes.RANK_RESIZE):
            result = self.rank_controller.maybe_resize(step, state, metrics)
        if result is None:
            return state
        state, self.step_fn, self.state_shardings = result
        self.rank_resizes += 1
        return state

    def _run_once(self) -> Any:
        start_step, state = self._start_state()
        # resize-on-restore: a restored checkpoint may carry a different
        # rank than the schedule dictates at this step (the schedule is
        # a pure function of the global step, so replay is consistent)
        state = self._apply_rank_decision(start_step, state)
        batches = self.batch_iter_factory(start_step)
        step = start_step
        while step < self.cfg.total_steps:
            with jax.profiler.StepTraceAnnotation(scopes.TRAIN_STEP, step_num=step):
                with jax.profiler.TraceAnnotation(scopes.BATCH):
                    batch = next(batches)
                # a monotonic clock: a wall-clock jump would corrupt
                # step_seconds and the straggler count
                t0 = time.perf_counter()
                if self.failure_hook is not None:
                    self.failure_hook(step)
                with jax.profiler.TraceAnnotation(scopes.DISPATCH):
                    state, metrics = self.step_fn(state, batch)
                with jax.profiler.TraceAnnotation(scopes.LOSS_SYNC):
                    # straggler detection needs the actual step time
                    jax.block_until_ready(metrics["loss"])
                    dt = time.perf_counter() - t0
                    loss = float(metrics["loss"])
                self.step_seconds.append(dt)
                self.losses.append(loss)
                if self.cfg.step_deadline_s and dt > self.cfg.step_deadline_s:
                    self.straggler_steps += 1
                step += 1
                state = self._apply_rank_decision(step, state, metrics)
                if self.metrics_cb and step % self.cfg.log_every == 0:
                    self.metrics_cb(step, {k: float(np.asarray(v))
                                           for k, v in metrics.items()})
                if step % self.cfg.checkpoint_every == 0 or step == self.cfg.total_steps:
                    with jax.profiler.TraceAnnotation(scopes.CHECKPOINT):
                        # fetch to host *before* handing off to the async
                        # writer: the next step donates these device
                        # buffers (train.py jits with donate_argnums), and
                        # a save thread reading them after donation sees
                        # deleted arrays
                        self.mgr.save(step, jax.device_get(state))
        with jax.profiler.TraceAnnotation(scopes.CHECKPOINT):
            self.mgr.wait()
        if isinstance(state, dict) and "loss_scale" in state:
            # derived once at the end, not per step — no extra host
            # readback in the hot loop
            self.overflow_steps = int(np.asarray(state["loss_scale"]["skipped"]))
        return state
