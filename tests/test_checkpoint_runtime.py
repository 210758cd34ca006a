"""Checkpoint + fault-tolerant runtime tests: roundtrip, rotation,
crash/restart bitwise continuation, failure injection, straggler
monitoring. (Gradient-compression tests live in test_compression.py.)"""
import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint import CheckpointManager, save_pytree, load_pytree, tree_equal
from repro.config import get_config
from repro.data.synthetic import SyntheticLMDataset
from repro.launch.steps import make_train_step
from repro.models.model import init_model
from repro.optim import make_sct_optimizer
from repro.runtime.train_loop import TrainLoop, TrainLoopConfig


def test_pytree_roundtrip(tmp_path, key):
    tree = {
        "a": jax.random.normal(key, (4, 5)),
        "nested": {"b": jnp.arange(7), "c": (jnp.ones((2,)), jnp.zeros((3,)))},
    }
    p = str(tmp_path / "ck.npz")
    save_pytree(tree, p)
    out = load_pytree(p)
    assert tree_equal(tree, out)
    assert isinstance(out["nested"]["c"], tuple)


def test_manager_rotation(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    for s in (10, 20, 30, 40):
        mgr.save(s, {"x": jnp.full((2,), s)})
    assert mgr.list_steps() == [30, 40]
    step, state = mgr.restore_latest()
    assert step == 40 and float(state["x"][0]) == 40


def _make_loop(tmp_path, cfg, total=12, ckpt_every=4, failure_hook=None,
               deadline=None):
    opt = make_sct_optimizer(cfg, lr=1e-3, warmup=2, total_steps=total)
    step_fn = jax.jit(make_train_step(cfg, opt))
    ds = SyntheticLMDataset(vocab=cfg.vocab, seq_len=16, seed=0)

    def batches(start):
        step = start
        while True:
            t, l = ds.batch(step, 4)
            yield {"tokens": jnp.asarray(t), "labels": jnp.asarray(l)}
            step += 1

    def init_state():
        return opt.init(init_model(jax.random.PRNGKey(0), cfg))

    return TrainLoop(
        step_fn=step_fn,
        batch_iter_factory=batches,
        ckpt_dir=str(tmp_path),
        cfg=TrainLoopConfig(total_steps=total, checkpoint_every=ckpt_every,
                            step_deadline_s=deadline, max_restarts=3),
        init_state_fn=init_state,
        failure_hook=failure_hook,
    )


def test_restart_is_bitwise_identical(tmp_path):
    """Train 12 steps straight vs. train-8/crash/restart: identical."""
    cfg = get_config("smollm2-1.7b", reduced=True)
    straight = _make_loop(tmp_path / "a", cfg).run()

    crashed = {"done": False}

    def bomb(step):
        if step == 8 and not crashed["done"]:
            crashed["done"] = True
            raise RuntimeError("injected node failure")

    loop = _make_loop(tmp_path / "b", cfg, failure_hook=bomb)
    resumed = loop.run()
    assert loop.restarts == 1
    assert tree_equal(straight["params"], resumed["params"])
    assert int(straight["step"]) == int(resumed["step"]) == 12


def test_restart_across_loop_instances(tmp_path):
    """Failure-injection restart across *processes*: the first loop dies
    mid-run past its restart budget, a fresh TrainLoop instance (new
    process in production) resumes from its checkpoints and lands
    bit-identical to an uninterrupted run."""
    cfg = get_config("smollm2-1.7b", reduced=True)
    straight = _make_loop(tmp_path / "a", cfg).run()

    def bomb(step):
        if step == 9:
            raise RuntimeError("injected node failure")

    first = _make_loop(tmp_path / "b", cfg, failure_hook=bomb)
    first.cfg.max_restarts = 0                      # process actually dies
    with pytest.raises(RuntimeError):
        first.run()
    resumed = _make_loop(tmp_path / "b", cfg).run()  # fresh instance, same dir
    assert tree_equal(straight["params"], resumed["params"])
    assert int(resumed["step"]) == 12


def test_too_many_failures_raises(tmp_path):
    cfg = get_config("smollm2-1.7b", reduced=True)

    def always_bomb(step):
        raise RuntimeError("persistent failure")

    loop = _make_loop(tmp_path, cfg, failure_hook=always_bomb)
    with pytest.raises(RuntimeError):
        loop.run()


def test_device_error_is_not_retried(tmp_path):
    """A runtime error from the device (here an out-of-memory refusal)
    re-raises on its first occurrence: no restart, no second attempt."""
    cfg = get_config("smollm2-1.7b", reduced=True)
    calls = []

    def oom(step):
        calls.append(step)
        raise jax.errors.JaxRuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory while trying to allocate")

    loop = _make_loop(tmp_path, cfg, failure_hook=oom)
    with pytest.raises(jax.errors.JaxRuntimeError, match="RESOURCE_EXHAUSTED"):
        loop.run()
    assert loop.restarts == 0
    assert calls == [0]


def test_async_checkpoint_with_donated_state(tmp_path):
    """Async checkpointing must not race with buffer donation (the
    production launcher jits with donate_argnums=(0,)): the loop fetches
    state to host before the next step deletes the donated buffers, so
    every periodic checkpoint lands complete."""
    cfg = get_config("smollm2-1.7b", reduced=True)
    total = 6
    opt = make_sct_optimizer(cfg, lr=1e-3, warmup=2, total_steps=total)
    step_fn = jax.jit(make_train_step(cfg, opt), donate_argnums=(0,))
    ds = SyntheticLMDataset(vocab=cfg.vocab, seq_len=16, seed=0)

    def batches(start):
        step = start
        while True:
            t, l = ds.batch(step, 4)
            yield {"tokens": jnp.asarray(t), "labels": jnp.asarray(l)}
            step += 1

    loop = TrainLoop(
        step_fn=step_fn,
        batch_iter_factory=batches,
        ckpt_dir=str(tmp_path),
        cfg=TrainLoopConfig(total_steps=total, checkpoint_every=2),
        init_state_fn=lambda: opt.init(init_model(jax.random.PRNGKey(0), cfg)),
    )
    loop.run()
    assert loop.mgr.list_steps() == [2, 4, 6]   # no save lost to the race
    step, state = loop.mgr.restore_latest()
    assert step == 6 and int(state["step"]) == 6


def test_restart_flushes_inflight_checkpoint_writes(tmp_path):
    """A step failure right after a periodic save hands off to the async
    writer must flush (mgr.wait) *before* the restart touches the
    checkpoint directory, and must swallow writer errors surfaced by
    that flush — the restarted run still lands bit-identical."""
    cfg = get_config("smollm2-1.7b", reduced=True)
    straight = _make_loop(tmp_path / "a", cfg).run()

    events = []
    crashed = {"done": False}

    def bomb(step):
        # the loop saves at step 4 (checkpoint_every=4) at the end of
        # that iteration; the hook fires at the top of the next one —
        # i.e. while the async writer may still be in flight
        if step == 4 and not crashed["done"]:
            crashed["done"] = True
            events.append("crash")
            raise RuntimeError("injected failure right after save")

    loop = _make_loop(tmp_path / "b", cfg, failure_hook=bomb)
    mgr = loop.mgr
    orig_wait, orig_restore = mgr.wait, mgr.restore_latest
    raised = {"done": False}

    def wait():
        events.append("wait_postcrash" if crashed["done"] else "wait")
        orig_wait()
        if crashed["done"] and not raised["done"]:
            raised["done"] = True          # the restart-path flush: a
            raise OSError("flaky writer")  # writer error must be swallowed

    def restore_latest(*a, **k):
        events.append("restore")
        return orig_restore(*a, **k)

    mgr.wait = wait
    mgr.restore_latest = restore_latest
    resumed = loop.run()
    assert loop.restarts == 1
    assert tree_equal(straight["params"], resumed["params"])
    assert int(resumed["step"]) == 12
    # the first thing after the crash is the flush, not the restore —
    # and the flush's writer error did not kill the restart
    after_crash = events[events.index("crash") + 1:]
    assert after_crash[0] == "wait_postcrash", events
    assert "restore" in after_crash


def test_straggler_detection(tmp_path):
    cfg = get_config("smollm2-1.7b", reduced=True)
    loop = _make_loop(tmp_path, cfg, total=4, deadline=1e-9)
    loop.run()
    assert loop.straggler_steps == 4  # every step 'misses' a 1ns deadline


def test_elastic_reshard_roundtrip(tmp_path, key):
    """Checkpoints are mesh-agnostic: save plain, load with explicit
    (single-device) shardings — the elastic-scaling path."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh

    tree = {"w": jax.random.normal(key, (8, 4))}
    p = str(tmp_path / "ck.npz")
    save_pytree(tree, p)
    mesh = make_mesh((1,), ("data",))
    sh = {"w": NamedSharding(mesh, P(None, None))}
    out = load_pytree(p, shardings=sh)
    assert tree_equal(tree, out)
    assert out["w"].sharding == sh["w"]
