"""Adaptive rank subsystem tests: resize ops (grow/shrink + moments),
telemetry, schedules, and the train -> shrink-checkpoint -> resume
integration the ISSUE's acceptance criteria name."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_config
from repro.core.convert import spectral_to_dense
from repro.core.manifold import orthogonality_error
from repro.core.spectral import spectral_init
from repro.checkpoint.manager import CheckpointManager
from repro.data.synthetic import SyntheticLMDataset
from repro.launch.steps import make_train_step
from repro.models.model import init_model
from repro.optim import make_sct_optimizer
from repro.rank import (
    EnergyRankSchedule,
    RankController,
    StaticRankSchedule,
    StepRankSchedule,
    current_ranks,
    grow_group,
    parse_rank_schedule,
    rank_metadata,
    resize_group,
    resize_train_state,
    resize_tree,
    shrink_group,
    spectral_telemetry,
    telemetry_summary,
)
from repro.rank.resize import clamp_target, shrink_indices
from repro.rank.telemetry import effective_rank, energy_capture, tail_mass
from repro.runtime.train_loop import TrainLoop, TrainLoopConfig

M, N, K = 48, 40, 16


@pytest.fixture
def group(key):
    return spectral_init(key, M, N, K)


@pytest.fixture
def stacked_group(key):
    return jax.vmap(lambda k: spectral_init(k, M, N, K))(jax.random.split(key, 3))


# ===================================================================
# resize ops
# ===================================================================

def test_grow_preserves_represented_matrix(key, group):
    grown = grow_group(key, group, 24)
    assert grown["U"].shape == (M, 24) and grown["s"].shape == (24,)
    np.testing.assert_allclose(
        np.asarray(spectral_to_dense(group)),
        np.asarray(spectral_to_dense(grown)), atol=5e-6)
    # zero singular values on the fresh directions
    assert float(jnp.max(jnp.abs(grown["s"][K:]))) == 0.0


def test_grow_factors_orthonormal_after_retraction(key, stacked_group):
    grown = grow_group(key, stacked_group, 32)
    assert float(orthogonality_error(grown["U"])) < 5e-6
    assert float(orthogonality_error(grown["V"])) < 5e-6


def test_shrink_keeps_topk_and_is_eckart_young(key, group):
    # make the spectrum distinctive so top-k is unambiguous
    g = dict(group, s=jnp.arange(K, 0, -1, dtype=jnp.float32))
    shrunk, idx = shrink_group(g, 6)
    np.testing.assert_array_equal(np.asarray(idx), np.arange(6))
    # Eckart-Young: the shrink error equals the dropped tail mass
    err = jnp.linalg.norm(spectral_to_dense(g) - spectral_to_dense(shrunk))
    tail = jnp.linalg.norm(g["s"][6:])
    np.testing.assert_allclose(float(err), float(tail), rtol=1e-4)
    assert float(orthogonality_error(shrunk["U"])) < 5e-6


def test_shrink_selects_by_magnitude_not_position(key, group):
    s = jnp.asarray([0.1, 9.0, 0.2, 8.0] + [0.01] * (K - 4))
    g = dict(group, s=s)
    shrunk, idx = shrink_group(g, 2)
    np.testing.assert_array_equal(np.asarray(idx), [1, 3])
    np.testing.assert_allclose(np.asarray(shrunk["s"]), [9.0, 8.0])


def test_grow_shrink_roundtrip_preserves_topk_subspace(key, group):
    """grow -> shrink back returns the original factors exactly: the
    grown columns carry zero singular values, so the shrink's top-k
    selection recovers precisely the pre-grow columns."""
    grown = grow_group(key, group, 24)
    back, _ = shrink_group(grown, K)
    # s of the original init is strictly positive, so selection is exact
    np.testing.assert_allclose(np.asarray(back["s"]), np.asarray(group["s"]),
                               atol=1e-6)
    # same subspace: projector difference is ~0 (columns may be
    # perturbed only by the grow-time re-retraction, which is ~eps)
    P0 = group["U"] @ group["U"].T
    P1 = back["U"] @ back["U"].T
    assert float(jnp.max(jnp.abs(P0 - P1))) < 5e-6


def test_stacked_layers_select_per_layer(key, stacked_group):
    s = np.ones((3, K), np.float32) * 0.01
    s[0, 2] = s[1, 7] = s[2, 11] = 5.0
    g = dict(stacked_group, s=jnp.asarray(s))
    idx = shrink_indices(g["s"], 1)
    np.testing.assert_array_equal(np.asarray(idx)[:, 0], [2, 7, 11])


def test_resize_train_state_moments_follow_params(key):
    cfg = get_config("smollm2-1.7b", reduced=True)
    opt = make_sct_optimizer(cfg, total_steps=10)
    state = opt.init(init_model(key, cfg))
    # put recognizable values in the moments so gather order is testable
    state["opt"]["mu"] = jax.tree.map(
        lambda x: jnp.arange(x.size, dtype=x.dtype).reshape(x.shape),
        state["opt"]["mu"])
    down = state["params"]["layers"]["mlp"]["down"]
    k0 = down["s"].shape[-1]

    shrunk = resize_train_state(key, state, k0 // 2)
    for tree in (shrunk["params"], shrunk["opt"]["mu"], shrunk["opt"]["nu"]):
        g = tree["layers"]["mlp"]["down"]
        assert g["s"].shape[-1] == k0 // 2
    # the moment columns were gathered with the same indices as params
    idx = shrink_indices(down["s"], k0 // 2)
    expect = jnp.take_along_axis(
        state["opt"]["mu"]["layers"]["mlp"]["down"]["U"], idx[..., None, :], axis=-1)
    np.testing.assert_array_equal(
        np.asarray(shrunk["opt"]["mu"]["layers"]["mlp"]["down"]["U"]),
        np.asarray(expect))

    grown = resize_train_state(key, state, k0 * 2)
    g = grown["opt"]["nu"]["layers"]["mlp"]["down"]
    assert g["U"].shape[-1] == k0 * 2
    # fresh directions start with zeroed optimizer state
    assert float(jnp.max(jnp.abs(g["U"][..., k0:]))) == 0.0
    # non-spectral entries untouched
    assert grown["step"].shape == state["step"].shape
    np.testing.assert_array_equal(
        np.asarray(grown["params"]["embed"]["w"]),
        np.asarray(state["params"]["embed"]["w"]))


def test_clamp_target_respects_min_dim(key):
    cfg = get_config("smollm2-1.7b", reduced=True)  # d_model=64, d_ff=256
    params = init_model(key, cfg)
    t = clamp_target(params, 1000)
    assert set(t.values()) == {64}  # min(m, n) = d_model
    resized = resize_tree(key, params, t)
    assert current_ranks(resized) == (64,)


def test_resize_rejects_bad_targets(key, group):
    with pytest.raises(ValueError):
        shrink_group(group, 0)
    with pytest.raises(ValueError):
        grow_group(key, group, min(M, N) + 1)


# ===================================================================
# telemetry
# ===================================================================

def test_effective_rank_bounds():
    flat = jnp.ones((8,))
    peaked = jnp.asarray([100.0] + [1e-6] * 7)
    assert float(effective_rank(flat)) == pytest.approx(8.0, rel=1e-5)
    assert float(effective_rank(peaked)) == pytest.approx(1.0, abs=1e-3)


def test_energy_capture_and_tail_mass():
    s = jnp.asarray([2.0, 1.0, 0.0, 0.0])
    assert float(energy_capture(s, 0.5)) == pytest.approx(1.0)
    np.testing.assert_allclose(float(tail_mass(s, 2)), 0.0, atol=1e-6)
    assert float(tail_mass(jnp.ones((4,)), 2)) == pytest.approx(np.sqrt(0.5), rel=1e-5)


def test_telemetry_tree_and_summary(key):
    cfg = get_config("smollm2-1.7b", reduced=True)
    params = init_model(key, cfg)
    per = spectral_telemetry(params)
    assert set(per) == {"layers/mlp/down", "layers/mlp/gate", "layers/mlp/up"}
    summary = telemetry_summary(params)
    assert float(summary["rank/mean"]) == cfg.sct.rank
    assert 1.0 <= float(summary["rank/eff_mean"]) <= cfg.sct.rank
    assert 0.0 <= float(summary["rank/energy_top"]) <= 1.0
    assert float(summary["rank/ortho_max"]) < 5e-6
    # dense model: no spectral groups -> empty summary, not zeros
    dense = init_model(key, cfg.replace_sct(spectral_mlp=False))
    assert telemetry_summary(dense) == {}


def test_telemetry_is_jittable(key):
    cfg = get_config("smollm2-1.7b", reduced=True)
    params = init_model(key, cfg)
    out = jax.jit(telemetry_summary)(params)
    assert float(out["rank/mean"]) == cfg.sct.rank


# ===================================================================
# schedules
# ===================================================================

def test_parse_and_decide_step_schedule():
    sch = parse_rank_schedule("step:30=64,60=128")
    assert isinstance(sch, StepRankSchedule)
    assert sch.decide(29, 32) is None
    assert sch.decide(30, 32) == 64
    assert sch.decide(45, 64) is None          # idempotent between triggers
    assert sch.decide(60, 64) == 128
    # restart at step 70 from a rank-32 checkpoint replays to 128
    assert sch.decide(70, 32) == 128


def test_parse_static_and_none():
    assert parse_rank_schedule(None) is None
    assert parse_rank_schedule("none") is None
    sch = parse_rank_schedule("static:64")
    assert isinstance(sch, StaticRankSchedule)
    assert sch.decide(0, 32) == 64
    assert sch.decide(0, 64) is None


def test_energy_schedule_decisions():
    sch = parse_rank_schedule("energy:0.9,min=8,every=10,factor=0.5,grow_below=0.3")
    assert isinstance(sch, EnergyRankSchedule)
    m_hi, m_lo = {"rank/energy_top": 0.95}, {"rank/energy_top": 0.2}
    assert sch.decide(10, 32, m_hi) == 16          # over-ranked -> shrink
    assert sch.decide(10, 16, m_lo) == 32          # saturated -> grow
    assert sch.decide(11, 32, m_hi) is None        # off-cadence
    assert sch.decide(10, 32, None) is None        # no telemetry yet
    assert sch.decide(10, 8, m_hi) is None         # floor reached
    with pytest.raises(ValueError):
        parse_rank_schedule("energy:0.9,bogus=1")
    with pytest.raises(ValueError):
        parse_rank_schedule("warp:9")


# ===================================================================
# integration: train -> resize mid-run / shrink-checkpoint -> resume
# ===================================================================

def _loop(tmp_path, cfg, opt, total, controller=None, telemetry=True):
    step_fn = jax.jit(make_train_step(cfg, opt, telemetry=telemetry))
    ds = SyntheticLMDataset(vocab=cfg.vocab, seq_len=16, seed=0)

    def batches(start):
        step = start
        while True:
            t, l = ds.batch(step, 4)
            yield {"tokens": jnp.asarray(t), "labels": jnp.asarray(l)}
            step += 1

    losses = []
    return TrainLoop(
        step_fn=step_fn,
        batch_iter_factory=batches,
        ckpt_dir=str(tmp_path),
        cfg=TrainLoopConfig(total_steps=total, checkpoint_every=5, log_every=1),
        init_state_fn=lambda: opt.init(init_model(jax.random.PRNGKey(0), cfg)),
        metrics_cb=lambda s, m: losses.append((s, m)),
        rank_controller=controller,
    ), losses


def test_midrun_resize_trains_through(tmp_path):
    """Step-triggered grow mid-run: loss stays finite, no >2x spike at
    the boundary, factors stay orthonormal, moments stay congruent."""
    cfg = get_config("smollm2-1.7b", reduced=True)
    opt = make_sct_optimizer(cfg, lr=1e-3, warmup=2, total_steps=16)
    ctrl = RankController(cfg, opt, StepRankSchedule(((8, 32),)))
    loop, losses = _loop(tmp_path, cfg, opt, 16, controller=ctrl)
    state = loop.run()

    assert loop.rank_resizes == 1
    assert ctrl.resizes == [(8, 16, 32)]
    assert current_ranks(state["params"]) == (32,)
    assert jax.tree.all(jax.tree.map(lambda p, m: p.shape == m.shape,
                                     state["params"], state["opt"]["mu"]))
    by_step = {s: m for s, m in losses}
    before, after = by_step[8]["loss"], by_step[9]["loss"]
    assert np.isfinite(after) and after < 2.0 * before
    # telemetry crossed the resize: rank metric tracks the new shapes
    assert by_step[8]["rank/mean"] == 16.0 and by_step[9]["rank/mean"] == 32.0
    from repro.core.tree import max_orthogonality_error

    assert float(max_orthogonality_error(state["params"])) < 5e-6


def test_train_shrink_checkpoint_resume_at_new_rank(tmp_path):
    """Train at rank 16 -> checkpoint -> resume the SAME run at rank 8
    via resize-on-restore (StaticRankSchedule), then finish training."""
    cfg = get_config("smollm2-1.7b", reduced=True)
    opt = make_sct_optimizer(cfg, lr=1e-3, warmup=2, total_steps=10)
    loop, _ = _loop(tmp_path, cfg, opt, 10)
    loop.run()
    mgr = CheckpointManager(str(tmp_path))
    assert mgr.rank_metadata_for(mgr.list_steps()[-1]) == {
        "layers/mlp/down": 16, "layers/mlp/gate": 16, "layers/mlp/up": 16}

    opt2 = make_sct_optimizer(cfg, lr=1e-3, warmup=2, total_steps=20)
    ctrl = RankController(cfg, opt2, StaticRankSchedule(8))
    loop2, losses2 = _loop(tmp_path, cfg, opt2, 20, controller=ctrl)
    state = loop2.run()
    assert current_ranks(state["params"]) == (8,)
    assert int(np.asarray(state["step"])) == 20
    assert all(np.isfinite(m["loss"]) for _, m in losses2)


def test_cross_rank_restore_and_greedy_decode(tmp_path):
    """Rank-16 training checkpoint restores at rank 8 through the
    manager and the engine classmethod; greedy decode stays functional
    and the shrunk engine pins fewer weight bytes."""
    from repro.serving import PagedCacheConfig, Request
    from repro.serving.engine import ServingEngine

    cfg = get_config("smollm2-1.7b", reduced=True)
    opt = make_sct_optimizer(cfg, lr=1e-3, warmup=2, total_steps=6)
    loop, _ = _loop(tmp_path, cfg, opt, 6, telemetry=False)
    loop.run()

    step, state = CheckpointManager(str(tmp_path)).restore_latest(target_rank=8)
    assert current_ranks(state["params"]) == (8,)
    # deterministic resize: same (checkpoint, rank) -> same factors
    _, state2 = CheckpointManager(str(tmp_path)).restore_latest(target_rank=8)
    np.testing.assert_array_equal(
        np.asarray(state["params"]["layers"]["mlp"]["up"]["U"]),
        np.asarray(state2["params"]["layers"]["mlp"]["up"]["U"]))

    pcfg = PagedCacheConfig(page_size=8, num_pages=16, max_slots=2,
                            max_pages_per_seq=4)
    eng = ServingEngine.from_checkpoint(cfg, str(tmp_path), pcfg, rank=8)
    full = ServingEngine.from_checkpoint(cfg, str(tmp_path), pcfg)
    assert eng.weight_bytes < full.weight_bytes
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, size=(5,)).astype(np.int32),
                    max_new_tokens=6, arrival=0) for i in range(2)]
    out = eng.run(reqs)
    assert sorted(out) == [0, 1]
    for toks in out.values():
        assert toks.shape == (6,) and toks.dtype == np.int32
        assert np.all((0 <= toks) & (toks < cfg.vocab))


_SUBPROCESS_MESH_RESIZE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, json
from jax.sharding import NamedSharding
from repro.config import get_config
from repro.config.shapes import ShapeSpec
from repro.data.synthetic import SyntheticLMDataset
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_mesh
from repro.models.model import init_model
from repro.optim import make_sct_optimizer
from repro.rank import RankController, StepRankSchedule, current_ranks
from repro.sharding.rules import set_current_mesh

cfg = get_config("smollm2-1.7b", reduced=True)
mesh = make_mesh((4, 2), ("data", "model"))
set_current_mesh(mesh)
shape = ShapeSpec("t", 16, 8, "train")
opt = make_sct_optimizer(cfg, lr=1e-3, warmup=2, total_steps=8)
ctrl = RankController(cfg, opt, StepRankSchedule(((4, 32),)), mesh=mesh, shape=shape)
state_sh, batch_sh = steps_mod.train_shardings(cfg, shape, mesh)
step_fn = jax.jit(steps_mod.make_train_step(cfg, opt, telemetry=True),
                  in_shardings=(state_sh, batch_sh), out_shardings=(state_sh, None),
                  donate_argnums=(0,))
with mesh:
    state = opt.init(init_model(jax.random.PRNGKey(0), cfg))
    state = jax.device_put(state, state_sh)
    ds = SyntheticLMDataset(vocab=cfg.vocab, seq_len=16, seed=0)
    losses = []
    for i in range(8):
        t, l = ds.batch(i, 8)
        state, m = step_fn(state, {"tokens": jnp.asarray(t), "labels": jnp.asarray(l)})
        losses.append(float(m["loss"]))
        res = ctrl.maybe_resize(i + 1, state, m)
        if res is not None:
            state, step_fn, state_sh = res
            assert isinstance(jax.tree.leaves(state_sh)[0], NamedSharding)
print(json.dumps({
    "resizes": ctrl.resizes,
    "ranks": list(current_ranks(state["params"])),
    "finite": all(x == x for x in losses),
}))
"""


def test_mesh_resize_regenerates_shardings():
    """Full mesh path in a subprocess (8 host devices): resize mid-run
    on a (4,2) mesh regenerates the NamedSharding tree and the re-jitted
    step keeps training at the new rank."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _SUBPROCESS_MESH_RESIZE],
                         capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    payload = json.loads(out.stdout.strip().splitlines()[-1])
    assert payload["resizes"] == [[4, 16, 32]]
    assert payload["ranks"] == [32]
    assert payload["finite"]


def test_same_rank_resize_is_bit_exact_noop(key):
    """Regression: a resize to the current rank (degenerate speculative
    ladder like [128,128], a schedule re-stating the rank) must neither
    gather nor re-retract — params come back as the same buffers."""
    g = {"U": jax.random.normal(key, (6, K)),
         "s": jnp.abs(jax.random.normal(jax.random.fold_in(key, 1), (K,))),
         "V": jax.random.normal(jax.random.fold_in(key, 2), (5, K))}
    same = resize_group(key, g, K)
    assert same is not g                         # fresh dict, shared leaves
    for name in ("U", "s", "V"):
        assert same[name] is g[name]

    cfg = get_config("smollm2-1.7b", reduced=True)
    opt = make_sct_optimizer(cfg, total_steps=10)
    state = opt.init(init_model(key, cfg))
    state["opt"]["mu"] = jax.tree.map(
        lambda x: jnp.arange(x.size, dtype=x.dtype).reshape(x.shape),
        state["opt"]["mu"])
    (k0,) = set(current_ranks(state["params"]))
    same_state = resize_train_state(key, state, k0)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(same_state)):
        assert a is b                            # moments included, bit-exact
