"""The ``qr`` retraction computed by full-precision Cholesky-QR2, with a
Householder fallback for factors too ill-conditioned for it, and the
train step's count of the leaves that fell back."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ModelSpec, PrecisionSpec, RunSpec, Trainer, TrainSpec
from repro.core import retraction
from repro.core.manifold import orthogonality_error
from repro.core.tree import QR_FALLBACKS, retract_tree_with_stats
from repro.optim import make_sct_optimizer, reporting_metrics_to


def _householder(U):
    """The ``qr`` retraction as it was computed before: Householder QR
    with the sign fix."""
    Q, R = jnp.linalg.qr(U.astype(jnp.float32))
    return retraction._sign_fix(Q, R)


def _noisy_stiefel(shape, noise, seed=0):
    U0, _ = jnp.linalg.qr(jax.random.normal(jax.random.PRNGKey(seed), shape))
    return U0 + noise * jax.random.normal(jax.random.PRNGKey(seed + 1), shape)


def _primitives(jaxpr):
    """Every equation of ``jaxpr``, sub-jaxprs included."""
    out = []
    for eqn in jaxpr.eqns:
        out.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _primitives(sub)
    return out


@pytest.mark.parametrize("shape, noise", [((3, 2048, 128), 1e-3), ((2, 8192, 128), 1e-2)])
def test_qr_retract_on_drifted_factors_at_cell_widths_matches_householder(shape, noise):
    U = _noisy_stiefel(shape, noise)
    got, fell = retraction.retract_with_fallback(U, "qr")
    assert int(fell) == 0
    assert float(jnp.max(jnp.abs(got - _householder(U)))) < 1e-5
    assert float(orthogonality_error(got)) <= 2e-6


def _ill_conditioned(m=256, k=16, cond=1e6):
    A, _ = jnp.linalg.qr(jax.random.normal(jax.random.PRNGKey(2), (m, k)))
    B, _ = jnp.linalg.qr(jax.random.normal(jax.random.PRNGKey(3), (k, k)))
    return (A * jnp.logspace(0, -np.log10(cond), k)) @ B


def _zero_column():
    return jax.random.normal(jax.random.PRNGKey(4), (2, 96, 12)).at[1, :, 5].set(0.0)


@pytest.mark.parametrize("make", [_ill_conditioned, _zero_column],
                         ids=["cond_1e6", "zero_column"])
def test_ill_conditioned_and_rank_deficient_factors_take_householder(make):
    U = make()
    got, fell = retraction.retract_with_fallback(U, "qr")
    assert int(fell) == 1
    np.testing.assert_array_equal(np.asarray(got), np.asarray(_householder(U)))
    np.testing.assert_array_equal(np.asarray(retraction.qr_retract(U)), np.asarray(got))


def test_every_product_of_the_fast_path_runs_at_highest_precision():
    U = _noisy_stiefel((2, 96, 8), 1e-2)
    eqns = _primitives(jax.make_jaxpr(retraction.qr_retract)(U).jaxpr)
    precisions = [e.params["precision"] for e in eqns if e.primitive.name == "dot_general"]
    assert len(precisions) >= 2                      # one Gram per pass
    highest = jax.lax.Precision.HIGHEST
    assert all(p == (highest, highest) for p in precisions), precisions


def _trainer(arch):
    return Trainer(RunSpec(
        model=ModelSpec(arch=arch, reduced=True, dtype="bfloat16"),
        train=TrainSpec(steps=4, batch=2, seq=16, lr=5e-4, warmup=2, seed=0),
        precision=PrecisionSpec(mode="legacy")))


def test_a_reduced_smollm2_step_reports_qr_fallbacks():
    trainer = _trainer("smollm2-1.7b")
    assert trainer.optimizer.retraction == "qr"
    assert int(trainer.step()[QR_FALLBACKS]) == 0
    # a layer of the up projection with two equal columns in U and V, and
    # equal values in s and in Adam's moments: the step moves both columns
    # alike, so both stacked leaves stay rank-deficient and fall back
    state = trainer.state
    for tree in (state["params"], state["opt"]["mu"], state["opt"]["nu"]):
        up = dict(tree["layers"]["mlp"]["up"])
        for name in ("U", "V", "s"):
            up[name] = up[name].at[0, ..., 1].set(up[name][0, ..., 0])
        tree["layers"]["mlp"]["up"] = up
    metrics = trainer.step()
    assert int(metrics[QR_FALLBACKS]) == 2
    assert bool(jnp.isfinite(metrics["loss"]))
    assert float(orthogonality_error(trainer.state["params"]["layers"]["mlp"]["up"]["U"])) < 2e-6


def test_granite_step_has_no_retraction_cond_and_no_fallback_metric(monkeypatch):
    from repro.configs import granite_3_2b

    monkeypatch.setattr(granite_3_2b, "REDUCED",
                        granite_3_2b.REDUCED.replace_sct(retraction="cholesky_qr2"))
    trainer = _trainer("granite-3-2b")
    trainer._ensure_state()
    batch = trainer.make_batch(0)
    jaxpr = jax.make_jaxpr(trainer._step_fn)(trainer.state, batch)
    assert not [e for e in _primitives(jaxpr.jaxpr) if e.primitive.name == "cond"]
    _, metrics = jax.eval_shape(trainer._step_fn, trainer.state, batch)
    assert QR_FALLBACKS not in metrics


@pytest.mark.parametrize("case", ["retract_every_2", "overflowed_mixed_step"])
def test_a_step_that_retracts_nothing_reports_no_fallbacks(case):
    U = _noisy_stiefel((2, 32, 4), 1e-2)
    params = {"mlp": {"U": U, "s": jnp.ones((2, 4)), "V": U}}
    if case == "retract_every_2":
        opt = dataclasses.replace(make_sct_optimizer(), retract_every=2)
        grads = jax.tree.map(jnp.ones_like, params)
    else:
        opt = make_sct_optimizer(precision="mixed")
        grads = jax.tree.map(lambda x: jnp.full_like(x, jnp.inf), params)

    @jax.jit
    def step(state, grads):
        with reporting_metrics_to({}) as metrics:
            state = opt.apply(state, grads)
        return state, metrics

    state, stats = step(opt.init(params), grads)
    assert list(stats) == [QR_FALLBACKS]
    assert int(stats[QR_FALLBACKS]) == 0
    if case == "retract_every_2":   # the next step retracts, through the fast path
        _, stats = step(state, grads)
        assert int(stats[QR_FALLBACKS]) == 0
    _, direct = retract_tree_with_stats(params, "qr")
    assert int(direct[QR_FALLBACKS]) == 0
