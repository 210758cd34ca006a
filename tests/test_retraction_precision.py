"""Cholesky-QR2 at full fp32 precision, and the count of traced factor
retractions by method.

On a TPU a matmul at default precision rounds fp32 operands to bf16,
which leaves a retracted factor ~1e-3 off the Stiefel manifold. The
CPU computes fp32 exactly at any precision, so the fault shows here
only as the precision each product asks for."""
import jax
import jax.numpy as jnp
import pytest

from repro.api import ModelSpec, PrecisionSpec, RunSpec, Trainer, TrainSpec
from repro.core import retraction
from repro.core.manifold import orthogonality_error


def _dot_precisions(jaxpr):
    """The ``precision`` of every dot_general in ``jaxpr``, sub-jaxprs
    included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(eqn.params["precision"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _dot_precisions(sub)
    return out


@pytest.mark.parametrize("shape", [(256, 16), (3, 2, 96, 8)])
def test_every_product_of_cholesky_qr2_runs_at_highest_precision(shape):
    U = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.float32)
    precisions = _dot_precisions(jax.make_jaxpr(retraction.cholesky_qr2_retract)(U).jaxpr)
    assert len(precisions) >= 2                      # one Gram per pass
    highest = jax.lax.Precision.HIGHEST
    assert all(p == (highest, highest) for p in precisions), precisions


def test_cholesky_qr2_matches_sign_fixed_qr():
    U = jax.random.normal(jax.random.PRNGKey(1), (4, 192, 24), jnp.float32)
    got = retraction.cholesky_qr2_retract(U)
    assert float(orthogonality_error(got)) < 2e-6
    assert float(jnp.max(jnp.abs(got - retraction.qr_retract(U)))) < 1e-4


def _spec(arch):
    return RunSpec(model=ModelSpec(arch=arch, reduced=True, dtype="bfloat16"),
                   train=TrainSpec(steps=4, batch=2, seq=16, lr=5e-4, warmup=2, seed=0),
                   precision=PrecisionSpec(mode="legacy"))


@pytest.mark.parametrize("arch, method", [("smollm2-1.7b", "qr"),
                                          ("granite-3-2b", "cholesky_qr2")])
def test_a_traced_step_counts_six_factors_a_layer_under_its_method(monkeypatch, arch, method):
    if arch == "granite-3-2b":
        from repro.configs import granite_3_2b

        monkeypatch.setattr(granite_3_2b, "REDUCED",
                            granite_3_2b.REDUCED.replace_sct(retraction=method))
    trainer = Trainer(_spec(arch))
    assert trainer.optimizer.retraction == method
    retraction.reset_retraction_counts()
    trainer._step_fn.lower(trainer.state, trainer.make_batch(0))
    # up, gate and down, each with U and V, in every layer
    assert retraction.retraction_counts() == {method: 6 * trainer.cfg.n_layers}
    retraction.reset_retraction_counts()
    assert retraction.retraction_counts() == {}


def test_retract_counts_stacked_factors_and_refuses_before_counting():
    retraction.reset_retraction_counts()
    retraction.retract(jnp.eye(8, 4)[None, None].repeat(3, 0).repeat(2, 1), "cholesky_qr2")
    retraction.retract(jnp.eye(8, 4), "qr")
    with pytest.raises(ValueError):
        retraction.retract(jnp.eye(8, 4), "qr", axis_name="model")
    with pytest.raises(ValueError):
        retraction.retract(jnp.eye(8, 4), "householder")
    assert retraction.retraction_counts() == {"cholesky_qr2": 6, "qr": 1}
