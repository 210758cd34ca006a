"""Granite's grouped-query model against the plain reference at CPU
size: the reduced preset (4 query heads over 2 KV heads) with its
published Cholesky-QR2 retraction, which the preset drops to QR."""
import json
import os

import numpy as np
import pytest

from perfbench_tiny import TINY_CFG, make_root
from perfbench import control, reference, run

TINY_GRANITE = dict(
    TINY_CFG, name="tiny-granite", source="repro.configs.granite_3_2b.REDUCED",
    model_type="granite", num_key_value_heads=2, intermediate_size=128, rope_theta=10000,
    program={"arch": "granite-3-2b", "reduced": True},
    sct=dict(TINY_CFG["sct"], retraction="cholesky_qr2"))

# set from CPU readings over seeds 1-4 and 2**31 + 3: the program reads grad
# 3.7e-3..1.0e-2 and change 1.4e-3..2.6e-3; the fp8 control grad 2.1e-2..1.7e-1
# and change 1.5e-2..1.9e-2; half of the batch left out grad 6.0e-2.. and
# change 3.5e-2..
LIMITS = {"grad_gap": 0.03, "change_gap": 0.006}


@pytest.fixture
def granite(monkeypatch):
    """The reduced granite preset with the published retraction."""
    from repro.configs import granite_3_2b

    cfg = granite_3_2b.REDUCED.replace_sct(retraction="cholesky_qr2")
    monkeypatch.setattr(granite_3_2b, "REDUCED", cfg)
    return cfg


def test_reference_weights_are_the_programs_draws(granite):
    import jax

    from repro.models.model import init_model

    seed = 2**31 + 7
    prog = init_model(reference.seed_key(seed), granite)
    ref = reference.init_params(TINY_GRANITE, reference.seed_key(seed))
    flat_p = {jax.tree_util.keystr(k): v
              for k, v in jax.tree_util.tree_flatten_with_path(prog)[0]}
    flat_r = {jax.tree_util.keystr(k): v
              for k, v in jax.tree_util.tree_flatten_with_path(ref)[0]}
    assert flat_p.keys() == flat_r.keys()
    assert flat_p["['layers']['attn']['wk']['w']"].shape == (2, 64, 2 * 16)
    for k in flat_p:
        np.testing.assert_allclose(flat_p[k], flat_r[k], rtol=1e-5, atol=1e-6, err_msg=k)


def test_reference_logits_match_the_program_in_fp32(granite):
    import jax
    import jax.numpy as jnp

    from repro.models.lm import forward_lm

    params = reference.init_params(TINY_GRANITE, reference.seed_key(11))
    tokens = np.random.default_rng(1).integers(0, 512, size=(1, 48)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        prog, _ = forward_lm(params, jnp.asarray(tokens), granite.replace(dtype="float32"))
        ref = reference.logits(TINY_GRANITE, params, jnp.asarray(tokens[0]))
    np.testing.assert_allclose(np.asarray(prog[0]), np.asarray(ref), rtol=2e-4, atol=2e-4)


@pytest.fixture
def cell(tmp_path, granite):
    root = make_root(str(tmp_path), extra_workloads=[
        {"name": "tiny-granite.train", "config": "tiny-granite", "traffic": "tiny_train",
         "chips": 1, "why": "test"}])
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-granite", "source": "test", "reduced": [],
                             "file": "perfbench/configs/tiny-granite.json", "why": "test"})
    bench["end_to_end"][0]["workloads"].append("tiny-granite.train")
    for name, obj in (("BENCHMARK.json", bench),
                      ("perfbench/configs/tiny-granite.json", TINY_GRANITE),
                      ("perfbench/limits/tiny-granite.train.json", LIMITS)):
        with open(os.path.join(root, name), "w") as f:
            json.dump(obj, f)
    return run.Cell(root, "tiny-granite.train")


def test_checked_steps_sit_under_limits_the_fp8_control_fails(cell):
    from perfbench.jobs.train import build_trainer

    trainer = build_trainer(cell, 0)
    assert (trainer.cfg.n_heads, trainer.cfg.n_kv_heads) == (4, 2)
    assert trainer.optimizer.retraction == "cholesky_qr2"
    out = control.train_control(cell, 2**31 + 3, trainer)
    for name in LIMITS:
        assert out["program"][name] < LIMITS[name], out["program"]
    assert any(out["control_fp8"][k] > LIMITS[k] for k in LIMITS), out["control_fp8"]
    assert any(out["half_batch"][k] > LIMITS[k] for k in LIMITS), out["half_batch"]


def test_a_run_of_the_tiny_granite_cell_is_correct(cell):
    res = run.run_cell(cell, 2**31 + 43, 0.5, False)
    assert res["correct"], res["check"]
    assert set(res["check"]) == set(LIMITS)
