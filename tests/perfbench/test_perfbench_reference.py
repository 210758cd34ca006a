"""The plain reference against the program at CPU size, and its fp8
control against the reference."""
import numpy as np

from perfbench_tiny import TINY_CFG, TINY_LIMITS, make_root
from perfbench import control, reference, run


def _program_cfg(dtype="float32"):
    from repro.config import get_config

    return get_config("smollm2-1.7b", reduced=True).replace(dtype=dtype)


def test_reference_weights_are_the_programs_draws():
    import jax

    from repro.models.model import init_model

    seed = 2**31 + 5
    prog = init_model(reference.seed_key(seed), _program_cfg())
    ref = reference.init_params(TINY_CFG, reference.seed_key(seed))
    flat_p = dict((jax.tree_util.keystr(k), v)
                  for k, v in jax.tree_util.tree_flatten_with_path(prog)[0])
    flat_r = dict((jax.tree_util.keystr(k), v)
                  for k, v in jax.tree_util.tree_flatten_with_path(ref)[0])
    assert flat_p.keys() == flat_r.keys()
    for k in flat_p:
        np.testing.assert_allclose(flat_p[k], flat_r[k], rtol=1e-5, atol=1e-6, err_msg=k)


def test_reference_logits_match_the_program_in_fp32():
    import jax
    import jax.numpy as jnp

    from repro.models.lm import forward_lm

    cfg = _program_cfg()
    params = reference.init_params(TINY_CFG, reference.seed_key(3))
    tokens = np.random.default_rng(0).integers(0, 512, size=(1, 48)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        prog, _ = forward_lm(params, jnp.asarray(tokens), cfg)
        ref = reference.logits(TINY_CFG, params, jnp.asarray(tokens[0]))
    np.testing.assert_allclose(np.asarray(prog[0]), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_seed_key_takes_seeds_past_32_bits():
    import jax

    a = jax.random.key_data(reference.seed_key(5))
    b = jax.random.key_data(reference.seed_key(2**32 + 5))
    assert not np.array_equal(a, b)


def test_fp8_control_and_half_batch_fail_the_tiny_limits(tmp_path):
    cell = run.Cell(make_root(str(tmp_path)), "tiny.train")
    out = control.train_control(cell, 1)
    lim = TINY_LIMITS["tiny.train"]
    for name in ("control_fp8", "half_batch"):
        assert any(out[name][k] > lim[k] for k in lim), (name, out[name])
