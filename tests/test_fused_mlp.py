"""The fused spectral SwiGLU path of ``nn/mlp.py:apply_mlp``
(``kernels/fused_mlp.py``, forward and backward) in interpret mode on the
CPU: equal to the XLA path, forward and every gradient, alone and inside a
rematerialized layer scan, and taken only for a plain spectral SwiGLU MLP
at rank 128 over an aligned token count on one TPU device."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ModelSpec, PrecisionSpec, RunSpec, Trainer, TrainSpec
from repro.configs import smollm2_1p7b
from repro.models import decode, lm
from repro.nn import mlp
from repro.serving.quantize import quantize_tree
from repro.sharding import rules

D_MODEL, RANK = 256, 128


@pytest.fixture
def on_tpu(monkeypatch):
    """Take the fused path as on a TPU; the kernel runs in interpret
    mode, which is the default off TPU."""
    monkeypatch.setattr(mlp, "fused_kernel_backend", lambda: True)
    monkeypatch.setattr(rules, "_CURRENT_MESH", None)
    monkeypatch.delenv("SCT_INTERPRET", raising=False)


def _params(d_ff, layers=None, seed=1):
    init = lambda key: mlp.init_mlp(key, D_MODEL, d_ff, rank=RANK)
    if layers is None:
        return init(jax.random.PRNGKey(seed))
    return jax.vmap(init)(jax.random.split(jax.random.PRNGKey(seed), layers))


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _grads(apply, p, x, w):
    loss = lambda p, x: jnp.sum(apply(p, x).astype(jnp.float32) * w)
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(p, x)


def _layer_scan(p, x):
    """Two layers under the train step's remat (models/blocks.py:remat_scan)."""
    body = lambda h, lp: (h + mlp.apply_mlp(lp, h), None)
    body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    return jax.lax.scan(body, x, p)[0]


_EQUIV = [(tokens, d_ff, dtype, False)
          for tokens in (512, 1024) for d_ff in (1024, 2048)
          for dtype in ("float32", "bfloat16")]
_EQUIV += [(512, 1024, "float32", True), (1024, 2048, "bfloat16", True)]


@pytest.mark.parametrize("tokens,d_ff,dtype,remat", _EQUIV)
def test_fused_mlp_equals_xla(on_tpu, monkeypatch, tokens, d_ff, dtype, remat):
    dtype = jnp.dtype(dtype)
    p = _params(d_ff, layers=2 if remat else None)
    x32 = jax.random.normal(jax.random.PRNGKey(2), (2, tokens // 2, D_MODEL), jnp.float32)
    x = x32.astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(3), x.shape, jnp.float32)
    apply = _layer_scan if remat else mlp.apply_mlp

    mlp.reset_mlp_path_counts()
    y_fused = jax.jit(apply)(p, x)
    _, g_fused = _grads(apply, p, x, w)
    assert mlp.mlp_path_counts() == {"fused": 2}
    assert y_fused.dtype == dtype

    monkeypatch.setattr(mlp, "fused_kernel_backend", lambda: False)
    y_xla = jax.jit(apply)(p, x)
    _, g_xla = _grads(apply, p, x, w)
    got = [("out", y_fused, y_xla)] + [
        (jax.tree_util.keystr(path), a, b) for (path, a), b in
        zip(jax.tree_util.tree_leaves_with_path(g_fused), jax.tree.leaves(g_xla))]
    if dtype == jnp.float32:       # summation order only
        for name, a, b in got:
            assert _rel(a, b) < 2e-6, name
        return
    # bf16: the XLA path rounds gate and up to bf16 and the kernel keeps
    # them in f32, so both are held to the f32 computation on the same
    # bf16 input, and the kernel must come at least as close
    y_ref = jax.jit(apply)(p, x.astype(jnp.float32))
    _, g_ref = _grads(apply, p, x.astype(jnp.float32), w)
    for (name, a, b), r in zip(got, [y_ref] + jax.tree.leaves(g_ref)):
        assert _rel(a, b) < 3e-2, name
        assert _rel(a, r) <= 1.1 * _rel(b, r), name


def _x(tokens, dtype=jnp.bfloat16):
    return jax.random.normal(jax.random.PRNGKey(0), (tokens, D_MODEL), dtype)


def _trace(p, x, act="swiglu"):
    mlp.reset_mlp_path_counts()
    jax.eval_shape(lambda p, x: mlp.apply_mlp(p, x, act=act), p, x)
    return mlp.mlp_path_counts()


def _paths(case, monkeypatch):
    p = _params(512)
    if case == "train":
        return _trace(p, _x(512))
    if case == "decode":                # a few tokens a step
        return _trace(p, _x(4))
    if case == "unaligned":
        return _trace(p, _x(200))
    if case == "rank32":
        return _trace(mlp.init_mlp(jax.random.PRNGKey(1), D_MODEL, 512, rank=32), _x(512))
    if case == "gelu":
        return _trace(mlp.init_mlp(jax.random.PRNGKey(1), D_MODEL, 512, rank=RANK,
                                   act="gelu"), _x(512), act="gelu")
    if case == "dense":
        return _trace(mlp.init_mlp(jax.random.PRNGKey(1), D_MODEL, 512), _x(512))
    if case == "bias":
        return _trace(mlp.init_mlp(jax.random.PRNGKey(1), D_MODEL, 512, rank=RANK,
                                   bias=True), _x(512))
    if case == "int8":
        return _trace(quantize_tree(p), _x(512))
    if case == "mesh":                  # a two-device virtual mesh
        monkeypatch.setattr(rules, "_CURRENT_MESH",
                            jax.sharding.AbstractMesh((2,), ("data",)))
        return _trace(p, _x(512))
    raise AssertionError(case)


# case -> path on a TPU, path on the CPU
_DISPATCH = {
    "train": ("fused", "xla"),
    "decode": ("xla", "xla"),
    "unaligned": ("xla", "xla"),
    "rank32": ("xla", "xla"),
    "gelu": ("xla", "xla"),
    "dense": ("xla", "xla"),
    "bias": ("xla", "xla"),
    "int8": ("xla", "xla"),
    "mesh": ("xla", "xla"),
}


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
@pytest.mark.parametrize("case", list(_DISPATCH))
def test_mlp_dispatch(case, backend, monkeypatch):
    monkeypatch.setattr(mlp, "fused_kernel_backend", lambda: backend == "tpu")
    monkeypatch.setattr(rules, "_CURRENT_MESH", None)
    want = _DISPATCH[case][0 if backend == "tpu" else 1]
    assert _paths(case, monkeypatch) == {want: 1}


def _rank128_smollm2(monkeypatch):
    """The reduced smollm2 preset at the cells' rank: d_model and d_ff
    widened to hold rank-128 factors."""
    cfg = smollm2_1p7b.REDUCED.replace(d_model=128, d_ff=256).replace_sct(rank=RANK)
    monkeypatch.setattr(smollm2_1p7b, "REDUCED", cfg)
    return cfg


def test_reduced_smollm2_train_step_takes_the_fused_mlp(on_tpu, monkeypatch):
    _rank128_smollm2(monkeypatch)
    spec = RunSpec(model=ModelSpec(arch="smollm2-1.7b", reduced=True, dtype="bfloat16"),
                   train=TrainSpec(steps=2, batch=2, seq=64, lr=5e-4, warmup=1, seed=0),
                   precision=PrecisionSpec(mode="legacy"))
    trainer = Trainer(spec)
    mlp.reset_mlp_path_counts()
    trainer._step_fn.lower(trainer.state, trainer.make_batch(0))
    assert mlp.mlp_path_counts() == {"fused": 1}     # the one scanned layer body


def test_aligned_prefill_takes_the_fused_mlp_and_decode_does_not(on_tpu, monkeypatch):
    cfg = _rank128_smollm2(monkeypatch).replace(dtype="float32")
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, cfg.vocab)
    prefill = jax.jit(lambda p, t: decode.prefill_lm(p, t, cfg, decode.lm_init_state(cfg, 2, 64)))

    mlp.reset_mlp_path_counts()
    logits, state = prefill(params, tokens)
    assert mlp.mlp_path_counts() == {"fused": 1}
    mlp.reset_mlp_path_counts()
    jax.eval_shape(lambda p, t, s: decode.decode_step_lm(p, t, s, jnp.int32(64), cfg),
                   params, tokens[:, :1], state)
    assert mlp.mlp_path_counts() == {"xla": 1}

    monkeypatch.setattr(mlp, "fused_kernel_backend", lambda: False)
    want, _ = jax.jit(lambda p, t: decode.prefill_lm(
        p, t, cfg, decode.lm_init_state(cfg, 2, 64)))(params, tokens)
    assert _rel(logits, want) < 1e-5
