"""The fused causal attention path of ``nn/attention.py:_sdpa`` (splash
attention, forward and backward) in interpret mode on the CPU: equal to
the direct softmax, forward and q/k/v gradients, and taken only for
causal self-attention at an aligned length on one TPU device."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.nn import attention as at
from repro.sharding import rules


@pytest.fixture
def on_tpu(monkeypatch):
    """Take the fused path as on a TPU; the kernel runs in interpret
    mode, which is the default off TPU."""
    monkeypatch.setattr(at, "fused_kernel_backend", lambda: True)
    monkeypatch.setattr(rules, "_CURRENT_MESH", None)
    monkeypatch.delenv("SCT_INTERPRET", raising=False)


def _qkv(b, s, h, kvh, d, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kvh, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kvh, d), jnp.float32)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype)


def _direct(q, k, v):
    b, s, h, d = q.shape
    kvh = k.shape[2]
    qg = q.reshape(b, s, kvh, h // kvh, d)
    return at._sdpa_direct(qg, k, v, causal=True).reshape(b, s, h, d)


_EQUIV = [(heads, dtype, s, d, False)
          for heads in ("mha", "gqa4") for dtype in ("float32", "bfloat16")
          for s in (256, 512) for d in (64, 128)]
_EQUIV += [("gqa4", "bfloat16", 256, 64, True), ("mha", "float32", 512, 128, True)]


@pytest.mark.parametrize("heads,dtype,s,d,remat", _EQUIV)
def test_fused_sdpa_equals_direct(on_tpu, heads, dtype, s, d, remat):
    h, kvh = (4, 4) if heads == "mha" else (4, 1)
    dtype = jnp.dtype(dtype)
    q, k, v = _qkv(2, s, h, kvh, d, dtype)
    w = jax.random.normal(jax.random.PRNGKey(7), (2, s, h, d), jnp.float32)

    def fused(q, k, v):
        return at._sdpa(q, k, v, causal=True)

    if remat:   # as models/blocks.py:remat_scan runs each layer
        fused = jax.checkpoint(fused, policy=jax.checkpoint_policies.nothing_saveable)

    def grads(fn):
        loss = lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * w)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

    at.reset_attention_path_counts()
    y_fused = jax.jit(fused)(q, k, v)
    g_fused = grads(fused)
    assert set(at.attention_path_counts()) == {"fused"}
    y_direct = _direct(q, k, v)
    g_direct = grads(_direct)
    assert y_fused.dtype == dtype

    # bf16: the direct path rounds the scores to bf16 before the softmax
    # and the kernel does not; fp32: summation order only
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    for name, a, b in [("out", y_fused, y_direct),
                       *zip(("dq", "dk", "dv"), g_fused, g_direct)]:
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        scale = float(np.max(np.abs(b)))
        np.testing.assert_allclose(a / scale, b / scale, atol=tol, err_msg=name)


def _trace(fn, *args):
    at.reset_attention_path_counts()
    jax.eval_shape(fn, *args)
    return at.attention_path_counts()


def _paths(case, monkeypatch):
    b, s, h, kvh, d = 2, 256, 4, 2, 64
    q, k, v = _qkv(b, s, h, kvh, d, jnp.bfloat16)
    causal = lambda q, k, v, **kw: at._sdpa(q, k, v, causal=True, **kw)
    if case == "train":
        return _trace(causal, q, k, v)
    if case == "decode":
        mask = jnp.ones((b, s), bool)
        return _trace(lambda q, k, v: at._sdpa(q, k, v, causal=False, kv_len_mask=mask),
                      q[:, :1], k, v)
    if case == "traced_offset":   # chunked prefill from a traced start
        return _trace(lambda q, k, v, o: causal(q, k, v, q_offset=o), q, k, v, jnp.int32(0))
    if case == "kv_len_mask":
        mask = jnp.ones((b, s), bool)
        return _trace(lambda q, k, v: causal(q, k, v, kv_len_mask=mask), q, k, v)
    if case == "mla":   # v head dim differs from q/k's
        return _trace(causal, q, k, v[..., :32])
    if case == "unaligned":
        return _trace(causal, q[:, :200], k[:, :200], v[:, :200])
    if case == "cross":
        return _trace(causal, q[:, :128], k, v)
    if case == "non_causal":
        return _trace(lambda q, k, v: at._sdpa(q, k, v, causal=False), q, k, v)
    if case == "mesh":
        monkeypatch.setattr(rules, "_CURRENT_MESH",
                            jax.sharding.AbstractMesh((4,), ("data",)))
        return _trace(causal, q, k, v)
    monkeypatch.setattr(at, "FLASH_THRESHOLD", 128)
    if case == "long_causal":
        return _trace(causal, q, k, v)
    if case == "long_non_causal":
        return _trace(lambda q, k, v: at._sdpa(q, k, v, causal=False), q, k, v)
    raise AssertionError(case)


# case -> path on a TPU, path on the CPU
_DISPATCH = {
    "train": ("fused", "direct"),
    "decode": ("direct", "direct"),
    "traced_offset": ("direct", "direct"),
    "kv_len_mask": ("direct", "direct"),
    "mla": ("direct", "direct"),
    "unaligned": ("direct", "direct"),
    "cross": ("direct", "direct"),
    "non_causal": ("direct", "direct"),
    "mesh": ("direct", "direct"),
    "long_causal": ("fused", "chunked"),
    "long_non_causal": ("chunked", "chunked"),
}


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
@pytest.mark.parametrize("case", list(_DISPATCH))
def test_sdpa_dispatch(case, backend, monkeypatch):
    monkeypatch.setattr(at, "fused_kernel_backend", lambda: backend == "tpu")
    monkeypatch.setattr(rules, "_CURRENT_MESH", None)
    want = _DISPATCH[case][0 if backend == "tpu" else 1]
    assert _paths(case, monkeypatch) == {want: 1}
