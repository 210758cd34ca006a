"""Compile-only tests of the main path's Pallas kernels for a TPU v5e
that is described, not attached.

Interpret mode runs a kernel's math on the CPU but never asks the TPU
compiler, so a block shape it refuses (the tiling rule on the last two
block dimensions) or a VMEM budget it cannot meet passes every
interpret-mode test. Here each kernel is lowered and compiled at
published widths for one chip of a described ``v5e:2x2`` topology, and
the compiled program must call the kernel (``tpu_custom_call``). The
guarded ``qr`` retraction is compiled the same way at the cell's factor
shapes.

The topology is described inside a module-scoped fixture, never at
import time: only one process at a time may load the TPU library, and
every test worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.retraction import qr_retract
from repro.kernels.paged_decode import (
    paged_gqa_decode_cold_pallas,
    paged_gqa_decode_pallas,
    paged_mla_decode_cold_pallas,
    paged_mla_decode_pallas,
)
from repro.kernels.spectral_matmul import (
    DEFAULT_BM,
    DEFAULT_CM,
    DEFAULT_CN,
    spectral_matmul_pallas,
)
from repro.kernels.spectral_matmul_q8 import spectral_matmul_q8_pallas
from repro.nn import attention as attn
from repro.nn import mlp
from repro.sharding import rules

# serving geometry of chip_smoke.py: 4 slots, page 16, 32 pages/seq
SLOTS, PAGE, PAGES_PER_SEQ, NUM_PAGES = 4, 16, 32, 128
# smollm2-1.7b attention: 32 kv heads (MHA), head dim 64
GQA_KVH, GQA_REP, GQA_HD = 32, 1, 64
# DeepSeek-V3 absorbed MLA, one chip's 16 query heads over the latent
MLA_H, MLA_LAT, MLA_ROPE = 16, 512, 64
# smollm2-1.7b MLP projection 2048 -> 8192 at the paper's rank 128
MLP_M, MLP_N, MLP_K, MLP_TOKENS = 2048, 8192, 128, 512
# smollm2-1.7b training attention: 4 packed rows of 2048, 32 heads of 64
TRAIN_B, TRAIN_S, TRAIN_H, TRAIN_HD = 4, 2048, 32, 64
# smollm2-1.7b's training MLP: 8192 tokens a step, d_ff 8192, rank 128
MLP_STEP_TOKENS, MLP_FF = 8192, 8192
# one d_ff-wide bf16 tensor of that MLP (gate, up, h or a gradient)
MLP_WIDE_BYTES = MLP_STEP_TOKENS * MLP_FF * 2
# smollm2-1.7b's MLP factors as the train step retracts them: 24 layers
# stacked, rank 128
FACTOR_LAYERS, FACTOR_K = 24, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else the compiler logs to /tmp
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, one_chip, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _paged_geometry():
    return [((SLOTS, PAGES_PER_SEQ), jnp.int32), ((SLOTS,), jnp.int32)]


@pytest.mark.parametrize("variant", ["hot", "cold"])
def test_paged_gqa_decode_compiles(one_chip, variant):
    q = ((SLOTS, GQA_KVH, GQA_REP, GQA_HD), jnp.bfloat16)
    pool = ((NUM_PAGES + 1, PAGE, GQA_KVH, GQA_HD), jnp.bfloat16)
    if variant == "hot":
        fn = lambda *a: paged_gqa_decode_pallas(*a, interpret=False)
        shapes = [q, pool, pool, *_paged_geometry()]
    else:
        fn = lambda *a: paged_gqa_decode_cold_pallas(*a, interpret=False)
        q8 = ((NUM_PAGES + 1, PAGE, GQA_KVH, GQA_HD), jnp.int8)
        scale = ((NUM_PAGES + 1, GQA_KVH, GQA_HD), jnp.float32)
        shapes = [q, pool, pool, q8, scale, q8, scale, *_paged_geometry(),
                  ((NUM_PAGES + 1,), jnp.int32)]
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, *shapes)


@pytest.mark.parametrize("variant", ["hot", "cold"])
def test_paged_mla_decode_compiles(one_chip, variant):
    q_lat = ((SLOTS, MLA_H, MLA_LAT), jnp.bfloat16)
    q_rope = ((SLOTS, MLA_H, MLA_ROPE), jnp.bfloat16)
    ckv = ((NUM_PAGES + 1, PAGE, MLA_LAT), jnp.bfloat16)
    kr = ((NUM_PAGES + 1, PAGE, MLA_ROPE), jnp.bfloat16)
    scale = 1.0 / (128 + MLA_ROPE) ** 0.5
    if variant == "hot":
        fn = lambda *a: paged_mla_decode_pallas(*a, scale=scale,
                                                interpret=False)
        shapes = [q_lat, q_rope, ckv, kr, *_paged_geometry()]
    else:
        fn = lambda *a: paged_mla_decode_cold_pallas(*a, scale=scale,
                                                     interpret=False)
        shapes = [q_lat, q_rope, ckv, kr,
                  ((NUM_PAGES + 1, PAGE, MLA_LAT), jnp.int8),
                  ((NUM_PAGES + 1, MLA_LAT), jnp.float32),
                  ((NUM_PAGES + 1, PAGE, MLA_ROPE), jnp.int8),
                  ((NUM_PAGES + 1, MLA_ROPE), jnp.float32),
                  *_paged_geometry(), ((NUM_PAGES + 1,), jnp.int32)]
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, *shapes)


@pytest.mark.parametrize("variant", ["fp", "q8"])
def test_spectral_matmul_compiles(one_chip, variant):
    tiles = dict(bm=DEFAULT_BM, cm=DEFAULT_CM, cn=DEFAULT_CN, interpret=False)
    x = ((MLP_TOKENS, MLP_M), jnp.bfloat16)
    scale = ((MLP_K,), jnp.float32)
    if variant == "fp":
        fn = lambda *a: spectral_matmul_pallas(*a, **tiles)
        factor = jnp.bfloat16
    else:
        fn = lambda *a: spectral_matmul_q8_pallas(*a, **tiles)
        factor = jnp.int8
    shapes = [x, ((MLP_M, MLP_K), factor), scale, ((MLP_N, MLP_K), factor)]
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, *shapes)


def _train_attention(fused: bool, grad: bool):
    def attend(q, k, v):
        if fused:
            return attn._sdpa(q, k, v, causal=True)
        b, s, h, d = q.shape
        return attn._sdpa_direct(q.reshape(b, s, h, 1, d), k, v,
                                 causal=True).reshape(b, s, h, d)

    if not grad:
        return attend
    loss = lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32) ** 2)
    return jax.grad(loss, argnums=(0, 1, 2))


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "grad"])
def test_fused_train_attention_compiles(one_chip, grad, monkeypatch):
    """The training step's causal attention at the smollm2 cell's shapes
    runs as the fused kernel on TPU, and its temporaries are a small
    fraction of the direct softmax gradient's [4,32,2048,2048] tensors."""
    monkeypatch.setattr(attn, "fused_kernel_backend", lambda: True)
    monkeypatch.setattr(rules, "_CURRENT_MESH", None)
    monkeypatch.setenv("SCT_INTERPRET", "0")
    x = ((TRAIN_B, TRAIN_S, TRAIN_H, TRAIN_HD), jnp.bfloat16)
    args = [jax.ShapeDtypeStruct(*x, sharding=one_chip)] * 3

    attn.reset_attention_path_counts()
    fused = jax.jit(_train_attention(True, grad)).lower(*args).compile()
    assert attn.attention_path_counts() == {"fused": 1}
    assert "tpu_custom_call" in fused.as_text()

    direct = jax.jit(_train_attention(False, True)).lower(*args).compile()
    fused_temp = fused.memory_analysis().temp_size_in_bytes
    direct_temp = direct.memory_analysis().temp_size_in_bytes
    assert fused_temp < direct_temp / 4, (fused_temp, direct_temp)


@pytest.mark.parametrize("rows", [MLP_M, MLP_N])
def test_qr_retraction_compiles(one_chip, rows):
    """The guarded ``qr`` retraction of a stacked factor compiles with both
    of its branches, Cholesky-QR2 and the Householder fallback, behind
    one conditional."""
    text = _compiled_text(qr_retract, one_chip, ((FACTOR_LAYERS, rows, FACTOR_K), jnp.float32))
    assert text.count("conditional(") == 1


def _mlp_grad(layers: int):
    """Loss and gradient of ``layers`` spectral SwiGLU MLPs under the train
    step's remat (``models/blocks.py:remat_scan``)."""
    def loss(p, x):
        body = lambda h, lp: (h + mlp.apply_mlp(lp, h), None)
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
        if layers == 1:
            h = body(x, p)[0]
        else:
            h = jax.lax.scan(body, x, p)[0]
        return jnp.sum(h.astype(jnp.float32) ** 2)

    return jax.value_and_grad(loss, argnums=(0, 1))


def _mlp_args(one_chip, layers: int):
    stack = () if layers == 1 else (layers,)
    f32 = lambda *s: jax.ShapeDtypeStruct((*stack, *s), jnp.float32, sharding=one_chip)
    wide = lambda: {"U": f32(MLP_M, MLP_K), "s": f32(MLP_K), "V": f32(MLP_FF, MLP_K)}
    p = {"gate": wide(), "up": wide(),
         "down": {"U": f32(MLP_FF, MLP_K), "s": f32(MLP_K), "V": f32(MLP_M, MLP_K)}}
    x = jax.ShapeDtypeStruct((MLP_STEP_TOKENS, MLP_M), jnp.bfloat16, sharding=one_chip)
    return p, x


def _custom_calls(computation: str, name: str) -> int:
    return len([line for line in computation.splitlines()
                if line.lstrip().startswith(f"%{name}") and "custom-call(" in line])


def test_fused_mlp_compiles(one_chip, monkeypatch):
    """The train step's spectral MLP at the smollm2 cell's widths runs as
    the fused kernels on TPU: no d_ff-wide tensor in the program, where
    the XLA path holds four at its peak; in a rematerialized layer scan
    the backward loop calls the backward kernel and no forward kernel."""
    monkeypatch.setattr(rules, "_CURRENT_MESH", None)
    monkeypatch.setenv("SCT_INTERPRET", "0")
    args = _mlp_args(one_chip, 1)

    monkeypatch.setattr(mlp, "fused_kernel_backend", lambda: True)
    mlp.reset_mlp_path_counts()
    fused = jax.jit(_mlp_grad(1)).lower(*args).compile()
    assert mlp.mlp_path_counts() == {"fused": 1}
    text = fused.as_text()
    assert "tpu_custom_call" in text
    assert f"[{MLP_STEP_TOKENS},{MLP_FF}]" not in text
    scan = jax.jit(_mlp_grad(2)).lower(*_mlp_args(one_chip, 2)).compile().as_text()
    monkeypatch.setattr(mlp, "fused_kernel_backend", lambda: False)
    xla = jax.jit(_mlp_grad(1)).lower(*args).compile()
    assert f"[{MLP_STEP_TOKENS},{MLP_FF}]" in xla.as_text()
    fused_temp = fused.memory_analysis().temp_size_in_bytes
    xla_temp = xla.memory_analysis().temp_size_in_bytes
    # the XLA path holds four d_ff-wide tensors at its peak, the fused
    # path less than a quarter of one (its d_model-wide temporaries)
    assert xla_temp >= 4 * MLP_WIDE_BYTES, xla_temp
    assert fused_temp < MLP_WIDE_BYTES / 4, fused_temp

    bodies = [c for c in scan.split("\n\n") if _custom_calls(c, "sct_mlp_bwd")]
    assert len(bodies) == 1 and _custom_calls(bodies[0], "sct_mlp_fwd") == 0
    assert _custom_calls(scan, "sct_mlp_fwd") == 1
