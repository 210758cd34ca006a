"""Gradient-compression correctness: int8 round-trip, error feedback,
and the compressed all-reduce on a real 2-device shard_map (subprocess +
``XLA_FLAGS=--xla_force_host_platform_device_count`` pattern from
tests/test_sharding.py, so the main process stays single-device).

The regression of record: shards quantized against *different* per-shard
scales cannot be summed as raw int8 payloads and rescaled by the
averaged scale — with a 1000x scale ratio the small shard's
contribution is inflated by orders of magnitude. The fixed path agrees
on the max scale first (scalar pmax), requantizes, and psums int8 under
the one shared scale; its mean error is bounded by shared_scale / 2 per
element. The subprocess computes the fp32 reference, the fixed result,
and the legacy math side by side: the fix must sit inside the bound and
the legacy math must blow it by orders of magnitude.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp

from repro.runtime.compression import (
    compress_int8,
    compressed_psum,
    decompress_int8,
    init_error_feedback,
)


def test_int8_roundtrip_and_error_feedback(key):
    g = jax.random.normal(key, (256,))
    q, scale = compress_int8(g)
    rec = decompress_int8(q, scale)
    rel = float(jnp.linalg.norm(rec - g) / jnp.linalg.norm(g))
    assert rel < 0.01  # int8 quantization error ~0.4% for gaussian
    ef = init_error_feedback({"g": g})
    assert float(jnp.max(jnp.abs(ef.residual["g"]))) == 0.0


def test_compressed_psum_single_device_is_identity_scale(key):
    """n=1 sanity inside shard_map: result equals the shard's own int8
    round-trip and the residual is exactly what the wire lost."""
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh

    g = {"w": jax.random.normal(key, (64,)) * 3.0}
    ef = init_error_feedback(g)
    mesh = make_mesh((1,), ("dp",))
    out, new_ef = jax.shard_map(
        lambda gg, rr: compressed_psum(gg, "dp", rr),
        mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
    )(g, ef)
    q, scale = compress_int8(g["w"])
    rec = decompress_int8(q, scale)
    assert float(jnp.max(jnp.abs(out["w"] - rec))) < 1e-6
    assert float(jnp.max(jnp.abs(new_ef.residual["w"] - (g["w"] - rec)))) < 1e-6


_SUBPROCESS_PSUM = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.runtime.compression import (
    compress_int8, compressed_psum, init_error_feedback)

# two shards with a ~1000x magnitude ratio: the shard-scale mismatch
# that breaks the averaged-scale math
k0, k1 = jax.random.split(jax.random.PRNGKey(0))
g = jnp.concatenate([jax.random.normal(k0, (1, 128)) * 1e-3,
                     jax.random.normal(k1, (1, 128)) * 1.0], axis=0)  # (2, 128)
ref = jnp.mean(g, axis=0)                         # fp32 mean across "pods"

mesh = make_mesh((2,), ("dp",))

def fixed(gg, rr):
    out, new_ef = compressed_psum({"w": gg}, "dp", rr)
    return out["w"], new_ef

def legacy(gg):
    # the old math: per-shard scales, raw int8 sum, averaged scale
    q, scale = compress_int8(gg)
    summed = jax.lax.psum(q.astype(jnp.int32), "dp")
    scale_sum = jax.lax.psum(scale, "dp")
    n = jax.lax.psum(jnp.ones((), jnp.float32), "dp")
    return summed.astype(jnp.float32) * (scale_sum / n) / n

ef = init_error_feedback({"w": g})
out_fixed, new_ef = jax.shard_map(
    fixed, mesh=mesh, in_specs=(P("dp"), P("dp")), out_specs=(P("dp"), P("dp")),
)(g, ef)
out_legacy = jax.shard_map(
    legacy, mesh=mesh, in_specs=(P("dp"),), out_specs=P("dp"),
)(g)

# per-element bound for the fixed path: shared_scale / 2 (each shard's
# rounding error <= shared/2, averaged over n=2)
shared_scale = float(jnp.max(jnp.abs(g)) / 127.0)
bound = shared_scale / 2.0 + 1e-12
err_fixed = float(jnp.max(jnp.abs(out_fixed[0] - ref)))
err_legacy = float(jnp.max(jnp.abs(out_legacy[0] - ref)))
resid = jax.device_get(new_ef.residual["w"])
print(json.dumps({
    "bound": bound,
    "err_fixed": err_fixed,
    "err_legacy": err_legacy,
    "resid_finite": bool(jnp.all(jnp.isfinite(resid))),
}))
"""


def test_compressed_psum_mismatched_shard_scales_two_devices():
    """Two processes' worth of shards (2 host devices), 1000x apart in
    magnitude: the fixed all-reduce matches the fp32 mean within the
    int8 bound; the legacy averaged-scale math violates it by orders of
    magnitude (the demonstration the fix exists for)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _SUBPROCESS_PSUM],
                         capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    p = json.loads(out.stdout.strip().splitlines()[-1])
    assert p["resid_finite"]
    assert p["err_fixed"] <= p["bound"], \
        f"fixed path error {p['err_fixed']} exceeds int8 bound {p['bound']}"
    assert p["err_legacy"] > 10 * p["bound"], \
        f"legacy math unexpectedly accurate ({p['err_legacy']} vs {p['bound']})"
