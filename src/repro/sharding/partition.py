"""Whole-state and input partition specs per (config, shape, mesh) —
for the training step and, since the distributed-serving refactor, the
paged decode path (serve meshes, pool placement, shard_map wrapping)."""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config.model_config import ModelConfig
from repro.config.shapes import ShapeSpec
from repro.sharding.rules import param_pspecs, dp_axes, MODEL


def state_pspecs(state_like: Any, n_model: int, n_data: int = 16) -> Any:
    """TrainState {'params','opt':{'mu','nu','count'},'step'} specs:
    optimizer moments mirror the parameter sharding exactly. Any extra
    state entries (e.g. the mixed-precision ``loss_scale`` scalars) are
    replicated."""
    pspec = param_pspecs(state_like["params"], n_model, n_data)
    out = {
        "params": pspec,
        "opt": {
            "mu": param_pspecs(state_like["opt"]["mu"], n_model, n_data),
            "nu": param_pspecs(state_like["opt"]["nu"], n_model, n_data),
            "count": P(),
        },
        "step": P(),
    }
    for key in state_like:
        if key not in out:
            out[key] = jax.tree.map(lambda _: P(), state_like[key])
    return out


def state_shardings_for(state_like: Any, mesh) -> Any:
    """NamedSharding tree for a TrainState (live arrays or
    ShapeDtypeStructs), regenerated from the mesh's axis sizes.

    This is the rank-resize path (rank/controller.py): a resize changes
    the spectral factors' k dimension, so the sharding tree must be
    rebuilt against the *new* shapes — the partition rules name mesh
    axes, not sizes, so the same rules re-apply and divisibility guards
    in rules.py drop any axis the new shape no longer divides."""
    n_model = mesh.shape.get("model", 1)
    n_data = mesh.shape.get("data", 1)
    return named_shardings(state_pspecs(state_like, n_model, n_data), mesh)


def batch_axes(global_batch: int, mesh):
    """The mesh axes the batch dim shards over: all DP axes when the
    batch divides them, 'data' alone as a fallback, else unsharded."""
    dp = dp_axes(mesh)
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    if dp and global_batch % n_dp == 0:
        return dp
    if "data" in mesh.axis_names and global_batch % mesh.shape["data"] == 0:
        return ("data",)
    return None


def batch_pspecs(cfg: ModelConfig, shape: ShapeSpec, mesh) -> Any:
    bt = batch_axes(shape.global_batch, mesh)

    specs = {}
    if shape.kind == "train":
        specs["tokens"] = P(bt, None)
        specs["labels"] = P(bt, None)
        if cfg.family == "encdec":
            specs["encoder_frames"] = P(bt, None, None)
        return specs
    if shape.kind == "prefill":
        specs["tokens"] = P(bt, None)
        if cfg.family == "encdec":
            specs["encoder_frames"] = P(bt, None, None)
        return specs
    # decode
    specs["tokens"] = P(bt, None)
    specs["cache_len"] = P()
    specs["state"] = decode_state_pspecs(cfg, shape, mesh, bt)
    if cfg.family == "encdec":
        specs["encoder_out"] = P(bt, None, None)
    return specs


def decode_state_pspecs(cfg: ModelConfig, shape: ShapeSpec, mesh, bt) -> Any:
    """Decode caches: batch-shard when the batch divides the DP axes;
    otherwise (long_500k, batch=1) shard the cache *sequence* axis over
    'data' (sequence parallelism for the KV cache)."""
    from repro.models.model import decode_state_specs

    specs = decode_state_specs(cfg, batch=shape.global_batch, max_seq=shape.seq_len)
    seq_shard = bt is None  # batch unshardable -> shard cache seq over data

    def spec_for(path, leaf):
        shp = leaf.shape
        nd = len(shp)
        # KV caches: (L, b, S, ...) — attn k/v/ckv/krope
        tail = path.split("/")[-1]
        if tail in ("k", "v", "ckv", "krope"):
            out = [None] * nd
            out[1] = bt
            if seq_shard and shp[2] % mesh.shape.get("data", 1) == 0:
                out[2] = "data"
            return P(*out)
        # recurrent states: (P, n, b, ...) or (P, b, ...); shard batch
        # axis if possible, model-dim channels over 'model' where they
        # divide (mamba di)
        out = [None] * nd
        # find the batch axis: it equals shape.global_batch
        for i, d in enumerate(shp):
            if d == shape.global_batch and bt is not None:
                out[i] = bt
                break
        return P(*out)

    def walk(tree, path=""):
        if isinstance(tree, dict):
            return {k: walk(v, f"{path}/{k}" if path else k) for k, v in tree.items()}
        return spec_for(path, tree)

    return walk(specs)


def named_shardings(pspecs: Any, mesh) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        pspecs,
        is_leaf=lambda x: isinstance(x, P),
    )


# ======================================================================
# Decode-path placement (distributed serving)
# ======================================================================

# the mesh axis tensor-parallel serving shards over — the same axis
# name the training rules use, so activation constraints compose
TP_AXIS = "model"


def serve_mesh(tp: int) -> Mesh:
    """1-D ``('model',)`` mesh over the first ``tp`` local devices —
    the tensor-parallel serve mesh. Raises when the host doesn't expose
    enough devices (tests force them with
    ``XLA_FLAGS=--xla_force_host_platform_device_count``)."""
    devices = jax.devices()
    if tp > len(devices):
        raise ValueError(
            f"serve mesh needs {tp} devices, host has {len(devices)} "
            f"(force more on CPU with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={tp})")
    from repro.launch.mesh import make_mesh

    return make_mesh((tp,), (TP_AXIS,), devices=devices)


def serve_tp_valid(cfg: ModelConfig, tp: int) -> bool:
    """Whether ``tp`` ways of head parallelism divide this config's
    attention: GQA shards the kv-head axis (each shard keeps whole
    query groups — see the (kvh, rep) grouping in nn/attention.py), MLA
    shards query heads over the replicated latent."""
    if cfg.attention == "mla":
        return cfg.n_heads % tp == 0
    return cfg.n_kv_heads % tp == 0


def paged_state_pspecs(cfg: ModelConfig, state_like: Any, n_model: int) -> Any:
    """Placement of the paged decode state over a serve mesh: GQA KV
    pool leaves (L, P+1, page, kvh, hd) shard the kv-head axis over
    'model' when it divides; MLA latent pools (no head axis — the
    latent is tiny, replication is the cheap placement) and recurrent
    slot state replicate. Used both as device_put placement and as the
    shard_map in/out specs for the decode and chunk-prefill steps."""
    def spec_for(path, leaf):
        tail = path.split("/")[-1]
        shp = getattr(leaf, "shape", ())
        if tail in ("k", "v") and len(shp) == 5 and shp[3] % n_model == 0:
            return P(None, None, None, TP_AXIS, None)
        return P()

    def walk(tree, path=""):
        if isinstance(tree, dict):
            return {k: walk(v, f"{path}/{k}" if path else k)
                    for k, v in tree.items()}
        return spec_for(path, tree)

    return walk(state_like)
