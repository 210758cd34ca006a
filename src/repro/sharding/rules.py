"""Logical sharding rules: param-path suffix -> PartitionSpec over the
trailing axes (leading stack axes — layers L, periods P, experts E —
are padded with None, except expert axes which shard over 'model').

Spectral-TP scheme (DESIGN.md S5): the *long* axis of each factor is
sharded over 'model'; the rank axis k is always replicated, so the TP
collective carries b x k activations instead of b x d_ff — the paper's
compression applied to communication.
"""
from __future__ import annotations

import re
from typing import Any, Optional, Sequence, Tuple

import jax
from jax.sharding import PartitionSpec as P

MODEL = "model"
DATA = "data"

# (suffix regex, trailing-axes spec). First match wins. `None` entries
# replicate. Specs are relative to the LAST len(spec) axes of the leaf.
_RULES: Sequence[Tuple[str, Tuple[Optional[str], ...]]] = (
    # ---- embeddings (vocab-sharded; rules.py falls back to d-sharding
    #      when vocab %% n_model != 0, see _embed_spec) ----
    (r"embed/w$", ("__embed__",)),
    (r"(enc_pos|dec_pos)/w$", (None, None)),
    # ---- MoE: expert axis E shards over 'model' (expert parallelism);
    #      the within-expert long axis shards over 'data' (FSDP) — SCT
    #      state is k(m+n+1) so even the *gathered* factor is small ----
    (r"moe/(gate|up|down)/(U|V)$", ("__expert__", DATA, None)),
    (r"moe/(gate|up|down)/s$", ("__expert__", None)),
    (r"moe/(gate|up|down)/w$", ("__expert__", DATA, None)),
    (r"router/w$", (None, MODEL)),
    # ---- spectral MLP / shared expert / mamba / xlstm projections ----
    # up/gate: U (d, k) FSDP rows; V (f, k) TP rows (the spectral-TP
    # scheme: rank axis replicated, collective payload is b x k)
    (r"(up|gate|ff_up|in_proj)/U$", (DATA, None)),
    (r"(up|gate|ff_up|in_proj)/V$", (MODEL, None)),
    # down: U (f, k) TP rows; V (d, k) FSDP rows
    (r"(down|ff_down|out_proj)/U$", (MODEL, None)),
    (r"(down|ff_down|out_proj)/V$", (DATA, None)),
    (r"(up|gate|down|ff_up|ff_down|in_proj|out_proj)/s$", (None,)),
    # ---- spectral attention (option): long axis = heads side ----
    (r"(wq|wk|wv)/U$", (DATA, None)),
    (r"(wq|wk|wv)/V$", (MODEL, None)),
    (r"wo/U$", (MODEL, None)),
    (r"wo/V$", (DATA, None)),
    (r"(wq|wk|wv|wo)/s$", (None,)),
    # ---- dense projections: FSDP rows x TP cols (in), TP rows x FSDP
    #      cols (out) ----
    (r"(wq|wk|wv|wuq|wdq|wdkv|wukv|wx|up|gate|ff_up|in_proj|dt_proj)/w$", (DATA, MODEL)),
    (r"(wq|wk|wv|wuq|wx|up|gate|ff_up|in_proj|dt_proj)/b$", (MODEL,)),
    (r"(wo|down|ff_down|out_proj|x_proj|wo_gate)/w$", (MODEL, DATA)),
    (r"(wo|down|ff_down|out_proj|x_proj|wo_gate)/b$", (None,)),
    (r"(wdq|wdkv)/b$", (MODEL,)),
    (r"(wi|wf)/(w|b)$", (None, None)),
    # ---- mamba per-channel tensors (di sharded like the conv) ----
    (r"conv_w$", (None, MODEL)),
    (r"conv_b$", (MODEL,)),
    (r"A_log$", (MODEL, None)),
    (r"D$", (MODEL,)),
    # ---- xlstm recurrent cell (small, replicated) ----
    (r"wr$", (None, None, None)),
    # ---- norms / everything else: replicated ----
)

_COMPILED = [(re.compile(rx), spec) for rx, spec in _RULES]


def _embed_spec(shape, n_model: int):
    vocab, d = shape[-2], shape[-1]
    if vocab % n_model == 0:
        return (MODEL, DATA)  # vocab-TP x FSDP
    if d % n_model == 0:
        return (DATA, MODEL)
    return (None, None)


def _resolve(path: str, shape, n_model: int):
    for rx, spec in _COMPILED:
        if rx.search(path):
            out = []
            for s in spec:
                if s == "__embed__":
                    return _embed_spec(shape, n_model)
                out.append(MODEL if s == "__expert__" else s)
            return tuple(out)
    return None  # fully replicated


def _divisible(shape, spec, n_model: int, n_data: int):
    """Drop mesh-axis entries whose dim isn't divisible (e.g.
    qwen1.5-4b's 20 heads on a 16-way axis) — replicate instead; GSPMD
    would insert a gather anyway, better to make it explicit."""
    out = []
    for dim, s in zip(shape[-len(spec):], spec):
        if s == MODEL and dim % n_model != 0:
            out.append(None)
        elif s == DATA and dim % n_data != 0:
            out.append(None)
        else:
            out.append(s)
    return tuple(out)


def param_pspecs(params: Any, n_model: int = 16, n_data: int = 16) -> Any:
    """PartitionSpec tree mirroring ``params``."""

    def walk(tree, path):
        if isinstance(tree, dict):
            return {k: walk(v, f"{path}/{k}" if path else k) for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            return type(tree)(walk(v, f"{path}/[{i}]") for i, v in enumerate(tree))
        shape = tree.shape
        spec = _resolve(path, shape, n_model)
        if spec is None:
            return P()
        spec = _divisible(shape, spec, n_model, n_data)
        lead = len(shape) - len(spec)
        return P(*((None,) * lead + spec))

    return walk(params, "")


# ----------------------------------------------------------------------
# Activation constraint helper (mesh-agnostic model code)
# ----------------------------------------------------------------------

_CURRENT_MESH = None
_ACT_SEQ_AXIS = None  # set to 'model' for sequence-parallel activations


def set_current_mesh(mesh) -> None:
    global _CURRENT_MESH
    _CURRENT_MESH = mesh


def current_mesh():
    """The mesh set by :func:`set_current_mesh`, or None."""
    return _CURRENT_MESH


def set_activation_seq_sharding(axis: Optional[str]) -> None:
    """Sequence-parallelism knob: shard layer-boundary activations'
    sequence axis over ``axis`` ('model'). Cuts per-device activation
    memory by n_model at the cost of boundary collectives (hillclimb
    lever, EXPERIMENTS.md §Perf)."""
    global _ACT_SEQ_AXIS
    _ACT_SEQ_AXIS = axis


def constrain(x, *spec):
    """with_sharding_constraint if a mesh is active, else identity."""
    if _CURRENT_MESH is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(_CURRENT_MESH, P(*spec))
    )


def constrain_activation(x):
    """Layer-boundary (b, s, d) activation constraint: batch over the DP
    axes, sequence optionally over 'model' (sequence parallelism)."""
    if _CURRENT_MESH is None or x.ndim != 3:
        return x
    bt = dp_axes(_CURRENT_MESH)
    if x.shape[0] % max(1, _prod(_CURRENT_MESH.shape[a] for a in bt)) != 0:
        bt = None
    seq = _ACT_SEQ_AXIS
    if seq is not None and x.shape[1] % _CURRENT_MESH.shape.get(seq, 1) != 0:
        seq = None
    return constrain(x, bt, seq, None)


def _prod(it):
    out = 1
    for v in it:
        out *= v
    return out


def constrain_expert_buffer(x):
    """MoE (E, C, d) dispatch buffer: experts over 'model', capacity over
    the DP axes — keeps the buffer's per-device footprint at
    E/n_model x C/n_dp x d (DESIGN.md S5)."""
    if _CURRENT_MESH is None or x.ndim != 3:
        return x
    m = MODEL if x.shape[0] % _CURRENT_MESH.shape.get(MODEL, 1) == 0 else None
    bt = dp_axes(_CURRENT_MESH)
    if bt and x.shape[1] % _prod(_CURRENT_MESH.shape[a] for a in bt) != 0:
        bt = None
    return constrain(x, m, bt, None)


def dp_axes(mesh) -> tuple:
    """The data-parallel mesh axes: ('pod', 'data') when a pod axis
    exists, else ('data',)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)
