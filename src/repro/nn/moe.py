"""Mixture-of-Experts with capacity-bounded sort-free dispatch, shared
experts (deepseek), and SCT inside every expert.

Dispatch strategy (TPU-native): instead of the dense
one-hot dispatch einsum (FLOPs = tokens x E x d — would dwarf the real
compute), tokens are scattered into an (E, C, d) buffer using positions
computed with a cumsum over the top-k assignment mask, processed with a
single batched per-expert matmul, and gathered back with the router
weights. FLOPs = active FLOPs = tokens x top_k x (expert matmuls); the
scatter/gather are memory ops that XLA turns into all-to-all style
collectives when experts are sharded over the 'model' mesh axis.

Expert weights carry a leading E axis; spectral experts are
{"U": (E, d, k), "s": (E, k), "V": (E, f, k)} and the Stiefel retraction
vmaps over E for free (retraction broadcasting, core/retraction.py).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.spectral import spectral_init, is_spectral


def _init_expert_linear(key, E, m, n, rank, dtype):
    if rank is not None:
        k = min(rank, m, n)
        ks = jax.random.split(key, E)
        return jax.vmap(lambda kk: spectral_init(kk, m, n, k, dtype=dtype))(ks)
    w = jax.random.normal(key, (E, m, n), dtype=jnp.float32) * (m ** -0.5)
    return {"w": w.astype(dtype)}


def init_moe(key, cfg, dtype=jnp.float32):
    """cfg needs: d_model, moe_d_ff, n_experts, n_shared_experts, top_k,
    mlp_rank (None => dense experts)."""
    ks = jax.random.split(key, 7)
    d, f, E = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    r = cfg.mlp_rank
    p = {
        "router": {"w": (jax.random.normal(ks[0], (d, E), dtype=jnp.float32) * d ** -0.5).astype(dtype)},
        "gate": _init_expert_linear(ks[1], E, d, f, r, dtype),
        "up": _init_expert_linear(ks[2], E, d, f, r, dtype),
        "down": _init_expert_linear(ks[3], E, f, d, r, dtype),
    }
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        from repro.nn.mlp import init_mlp

        p["shared"] = init_mlp(ks[4], d, fs, rank=r, act="swiglu", dtype=dtype)
    return p


def _expert_matmul(p, x):
    """x: (G, E, C, d) @ expert weights -> (G, E, C, n). Spectral experts
    never materialize (E, d, n); with U/V long axes FSDP-sharded the
    cross-shard reduction payload is the RANK axis (G,E,C,k) — the
    spectral-TP collective win."""
    if is_spectral(p):
        h = jnp.einsum("gecd,edk->geck", x, p["U"].astype(x.dtype))
        h = h * p["s"][None, :, None, :].astype(x.dtype)
        return jnp.einsum("geck,enk->gecn", h, p["V"].astype(x.dtype))
    return jnp.einsum("gecd,edn->gecn", x, p["w"].astype(x.dtype))


def apply_moe_sharded(p, x, cfg, *, capacity_factor: float = 1.25):
    """Explicit shard_map MoE. Device (i, j) on the (data, model) mesh holds tokens-
    shard-i and experts-shard-j; it dispatches ITS tokens to ITS experts
    locally (zero-communication dispatch), so the only collectives are:

      * router logits all-gather over 'model'   (T_loc x E, tiny)
      * FSDP weight all-gather over 'data'      (k(m+n) per expert, the
        SCT factors — this is where the paper's compression pays again)
      * combine psum over 'model'               (T_loc x d bf16)

    vs. the GSPMD-inferred version whose gather/scatter partitioning
    replicated the (E, C, d) buffers (measured 224-1552 s/step collective
    at deepseek-v3 scale; this path: ~2 s/step class).
    """
    from repro.sharding import rules as rules_mod

    mesh = rules_mod._CURRENT_MESH
    b, s, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    n_model = mesh.shape.get("model", 1)
    n_data = mesh.shape.get("data", 1)
    dp = rules_mod.dp_axes(mesh)
    E_loc = E // n_model

    moe_specs = rules_mod.param_pspecs({"moe": p}, n_model, n_data)["moe"]
    # shared expert runs outside (plain jnp path handles it)
    router_experts = {k: v for k, v in p.items() if k != "shared"}
    re_specs = {k: moe_specs[k] for k in router_experts}

    from jax.sharding import PartitionSpec as P

    x_spec = P(dp, None, None)

    def f(pp, xx):
        j = jax.lax.axis_index("model")
        bl, sl, _ = xx.shape
        T_loc = bl * sl
        xt = xx.reshape(T_loc, d)

        # router: local columns -> all-gather over model (tiny)
        w_loc = pp["router"]["w"].astype(xt.dtype)              # (d, E_loc)
        logits_loc = (xt @ w_loc).astype(jnp.float32)
        logits = jax.lax.all_gather(logits_loc, "model", axis=1, tiled=True)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, K)          # (T_loc, K)
        if cfg.moe_norm_topk:
            gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

        # aux loss: local token fractions, global mean over data+model
        assign = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)
        frac_tokens = jnp.mean(jnp.sum(assign, axis=1), axis=0)
        frac_probs = jnp.mean(probs, axis=0)
        for ax in dp:
            frac_tokens = jax.lax.pmean(frac_tokens, ax)
            frac_probs = jax.lax.pmean(frac_probs, ax)
        aux = E * jnp.sum(frac_tokens * frac_probs)

        # local dispatch: this shard's tokens x this shard's experts.
        # Only int32 slot bookkeeping is (T_loc*K)-sized; token payloads
        # move via an (E_loc*C_loc)-sized gather — the (T_loc*K, d)
        # repeat never exists (§Perf iteration 3).
        C_loc = max(1, int(capacity_factor * T_loc * K / E))
        flat_idx = expert_idx.reshape(T_loc * K)
        local_e = flat_idx - j * E_loc
        in_range = (local_e >= 0) & (local_e < E_loc)
        local_e = jnp.clip(local_e, 0, E_loc - 1)
        onehot = jax.nn.one_hot(local_e, E_loc, dtype=jnp.int32)
        onehot = onehot * in_range[:, None].astype(jnp.int32)
        pos = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=-1)
        keep = in_range & (pos < C_loc)
        slot = jnp.where(keep, local_e * C_loc + pos, 0)
        # inverse map: which token (and gate) fills each slot
        tok_of_pick = jnp.repeat(jnp.arange(T_loc, dtype=jnp.int32), K)
        gate_of_pick = gate_vals.reshape(T_loc * K)
        slot_tok = jnp.zeros((E_loc * C_loc,), jnp.int32).at[slot].add(
            jnp.where(keep, tok_of_pick + 1, 0))
        slot_gate = jnp.zeros((E_loc * C_loc,), jnp.float32).at[slot].add(
            jnp.where(keep, gate_of_pick, 0.0))
        slot_mask = slot_tok > 0
        slot_tok = jnp.maximum(slot_tok - 1, 0)
        ein = jnp.where(slot_mask[:, None], xt[slot_tok], 0).reshape(E_loc, C_loc, d)

        # FSDP just-in-time weight gather over 'data' (factors are small)
        def gather_w(q, axis):
            return jax.lax.all_gather(q, "data", axis=axis, tiled=True)

        def expert_mm(wp, t):
            if is_spectral(wp):
                U = gather_w(wp["U"], 1).astype(t.dtype)         # (E_loc, m, k)
                V = gather_w(wp["V"], 1).astype(t.dtype)         # (E_loc, n, k)
                hh = jnp.einsum("ecd,edk->eck", t, U)
                hh = hh * wp["s"][:, None, :].astype(t.dtype)
                return jnp.einsum("eck,enk->ecn", hh, V)
            w = gather_w(wp["w"], 1).astype(t.dtype)
            return jnp.einsum("ecd,edn->ecn", t, w)

        g = expert_mm(pp["gate"], ein)
        u = expert_mm(pp["up"], ein)
        hh = jax.nn.silu(g) * u
        eout = expert_mm(pp["down"], hh)                          # (E_loc, C_loc, d)

        # combine: scatter-add slot contributions back to tokens (slots
        # holding different picks of a token sum correctly), then ONE
        # psum over 'model' of (T_loc, d)
        contrib = eout.reshape(E_loc * C_loc, d) * slot_gate[:, None].astype(eout.dtype)
        contrib = jnp.where(slot_mask[:, None], contrib, 0)
        partial = jnp.zeros((T_loc, d), eout.dtype).at[slot_tok].add(contrib)
        out = jax.lax.psum(partial, "model")
        return out.reshape(bl, sl, d), aux

    out, aux = jax.shard_map(
        f, mesh=mesh, in_specs=(re_specs, x_spec), out_specs=(x_spec, P()),
        check_vma=False)(router_experts, x)

    if cfg.n_shared_experts:
        from repro.nn.mlp import apply_mlp

        out = out + apply_mlp(p["shared"], x, act="swiglu")
    return out, aux


def _dp_groups(b: int) -> int:
    """Number of local-dispatch groups = the data-parallel degree the
    batch is actually sharded over (1 when no mesh is active)."""
    from repro.sharding import rules as rules_mod

    mesh = rules_mod._CURRENT_MESH
    if mesh is None:
        return 1
    n = 1
    for a in rules_mod.dp_axes(mesh):
        n *= mesh.shape[a]
    return n if (n > 1 and b % n == 0) else 1


def _sharded_moe_ok(cfg, b, s):
    """Use the explicit shard_map path when the mesh and dims permit."""
    from repro.sharding import rules as rules_mod

    mesh = rules_mod._CURRENT_MESH
    if mesh is None or "model" not in mesh.axis_names:
        return False
    n_model = mesh.shape["model"]
    n_data = mesh.shape.get("data", 1)
    n_dp = 1
    for a in rules_mod.dp_axes(mesh):
        n_dp *= mesh.shape[a]
    return (
        cfg.n_experts % n_model == 0
        and b % max(n_dp, 1) == 0
        and cfg.d_model % n_data == 0
        and cfg.moe_d_ff % n_data == 0
    )


def apply_moe(p, x, cfg, *, capacity_factor: float = 1.25):
    """x: (b, s, d) -> (b, s, d), plus the load-balance aux loss.

    Dispatches to the explicit shard_map implementation under a mesh
    (apply_moe_sharded); the pure-jnp path below is the single-device /
    fallback reference the tests validate against.

    Hierarchical LOCAL-CAPACITY dispatch: tokens are grouped by their data shard; the
    capacity cumsum, scatter and gather-back are all group-local (no
    collective), and the single cross-shard movement is the
    (data-major -> expert-major) buffer transpose, which GSPMD lowers to
    the canonical MoE all-to-all. Capacity is enforced per shard
    (C_loc = C/n_dp), as production MoE systems do."""
    b, s_len, d = x.shape
    if _sharded_moe_ok(cfg, b, s_len):
        return apply_moe_sharded(p, x, cfg, capacity_factor=capacity_factor)
    s = s_len
    E, K = cfg.n_experts, cfg.top_k
    T = b * s
    G = _dp_groups(b)
    Tg = T // G
    xg = x.reshape(G, Tg, d)

    from repro.sharding import rules as rules_mod

    dp = rules_mod.dp_axes(rules_mod._CURRENT_MESH) if rules_mod._CURRENT_MESH else None
    xg = rules_mod.constrain(xg, dp, None, None)

    logits = jnp.einsum("gtd,de->gte", xg, p["router"]["w"].astype(xg.dtype)
                        ).astype(jnp.float32)                              # (G, Tg, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)                        # (G, Tg, K)
    if cfg.moe_norm_topk:
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # aux load-balance loss (Switch-style): E * sum_e f_e * P_e
    assign = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)              # (G, Tg, K, E)
    frac_tokens = jnp.mean(jnp.sum(assign, axis=2), axis=(0, 1))           # (E,)
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux_loss = E * jnp.sum(frac_tokens * frac_probs)

    # group-local capacity positions: cumsum runs within each group only
    C_loc = max(1, int(capacity_factor * Tg * K / E))
    flat_idx = expert_idx.reshape(G, Tg * K)                               # (G, TgK)
    onehot = jax.nn.one_hot(flat_idx, E, dtype=jnp.int32)                  # (G, TgK, E)
    pos_in_expert = jnp.cumsum(onehot, axis=1) - onehot
    pos = jnp.sum(pos_in_expert * onehot, axis=-1)                         # (G, TgK)
    keep = pos < C_loc
    slot = jnp.where(keep, flat_idx * C_loc + pos, 0)                      # group-local

    # group-local scatter-add into (G, E*C_loc, d), then slice experts to
    # their model shard: device (i, j) holds groups-shard-i x
    # experts-shard-j, so the expert matmuls below are fully LOCAL —
    # the classic MoE all-to-all is traded for a redundant local scatter
    # plus a slice (the no-a2a dispatch).
    src = jnp.repeat(xg, K, axis=1)                                        # (G, TgK, d)
    src = jnp.where(keep[..., None], src, 0)
    buf = jnp.zeros((G, E * C_loc, d), dtype=x.dtype)
    buf = buf.at[jnp.arange(G)[:, None], slot].add(src)
    buf = rules_mod.constrain(buf, dp, None, None)
    expert_in = buf.reshape(G, E, C_loc, d)
    expert_in = rules_mod.constrain(expert_in, dp, "model", None, None)

    # per-expert SwiGLU MLP (spectral or dense), (g, e) batch all-local
    g = _expert_matmul(p["gate"], expert_in)
    u = _expert_matmul(p["up"], expert_in)
    h = jax.nn.silu(g) * u
    expert_out = _expert_matmul(p["down"], h)                              # (G, E, C_loc, d)
    expert_out = rules_mod.constrain(expert_out, dp, "model", None, None)

    # combine: gather over the model-sharded expert axis — GSPMD lowers
    # this to a local gather + psum over 'model' (the return movement)
    out_flat = expert_out.reshape(G, E * C_loc, d)
    gathered = out_flat[jnp.arange(G)[:, None], slot]                      # (G, TgK, d)
    gathered = jnp.where(keep[..., None], gathered, 0)
    weighted = gathered * gate_vals.reshape(G, Tg * K, 1).astype(gathered.dtype)
    out = jnp.sum(weighted.reshape(G, Tg, K, d), axis=2)                   # (G, Tg, d)

    if cfg.n_shared_experts:
        from repro.nn.mlp import apply_mlp

        out = out + apply_mlp(p["shared"], xg, act="swiglu")
    return out.reshape(b, s, d), aux_loss
