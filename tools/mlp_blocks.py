#!/usr/bin/env python3
"""Time the fused spectral SwiGLU kernels' blocks on one TPU chip.

  PYTHONPATH=src python3 tools/mlp_blocks.py [--tokens 8192 --d-model 2048
      --d-ff 8192 --rank 128 --iters 20]

For each (token block, d_ff chunk) it times the forward kernel
(``kernels/fused_mlp.py:swiglu_down_pallas``) and the backward kernel
alone. Then, at the fastest blocks of each, the whole MLP of
``nn/mlp.py:apply_mlp`` as the remat train step runs it (the forward
alone, and the gradient of a checkpointed layer with nothing saved),
through the fused path and through XLA. One JSON line per row, then the
blocks ordered by time. The defaults are the smollm2-1.7b training
cell's widths. Needs a TPU.
"""
from __future__ import annotations

import argparse
import itertools
import json
import time

import jax
import jax.numpy as jnp

from repro.kernels import fused_mlp
from repro.nn import mlp


def _ms(fn, args, iters: int) -> float:
    jax.block_until_ready(fn(*args))           # compile and warm up
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def _kernel_rows(a, tokens, d_ff, rank, iters):
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    rows_in = [jax.random.normal(k, (tokens, rank), jnp.bfloat16) for k in keys[:3]]
    factors = [(jax.random.normal(k, (d_ff, rank)) / rank ** 0.5).astype(jnp.bfloat16)
               for k in keys[3:]]
    rows = []
    for kind, tm, tn in itertools.product(("fwd", "bwd"), a.block_m, a.block_ff):
        if tokens % tm or d_ff % tn:
            continue
        row = dict(kernel=kind, block_m=tm, block_ff=tn)
        try:
            if kind == "fwd":
                fn = jax.jit(lambda ag, au, g, u, d: fused_mlp.swiglu_down_pallas(
                    ag, au, g, u, d, tm=tm, tn=tn))
                row["ms"] = _ms(fn, (*rows_in[:2], *factors), iters)
            else:
                fn = jax.jit(lambda ag, au, dz, g, u, d: fused_mlp.swiglu_down_bwd_pallas(
                    ag, au, dz, g, u, d, tm=tm, tn=tn))
                row["ms"] = _ms(fn, (*rows_in, *factors), iters)
        except Exception as e:  # noqa: BLE001 — a block the compiler refuses
            row["error"] = str(e).splitlines()[0][:200]
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--d-model", type=int, default=2048)
    ap.add_argument("--d-ff", type=int, default=8192)
    ap.add_argument("--rank", type=int, default=128)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--block-m", type=int, nargs="+", default=[256, 512, 1024, 2048])
    ap.add_argument("--block-ff", type=int, nargs="+", default=[256, 512, 1024, 2048])
    a = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        raise SystemExit("mlp_blocks: needs a TPU")
    rows = _kernel_rows(a, a.tokens, a.d_ff, a.rank, a.iters)
    best = {}
    for kind in ("fwd", "bwd"):
        timed = [r for r in rows if r["kernel"] == kind and "ms" in r]
        if timed:
            r = min(timed, key=lambda r: r["ms"])
            best[kind] = (r["block_m"], r["block_ff"])

    p = mlp.init_mlp(jax.random.PRNGKey(1), a.d_model, a.d_ff, rank=a.rank)
    x = jax.random.normal(jax.random.PRNGKey(2), (a.tokens, a.d_model), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(3), (a.tokens, a.d_model), jnp.bfloat16)
    paths = [("xla", None)] + ([("fused", best)] if len(best) == 2 else [])
    for path, blocks in paths:
        if blocks:
            mlp.FUSED_BLOCKS_FWD, mlp.FUSED_BLOCKS_BWD = blocks["fwd"], blocks["bwd"]
        on_tpu = path == "fused"
        mlp.fused_kernel_backend = lambda on_tpu=on_tpu: on_tpu
        layer = jax.checkpoint(lambda p, x: mlp.apply_mlp(p, x),
                               policy=jax.checkpoint_policies.nothing_saveable)
        loss = lambda p, x: jnp.sum(layer(p, x).astype(jnp.float32) * w)
        mlp.reset_mlp_path_counts()
        row = dict(path=path, blocks=blocks,
                   fwd_ms=_ms(jax.jit(layer), (p, x), a.iters),
                   grad_ms=_ms(jax.jit(jax.grad(loss, argnums=(0, 1))), (p, x), a.iters),
                   counts=mlp.mlp_path_counts())
        rows.append(row)
        print(json.dumps(row), flush=True)
    ranked = sorted((r for r in rows if "ms" in r), key=lambda r: (r["kernel"], r["ms"]))
    print(json.dumps({"ranked": ranked}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
