#!/usr/bin/env python3
"""Time the fused causal attention kernel's block sizes on one TPU chip.

  PYTHONPATH=src python3 tools/attention_blocks.py [--batch 4 --seq 2048
      --heads 32 --kv-heads 32 --head-dim 64 --iters 20]

For each candidate layout of q, k and v and ``(block_q, block_kv,
block_kv_compute)``, with the fused and with the split backward
kernels, it times the forward alone
and the gradient (a forward that keeps its residuals, then the
backward), as the remat train step runs them, and the direct softmax
of ``nn/attention.py`` at the same shapes. One JSON line per candidate,
then the candidates ordered by forward plus gradient. The defaults are
the smollm2-1.7b training cell's shapes. Needs a TPU.
"""
from __future__ import annotations

import argparse
import itertools
import json
import time

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as splash

from repro.nn import attention as attn


def _seconds(fn, args, iters: int) -> float:
    jax.block_until_ready(fn(*args))           # compile and warm up
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def _timings(attend, q, k, v, iters, fwd=None):
    """Forward and gradient ms; ``fwd`` reuses a forward already timed
    (the forward kernel does not depend on the backward's choice)."""
    w = jnp.cos(jnp.arange(q.shape[-1], dtype=jnp.float32))
    loss = lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32) * w)
    if fwd is None:
        fwd = _seconds(jax.jit(attend), (q, k, v), iters) * 1e3
    grad = _seconds(jax.jit(jax.grad(loss, argnums=(0, 1, 2))), (q, k, v), iters) * 1e3
    return {"fwd_ms": fwd, "grad_ms": grad, "sum_ms": fwd + grad}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=32)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--block-q", type=int, nargs="+", default=[256, 512, 1024])
    ap.add_argument("--block-kv", type=int, nargs="+", default=[512, 1024, 2048])
    ap.add_argument("--block-kv-compute", type=int, nargs="+", default=[512, 1024, 2048])
    ap.add_argument("--bwd", nargs="+", choices=("fused", "split"), default=["fused", "split"])
    ap.add_argument("--layout", nargs="+", choices=("head_dim_minor", "seq_minor"),
                    default=["head_dim_minor", "seq_minor"],
                    help="physical layout of q, k and v in the kernel")
    a = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        raise SystemExit("attention_blocks: needs a TPU")
    b, s, h, kvh, d = a.batch, a.seq, a.heads, a.kv_heads, a.head_dim
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (b, s, h, d), jnp.bfloat16)
    k = jax.random.normal(keys[1], (b, s, kvh, d), jnp.bfloat16)
    v = jax.random.normal(keys[2], (b, s, kvh, d), jnp.bfloat16)

    def direct(q, k, v):
        qg = q.reshape(b, s, kvh, h // kvh, d)
        return attn._sdpa_direct(qg, k, v, causal=True).reshape(b, s, h, d)

    rows = [dict(path="direct", **_timings(direct, q, k, v, a.iters))]
    print(json.dumps(rows[-1]), flush=True)
    heads_first = lambda t: jnp.swapaxes(t, 1, 2)
    scale = 1.0 / d ** 0.5
    fwd_ms = {}
    for layout, bq, bkv, bkvc, bwd in itertools.product(
            a.layout, a.block_q, a.block_kv, a.block_kv_compute, a.bwd):
        if bkv % bkvc or s % bq or s % bkv:
            continue
        fused = bwd == "fused"
        split = {} if fused else {"block_q_dq": bq, "block_kv_dq": bkv}
        qkv = splash.QKVLayout[layout.upper()]
        sizes = splash.BlockSizes(
            block_q=bq, block_kv=bkv, block_kv_compute=bkvc, block_q_dkv=bq,
            block_kv_dkv=bkv, block_kv_dkv_compute=bkvc, use_fused_bwd_kernel=fused,
            q_layout=qkv, k_layout=qkv, v_layout=qkv, **split)
        row = dict(path="fused", layout=layout, block_q=bq, block_kv=bkv,
                   block_kv_compute=bkvc, bwd=bwd)
        try:
            kernel = attn._fused_kernel(h, s, False, sizes)

            def attend(q, k, v, kernel=kernel):
                out = jax.vmap(kernel)(heads_first(q * scale), heads_first(k), heads_first(v))
                return jnp.swapaxes(out, 1, 2)

            row.update(_timings(attend, q, k, v, a.iters, fwd_ms.get((layout, bq, bkv, bkvc))))
            fwd_ms[(layout, bq, bkv, bkvc)] = row["fwd_ms"]
        except Exception as e:  # noqa: BLE001 — a block the compiler refuses
            row["error"] = str(e).splitlines()[0][:200]
        rows.append(row)
        print(json.dumps(row), flush=True)
    ranked = sorted((r for r in rows if "sum_ms" in r), key=lambda r: r["sum_ms"])
    print(json.dumps({"ranked": ranked}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
