#!/usr/bin/env python3
"""Chip smoke test: drive the main path once on a TPU at published width.

  python chip_smoke.py              # one chip: train, then paged serving
  python chip_smoke.py --four-chip  # four chips: the two multi-chip paths

One chip (the default):

  1. device — refuses anything but a TPU (no CPU fallback), and refuses
     the test-only switches ``SCT_INTERPRET`` / ``SCT_PAGED_KERNEL``;
  2. train  — ``Trainer(RunSpec(...)).fit()`` for 4 steps of
     SmolLM2-1.7B at full width (24 layers, d_model 2048, the spectral
     MLP at rank 128), batch 4 x 1024 tokens, into a fresh checkpoint
     directory: the README quickstart path. Every loss finite, no loop
     restart, U/V orthonormal after the last retraction;
  3. serve  — ``Server.from_checkpoint`` on that checkpoint (the serving
     geometry rides in its RunSpec): 4 paged slots, page 16, 32 pages
     per sequence, 4 requests of 64-256 prompt tokens and 32 new tokens.
     Each greedy output must equal the static-cache oracle
     (``static_greedy_reference``) token for token, and the compiled
     decode step must hold the paged Pallas kernel (``tpu_custom_call``).
     Serving and oracle both compute in fp32 (see ``SERVE_DTYPE``).

Four chips (``--four-chip``): only the paths that exist across chips,
each with what it is compared against:

  * serve — ``decode_mesh=4`` tensor-parallel paged decode of the same
    4 requests, against the single-device static oracle, token for token;
  * train — 2 steps on the Trainer's automatic (data, model) mesh over
    all 4 chips, against the same 2 steps jitted unsharded on chip 0;
    every chip must hold a share of the train state, none all of it.

One line per phase goes to stdout; the last line is exactly
``{"ok": true, "device": {...}}``. Any failure exits non-zero before
that line is printed. Weights are random, made from ``SEED``.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "smollm2-1.7b"
SEED = 0
PROMPT_LENS = (64, 128, 192, 256)
GEN = 32
# paper Table 2 reports < 2e-6; an fp32 QR of a 2048x128 / 8192x128
# factor leaves ~1e-5, while one bf16 pass anywhere in the retraction
# would leave ~1e-3
ORTHO_LIMIT = 1e-4
# The token-identity check holds in fp32, as in the repo's own oracle
# tests: in bf16 the paged and static programs round differently, and a
# one-ulp logit gap splits a near-tie of the greedy argmax (random
# weights give near-flat logits). fp32 matmuls on a TPU round their
# operands to bf16 unless asked for full precision, hence both knobs.
SERVE_DTYPE = "float32"
SERVE_MATMUL_PRECISION = "float32"
# sharded vs unsharded losses: bf16 activations (2^-8 relative rounding)
# reduced in a different order across chips
LOSS_RTOL = 1e-2


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def peak_bytes(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


def make_spec(ckpt_dir=None, *, train_steps=4):
    from repro.api import (CheckpointSpec, ModelSpec, RunSpec, ServeSpec,
                           TrainSpec)

    return RunSpec(
        model=ModelSpec(ARCH),
        train=TrainSpec(steps=train_steps, batch=4, seq=1024, seed=SEED),
        serve=ServeSpec(slots=4, page_size=16, num_pages=128,
                        pages_per_seq=32, gen=GEN),
        checkpoint=CheckpointSpec(directory=ckpt_dir),
    )


def prompts(vocab: int) -> list:
    import numpy as np

    rng = np.random.default_rng(SEED)
    return [rng.integers(0, vocab, size=(n,)).astype(np.int32)
            for n in PROMPT_LENS]


def train_phase(spec) -> dict:
    """Trainer.fit on a fresh directory; returns the phase record."""
    import numpy as np

    from repro.api import Trainer
    from repro.core.tree import max_orthogonality_error

    trainer = Trainer(spec)
    check(trainer.mesh is None, "one-chip training built a mesh")
    t0 = time.time()
    state = trainer.fit()
    wall = time.time() - t0
    loop = trainer.loop
    steps = int(np.asarray(state["step"]))
    check(steps == spec.train.steps and len(loop.losses) == steps,
          f"{len(loop.losses)} steps ran, state at step {steps}, "
          f"wanted {spec.train.steps}")
    check(all(math.isfinite(x) for x in loop.losses),
          f"non-finite loss {loop.losses}")
    check(loop.restarts == 0, f"{loop.restarts} loop restarts")
    ortho = float(max_orthogonality_error(state["params"]))
    check(ortho < ORTHO_LIMIT,
          f"orthogonality error {ortho} >= {ORTHO_LIMIT} after retraction")
    return {"steps": steps, "step_s": loop.step_seconds,
            "loss": loop.losses, "restarts": loop.restarts,
            "ortho_err": ortho, "fit_s": wall}


def serve_and_verify(server, reqs) -> dict:
    """Serve ``reqs`` through the paged engine and check every greedy
    output against the static-cache oracle, token for token, and that
    the compiled decode step calls the paged kernel."""
    import jax

    with jax.default_matmul_precision(SERVE_MATMUL_PRECISION):
        rec = _serve_and_verify(server, reqs)
        check(decode_has_kernel(server.engine),
              "no tpu_custom_call in the compiled decode step")
    return rec


def _serve_and_verify(server, reqs) -> dict:
    import numpy as np

    from repro.launch.serve import static_greedy_reference

    rids = [server.submit(p, GEN) for p in reqs]
    t0 = time.time()
    out = server.run()
    serve_s = time.time() - t0
    statuses = server.last_statuses
    check(all(statuses.get(r) == "finished" for r in rids),
          f"request statuses {statuses}")
    max_seq = server.spec.serve.paged_config().max_seq
    t0 = time.time()
    bad = []
    for rid, prompt in zip(rids, reqs):
        ref = static_greedy_reference(server.cfg, server.params, prompt,
                                      GEN, max_seq)
        if not np.array_equal(out[rid], ref):
            bad.append(rid)
            at, gap = first_divergence(server, prompt, ref, out[rid])
            print(f"request {rid}: MISMATCH at token {at}: oracle logit "
                  f"gap {gap} (oracle pick minus engine pick)\n  oracle "
                  f"{ref}\n  paged  {out[rid]}", file=sys.stderr)
    check(not bad, f"{len(bad)}/{len(rids)} requests differ from the "
                   f"static oracle")
    return {"requests": len(rids),
            "tokens_served": int(sum(len(out[r]) for r in rids)),
            "verify": f"{len(rids)}/{len(rids)} token-identical",
            "serve_s": serve_s, "oracle_s": time.time() - t0}


def first_divergence(server, prompt, ref, got):
    """Diagnosis of a mismatch: the first differing position, and the
    oracle's logit gap between its own token and the engine's there. A
    gap the size of bf16 rounding is a near-tie; a large gap is a bug."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.model import init_decode_state, prefill

    cfg = server.cfg
    at = int(np.argmax(ref != got))
    toks = jnp.asarray(np.concatenate([prompt, ref[:at]]))[None]
    state = init_decode_state(cfg, 1, toks.shape[1])
    logits, _ = jax.jit(lambda p, t, s: prefill(p, t, cfg, s))(
        server.params, toks, state)
    row = logits[0, -1].astype(jnp.float32)
    return at, float(row[int(ref[at])] - row[int(got[at])])


def decode_has_kernel(engine) -> bool:
    """Whether the engine's compiled decode step calls a Pallas kernel."""
    import jax
    import jax.numpy as jnp

    pcfg = engine.pcfg
    toks = jax.ShapeDtypeStruct((pcfg.max_slots, 1), jnp.int32)
    bt = jax.ShapeDtypeStruct((pcfg.max_slots, pcfg.max_pages_per_seq),
                              jnp.int32)
    sl = jax.ShapeDtypeStruct((pcfg.max_slots,), jnp.int32)
    compiled = engine._decode_fn.lower(engine.params, toks, engine.state,
                                       bt, sl).compile()
    return "tpu_custom_call" in compiled.as_text()


def one_chip(devices) -> None:
    from repro.api import Server

    ckpt_dir = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    try:
        spec = make_spec(ckpt_dir)
        rec = train_phase(spec)
        gc.collect()                  # the trainer's device state
        print(f"train: arch={ARCH} batch={spec.train.batch} "
              f"seq={spec.train.seq} steps={rec['steps']} "
              f"step_s={rec['step_s']} loss={rec['loss']} "
              f"restarts={rec['restarts']} ortho_err={rec['ortho_err']} "
              f"fit_s={rec['fit_s']:.1f} "
              f"peak_bytes_in_use={peak_bytes(devices)}", flush=True)

        t0 = time.time()
        server = Server.from_checkpoint(ckpt_dir,
                                        **{"model.dtype": SERVE_DTYPE})
        load_s = time.time() - t0
        check(server.checkpoint_step == spec.train.steps,
              f"served checkpoint step {server.checkpoint_step}")
        rec = serve_and_verify(server, prompts(server.cfg.vocab))
        sv = server.spec.serve
        print(f"serve: dtype={SERVE_DTYPE} slots={sv.slots} page={sv.page_size} "
              f"pages_per_seq={sv.pages_per_seq} prompt_lens="
              f"{list(PROMPT_LENS)} gen={GEN} requests={rec['requests']} "
              f"tokens_served={rec['tokens_served']} verify={rec['verify']} "
              f"decode_kernel=tpu_custom_call load_s={load_s:.1f} "
              f"serve_s={rec['serve_s']:.1f} oracle_s={rec['oracle_s']:.1f} "
              f"peak_bytes_in_use={peak_bytes(devices)}", flush=True)
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)


def four_chip(devices) -> None:
    import jax

    from repro.api import Server, Trainer
    from repro.data.synthetic import SyntheticLMDataset
    from repro.launch import steps as steps_mod
    from repro.models.model import init_model
    from repro.optim import make_sct_optimizer

    n = len(devices)
    spec = make_spec(train_steps=2)
    cfg = spec.model.config()

    # tensor-parallel paged decode first: the Trainer below installs its
    # training mesh process-wide, and serving runs on its own 1-D mesh
    params = init_model(jax.random.PRNGKey(SEED), cfg)
    server = Server(spec.replace(**{"sharding.decode_mesh": n,
                                    "model.dtype": SERVE_DTYPE}), params)
    check(server.engine.tp == n, f"serving tp={server.engine.tp}")
    rec = serve_and_verify(server, prompts(cfg.vocab))
    print(f"serve_tp: dtype={SERVE_DTYPE} decode_mesh={n} requests={rec['requests']} "
          f"tokens_served={rec['tokens_served']} verify={rec['verify']} "
          f"decode_kernel=tpu_custom_call serve_s={rec['serve_s']:.1f} "
          f"oracle_s={rec['oracle_s']:.1f} "
          f"peak_bytes_in_use={peak_bytes(devices)}", flush=True)
    del server, params
    gc.collect()                      # free the replicated weights

    # unsharded reference: the Trainer's own pieces, jitted on chip 0
    t = spec.train
    opt = make_sct_optimizer(cfg, lr=t.lr, warmup=t.warmup_steps,
                             total_steps=t.steps,
                             precision=spec.precision.mode)
    step_fn = jax.jit(steps_mod.make_train_step(cfg, opt),
                      donate_argnums=(0,))
    data = SyntheticLMDataset(vocab=cfg.vocab, seq_len=t.seq, seed=t.seed)
    state = opt.init(init_model(jax.random.PRNGKey(t.seed), cfg))
    ref = []
    for i in range(t.steps):
        tok, lab = data.batch(i, t.batch)
        state, m = step_fn(state, {"tokens": tok, "labels": lab})
        ref.append(float(m["loss"]))
    del state
    gc.collect()

    trainer = Trainer(spec)
    check(trainer.mesh is not None and trainer.mesh.size == n,
          f"Trainer mesh {trainer.mesh}")
    got = [float(trainer.step()["loss"]) for _ in range(t.steps)]
    err = [abs(a - b) / abs(b) for a, b in zip(got, ref)]
    check(all(math.isfinite(x) for x in got + ref), f"losses {got} {ref}")
    check(max(err) <= LOSS_RTOL,
          f"sharded losses {got} vs unsharded {ref}: rel err {err}")
    held = state_bytes(trainer.state, devices)
    check(min(held) > 0 and max(held) < sum(held),
          f"train state bytes per chip {held}: not spread over the mesh")
    print(f"train_mesh: mesh={dict(trainer.mesh.shape)} steps={t.steps} "
          f"batch={t.batch} seq={t.seq} loss={got} unsharded_loss={ref} "
          f"rel_err={err} rtol={LOSS_RTOL} state_bytes={held} "
          f"peak_bytes_in_use={peak_bytes(devices)}", flush=True)


def state_bytes(tree, devices) -> list:
    """Bytes of ``tree``'s shards held on each of ``devices``."""
    import jax

    held = {d.id: 0 for d in devices}
    for x in jax.tree.leaves(tree):
        for shard in x.addressable_shards:
            held[shard.device.id] += shard.data.nbytes
    return [held[d.id] for d in devices]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the multi-chip paths, on 4 chips")
    args = ap.parse_args(argv)
    for var in ("SCT_INTERPRET", "SCT_PAGED_KERNEL"):
        if var in os.environ:
            fail(f"{var} is set: it is a test-only switch, and the smoke "
                 f"must run the compiled kernels")

    import jax

    from repro.launch.cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        fail(f"no TPU: JAX's first device is {d0.platform} ({d0.device_kind})")
    want = 4 if args.four_chip else 1
    check(len(devices) == want,
          f"{len(devices)} devices visible, this path runs on {want}")
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devices)} compile_cache={cache_dir}", flush=True)

    (four_chip if args.four_chip else one_chip)(devices)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
