"""Training cells: ``Trainer.step(batch)`` (``api/trainer.py``) back to
back on packed bigram batches, reading each step's loss to the host as
``TrainLoop`` does, so the per-step sync users pay is in the window.

Set-up builds one Trainer, gives it a train state made on the device in
one jitted call from the seed, and drives it through the checked steps
(the window's own call and feed, on batches 0..n-1 of the seed). The
window then runs batches n, n+1, ... on that same Trainer. Once the
window has closed and memory has been read, the program's state is
dropped and the reference follows the checked steps for the check.
"""
from __future__ import annotations

import gc
import inspect
import math
import sys
import time

from perfbench import check as check_mod
from perfbench import reference, traffic
from perfbench.device import memory_peak

TRACE_FIRST_STEP = 1     # the traced stretch skips the window's first step
TRACE_STEPS = 4


def program_spec(cfg: dict, mix: dict, seed: int):
    from repro.api import ModelSpec, PrecisionSpec, RunSpec, TrainSpec

    opt, prog = cfg["optimizer"], cfg["program"]
    return RunSpec(
        model=ModelSpec(arch=prog["arch"], reduced=prog["reduced"], rank=cfg["sct"]["rank"],
                        spectral_mlp=cfg["sct"]["spectral_mlp"],
                        dtype=cfg["precision"]["train_compute"]),
        train=TrainSpec(steps=opt["total_steps"], batch=mix["batch"], seq=mix["seq"],
                        lr=opt["lr"], warmup=opt["warmup"], seed=seed & 0x7FFFFFFF),
        precision=PrecisionSpec(mode=cfg["precision"]["mode"]),
    )


def _mismatch(have: dict, want: dict) -> None:
    off = {k: (have[k], want[k]) for k in want if have[k] != want[k]}
    if off:
        raise SystemExit(f"perfbench: the program departs from the configuration file "
                         f"(program, file): {off}")


def verify_model(c, cfg: dict) -> None:
    """Refuse to run a model that departs from the configuration file:
    the file states the configuration as it is run."""
    from repro.models.lm import cross_entropy
    from repro.nn.norms import apply_rmsnorm

    sct = cfg["sct"]
    want = {
        "n_layers": cfg["num_hidden_layers"], "d_model": cfg["hidden_size"],
        "n_heads": cfg["num_attention_heads"], "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"], "d_ff": cfg["intermediate_size"],
        "vocab": cfg["vocab_size"], "rope_theta": float(cfg["rope_theta"]),
        "tie_embeddings": cfg["tie_word_embeddings"], "qkv_bias": cfg["attention_bias"],
        "family": "dense_lm", "act": "swiglu", "norm": "rmsnorm", "rope": "rope",
        "sct.rank": sct["rank"], "sct.spectral_mlp": sct["spectral_mlp"],
        "sct.retraction": sct["retraction"], "sct.retract_every": sct["retract_every"],
        "z_loss": cfg["optimizer"]["z_loss"], "rms_norm_eps": cfg["rms_norm_eps"],
    }
    have = {
        "n_layers": c.n_layers, "d_model": c.d_model, "n_heads": c.n_heads,
        "n_kv_heads": c.n_kv_heads, "head_dim": c.head_dim, "d_ff": c.d_ff,
        "vocab": c.vocab, "rope_theta": float(c.rope_theta),
        "tie_embeddings": c.tie_embeddings, "qkv_bias": c.qkv_bias,
        "family": c.family, "act": c.act, "norm": c.norm, "rope": c.rope,
        "sct.rank": c.sct.rank, "sct.spectral_mlp": c.sct.spectral_mlp,
        "sct.retraction": c.sct.retraction, "sct.retract_every": c.sct.retract_every,
        "z_loss": inspect.signature(cross_entropy).parameters["z_loss"].default,
        "rms_norm_eps": inspect.signature(apply_rmsnorm).parameters["eps"].default,
    }
    _mismatch(have, want)


def verify_optimizer(o, cfg: dict) -> None:
    opt = cfg["optimizer"]
    _mismatch({"b1": o.adamw.b1, "b2": o.adamw.b2, "eps": o.adamw.eps,
               "weight_decay": o.adamw.weight_decay, "clip_norm": o.clip_norm,
               "decay_spectral": o.adamw.decay_spectral, "schedule": o.schedule.kind,
               "final_fraction": o.schedule.final_fraction},
              {"b1": opt["b1"], "b2": opt["b2"], "eps": opt["eps"],
               "weight_decay": opt["weight_decay"], "clip_norm": opt["clip_norm"],
               "decay_spectral": False, "schedule": "cosine", "final_fraction": 0.1})


def build_trainer(cell, seed: int):
    """The Trainer of the cell, checked against the configuration file,
    with no state yet."""
    from repro.api import Trainer

    trainer = Trainer(program_spec(cell.cfg, cell.mix, seed))
    verify_model(trainer.cfg, cell.cfg)
    verify_optimizer(trainer.optimizer, cell.cfg)
    return trainer


def checked_steps(trainer, cell, seed: int, table) -> dict:
    """Give ``trainer`` a train state born on the device from ``seed``
    in one program, drive it through the checked steps (its own call
    and feed, on the seed's batches 0..n-1) and return what the check
    compares: each step's loss, the norms of the first gradient as the
    optimizer got it (Adam's first moment over ``1 - b1``), and of each
    parameter's change over the steps."""
    import jax
    import jax.numpy as jnp

    from repro.models.model import init_model

    cfg, mix = cell.cfg, cell.mix
    key = reference.seed_key(seed)
    trainer._state = None
    # the key is an argument, not a constant: one program serves every seed
    trainer._state = jax.jit(lambda k: trainer.optimizer.init(init_model(k, trainer.cfg)))(key)
    trainer._step = 0
    b1 = trainer.optimizer.adamw.b1
    norms = jax.jit(reference.leaf_norms)
    diff = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
    p0 = jax.device_get(trainer.params)
    prog = {"losses": []}
    for step in range(mix["checked_steps"]):
        t, l = traffic.packed_batch(mix, cfg, table, seed, step)
        metrics = trainer.step({"tokens": jnp.asarray(t), "labels": jnp.asarray(l)})
        prog["losses"].append(float(metrics["loss"]))
        if step == 0:
            mu = trainer.state["opt"]["mu"]
            prog["grad_norms"] = {k: float(v) / (1.0 - b1) for k, v in norms(mu).items()}
    flat, _ = jax.tree_util.tree_flatten_with_path(trainer.params)
    prog["change_norms"] = {jax.tree_util.keystr(p): float(diff(x, jnp.asarray(x0)))
                            for (p, x), x0 in zip(flat, jax.tree.leaves(p0))}
    return prog


def run(cell, seed, seconds, trace, *, counter, devices, peaks, t_start):
    import jax
    import jax.numpy as jnp

    cfg, mix = cell.cfg, cell.mix
    n_checked = mix["checked_steps"]
    tokens_per_step = mix["batch"] * mix["seq"]
    trainer = build_trainer(cell, seed)
    table = traffic.bigram_table(cfg["vocab_size"], seed)
    prog = checked_steps(trainer, cell, seed, table)
    print(f"perfbench: set-up {time.perf_counter() - t_start:.1f} s after "
          f"{n_checked} checked steps", file=sys.stderr, flush=True)

    def feed(step):
        t, l = traffic.packed_batch(mix, cfg, table, seed, step)
        return {"tokens": jnp.asarray(t), "labels": jnp.asarray(l)}

    # ---- window ----
    from perfbench.trace import WINDOW, capture

    step = n_checked
    batch = feed(step)
    jax.block_until_ready(batch)
    setup_s = time.perf_counter() - t_start
    losses = []
    trace_ctx = capture(cell.trace_dir) if trace else None
    ann = None
    counter.armed = True
    t0 = time.perf_counter()
    t_end = t0
    while True:
        i = step - n_checked
        if trace_ctx is not None and i == TRACE_FIRST_STEP:
            trace_ctx.__enter__()
            ann = jax.profiler.TraceAnnotation(WINDOW)
            ann.__enter__()
        with jax.profiler.TraceAnnotation("perfbench.step"):
            metrics = trainer.step(batch)
        step += 1
        with jax.profiler.TraceAnnotation("perfbench.batch"):
            batch = feed(step)
        with jax.profiler.TraceAnnotation("perfbench.loss_sync"):
            losses.append(float(metrics["loss"]))
        t_end = time.perf_counter()
        if ann is not None and i == TRACE_FIRST_STEP + TRACE_STEPS - 1:
            ann.__exit__(None, None, None)
            trace_ctx.__exit__(None, None, None)
            ann = trace_ctx = None
        if t_end - t0 >= seconds and (not trace or ann is None and i >= TRACE_FIRST_STEP):
            break
    counter.armed = False
    window_s = t_end - t0
    n_steps = len(losses)
    mem = memory_peak(devices)
    failed = sum(1 for x in losses if not math.isfinite(x))

    trace_summary = None
    if trace:
        from perfbench.trace import find_xplane, reduce

        trace_summary = reduce(find_xplane(cell.trace_dir), len(devices))

    # ---- the check: the program's state goes, the reference follows ----
    trainer = metrics = batch = None
    gc.collect()
    t_ref = time.perf_counter()
    ref_trainer = reference.Trainer(cfg, seed)
    ref = ref_trainer.run(
        [traffic.packed_batch(mix, cfg, table, seed, s) for s in range(n_checked)])
    readings = check_mod.train_readings(prog, ref)
    print(f"perfbench: reference {time.perf_counter() - t_ref:.1f} s "
          f"{ {k: round(v, 2) for k, v in ref_trainer.seconds.items()} }; program losses "
          f"{prog['losses']} reference {ref['losses']}; worst leaves {readings['_at']}",
          file=sys.stderr, flush=True)
    correct, check = check_mod.judge(readings, cell.limits)
    return {
        "correct": correct and failed == 0, "attempted": n_steps, "failed": failed,
        "end_to_end": {"train_tokens_per_s": n_steps * tokens_per_step / window_s,
                       "train_peak_hbm_bytes": mem, "setup_s": setup_s},
        "memory_peak_bytes": mem, "check": check,
        "compiles_in_window": counter.count, "trace": trace_summary, "readings": readings,
        "context": {"tokens_per_step": tokens_per_step, "steps_traced": TRACE_STEPS,
                    "seq": mix["seq"], "window_steps": n_steps, "window_s": window_s},
    }
