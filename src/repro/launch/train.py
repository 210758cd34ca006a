"""Training launcher — a thin argparse adapter over the experiment API
(api/specs.py + api/trainer.py). Every flag maps onto a RunSpec field;
the Trainer facade owns the wiring (mesh, optimizer, rank controller,
fault-tolerant loop), so this file is only flag parsing and end-of-run
printing.

  PYTHONPATH=src python -m repro.launch.train --arch smollm2-1.7b \\
      --reduced --steps 200 --batch 8 --seq 64 --ckpt-dir /tmp/run1

(equivalently: ``python -m repro train ...``). ``--dump-spec`` prints
the resolved RunSpec JSON and exits — the declarative record of what
the flags mean, replayable programmatically via ``RunSpec.from_json``.

Runs on whatever devices exist (1 CPU here; the production mesh on a
real slice) with the same code path the dry-run proves at 512 devices:
sharded state, jitted train_step with donation, fault-tolerant loop.
"""
from __future__ import annotations

import argparse
import tempfile
from typing import Optional, Sequence

from repro.api import (
    CheckpointSpec,
    ModelSpec,
    PrecisionSpec,
    RankScheduleSpec,
    RunSpec,
    Trainer,
    TrainSpec,
    log_metrics,
)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm2-1.7b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-test-sized config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--precision",
                    choices=["legacy", "fp32", "bf16", "mixed"],
                    default="legacy",
                    help="legacy: compute in the config dtype, no scaling "
                         "(the default, now an explicit mode); fp32: "
                         "everything fp32; bf16: bf16 factors+compute; "
                         "mixed: fp32 master factors, bf16 compute, dynamic "
                         "loss scaling with overflow skip")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--rank-schedule", default=None,
                    help="adaptive spectral rank schedule: 'static:K' "
                         "(resize once, incl. on restore), "
                         "'step:S1=K1[,S2=K2...]' (step-triggered), or "
                         "'energy:T[,min=..][,max=..][,every=..][,factor=..]"
                         "[,grow_below=..]' (telemetry-triggered on the "
                         "rank/energy_top metric). See src/repro/rank/.")
    ap.add_argument("--telemetry", action="store_true",
                    help="emit spectral telemetry (rank/* metrics) in the "
                         "train log even without a rank schedule")
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint directory; one that already holds a "
                         "checkpoint is resumed from (default: a fresh "
                         "temporary directory, so a rerun starts over)")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dump-spec", action="store_true",
                    help="print the resolved RunSpec JSON and exit")
    return ap


def build_spec(args: argparse.Namespace) -> RunSpec:
    """argparse Namespace -> RunSpec: the whole adapter."""
    return RunSpec(
        model=ModelSpec(arch=args.arch, reduced=args.reduced),
        train=TrainSpec(steps=args.steps, batch=args.batch, seq=args.seq,
                        lr=args.lr, microbatches=args.microbatches,
                        seed=args.seed, telemetry=args.telemetry),
        precision=PrecisionSpec(mode=args.precision or "legacy"),
        rank=RankScheduleSpec(schedule=args.rank_schedule),
        checkpoint=CheckpointSpec(directory=args.ckpt_dir,
                                  every=args.ckpt_every),
    )


def main(argv: Optional[Sequence[str]] = None) -> None:
    args = build_parser().parse_args(argv)
    if args.dump_spec:
        print(build_spec(args).to_json(indent=2))
        return
    if args.ckpt_dir is None:
        args.ckpt_dir = tempfile.mkdtemp(prefix="repro_ckpt_")
        print(f"checkpoints: {args.ckpt_dir}")
    spec = build_spec(args)

    trainer = Trainer(spec, metrics_cb=log_metrics)
    state = trainer.fit()

    if trainer.controller is not None:
        for at, old, new in trainer.controller.resizes:
            print(f"rank resize @ step {at}: {old} -> {new}")
    from repro.core.tree import max_orthogonality_error

    print("final ortho error:", float(max_orthogonality_error(state["params"])))
    if "loss_scale" in state:
        print(f"loss scale: {float(state['loss_scale']['scale']):.0f}  "
              f"overflow-skipped steps: {int(state['loss_scale']['skipped'])} "
              f"(loop saw {trainer.loop.overflow_steps})")


if __name__ == "__main__":
    main()
