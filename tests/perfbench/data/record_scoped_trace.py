"""Record ``scoped_chip_trace.xplane.pb`` and ``scoped_chip_step.hlo.txt.gz``
beside this file: two traced ``Trainer.step`` calls of a two-layer,
scanned, remat'd SmolLM2-1.7B train step (published widths, spectral
MLP at rank 128, QR retraction; batch 2 x 512) on one TPU chip, inside
a ``perfbench.window`` span, and the compiled step's HLO text.

  PYTHONPATH=src python tests/perfbench/data/record_scoped_trace.py [--out DIR]

Needs a TPU; writes to ``--out`` (default: beside this file).
"""
from __future__ import annotations

import argparse
import gzip
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
for p in (REPO, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

STEPS_TRACED = 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=HERE)
    args = ap.parse_args(argv)

    import jax

    from perfbench import trace
    from repro.api import ModelSpec, PrecisionSpec, RunSpec, Trainer, TrainSpec
    from repro.configs import smollm2_1p7b

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_scoped_trace: needs a TPU")
    # the published widths, two layers: the reduced preset's depth
    smollm2_1p7b.REDUCED = smollm2_1p7b.CONFIG.replace(n_layers=2)
    spec = RunSpec(model=ModelSpec(arch="smollm2-1.7b", reduced=True, dtype="bfloat16"),
                   train=TrainSpec(steps=100, batch=2, seq=512, lr=5e-4, warmup=10, seed=0),
                   precision=PrecisionSpec(mode="legacy"))
    trainer = Trainer(spec)
    assert trainer.cfg.remat and trainer.cfg.sct.spectral_mlp and trainer.cfg.n_layers == 2
    for _ in range(2):                              # compile and warm up
        float(trainer.step()["loss"])
    log_dir = tempfile.mkdtemp()
    try:
        with trace.capture(log_dir), jax.profiler.TraceAnnotation(trace.WINDOW):
            for _ in range(STEPS_TRACED):
                float(trainer.step()["loss"])
        os.makedirs(args.out, exist_ok=True)
        shutil.copy(trace.find_xplane(log_dir),
                    os.path.join(args.out, "scoped_chip_trace.xplane.pb"))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    text = trainer._step_fn.lower(trainer.state, trainer.make_batch(0)).compile().as_text()
    with gzip.open(os.path.join(args.out, "scoped_chip_step.hlo.txt.gz"), "wt") as f:
        f.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
