#!/usr/bin/env python3
"""Readings that set the limits of a cell's correctness check: the
control and the planted faults, next to the program's own readings.
The benchmark's runs never run this.

  python3 perfbench/control.py --workload <name> --seeds 1,...,12 --control-seeds 1,2,3

Training cells, per seed: the reference at float32 against itself put
in the program's place at the precision below the configuration's
(``lowp="fp8"``), and with half of every batch left out (the mean over
the rest). A step that returns its state unchanged reads 1 on
``change_gap`` by construction and is not run.

Serving cells, per seed: one run of the cell (its program readings),
the fp8 control's reading over the same prompts and served tokens (the
gap of the token the control puts first at each position), and one run
with one served token altered where the scheduler records it.

One JSON line per seed and reading goes to standard output.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def train_control(cell, seed: int, trainer=None, control: bool = True) -> dict:
    """Readings of one seed against the float32 reference: with
    ``trainer`` (the cell's program, built once) the program's own, and
    with ``control`` the fp8 control's and the half-batch fault's."""
    import gc

    from perfbench import check, reference, traffic

    cfg, mix = cell.cfg, cell.mix
    table = traffic.bigram_table(cfg["vocab_size"], seed)
    batches = [traffic.packed_batch(mix, cfg, table, seed, s)
               for s in range(mix["checked_steps"])]
    prog = None
    if trainer is not None:
        from perfbench.jobs.train import checked_steps

        prog = checked_steps(trainer, cell, seed, table)
        trainer._state = None
        gc.collect()
    ref = reference.Trainer(cfg, seed).run(batches)
    out = {}
    runs = [("program", None)] if prog is not None else []
    if control:
        runs += [("control_fp8", {"lowp": "fp8"}),
                 ("half_batch", {"rows": mix["batch"] // 2})]
    for name, kw in runs:
        got = prog if kw is None else reference.Trainer(cfg, seed, **kw).run(batches)
        r = check.train_readings(got, ref)
        out[name] = {k: v for k, v in r.items() if not k.startswith("_")}
    return out


def altered_token(scheduler_cls, every: int = 7):
    """Patch ``scheduler_cls.on_token`` so that every ``every``-th token
    it records is replaced by its successor id; returns the undo."""
    orig = scheduler_cls.on_token
    count = [0]

    def on_token(self, slot, token):
        count[0] += 1
        if count[0] % every == 0:
            token = token + 1
        return orig(self, slot, token)

    scheduler_cls.on_token = on_token
    return lambda: setattr(scheduler_cls, "on_token", orig)


def serve_control(cell, seed: int, seconds: float, run_cell, control: bool = True) -> dict:
    import numpy as np

    from perfbench import reference
    from repro.serving.scheduler import ContinuousBatchingScheduler

    res = run_cell(cell, seed, seconds)
    out = {"program": res["check"]["logit_gap"]["value"]}
    if not control:
        return out
    scorer = reference.Scorer(cell.cfg, seed,
                              cell.mix["page_size"] * cell.mix["pages_per_seq"])
    out["control_fp8"] = max(float(np.max(scorer.gaps(p, t, control=True)))
                             for _, p, t in res["sample"])
    del scorer
    undo = altered_token(ContinuousBatchingScheduler)
    try:
        bad = run_cell(cell, seed, seconds)
    finally:
        undo()
    out["altered_token"] = bad["check"]["logit_gap"]["value"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="seeds to read the program on (training: checked steps only)")
    ap.add_argument("--control-seeds", default="",
                    help="seeds on which the control and the faults are read too")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--write-limits", action="store_true",
                    help="set the cell's limits from these readings (check.set_limits)")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from perfbench import device as device_mod
    from perfbench import run

    device_mod.keep_logs_in(ROOT)
    cell = run.Cell(ROOT, args.workload)
    device_mod.enable_cache()
    device, devices, peaks = device_mod.look_for_chip(cell.chips)

    def run_cell(c, seed, seconds):
        import importlib

        job = importlib.import_module(f"perfbench.jobs.{c.kind}")
        return job.run(c, seed, seconds, False, counter=device_mod.CompileCounter(),
                       devices=devices, peaks=peaks, t_start=run.T_START)

    trainer = None
    if cell.kind == "train":
        from perfbench.jobs.train import build_trainer

        trainer = build_trainer(cell, 0)
    controls = {int(x) for x in args.control_seeds.split(",") if x}
    rows = []
    for seed in (int(x) for x in args.seeds.split(",")):
        if cell.kind == "train":
            out = train_control(cell, seed, trainer, seed in controls)
        else:
            out = serve_control(cell, seed, args.seconds, run_cell, seed in controls)
        rows.append(out)
        print(json.dumps({"workload": cell.name, "seed": seed, "device": device["kind"],
                          **out}), flush=True)
    if args.write_limits:
        write_limits(cell, rows)
    return 0


def write_limits(cell, rows) -> None:
    from perfbench import check

    as_dict = (lambda v: v) if cell.kind == "train" else (lambda v: {"logit_gap": v})
    program = [as_dict(r["program"]) for r in rows]
    control = [as_dict(r["control_fp8"]) for r in rows if "control_fp8" in r]
    fault_names = ("half_batch",) if cell.kind == "train" else ("altered_token",)
    faults = {f: [as_dict(r[f]) for r in rows if f in r] for f in fault_names}
    if cell.kind == "train":
        faults["state_unchanged"] = [{"loss_gap": 0.0, "grad_gap": 0.0, "change_gap": 1.0}]
    limits, notes = check.set_limits(program, control, faults)
    print(json.dumps({"workload": cell.name, "limits": limits, "from": notes}), flush=True)
    path = os.path.join(cell.root, "perfbench", "limits", cell.name + ".json")
    with open(path, "w") as f:
        json.dump(limits, f, indent=2)
        f.write("\n")


if __name__ == "__main__":
    sys.exit(main())
