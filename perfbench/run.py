#!/usr/bin/env python3
"""One run of one benchmark cell, on the chip this process holds.

  python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the checkout's
root: the cell's entry under ``workloads``, its configuration file (the
``file`` of the entry under ``configs``), its traffic mix
(``perfbench/traffic/<traffic>.json``, whose ``kind`` picks the job in
``perfbench/jobs/``), the limits of its correctness check
(``perfbench/limits/<workload>.json``) and one reader per per-layer
metric (``perfbench/metrics/<metric>.py``, a ``read(ctx)`` that returns
a number, or None where it finds nothing to read).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics
with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` a ``breakdown``, and last ``check``: each
number the correctness check compared, with its limit. The same check
lines end standard error. A run that finds no TPU, fewer chips than the
cell asks for, or a device kind missing from ``perfbench/peaks.py``
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    def __init__(self, root: str, name: str):
        from perfbench import traffic

        with open(os.path.join(root, "BENCHMARK.json")) as f:
            bench = json.load(f)
        by_name = {w["name"]: w for w in bench["workloads"]}
        if name not in by_name:
            raise SystemExit(f"perfbench: no workload {name!r} in BENCHMARK.json; "
                             f"known: {sorted(by_name)}")
        self.root, self.name = root, name
        self.workload = by_name[name]
        self.chips = int(self.workload["chips"])
        entry = {c["name"]: c for c in bench["configs"]}[self.workload["config"]]
        with open(os.path.join(root, entry["file"])) as f:
            self.cfg = json.load(f)
        self.mix = traffic.load_mix(root, self.workload["traffic"])
        self.kind = self.mix["kind"]
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        moved = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if (name in m["workloads"] if "workloads" in m
                              else m["moves"] in moved)]
        path = os.path.join(root, "perfbench", "limits", name + ".json")
        self.limits = {}
        if os.path.exists(path):
            with open(path) as f:
                self.limits = json.load(f)
        self.trace_dir = os.path.join(root, "perfbench_out", "traces", name)


def metric_reader(root: str, name: str):
    path = os.path.join(root, "perfbench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("perfbench_metric_" + name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             device=None, devices=None, peaks=None) -> dict:
    """Drive one run; returns the result object (not yet printed)."""
    import jax

    from perfbench import device as device_mod

    counter = device_mod.CompileCounter()
    job = importlib.import_module(f"perfbench.jobs.{cell.kind}")
    out = job.run(cell, seed, seconds, trace, counter=counter,
                  devices=devices or jax.devices()[:cell.chips], peaks=peaks,
                  t_start=T_START)
    print(f"perfbench: compilations in window: {out['compiles_in_window']}",
          file=sys.stderr, flush=True)
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"]}
    dev = dict(device or {"platform": jax.devices()[0].platform,
                          "kind": jax.devices()[0].device_kind,
                          "count": len(jax.devices())})
    dev["memory_peak_bytes"] = out["memory_peak_bytes"]
    metrics = {}
    if not trace:
        for m in cell.end_to_end:
            if m["name"] in out["end_to_end"]:
                metrics[m["name"]] = {"value": out["end_to_end"][m["name"]], "unit": m["unit"]}
    else:
        ctx = dict(out["context"], cfg=cell.cfg, mix=cell.mix, peaks=peaks,
                   trace=out["trace"], chips=cell.chips)
        for m in cell.per_layer:
            value = metric_reader(cell.root, m["name"])(ctx)
            if value is not None and math.isfinite(value):
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        dev["busy_s"] = out["trace"]["busy_s"]
        dev["window_s"] = out["trace"]["window_s"]
    result["metrics"] = metrics
    result["device"] = dev
    if trace:
        from perfbench.trace import breakdown

        result["breakdown"] = breakdown(out["trace"])
    result["check"] = out["check"]
    return result


def print_result(result: dict) -> None:
    for name, c in result["check"].items():
        print(f"check {name}: {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="One run of one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("perfbench: --seed must be >= 0")
    for var in ("SCT_INTERPRET", "SCT_PAGED_KERNEL"):
        if var in os.environ:
            raise SystemExit(f"perfbench: {var} is set; the benchmark runs the "
                             f"compiled kernels")
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from perfbench import device as device_mod

    device_mod.keep_logs_in(ROOT)
    cell = Cell(ROOT, args.workload)
    device_mod.enable_cache()
    device, devices, peaks = device_mod.look_for_chip(cell.chips)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      device=device, devices=devices, peaks=peaks)
    print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
