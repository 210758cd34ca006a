"""The harness: cells found by name from files and entries alone, and
the refusals that keep a run off anything but the chip it asks for."""
import json
import os
import subprocess
import sys
import types

import pytest

from perfbench_tiny import REPO, make_root
from perfbench import device, run


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    """A later change adds a cell as new files and entries only."""
    root = make_root(str(tmp_path), extra_workloads=[
        {"name": "tiny2.serve_short", "config": "tiny2", "traffic": "tiny_short",
         "chips": 1, "why": "test"}], extra_metrics=[
        {"name": "requests_seen.serve", "unit": "requests", "better": "higher",
         "source": "program_counter", "layer": "serving engine",
         "moves": "serve_tokens_per_s", "workloads": ["tiny2.serve_short"]}])
    pb = os.path.join(root, "perfbench")
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["configs"].append({"name": "tiny2", "source": "test", "reduced": [],
                             "file": "perfbench/configs/tiny2.json", "why": "test"})
    bench["end_to_end"][1]["workloads"].append("tiny2.serve_short")
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))
    cfg = json.load(open(os.path.join(pb, "configs", "tiny.json")))
    json.dump(dict(cfg, name="tiny2"), open(os.path.join(pb, "configs", "tiny2.json"), "w"))
    mix = json.load(open(os.path.join(pb, "traffic", "tiny_serve.json")))
    json.dump(dict(mix, clients=2), open(os.path.join(pb, "traffic", "tiny_short.json"), "w"))
    with open(os.path.join(pb, "metrics", "requests_seen.serve.py"), "w") as f:
        f.write("def read(ctx):\n    return float(ctx['seen'])\n")

    cell = run.Cell(root, "tiny2.serve_short")
    assert cell.cfg["name"] == "tiny2" and cell.mix["clients"] == 2 and cell.kind == "serve"
    assert [m["name"] for m in cell.end_to_end] == ["serve_tokens_per_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["requests_seen.serve"]
    assert run.metric_reader(root, "requests_seen.serve")({"seen": 3}) == 3.0
    # the cells already there do not see the new metric
    assert "requests_seen.serve" not in [m["name"] for m in run.Cell(root, "tiny.serve").per_layer]


def test_metric_without_workloads_key_follows_its_end_to_end_metric(tmp_path):
    root = make_root(str(tmp_path), extra_metrics=[
        {"name": "decode_batch_occupancy", "unit": "slots", "better": "higher",
         "source": "program_counter", "layer": "serving engine",
         "moves": "train_tokens_per_s"}])
    assert [m["name"] for m in run.Cell(root, "tiny.train").per_layer] == \
        ["decode_batch_occupancy"]


def test_unknown_workload_is_refused(tmp_path):
    with pytest.raises(SystemExit):
        run.Cell(make_root(str(tmp_path)), "no.such.cell")


def _fake_devices(monkeypatch, platform, kind, n=1):
    import jax

    devs = [types.SimpleNamespace(platform=platform, device_kind=kind, id=i)
            for i in range(n)]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: devs)


def test_a_device_that_is_no_tpu_is_refused():
    with pytest.raises(SystemExit, match="no TPU"):
        device.look_for_chip(1)


def test_a_device_kind_missing_from_the_peaks_table_is_refused(monkeypatch):
    _fake_devices(monkeypatch, "tpu", "TPU v99")
    with pytest.raises(SystemExit, match="peaks"):
        device.look_for_chip(1)


def test_fewer_chips_than_the_cell_asks_for_are_refused(monkeypatch):
    _fake_devices(monkeypatch, "tpu", "TPU v5 lite", n=1)
    with pytest.raises(SystemExit, match="asks for 4"):
        device.look_for_chip(4)
    _fake_devices(monkeypatch, "tpu", "TPU v5 lite", n=4)
    record, devs, peaks = device.look_for_chip(4)
    assert record == {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}
    assert peaks["bf16_flops"] == 197e12


def test_benchmark_alone_exits_non_zero_and_prints_no_result(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files
    holds no program: the run fails before any result."""
    import shutil

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "perfbench/run.py", "--workload",
                           "smollm2-1.7b.train_packed_2k", "--seed", "1", "--seconds", "1",
                           "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode != 0
    assert "correct" not in proc.stdout


def test_result_line_puts_check_last(capsys):
    result = {"correct": True, "attempted": 3, "failed": 0, "metrics": {},
              "device": {"platform": "tpu"}, "check": {"logit_gap": {"value": 0.1,
                                                                     "limit": 0.5}}}
    run.print_result(result)
    out, err = capsys.readouterr()
    assert list(json.loads(out.strip().splitlines()[-1]))[-1] == "check"
    assert err.strip().splitlines()[-1] == "check logit_gap: 0.1 limit 0.5"


def test_every_per_layer_metric_of_the_benchmark_has_a_reader():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert callable(run.metric_reader(REPO, m["name"]))
    for w in bench["workloads"]:
        cell = run.Cell(REPO, w["name"])
        assert cell.per_layer and any(m["name"] == "setup_s" for m in cell.end_to_end)
        # every cell is judged: it has limits, on numbers its job reads
        assert cell.limits and set(cell.limits) <= (
            {"logit_gap"} if cell.kind == "serve" else {"loss_gap", "grad_gap", "change_gap"})
