"""A benchmark root at CPU size for the benchmark's own tests: the same
harness, jobs, reference and metric readers as ``perfbench/``, given a
``BENCHMARK.json`` whose configurations are the program's reduced
presets and whose mixes are a few tokens long."""
from __future__ import annotations

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (REPO, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CFG = {
    "name": "tiny-smollm2", "source": "repro.configs.smollm2_1p7b.REDUCED",
    "model_type": "llama", "hidden_act": "silu",
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 256,
    "vocab_size": 512, "rope_theta": 130000, "tie_word_embeddings": True,
    "attention_bias": False, "eos_token_id": 0, "rms_norm_eps": 1e-06,
    "reduced": [], "program": {"arch": "smollm2-1.7b", "reduced": True},
    "sct": {"spectral_mlp": True, "rank": 16, "retraction": "qr", "retract_every": 1},
    "precision": {"mode": "legacy", "train_compute": "bfloat16", "master": "float32",
                  "serve_weights": "bfloat16", "serve_compute": "bfloat16"},
    "optimizer": {"lr": 0.0005, "warmup": 100, "total_steps": 10000, "b1": 0.9,
                  "b2": 0.999, "eps": 1e-08, "weight_decay": 0.01, "clip_norm": 1.0,
                  "z_loss": 0.0001},
}

TINY_TRAIN = {"kind": "train", "batch": 4, "seq": 32,
              "doc_len": {"mean": 12, "cv": 1.0, "min": 2, "max": 64}, "checked_steps": 3}

TINY_SERVE = {"kind": "serve", "clients": 4, "slots": 4, "page_size": 16,
              "pages_per_seq": 8, "num_pages": 32, "prefill_budget": 32,
              "chunked_prefill": True,
              "prompt": {"mean": 40, "cv": 0.5, "min": 32, "max": 64, "multiple": 32},
              "output": {"mean": 12, "cv": 0.5, "min": 6, "max": 24},
              "pool_requests": 64, "size_seed": 7, "strata": 4, "check_tokens": 40,
              "check_max_requests": 4}

# limits of the tiny cells, set from CPU readings over 8 seeds: the
# program reads loss 1.0e-4..4.7e-4, grad 1.8e-3..2.2e-2, change
# 1.4e-3..2.9e-3 and logit 0..0.039; the fp8 control on 3 seeds reads
# loss 2.1e-3..4.7e-3, grad 3.6e-2..4.8e-2, change 1.3e-2..1.9e-2, and
# half of the batch left out loss 9.8e-3.., grad 1.0e-1.., change 4.7e-2..
TINY_LIMITS = {
    "tiny.train": {"loss_gap": 0.0015, "grad_gap": 0.03, "change_gap": 0.006},
    "tiny.serve": {"logit_gap": 0.3},
}


def make_root(path: str, extra_workloads=(), extra_metrics=()) -> str:
    """Write a benchmark root under ``path``; returns it."""
    pb = os.path.join(path, "perfbench")
    for sub in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(pb, sub), exist_ok=True)
    shutil.copytree(os.path.join(REPO, "perfbench", "metrics"), os.path.join(pb, "metrics"),
                    dirs_exist_ok=True)
    with open(os.path.join(pb, "configs", "tiny.json"), "w") as f:
        json.dump(TINY_CFG, f)
    for name, mix in (("tiny_train", TINY_TRAIN), ("tiny_serve", TINY_SERVE)):
        with open(os.path.join(pb, "traffic", name + ".json"), "w") as f:
            json.dump(mix, f)
    for name, lim in TINY_LIMITS.items():
        with open(os.path.join(pb, "limits", name + ".json"), "w") as f:
            json.dump(lim, f)
    bench = {
        "command": ["python3", "perfbench/run.py"], "paths": ["perfbench"],
        "run_seconds": 1,
        "configs": [{"name": "tiny", "source": "test", "file": "perfbench/configs/tiny.json",
                     "reduced": [], "why": "test"}],
        "workloads": [
            {"name": "tiny.train", "config": "tiny", "traffic": "tiny_train", "chips": 1,
             "why": "test"},
            {"name": "tiny.serve", "config": "tiny", "traffic": "tiny_serve", "chips": 1,
             "why": "test"},
            *extra_workloads,
        ],
        "end_to_end": [
            {"name": "train_tokens_per_s", "unit": "tokens/s", "better": "higher",
             "bound": 0.25, "source": "host_clock", "workloads": ["tiny.train"]},
            {"name": "serve_tokens_per_s", "unit": "tokens/s", "better": "higher",
             "bound": 0.25, "source": "host_clock", "workloads": ["tiny.serve"]},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
             "source": "host_clock"},
        ],
        "per_layer": [
            {"name": "decode_batch_occupancy", "unit": "slots", "better": "higher",
             "source": "program_counter", "layer": "serving engine",
             "moves": "serve_tokens_per_s", "workloads": ["tiny.serve"]},
            *extra_metrics,
        ],
    }
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return path
