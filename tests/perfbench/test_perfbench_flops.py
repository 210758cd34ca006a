"""The benchmark's operation and byte counts against hand counts."""
import json
import os

import pytest

from perfbench_tiny import REPO
from perfbench import flops
from perfbench.peaks import PEAKS, peaks_for


def _cfg(name):
    return json.load(open(os.path.join(REPO, "perfbench", "configs", name + ".json")))


def test_smollm2_matmul_params_by_hand():
    # per layer: q, k, v, o at 2048 x 2048 = 4 * 4,194,304; three spectral
    # MLP projections at rank 128 over 2048 + 8192 = 3 * 1,310,720
    per_layer = 4 * 2048 * 2048 + 3 * 128 * (2048 + 8192)
    assert flops.matmul_params_per_layer(_cfg("smollm2-1.7b")) == per_layer
    assert flops.matmul_params(_cfg("smollm2-1.7b")) == 24 * per_layer + 49152 * 2048


def test_granite_gqa_params_by_hand():
    # q and o 2048 x 2048, k and v 2048 x (8 heads * 64)
    per_layer = 2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 128 * (2048 + 8192)
    assert flops.matmul_params_per_layer(_cfg("granite-3-2b")) == per_layer


def test_train_flops_per_token_smollm2():
    n = 24 * (4 * 2048 * 2048 + 3 * 128 * 10240) + 49152 * 2048
    want = 6 * n + 12 * 24 * 2048 * 2048
    got = flops.train_flops_per_token(_cfg("smollm2-1.7b"), 2048)
    assert got == pytest.approx(want)
    assert 4.7e9 < got < 4.9e9                      # about 4.8 GFLOP a token


def test_prompt_flops_is_the_sum_of_its_tokens():
    cfg = _cfg("granite-3-2b")
    total = sum(flops.forward_flops(cfg, ctx) for ctx in range(257, 513))
    assert flops.prompt_flops(cfg, 256, 256) == pytest.approx(total)


def test_paged_decode_bytes_by_hand():
    cfg = _cfg("smollm2-1.7b")
    f, b = flops.paged_decode_terms(cfg, [100, 300])
    # K and V: 400 positions * 32 kv heads * 64 * 2 bytes * 2, q and out:
    # 2 slots * 32 heads * 64 * 2 bytes * 2, over 24 layers
    assert b == 24 * (400 * 32 * 64 * 2 * 2 + 2 * 32 * 64 * 2 * 2)
    assert f == 24 * 4 * 32 * 64 * 400
    gf, gb = flops.paged_decode_terms(_cfg("granite-3-2b"), [100])
    assert gb == 40 * (100 * 8 * 64 * 2 * 2 + 32 * 64 * 2 * 2)


def test_peaks_table_has_its_source_and_refuses_unknown_kinds():
    v5e = peaks_for("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert all(p["source"] for p in PEAKS.values())
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
