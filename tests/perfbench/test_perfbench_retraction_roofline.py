"""``retraction_roofline.train``: the retraction's bytes against hand
counts, and the reader over a scoped stretch and without one."""
import json
import os

import pytest

from perfbench_tiny import REPO
from perfbench import run
from perfbench.peaks import peaks_for
from perfbench.retraction_bytes import retraction_bytes


def _cfg(name):
    return json.load(open(os.path.join(REPO, "perfbench", "configs", name + ".json")))


@pytest.mark.parametrize("name, layers, want", [("granite-3-2b", 40, 1_258_291_200),
                                                ("smollm2-1.7b", 24, 754_974_720)])
def test_retraction_bytes_by_hand(name, layers, want):
    # U (2048 x 128) and V (8192 x 128) of up, gate and down, read and
    # written once in fp32
    assert 2 * 4 * layers * 3 * (2048 + 8192) * 128 == want
    assert retraction_bytes(_cfg(name)) == want


def test_a_dense_mlp_has_nothing_to_retract():
    cfg = _cfg("smollm2-1.7b")
    assert retraction_bytes(dict(cfg, sct=dict(cfg["sct"], spectral_mlp=False))) == 0


def _ctx(scope_s):
    return {"cfg": _cfg("granite-3-2b"), "peaks": peaks_for("TPU v5 lite"),
            "steps_traced": 4, "trace": {"window_s": 1.0},
            "scoped_trace": None if scope_s is None else {"scope_s": scope_s}}


def test_reader_is_the_least_time_over_the_scope_time():
    read = run.metric_reader(REPO, "retraction_roofline.train")
    least_s = 1_258_291_200 / 819e9
    # four traced steps of 20 ms each in the scope
    got = read(_ctx({"sct.retraction": 0.08, "sct.mlp": 0.3}))
    assert got == pytest.approx(100.0 * least_s / 0.02)


@pytest.mark.parametrize("scope_s", [None, {"sct.mlp": 0.3}])
def test_reader_finds_nothing_without_the_scope(scope_s):
    assert run.metric_reader(REPO, "retraction_roofline.train")(_ctx(scope_s)) is None
