"""The reduction from a profiler trace to busy time, idle gaps, kernel
and program time: on hand-made planes, and on a small trace recorded
on a TPU v5e chip and committed beside this file."""
import os

import pytest

from perfbench import trace

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP_TRACE = os.path.join(HERE, "data", "small_chip_trace.xplane.pb")


def _planes():
    ms = 1_000_000
    host = {"python3": [("perfbench.window", 10 * ms, 110 * ms),
                        ("perfbench.step", 10 * ms, 60 * ms),
                        ("perfbench.sync", 60 * ms, 110 * ms),
                        ("unrelated", 0, 200 * ms)]}
    ops = [
        ("%while.1 = (f32[2]) while(f32[2] %x)", 0, 40 * ms),          # holds the two below
        ("%fusion.1 = f32[2] fusion(f32[2] %a), kind=kLoop", 0, 15 * ms),
        ("%custom-call.7 = f32[2] custom-call(f32[2] %b), custom_call_target=\"tpu_custom_call\"",
         20 * ms, 40 * ms),
        ("%fusion.2 = f32[2] fusion(f32[2] %a), kind=kLoop", 70 * ms, 80 * ms),
        ("%copy.3 = f32[2] copy(f32[2] %c)", 100 * ms, 130 * ms),
    ]
    dev = {"XLA Ops": ops,
           "XLA Modules": [("jit_step(123)", 0, 40 * ms), ("jit_step(123)", 70 * ms, 80 * ms),
                           ("jit_other(9)", 100 * ms, 130 * ms)]}
    return [("/host:CPU", host), ("/device:TPU:0", dev), ("#Chip0 Misc", {})]


def test_busy_idle_and_window_by_hand():
    s = trace.summarize(_planes())
    assert s["window_s"] == pytest.approx(0.100)
    # device busy inside [10, 110] ms: 10-40, 70-80, 100-110 -> 50 ms
    assert s["busy_s"] == pytest.approx(0.050)
    assert s["idle_share"] == pytest.approx(0.5)


def test_leaf_ops_are_counted_once_and_clipped_to_the_window():
    s = trace.summarize(_planes())
    ops = s["ops"]
    assert "%while.1 while" not in ops                      # holds other ops
    assert ops["%fusion.1 fusion"] == pytest.approx(0.005)  # 10-15 ms
    assert ops["%custom-call.7 custom-call tpu_custom_call"] == pytest.approx(0.020)
    assert ops["%copy.3 copy"] == pytest.approx(0.010)      # 100-110 ms
    assert s["modules"] == pytest.approx({"jit_step": 0.040, "jit_other": 0.010})
    assert len(s["custom_calls"]) == 1


def test_idle_gaps_longest_first_named_by_the_innermost_host_span():
    s = trace.summarize(_planes())
    # gaps: 40-70 ms (middle 55: the step span), 80-100 ms (middle 90: sync)
    assert s["gaps"] == [("step", pytest.approx(0.030)), ("sync", pytest.approx(0.020))]
    b = trace.breakdown(s)
    assert b["idle_gaps"][0][0] == "step"
    assert b["device_ops"][0][0] == "%custom-call.7 custom-call tpu_custom_call"


def test_a_trace_without_the_window_span_is_refused():
    planes = [(p, {k: [ev for ev in v if ev[0] != "perfbench.window"] for k, v in l.items()})
              for p, l in _planes()]
    with pytest.raises(ValueError, match="perfbench.window"):
        trace.summarize(planes)


def test_union_and_gaps():
    assert trace.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert trace.idle_gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]


def test_reduction_of_a_recorded_chip_trace():
    """Three rounds of a jitted 2048x2048 matmul and the paged decode
    kernel, with 2 ms host sleeps between them, traced on one v5e."""
    planes = trace.read_planes(CHIP_TRACE)
    s = trace.summarize(planes)
    assert 0 < s["busy_s"] < s["window_s"]
    # the host slept 3 x 2 ms with the device idle
    assert s["window_s"] - s["busy_s"] > 0.006
    assert any(name == "host_wait" for name, _ in s["gaps"][:3])
    assert s["modules"].keys() >= {"jit__lambda", "jit_paged_gqa_decode_pallas"}
    kernel = [k for k in s["ops"] if "custom-call" in k]
    assert kernel and all(s["ops"][k] > 0 for k in kernel)
    # leaf ops add up to the busy time: none overlaps another here
    assert sum(s["ops"].values()) == pytest.approx(s["busy_s"], rel=1e-6)
    # the kernel's custom call is found by its Mosaic target
    assert [name for name, _, _ in s["custom_calls"]
            if "tpu_custom_call" in name and "paged_gqa_decode_pallas" in name]
