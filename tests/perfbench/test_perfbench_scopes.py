"""Device time by the program's named scopes (``perfbench/scopes.py``):
on hand-made planes and HLO text, on the small unscoped trace beside
this file, and on a scoped train step recorded on a TPU v5e chip
(``data/record_scoped_trace.py``), with the seven readers that give
its per-layer metrics."""
import gzip
import os
import shutil

import pytest

from perfbench_tiny import make_root
from perfbench import run, scopes, trace

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP_TRACE = os.path.join(HERE, "data", "small_chip_trace.xplane.pb")
SCOPED_TRACE = os.path.join(HERE, "data", "scoped_chip_trace.xplane.pb")
SCOPED_HLO = os.path.join(HERE, "data", "scoped_chip_step.hlo.txt.gz")
SCOPED_STEPS = 2
READERS = ("attention_ms.train", "spectral_mlp_ms.train", "vocab_ms.train",
           "optimizer_ms.train", "retraction_ms.train", "recompute_ms.train",
           "dispatch_ms.train")

MS = 1_000_000
STEP = "jit(train_step)"
HLO = f"""HloModule jit_train_step, is_scheduled=true

FileNames
1 "lm.py"

%fused_computation.1 (param_0: f32[2]) -> f32[2] {{
  %param_0 = f32[2]{{0}} parameter(0)
  %multiply.3 = f32[2]{{0}} multiply(%param_0, %param_0), metadata={{op_name="{STEP}/transpose(jvp())/while/body/closed_call/checkpoint/sct.attention/dot_general"}}
  ROOT %dynamic-update-slice.4 = f32[2]{{0}} dynamic-update-slice(%multiply.3, %param_0), metadata={{op_name="{STEP}/transpose(jvp())/while/body/dynamic_update_slice" stack_frame_id=1}}
}}

ENTRY %main.9 (p: f32[2]) -> f32[2] {{
  %p = f32[2]{{0}} parameter(0), metadata={{op_name="state[\\'params\\']"}}
  %fusion.1 = f32[2]{{0}} fusion(%p), kind=kLoop, calls=%fused_computation.1
  %copy-start.7 = (f32[2]{{0}}, f32[2]{{0}}) copy-start(%p)
  %copy-done.8 = f32[2]{{0}} copy-done((f32[2]{{0}}, f32[2]{{0}}) %copy-start.7)
  %fusion.2 = f32[2]{{0}} fusion(%copy-done.8), kind=kOutput, calls=%fused_computation.1, metadata={{op_name="{STEP}/jvp()/while/body/closed_call/sct.mlp/dot_general"}}
  %fusion.3 = f32[2]{{0}} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{STEP}/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/sct.attention/exp"}}
  %copy.4 = f32[2]{{0}} copy(%p), metadata={{op_name="{STEP}/jvp()/while/body/dynamic_slice"}}
  %fusion.5 = f32[2]{{0}} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{STEP}/sct.optimizer/cond/branch_1_fun/sct.retraction/householder_product"}}
  %fusion.6 = f32[2]{{0}} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{STEP}/transpose(jvp(sct.head))/mul"}}
  ROOT %convert.9 = bf16[2]{{0}} convert(%fusion.6)
}}
"""


def _op(name):
    return f"%{name} = f32[2]{{0}} fusion(f32[2]{{0}} %p), kind=kLoop"


def _planes():
    host = {"python3": [("perfbench.window", 10 * MS, 110 * MS),
                        ("perfbench.step", 10 * MS, 60 * MS),
                        ("sct.train.dispatch", 11 * MS, 12 * MS),
                        ("sct.train.dispatch", 61 * MS, 64 * MS),
                        ("sct.train.batch", 100 * MS, 120 * MS),     # leaves the stretch
                        ("sct.train.dispatch", 0, 5 * MS)]}          # before it
    ops = [
        ("%while.7 = (f32[2]) while(f32[2] %p)", 0, 40 * MS),       # holds the three below
        (_op("fusion.1"), 5 * MS, 20 * MS),      # 10-20 in the stretch: attention (fused)
        (_op("fusion.3"), 20 * MS, 30 * MS),     # attention, recomputed
        (_op("fusion.2"), 30 * MS, 40 * MS),     # mlp
        (_op("copy.4"), 40 * MS, 45 * MS),       # unscoped
        (_op("fusion.5"), 45 * MS, 50 * MS),     # retraction
        (_op("fusion.2"), 70 * MS, 80 * MS),     # another program: not counted
        (_op("fusion.6"), 90 * MS, 100 * MS),    # head
        (_op("fusion.99"), 100 * MS, 104 * MS),  # not in the HLO: unscoped
        (_op("convert.9"), 104 * MS, 106 * MS),  # no op_name: its operand's scope, head
        (_op("copy-done.8"), 106 * MS, 108 * MS),  # no op_name: its user's scope, mlp
    ]
    dev = {"XLA Ops": ops,
           "XLA Modules": [("jit_train_step(123)", 0, 50 * MS),
                           ("jit_other(9)", 70 * MS, 80 * MS),
                           ("jit_train_step(123)", 90 * MS, 108 * MS)]}
    return [("/host:CPU", host), ("/device:TPU:0", dev)]


def test_scope_table_reads_op_names_and_falls_back_to_fused_instructions():
    table = scopes.scope_table(HLO)
    assert table["fusion.2"] == ("sct.mlp", False)
    assert table["fusion.3"] == ("sct.attention", True)
    assert table["fusion.5"] == ("sct.retraction", False)     # innermost scope
    assert table["fusion.6"] == ("sct.head", False)           # inside the transform's name
    assert table["copy.4"] == (None, False)
    # no op_name of its own: the scope its fused instructions carry
    assert table["fusion.1"] == ("sct.attention", False)
    assert table["p"] == (None, False)
    # made by the compiler, no op_name: the scope of what it feeds, else
    # of what feeds it
    assert table["copy-done.8"] == table["copy-start.7"] == ("sct.mlp", False)
    assert table["convert.9"] == ("sct.head", False)


def test_scoped_attribution_by_hand():
    s = scopes.summarize(_planes(), hlo=HLO)
    assert s["scope_s"] == pytest.approx({"sct.attention": 0.020, "sct.mlp": 0.012,
                                          "sct.retraction": 0.005, "sct.head": 0.012,
                                          "unscoped": 0.009})
    assert s["recompute_s"] == pytest.approx(0.010)
    # the other program's op (70-80 ms) is left out; the rest is busy time
    assert sum(s["scope_s"].values()) == pytest.approx(s["busy_s"] - 0.010)
    assert {k: pytest.approx(v) for k, v in s["program_spans"].items()} == \
        {"sct.train.dispatch": [0.001, 0.003]}


def test_hlo_by_module_name_and_missing_hlo():
    by_module = scopes.summarize(_planes(), hlo={"jit_train_step(123)": HLO})
    assert by_module["scope_s"] == scopes.summarize(_planes(), hlo=HLO)["scope_s"]
    none = scopes.summarize(_planes(), hlo={})
    assert none["scope_s"] == pytest.approx({"unscoped": 0.058})
    assert none["recompute_s"] == 0.0


def test_every_output_of_the_plain_reduction_is_kept():
    plain = trace.summarize(_planes())
    for hlo in (HLO, None):
        s = scopes.summarize(_planes(), hlo=hlo)
        assert set(s) == set(plain) | {"scope_s", "recompute_s", "program_spans"}
        assert {k: s[k] for k in plain} == plain


def test_unscoped_program_of_the_recorded_chip_trace():
    """The plain reduction's chip trace (a matmul and the paged decode kernel) names no
    scope: the HLO the trace stores is found and read, all unscoped."""
    texts = scopes.hlo_modules(CHIP_TRACE, "jit__lambda")
    assert list(texts) == ["jit__lambda(12082329257286557678)"]
    assert "convolution_tanh_fusion" in next(iter(texts.values()))
    s = scopes.reduce(CHIP_TRACE, program="jit__lambda")
    assert set(s["scope_s"]) == {"unscoped"}
    assert s["scope_s"]["unscoped"] == pytest.approx(s["modules"]["jit__lambda"], rel=1e-3)


@pytest.fixture(scope="module")
def scoped():
    return scopes.reduce(SCOPED_TRACE)


def test_scopes_hold_the_busy_time_of_a_recorded_scoped_step(scoped):
    busy = scoped["busy_s"]
    in_scopes = sum(v for k, v in scoped["scope_s"].items() if k != "unscoped")
    assert set(scoped["scope_s"]) >= {"sct.attention", "sct.mlp", "sct.embed", "sct.head",
                                      "sct.optimizer", "sct.retraction"}
    assert sum(scoped["scope_s"].values()) == pytest.approx(busy, rel=0.01)
    assert in_scopes >= 0.9 * busy
    assert 0 < scoped["recompute_s"] < scoped["scope_s"]["sct.attention"] \
        + scoped["scope_s"]["sct.mlp"]
    assert len(scoped["program_spans"]["sct.train.dispatch"]) == SCOPED_STEPS


def test_the_trace_s_own_hlo_matches_the_compiled_text(scoped):
    with gzip.open(SCOPED_HLO, "rt") as f:
        text = f.read()
    from_text = scopes.reduce(SCOPED_TRACE, hlo=text)
    assert from_text["scope_s"] == pytest.approx(scoped["scope_s"])
    assert from_text["recompute_s"] == pytest.approx(scoped["recompute_s"])


def _reader_root(tmp_path, xplane):
    root = make_root(str(tmp_path))
    dest = os.path.join(root, "perfbench_out", "traces", "cell", "plugins", "profile", "1")
    os.makedirs(dest)
    shutil.copy(xplane, dest)
    ctx = {"trace": trace.reduce(xplane), "steps_traced": SCOPED_STEPS, "chips": 1}
    return root, ctx


@pytest.mark.parametrize("name", READERS)
def test_reader_of_a_scoped_trace(tmp_path, scoped, name):
    root, ctx = _reader_root(tmp_path, SCOPED_TRACE)
    value = run.metric_reader(root, name)(ctx)
    assert value is not None and value > 0
    if name == "attention_ms.train":
        assert value == pytest.approx(1000 * scoped["scope_s"]["sct.attention"] / SCOPED_STEPS)
    # the readers share one reduction through ctx
    assert ctx["scoped_trace"]["window_s"] == ctx["trace"]["window_s"]


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_in_an_unscoped_program(tmp_path, name):
    root, ctx = _reader_root(tmp_path, CHIP_TRACE)
    assert run.metric_reader(root, name)(ctx) is None


def test_reader_ignores_a_trace_of_another_stretch(tmp_path):
    root, ctx = _reader_root(tmp_path, SCOPED_TRACE)
    ctx["trace"] = dict(ctx["trace"], window_s=ctx["trace"]["window_s"] + 1.0)
    assert run.metric_reader(root, "attention_ms.train")(ctx) is None
