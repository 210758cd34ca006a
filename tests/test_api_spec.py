"""RunSpec serialization contract (api/specs.py): property-based
JSON/dict round-trips over randomized specs, unknown-key rejection at
every nesting level, ``replace`` override semantics (sub-spec / dict /
dotted-path forms), and the explicit legacy precision mode."""
import json

import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # container without hypothesis: deterministic shim
    from _hypothesis_fallback import given, settings, st

from repro.api import (
    BenchSpec,
    CheckpointSpec,
    ModelSpec,
    PrecisionSpec,
    RankScheduleSpec,
    RunSpec,
    ServeSpec,
    ShardingSpec,
    SLOSpec,
    TrainSpec,
    WorkloadSpec,
)
from repro.core.precision import LEGACY, POLICIES, PrecisionPolicy, precision_policy

PRECISIONS = [LEGACY, *POLICIES]
SCHEDULES = [None, "static:16", "step:10=32,20=16", "energy:0.9,min=8,every=5"]
QUANTIZE = [None, "int8"]
ARCHS = ["smollm2-1.7b", "llama3.2-1b", "qwen1.5-0.5b"]


def _build_spec(arch_i, steps, lr, seed, prec_i, sched_i, quant_i, rank_i,
                telemetry, prefix_cache):
    """Deterministic spec from drawn scalars — the property-test
    generator shared by the round-trip cases."""
    return RunSpec(
        model=ModelSpec(arch=ARCHS[arch_i % len(ARCHS)], reduced=True,
                        rank=[None, 8, 32][rank_i % 3]),
        train=TrainSpec(steps=steps, lr=lr, seed=seed, telemetry=telemetry),
        precision=PrecisionSpec(mode=PRECISIONS[prec_i % len(PRECISIONS)]),
        rank=RankScheduleSpec(schedule=SCHEDULES[sched_i % len(SCHEDULES)]),
        serve=ServeSpec(quantize=QUANTIZE[quant_i % len(QUANTIZE)],
                        prefix_cache=prefix_cache,
                        request_timeout=[None, 64][seed % 2]),
        checkpoint=CheckpointSpec(directory=[None, "/tmp/x"][steps % 2]),
    )


@settings(max_examples=40, deadline=None)
@given(arch_i=st.integers(0, 10), steps=st.integers(1, 10_000),
       lr=st.floats(1e-6, 1.0), seed=st.integers(0, 2**31 - 1),
       prec_i=st.integers(0, 10), sched_i=st.integers(0, 10),
       quant_i=st.integers(0, 10), rank_i=st.integers(0, 10),
       telemetry=st.booleans(), prefix_cache=st.booleans())
def test_json_round_trip_bit_exact(arch_i, steps, lr, seed, prec_i, sched_i,
                                   quant_i, rank_i, telemetry, prefix_cache):
    spec = _build_spec(arch_i, steps, lr, seed, prec_i, sched_i, quant_i,
                       rank_i, telemetry, prefix_cache)
    text = spec.to_json()
    restored = RunSpec.from_json(text)
    assert restored == spec
    assert restored.to_json() == text            # bit-exact
    # dict round-trip too, and through an actual json encode/decode
    assert RunSpec.from_dict(json.loads(json.dumps(spec.to_dict()))) == spec


def test_unknown_keys_rejected_at_every_level():
    good = RunSpec().to_dict()
    with pytest.raises(ValueError, match="unknown key"):
        RunSpec.from_dict({**good, "extra": 1})
    bad_nested = {**good, "train": {**good["train"], "stepz": 3}}
    with pytest.raises(ValueError, match="TrainSpec: unknown key"):
        RunSpec.from_dict(bad_nested)
    with pytest.raises(TypeError):
        RunSpec.from_dict({**good, "serve": "paged"})   # not a dict


def test_value_validation_happens_on_deserialize():
    good = RunSpec().to_dict()
    with pytest.raises(ValueError, match="precision mode"):
        RunSpec.from_dict({**good, "precision": {"mode": "fp64"}})
    with pytest.raises(ValueError, match="rank schedule"):
        RunSpec.from_dict({**good, "rank": {"schedule": "bogus:1"}})
    with pytest.raises(ValueError, match="serve mode"):
        RunSpec.from_dict({**good, "serve": {**good["serve"], "mode": "warp"}})


def test_replace_subspec_dict_and_dotted_forms():
    spec = RunSpec(train=TrainSpec(steps=10))
    # sub-spec instance
    s1 = spec.replace(precision=PrecisionSpec("mixed"))
    assert s1.precision.mode == "mixed" and spec.precision.mode == LEGACY
    # dict merged into the existing sub-spec
    s2 = spec.replace(serve={"quantize": "int8"})
    assert s2.serve.quantize == "int8"
    assert s2.serve.page_size == spec.serve.page_size   # untouched fields kept
    # dotted leaf paths, several at once
    s3 = spec.replace(**{"train.steps": 77, "serve.rank": 8,
                         "checkpoint.directory": "/tmp/y"})
    assert (s3.train.steps, s3.serve.rank, s3.checkpoint.directory) == \
        (77, 8, "/tmp/y")
    assert spec.train.steps == 10                       # original frozen
    # dict + dotted on the same sub-spec compose
    s4 = spec.replace(serve={"slots": 8}, **{"serve.gen": 5})
    assert (s4.serve.slots, s4.serve.gen) == (8, 5)


def test_replace_rejects_unknown_and_mistyped():
    spec = RunSpec()
    with pytest.raises(ValueError, match="unknown field"):
        spec.replace(bogus=1)
    with pytest.raises(ValueError, match="unknown field"):
        spec.replace(**{"train.stepz": 3})
    with pytest.raises(TypeError, match="TrainSpec"):
        spec.replace(train=3)
    with pytest.raises(ValueError, match="unknown field"):
        spec.replace(**{"bogus.steps": 3})


def test_model_spec_overrides_reach_config():
    cfg = ModelSpec("smollm2-1.7b", reduced=True, rank=8).config()
    assert cfg.sct.rank == 8
    dense = ModelSpec("smollm2-1.7b", reduced=True, spectral_mlp=False).config()
    assert dense.sct.spectral_mlp is False
    plain = ModelSpec("smollm2-1.7b", reduced=True).config()
    assert plain.sct.rank != 8 and plain.sct.spectral_mlp is True


def test_model_spec_dtype_override():
    """``model.dtype`` reaches the config, survives a JSON round trip,
    and rejects anything but the two compute dtypes."""
    base = ModelSpec("smollm2-1.7b", reduced=True).config()
    assert base.dtype == "bfloat16"
    spec = RunSpec().replace(**{"model.dtype": "float32"})
    assert spec.model.config().dtype == "float32"
    assert RunSpec.from_json(spec.to_json()) == spec
    with pytest.raises(ValueError, match="model dtype"):
        ModelSpec(dtype="float16")


def test_precision_spec_legacy_is_explicit():
    """The legacy path is a named mode, not a sentinel: the spec says
    'legacy', the optimizer-facing policy is None, and the effective
    policy resolves to the config dtype with no scaling."""
    from repro.core.precision import effective_policy

    legacy = PrecisionSpec()                 # the default
    assert legacy.mode == LEGACY
    assert legacy.policy() is None
    assert precision_policy(LEGACY) is None  # name and sentinel agree

    cfg = ModelSpec("smollm2-1.7b", reduced=True).config()
    eff = effective_policy(cfg, LEGACY)
    assert isinstance(eff, PrecisionPolicy)
    assert eff.name == LEGACY
    assert eff.compute_dtype == cfg.dtype
    assert eff.accum_dtype == "float32"
    assert not eff.loss_scaling
    # presets pass through untouched
    assert effective_policy(cfg, "mixed") is POLICIES["mixed"]
    assert PrecisionSpec("mixed").policy() is POLICIES["mixed"]


def test_serve_spec_paged_config_geometry():
    sv = ServeSpec(page_size=8, num_pages=20, slots=3, pages_per_seq=5)
    pcfg = sv.paged_config()
    assert (pcfg.page_size, pcfg.num_pages, pcfg.max_slots,
            pcfg.max_pages_per_seq) == (8, 20, 3, 5)
    assert pcfg.max_seq == 40


def test_serve_spec_slo_fields_round_trip():
    sv = ServeSpec(scheduler="slo", shed=False, tenant="acme", priority=2,
                   default_deadline=40, request_timeout=64)
    restored = ServeSpec.from_json(sv.to_json())
    assert restored == sv
    assert restored.to_json() == sv.to_json()
    assert (restored.scheduler, restored.tenant, restored.priority,
            restored.default_deadline) == ("slo", "acme", 2, 40)
    # the submit-time deadline default prefers default_deadline, then
    # falls back to the pre-SLO request_timeout flag
    assert sv.effective_deadline == 40
    assert ServeSpec(request_timeout=64).effective_deadline == 64
    assert ServeSpec().effective_deadline is None


def test_serve_spec_slo_field_validation():
    with pytest.raises(ValueError, match="scheduler"):
        ServeSpec(scheduler="lifo")
    with pytest.raises(ValueError, match="priority"):
        ServeSpec(priority=-1)
    with pytest.raises(ValueError, match="tenant"):
        ServeSpec(tenant="")
    good = RunSpec().to_dict()
    with pytest.raises(ValueError, match="ServeSpec: unknown key"):
        RunSpec.from_dict({**good, "serve": {**good["serve"], "tennant": "x"}})
    with pytest.raises(ValueError, match="scheduler"):
        RunSpec.from_dict({**good,
                           "serve": {**good["serve"], "scheduler": "edf"}})


@settings(max_examples=40, deadline=None)
@given(arrival_i=st.integers(0, 10), rate=st.floats(0.05, 4.0),
       requests=st.integers(1, 200), seed=st.integers(0, 2**31 - 1),
       tenants_i=st.integers(0, 10), prefix=st.integers(0, 32),
       deadlines_i=st.integers(0, 10), shed=st.booleans(),
       overloads_i=st.integers(0, 10), scheds_i=st.integers(0, 10))
def test_bench_spec_round_trip_bit_exact(arrival_i, rate, requests, seed,
                                         tenants_i, prefix, deadlines_i,
                                         shed, overloads_i, scheds_i):
    bench = BenchSpec(
        workload=WorkloadSpec(
            arrival=["poisson", "onoff", "fixed"][arrival_i % 3],
            rate=rate, requests=requests, seed=seed,
            tenants=["1", "2,1", "1,1,1"][tenants_i % 3],
            shared_prefix=prefix),
        slo=SLOSpec(deadlines=[None, "64", "0=32,1=96"][deadlines_i % 3],
                    shed=shed),
        overloads=["1", "1,2", "1,1.5,2"][overloads_i % 3],
        schedulers=["fifo", "slo", "fifo,slo"][scheds_i % 3],
    )
    text = bench.to_json()
    restored = BenchSpec.from_json(text)
    assert restored == bench
    assert restored.to_json() == text
    assert BenchSpec.from_dict(json.loads(json.dumps(bench.to_dict()))) == bench


def test_bench_spec_unknown_keys_rejected_at_every_level():
    good = BenchSpec().to_dict()
    with pytest.raises(ValueError, match="unknown key"):
        BenchSpec.from_dict({**good, "extra": 1})
    with pytest.raises(ValueError, match="WorkloadSpec: unknown key"):
        BenchSpec.from_dict(
            {**good, "workload": {**good["workload"], "ratez": 1.0}})
    with pytest.raises(ValueError, match="SLOSpec: unknown key"):
        BenchSpec.from_dict({**good, "slo": {**good["slo"], "ttf": 4}})


def test_bench_spec_validation_and_replace():
    with pytest.raises(ValueError, match="arrival"):
        WorkloadSpec(arrival="bursty")
    with pytest.raises(ValueError, match="rate"):
        WorkloadSpec(rate=0)
    with pytest.raises(ValueError, match="tenants"):
        WorkloadSpec(tenants="1,-2")
    with pytest.raises(ValueError, match="deadlines"):
        SLOSpec(deadlines="fast")
    with pytest.raises(ValueError, match="scheduler"):
        BenchSpec(schedulers="fifo,edf")
    with pytest.raises(ValueError, match="overloads"):
        BenchSpec(overloads="")
    # replace: dict-merge and dotted forms, same semantics as RunSpec
    bench = BenchSpec()
    b2 = bench.replace(workload={"rate": 2.0}, **{"slo.deadlines": "32"})
    assert b2.workload.rate == 2.0
    assert b2.workload.requests == bench.workload.requests
    assert b2.slo.deadlines == "32"
    assert bench.slo.deadlines is None          # original frozen
    with pytest.raises(ValueError, match="unknown field"):
        bench.replace(**{"workload.ratez": 3})


def test_slo_spec_deadline_semantics():
    assert SLOSpec().deadline_for(0) is None
    flat = SLOSpec(deadlines="64")
    assert flat.deadline_for(0) == 64 and flat.deadline_for(3) == 64
    per = SLOSpec(deadlines="0=32,1=96")
    assert per.deadline_for(0) == 32 and per.deadline_for(1) == 96
    # classes beyond the map inherit the lowest-urgency entry
    assert per.deadline_for(5) == 96
    assert per.deadline_map() == {0: 32, 1: 96}


def test_workload_spec_weight_parsing():
    assert WorkloadSpec(tenants="2,1").tenant_weights() == [2.0, 1.0]
    assert WorkloadSpec(priority_mix="1,1,2").priority_weights() == \
        [1.0, 1.0, 2.0]
    assert BenchSpec(overloads="1,1.5,2").overload_factors() == [1.0, 1.5, 2.0]
    assert BenchSpec(ranks="8,16").rank_arms() == [8, 16]
    with pytest.raises(ValueError, match="ranks"):
        BenchSpec(ranks="8,x")


def test_sharding_spec_single_device_mesh_is_none():
    cfg = ModelSpec("smollm2-1.7b", reduced=True).config()
    assert ShardingSpec().mesh(cfg) is None              # 1 visible device
    assert ShardingSpec(data=1, model=1).mesh(cfg) is None
    with pytest.raises(ValueError, match="devices"):
        ShardingSpec(data=4, model=2).mesh(cfg)


def test_serve_spec_speculative_validation():
    """Speculative-decoding knobs: ladder grammar checked at spec
    construction, paged-mode and prefix-cache exclusivity enforced, and
    the ladder round-trips through JSON like every other field."""
    good = ServeSpec(speculative_rank="8,16", draft_tokens=3)
    assert good.speculative_ladder() == [8, 16]
    assert ServeSpec().speculative_ladder() == []    # off by default
    run = RunSpec(serve=good)
    assert RunSpec.from_json(run.to_json()) == run
    for bad in ("16,8", "", "a", "0"):               # decreasing/empty/junk
        with pytest.raises(ValueError):
            ServeSpec(speculative_rank=bad)
    with pytest.raises(ValueError):
        ServeSpec(speculative_rank="8", prefix_cache=True)
    with pytest.raises(ValueError):
        ServeSpec(mode="static", speculative_rank="8")
    with pytest.raises(ValueError):
        ServeSpec(draft_tokens=0)
