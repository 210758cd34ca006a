"""The train step's named scopes and the training host spans
(``repro.core.scopes``), on the CPU: every scope reaches the compiled
step's ``op_name`` metadata, backward and remat recompute included;
scopes leave the compiled program as it was; every host span lands on
the profiler's clock from ``Trainer.step`` and ``Trainer.fit``."""
import contextlib
import os
import re
import sys

import jax
import pytest

from repro.api import ModelSpec, PrecisionSpec, RunSpec, Trainer, TrainSpec
from repro.core import scopes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_METADATA = re.compile(r',? metadata=\{(?:[^{}"]|"(?:[^"\\]|\\.)*")*\}')
_STACK_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def _spec(**over):
    spec = RunSpec(model=ModelSpec(arch="smollm2-1.7b", reduced=True, dtype="bfloat16"),
                   train=TrainSpec(steps=4, batch=2, seq=16, lr=5e-4, warmup=2, seed=0),
                   precision=PrecisionSpec(mode="legacy"))
    return spec.replace(**over) if over else spec


def _compiled_step(trainer) -> str:
    return trainer._step_fn.lower(trainer.state, trainer.make_batch(0)).compile().as_text()


@pytest.fixture(scope="module")
def step_hlo():
    trainer = Trainer(_spec())
    assert trainer.cfg.remat and trainer.cfg.sct.spectral_mlp
    return _compiled_step(trainer)


@pytest.fixture(scope="module")
def op_names(step_hlo):
    return _OP_NAME.findall(step_hlo)


def test_every_device_scope_reaches_the_compiled_step(op_names):
    assert set(scopes.DEVICE_SCOPES) <= {scopes.scope_of(n) for n in op_names}


@pytest.mark.parametrize("scope", [scopes.ATTENTION, scopes.MLP])
def test_backward_and_recompute_carry_the_layer_scopes(op_names, scope):
    ops = [n for n in op_names if scopes.scope_of(n) == scope]
    assert any("transpose(" not in n and not scopes.is_recomputed(n) for n in ops)  # forward
    assert any("transpose(" in n for n in ops)                                       # backward
    assert any(scopes.is_recomputed(n) for n in ops)                                 # remat


def test_the_step_program_is_the_one_the_benchmark_reads(step_hlo):
    from perfbench.scopes import STEP_PROGRAM

    assert step_hlo.startswith(f"HloModule {STEP_PROGRAM},")


@pytest.mark.parametrize("op_name, scope", [
    ("jit(s)/jvp()/while/body/closed_call/sct.attention/dot_general", scopes.ATTENTION),
    ("jit(s)/transpose(jvp(sct.head))/mul", scopes.HEAD),
    ("jit(s)/sct.optimizer/cond/branch_1_fun/sct.retraction/qr", scopes.RETRACTION),
    ("jit(s)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/"
     "sct.mlp/tanh", scopes.MLP),
    ("jit(s)/transpose(jvp())/while/body/dynamic_slice", None),
    ("reduce_sum", None),
])
def test_scope_of_reads_the_innermost_scope(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def _program_only(hlo: str) -> str:
    """The HLO with metadata, stack-frame tables and instruction
    numbering taken out: what runs, and nothing of where it came from."""
    lines, skip = [], False
    for line in hlo.splitlines():
        if line in _STACK_TABLES:
            skip = True
        elif skip and line.startswith(("%", "ENTRY")):
            skip = False
        if not skip:
            lines.append(_METADATA.sub("", line))
    names = {}
    return re.sub(r"%([\w.\-]+)",
                  lambda m: "%" + names.setdefault(m.group(1), f"v{len(names)}"),
                  "\n".join(lines))


def test_scopes_change_metadata_only(step_hlo, monkeypatch):
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    unscoped = _compiled_step(Trainer(_spec()))
    assert not {scopes.scope_of(n) for n in _OP_NAME.findall(unscoped)} - {None}
    assert _program_only(unscoped) == _program_only(step_hlo)


def test_host_spans_land_on_the_profiler_clock(tmp_path):
    from perfbench import scopes as scoped_trace, trace

    spec = _spec(**{"checkpoint.directory": str(tmp_path / "ckpt"), "checkpoint.every": 1,
                    "rank.schedule": "static:8"})
    trainer = Trainer(spec)
    with jax.profiler.trace(str(tmp_path / "profile")):
        trainer.step()
        trainer.step()
        state = trainer.fit()                          # steps 2 and 3 through TrainLoop
        jax.block_until_ready(state)
    assert int(state["step"]) == 4 and len(trainer.loop.losses) == 2
    # the checkpoint writer's thread shares the main thread's name: the
    # reader keeps both threads' events
    seen = {name for plane, lines in scoped_trace.read_planes(trace.find_xplane(str(tmp_path)))
            if plane.startswith("/host:") for events in lines.values()
            for name, _, _ in events}
    assert set(scopes.HOST_SPANS) | {scopes.TRAIN_STEP} <= seen


def test_step_seconds_come_from_a_monotonic_clock(tmp_path, monkeypatch):
    """A wall-clock jump during a step must not show as a slow step."""
    import itertools
    import time

    wall = itertools.count(0.0, 3600.0)            # an hour passes at every reading
    monkeypatch.setattr(time, "time", lambda: next(wall))
    trainer = Trainer(_spec(**{"checkpoint.directory": str(tmp_path / "ckpt"),
                               "train.steps": 2}))
    trainer.fit()
    assert len(trainer.loop.step_seconds) == 2
    assert all(0 < dt < 3600 for dt in trainer.loop.step_seconds)


def test_compile_cache_keys_on_metadata_with_paths_from_the_checkout(monkeypatch, tmp_path):
    """Scopes live in metadata alone: a cache keyed without it would
    hand a profile another version's executable, with its scopes."""
    from repro.launch import cache

    flags = ("jax_compilation_cache_include_metadata_in_key",
             "jax_hlo_source_file_canonicalization_regex")
    before = {f: getattr(jax.config, f) for f in flags}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        assert cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        lm = os.path.join("src", "repro", "models", "lm.py")
        pattern = jax.config.jax_hlo_source_file_canonicalization_regex
        assert re.sub(pattern, "", os.path.join(cache.CHECKOUT, lm)) == lm
    finally:
        for f, v in before.items():
            jax.config.update(f, v)
