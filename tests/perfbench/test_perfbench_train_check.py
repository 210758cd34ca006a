"""The training check at CPU size: the harness drives a whole run of
the tiny training cell (the look for a chip skipped) and ``correct``
holds for the program as it is and fails with the timed path broken."""
import pytest

from perfbench_tiny import make_root
from perfbench import run


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("bench")))


def _run(root, seed=2**31 + 41):
    return run.run_cell(run.Cell(root, "tiny.train"), seed, 0.5, False)


def test_sound_program_is_correct_and_compiles_nothing_in_the_window(root):
    res = _run(root)
    assert res["correct"], res["check"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(res)[-1] == "check"


def test_a_step_that_returns_its_state_unchanged_is_not_correct(root, monkeypatch):
    from repro.optim.sct_optimizer import SCTOptimizer

    monkeypatch.setattr(SCTOptimizer, "apply",
                        lambda self, state, grads: dict(state, step=state["step"] + 1))
    res = _run(root)
    assert not res["correct"]
    assert res["check"]["change_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out_is_not_correct(root, monkeypatch):
    from repro.launch import steps

    orig = steps.train_loss

    def half(params, batch, cfg):
        return orig(params, {k: v[: v.shape[0] // 2] for k, v in batch.items()}, cfg)

    monkeypatch.setattr(steps, "train_loss", half)
    res = _run(root)
    assert not res["correct"], res["check"]
