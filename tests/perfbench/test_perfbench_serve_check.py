"""The serving check at CPU size: a whole run of the tiny serving cell
(the look for a chip skipped) is correct as the program stands, and
not when a served token is altered where it is produced."""
import pytest

from perfbench_tiny import make_root
from perfbench import control, run


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("bench")))


def test_sound_engine_is_correct_and_compiles_nothing_in_the_window(root, capsys):
    res = run.run_cell(run.Cell(root, "tiny.serve"), 2**31 + 7, 0.5, False)
    assert res["correct"], res["check"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert "compilations in window: 0" in capsys.readouterr().err


def test_a_token_altered_where_it_is_produced_is_not_correct(root):
    from repro.serving.scheduler import ContinuousBatchingScheduler

    undo = control.altered_token(ContinuousBatchingScheduler, every=3)
    try:
        res = run.run_cell(run.Cell(root, "tiny.serve"), 2**31 + 7, 0.5, False)
    finally:
        undo()
    assert not res["correct"]
    assert res["check"]["logit_gap"]["value"] > 0.3
