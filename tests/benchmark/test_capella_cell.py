"""The capella cell at a small registry on the CPU: it comes out correct;
the control (blocks imported with BLS off) accepts every tampered variant,
the BLS-to-execution change's included; and a program whose block engine
does not take capella on its fast path stops the cell at once."""
import pytest

from benchmark import control
from consensus_specs_tpu.stf import engine

from . import helpers

CELL = "capella-1m.blocks"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return helpers.small_root(tmp_path_factory.mktemp("capella"))


def test_cell_is_correct(root):
    result = helpers.run(root, CELL)
    assert result["correct"], result["checks"]
    assert result["attempted"] == 32 and result["failed"] == 0
    assert set(result["metrics"]) == {"epoch_replay_s", "setup_s"}


def test_control_without_signature_checks_accepts_every_variant(root):
    result = helpers.run(root, CELL, runner=control.run_control)
    checks = result["checks"]
    assert not result["correct"]
    # first block: aggregate, sync, proposer, BLS change; middle block: two aggregates
    assert checks["tampered_blocks_accepted"]["value"] == 6
    assert checks["post_root_mismatches"]["value"] == 0


def test_program_without_capella_on_its_fast_path_stops_the_cell(root, monkeypatch):
    monkeypatch.setattr(engine, "FAST_FORKS", ("phase0", "altair", "bellatrix"))
    with pytest.raises(RuntimeError, match="does not take capella blocks on its fast path"):
        helpers.run(root, CELL)
