"""The readers of the program's spans (``committee_derive_s``,
``shuffle_computes``, ``state_root_s``) on one traced step of each cell at
a small registry: each reads what its cell's manifest entry says it reads,
and nothing where the program opens no such span."""
from types import SimpleNamespace

import pytest

from benchmark import run as harness
from consensus_specs_tpu import tracing

from . import helpers

CELLS = ["phase0-400k.blocks", "altair-400k.blocks", "phase0-400k.epoch", "altair-400k.epoch"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return helpers.small_root(tmp_path_factory.mktemp("spans"))


@pytest.fixture(scope="module")
def window_spans(root):
    """{cell: the spans of ``tracing.report()`` as the harness reads them
    right after the window (one step; before the checks run more steps)}."""
    out = {}
    for cell in CELLS:
        reports = []
        report = tracing.report
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(tracing, "report", lambda: reports.append(report()) or reports[-1])
            tracing.enable()
            try:
                result = helpers.run(root, cell)
            finally:
                tracing.disable()
                tracing.reset()
        assert result["correct"], result["checks"]
        out[cell] = reports[0]["spans"]
    return out


def read(root, spans, cell, metric):
    reader = harness._load_module(harness._reader_path(root, metric))
    return reader.read(SimpleNamespace(spans=spans[cell], per=1.0))


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("metric", ["committee_derive_s", "shuffle_computes", "state_root_s"])
def test_reader_reads_where_the_manifest_lists_the_cell(root, window_spans, cell, metric):
    name = metric + "." + cell.split(".")[1]
    value = read(root, window_spans, cell, name)
    if name in [m["name"] for m in harness.load_cell(root, cell)["per_layer"]]:
        assert value is not None and value > 0
    else:
        assert value is None


@pytest.mark.parametrize("cell, computes", [
    # the blocks attest to slots 63..95: the committees of epochs 1 and 2
    ("phase0-400k.blocks", 2), ("altair-400k.blocks", 2),
    # phase0's epoch 2 -> 3 boundary reads the pending attestations of
    # epochs 1 (previous) and 2 (current)
    ("phase0-400k.epoch", 2)])
def test_shuffle_computes_per_step(root, window_spans, cell, computes):
    assert read(root, window_spans, cell, "shuffle_computes." + cell.split(".")[1]) == computes


def test_altair_epoch_derives_no_committees(window_spans):
    """Altair's epoch reads participation flags, not committees."""
    assert not [k for k in window_spans["altair-400k.epoch"]
                if k.endswith("stf/committees") or k.endswith("ops/shuffle")]


def test_state_root_leaves_out_the_per_slot_roots(root, window_spans):
    spans = window_spans["phase0-400k.blocks"]
    post_block = [v for k, v in spans.items() if k.endswith("host/state_root/stf/state_root")]
    assert sum(v["count"] for v in post_block) == 32
    assert read(root, window_spans, "phase0-400k.blocks", "state_root_s.blocks") == pytest.approx(
        sum(v["total_s"] for v in post_block))
