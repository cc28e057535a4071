"""The harness's tensor-parallel path without the chip, under four forced
host devices (``tests/tp_rig.py``): weights made in place on the mesh
are the one-device weights bit for bit, and no device holds a whole
stacked matrix; a tiny ``tp: 4`` cell runs through the harness's whole
run and comes out correct, and not correct once a served token is
altered where it is produced; a ``tp`` other than the cell's chips is
refused when the cell is loaded, before any set-up."""

import pathlib
import sys

import pytest

TESTS = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(TESTS), str(TESTS / "chip_bench")]

from chip_bench_fixtures import tiny_cell  # noqa: E402
from tp_rig import run_under_devices  # noqa: E402

CASES = "chip_bench.chip_bench_tp_cases"


@pytest.fixture(scope="module")
def tp_run():
    return run_under_devices(f"{CASES}:tp_run", n_devices=4, timeout=600)


@pytest.mark.parametrize("registry", ["mistral-large-123b", "qwen3-1.7b"])
def test_weights_made_on_the_mesh_are_the_same(registry):
    r = run_under_devices(f"{CASES}:sharded_weights", {"registry": registry},
                          n_devices=4, timeout=600)
    assert r["differ"] == []
    assert r["stacked"] == 7 and r["whole"] == []
    assert r["devices"] == 4


def test_tp_cell_is_correct(tp_run):
    served = tp_run["served"]
    assert served["correct"], served["checks"]
    assert served["attempted"] == 8 and served["failed"] == 0
    assert served["count"] == 4
    # one engine per run, tensor-parallel over all four devices
    assert tp_run["meshes"] == [{"data": 1, "model": 4}] * 2


def test_tp_cell_with_an_altered_token_is_not_correct(tp_run):
    altered = tp_run["altered"]
    assert not altered["correct"]
    gap = altered["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("chips,tp", [(4, 2), (1, 4), (4, True)])
def test_tp_other_than_the_cells_chips_is_refused(tmp_path, chips, tp):
    with pytest.raises(ValueError, match="chips"):
        tiny_cell(tmp_path, chips=chips, engine={"tp": tp})
