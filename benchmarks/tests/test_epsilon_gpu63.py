"""``epsilon-gpu63.train`` rehearsed at a size a test can hold (PR 32).

The wide float cell's files, and one whole run of it on the CPU at 12k
rows x 2000 features through ``run.run_cell`` as ``test_higgs_gpu63.py``
makes them for the narrow float cell: a sound run is ``correct`` by every
limit of the cell's file, the ``float8`` control and both planted faults,
the reference put in the program's place, are not; and the new per-layer
metric ``dataset_bin_s`` reads the program's counter.

None of these numbers is a device number. On the CPU the program's
histograms are exact float32 (the XLA twin; nothing rounds the gradient
operand to bfloat16), so a sound run here reads far under what the chip
reads; the limits are set from the chip's readings (PERF.md section 2).
"""

import json
import os

import pytest

import manifest
import run
import test_faults

CELL = "epsilon-gpu63.train"
ROWS = 12_000


@pytest.fixture(scope="module")
def cell():
    cell = manifest.load_cell(CELL)
    cell.check = dict(cell.check, score_rows=10_000, split_rows=ROWS)
    return cell


@pytest.fixture(scope="module")
def stood_in(cell):
    """One sound run, and every stand-in judged after it."""
    return run.run_cell(cell, test_faults.SEED, 0.1, False,
                        require_chip=False, rows=ROWS,
                        stand_ins=test_faults._stand_ins(cell))


def test_the_configuration_is_the_narrow_float_one_at_2000_features(cell):
    narrow = manifest.load_cell("higgs-gpu63.train")
    assert cell.config["params"] == narrow.config["params"]
    assert cell.config["precision"] == narrow.config["precision"]
    assert cell.config["data"] == narrow.config["data"]
    assert cell.config["num_features"] == 2000
    assert cell.config["published_rows"] == 400_000
    assert cell.config["reduced"] == ["rows"]
    assert not any(k.startswith("tpu_") for k in cell.config["params"])
    assert cell.traffic["rows"] == 3 * cell.config["published_rows"]
    assert cell.control == "float8" and cell.chips == 1
    assert cell.check == {"trees": 2, "score_rows": 10_000,
                          "split_nodes": 4, "split_rows": ROWS}
    assert len(cell.config["source"]) <= 200
    assert "GPU-Performance.rst" in cell.config["source"]


def test_the_cell_reports_every_metric_and_the_entry_layers():
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    names = [m["name"] for m in bench["per_layer"] if CELL in m["workloads"]]
    assert len(names) == len(bench["per_layer"]) == 14
    entry = bench["per_layer"][-1]
    assert entry["name"] == "dataset_bin_s" and entry["moves"] == "setup_s"
    assert entry["workloads"] == [w["name"] for w in bench["workloads"]]
    assert manifest.load_cell(CELL).per_layer == names


def test_a_sound_run_is_correct(stood_in):
    assert stood_in["attempted"] >= 1 and stood_in["failed"] == 0
    assert set(stood_in["metrics"]) == {"train_iters_per_s", "setup_s"}
    assert stood_in["correct"] is True, stood_in["compared"]


def test_the_float8_control_is_not_correct(stood_in, cell):
    control = stood_in["stand_ins"]["float8"]
    values = test_faults._values(control)
    assert control["correct"] is False, control["compared"]
    # by the numbers that read the precision of the sums, and by no other
    over = {name for name, pair in control["compared"].items()
            if not pair["value"] <= pair["limit"]}
    assert over and over <= {"leaf_value_gap", "median_leaf_value_gap"}
    assert values["median_leaf_value_gap"] > \
        cell.limits["median_leaf_value_gap"]
    assert values["leaf_count_gap"] == 0


def test_the_reference_with_half_of_the_batch_left_out(stood_in):
    half = stood_in["stand_ins"]["half_batch"]
    assert half["correct"] is False
    assert test_faults._values(half)["leaf_count_gap"] > 0.4


def test_the_reference_with_an_answer_altered(stood_in):
    altered = stood_in["stand_ins"]["altered_leaf"]
    assert altered["correct"] is False
    assert 0.03 < test_faults._values(altered)["leaf_value_gap"] < 0.07
    assert test_faults._values(altered)["score_gap"] > 1e-3


def test_dataset_bin_s_reads_the_programs_counter(stood_in, monkeypatch):
    """After the run above the counter holds the cell's data set; the
    reader sums ``seconds`` over the records, and gives None where the
    program keeps no such counter (the parent of PR 32) or no record."""
    from lightgbm_tpu.obs.metrics import global_metrics
    read = run.load_reader("dataset_bin_s")
    records = global_metrics.meta["data_binning"]
    assert records[-1]["columns"] == 2000
    assert records[-1]["sample_rows"] == ROWS
    got = read({})
    assert got == sum(r["seconds"] for r in records) > 0
    assert records[-1]["seconds"] >= records[-1]["find_bins_s"] > 0
    monkeypatch.delitem(global_metrics.meta, "data_binning")
    assert read({}) is None
    monkeypatch.setitem(global_metrics.meta, "data_binning", [])
    assert read({}) is None
