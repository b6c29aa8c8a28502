"""``higgs-gpu63.train`` rehearsed at a size a test can hold (PR 28).

The float cell's files, and whole runs of it on the CPU at 60k rows
through ``run.run_cell`` as ``test_faults.py`` makes them for the cell it
names (that file's ``ROWS`` may not be edited, so the new cell's cases
live here and borrow its helpers): a sound run is ``correct`` by every
limit of the cell's file, and the ``float8`` control and both planted
faults, the reference put in the program's place, are not.

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

CELL = "higgs-gpu63.train"
ROWS = 60_000


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


def test_the_configuration_is_the_int8_one_less_its_two_keys(cell):
    int8 = manifest.load_cell("higgs-gpu63-int8.train")
    params = dict(int8.config["params"])
    assert params.pop("use_quantized_grad") is True
    assert params.pop("num_grad_quant_bins") == 126
    assert cell.config["params"] == params
    assert cell.config["reduced"] == ["rows"]
    assert not any(k.startswith("tpu_") for k in cell.config["params"])
    assert cell.control == "float8" and cell.chips == 1
    assert cell.traffic == int8.traffic and cell.check == dict(
        int8.check, score_rows=10_000, split_rows=ROWS)
    assert cell.per_layer == int8.per_layer


def test_every_metric_the_int8_cell_reports_lists_this_cell():
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    for metric in bench["per_layer"]:
        if "higgs-gpu63-int8.train" in metric["workloads"]:
            assert CELL in metric["workloads"], metric


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
