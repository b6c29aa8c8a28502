"""``higgs-gpu15.train`` rehearsed at a size a test can hold (PR 35).

The 15-bin cell's files, and one whole run of it on the CPU at 60k rows
through ``run.run_cell`` as ``test_higgs_gpu63.py`` makes them for the
63-bin float cell: the run really took the bit-packed storage (two rows a
byte, ``meta["bin_pack"]``), a sound run is ``correct`` by every limit of
the cell's file, the ``float8`` control and both planted faults, the
reference put in the program's place, are not; and the new per-layer
metric ``bins_pack_s`` reads the program's counter.

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

CELL = "higgs-gpu15.train"
ROWS = 60_000


@pytest.fixture(scope="module")
def cell():
    cell = manifest.load_cell(CELL)
    cell.check = dict(cell.check, score_rows=10_000, split_rows=ROWS)
    return cell


@pytest.fixture(scope="module")
def stood_in(cell):
    """One sound run, and every stand-in judged after it."""
    from lightgbm_tpu.obs.metrics import global_metrics
    global_metrics.meta.pop("bin_pack", None)
    return run.run_cell(cell, test_faults.SEED, 0.1, False,
                        require_chip=False, rows=ROWS,
                        stand_ins=test_faults._stand_ins(cell))


def test_the_configuration_is_the_63_bin_one_at_15_bins(cell):
    wide_bins = manifest.load_cell("higgs-gpu63.train")
    assert set(cell.config) == set(wide_bins.config)
    assert cell.config["params"] == dict(wide_bins.config["params"],
                                         max_bin=15)
    assert cell.config["precision"].startswith(
        wide_bins.config["precision"])
    assert "4-bit" in cell.config["precision"]
    assert cell.config["data"] == wide_bins.config["data"]
    assert cell.config["num_features"] == 28
    assert cell.config["published_rows"] == 10_500_000
    assert cell.config["reduced"] == ["rows"]
    assert not any(k.startswith("tpu_") for k in cell.config["params"])
    assert cell.traffic["rows"] == 84_000_000 == \
        8 * cell.config["published_rows"]
    assert cell.control == "float8" and cell.chips == 1
    # the root's split only: deeper, the reference's 15-bin grid and the
    # program's differ by up to half a bin of 6.7% of the rows, and the
    # best gain on the one reads up to 0.92 over a split that is the best
    # on the other (PERF.md section 2)
    assert cell.check == dict(wide_bins.check, score_rows=10_000,
                              split_rows=ROWS, split_nodes=1)
    assert len(cell.config["source"]) <= 200
    assert "GPU-Performance.rst" in cell.config["source"]
    assert "max_bin=15" in cell.config["source"]


def test_the_cell_reports_every_metric_of_the_float_cells_and_its_own():
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    for metric in bench["per_layer"]:
        if "higgs-gpu63.train" in metric["workloads"]:
            assert metric["workloads"][-1] == CELL, metric
    own = bench["per_layer"][-1]
    assert own == {"name": "bins_pack_s", "unit": "s", "better": "lower",
                   "source": "host_clock", "moves": "setup_s",
                   "layer": "entry (ops/bin_pack.py, "
                            "boosting._maybe_pack_bins)",
                   "workloads": [CELL]}
    float_cell = manifest.load_cell("higgs-gpu63.train")
    assert manifest.load_cell(CELL).per_layer == \
        float_cell.per_layer + ["bins_pack_s"]


def test_the_run_took_packed_bins(stood_in):
    from lightgbm_tpu.obs.metrics import global_metrics
    rec, = global_metrics.meta["bin_pack"]
    assert rec["vpb"] == 2 and rec["rows"] == ROWS and rec["features"] == 28
    assert rec["section"] == 30_720      # 30,000 up to whole 2,048s
    assert rec["bytes_packed"] == 28 * rec["section"]
    assert rec["bytes_raw"] == 28 * ROWS


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


def test_bins_pack_s_reads_the_programs_counter(stood_in, monkeypatch):
    """After the run above the counter holds the cell's packed matrix;
    the reader sums ``seconds`` over the records, and gives None where
    the program keeps no such counter (the parent of PR 35) or packed
    nothing (a configuration of more than 15 bins)."""
    from lightgbm_tpu.obs.metrics import global_metrics
    read = run.load_reader("bins_pack_s")
    records = global_metrics.meta["bin_pack"]
    got = read({})
    assert got == sum(r["seconds"] for r in records) > 0
    monkeypatch.delitem(global_metrics.meta, "bin_pack")
    assert read({}) is None
    monkeypatch.setitem(global_metrics.meta, "bin_pack", [])
    assert read({}) is None
