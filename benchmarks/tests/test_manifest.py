"""The loader finds every piece of a cell by name and refuses what it
does not know."""

import json
import os
import shutil

import pytest

import manifest


@pytest.fixture()
def root(tmp_path):
    """A copy of BENCHMARK.json and the data files, free to break."""
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), tmp_path)
    for sub in ("configs", "traffic", "workloads"):
        shutil.copytree(os.path.join(manifest.BENCH_DIR, sub),
                        tmp_path / "benchmarks" / sub)
    return tmp_path


def _edit(path, fn):
    with open(path, encoding="utf-8") as fh:
        obj = json.load(fh)
    fn(obj)
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(obj, fh)


def _cells():
    return [w["name"] for w in manifest.load_benchmark()["workloads"]]


@pytest.mark.parametrize("name", _cells())
def test_every_cell_of_the_benchmark_loads(name, root):
    cell = manifest.load_cell(name, str(root))
    assert cell.chips in (1, 4)
    assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
    assert cell.per_layer
    assert set(cell.limits) == set(manifest.NUMBERS)
    for metric in cell.per_layer:
        assert os.path.exists(os.path.join(manifest.BENCH_DIR, "metrics",
                                           metric + ".py"))


def test_an_unknown_cell_is_refused(root):
    with pytest.raises(manifest.ManifestError, match="names no workload"):
        manifest.load_cell("no-such.cell", str(root))


@pytest.mark.parametrize("bad", ["has space", "a/b", "", "-lead", "x" * 65,
                                 "grüß"])
def test_bad_names_are_refused(bad):
    with pytest.raises(manifest.ManifestError):
        manifest.check_name(bad, "workload")


@pytest.mark.parametrize("sub,file", [
    ("traffic", "train-rows-63m.json"),
    ("configs", "higgs-gpu63-int8.json"),
    ("workloads", "higgs-gpu63-int8.train.json")])
def test_an_unknown_key_is_an_error_never_ignored(root, sub, file):
    _edit(root / "benchmarks" / sub / file,
          lambda obj: obj.update(bagging_fraction=0.5))
    with pytest.raises(manifest.ManifestError, match="unknown key"):
        manifest.load_cell("higgs-gpu63-int8.train", str(root))


def test_traffic_the_harness_does_not_implement_is_refused(root):
    _edit(root / "benchmarks" / "traffic" / "train-rows-63m.json",
          lambda obj: obj.update(bagging="0.5"))
    with pytest.raises(manifest.ManifestError, match="implemented"):
        manifest.load_cell("higgs-gpu63-int8.train", str(root))


def test_a_cell_file_that_disagrees_with_the_benchmark_is_refused(root):
    _edit(root / "benchmarks" / "workloads" / "higgs-gpu63-int8.train.json",
          lambda obj: obj.update(chips=4))
    with pytest.raises(manifest.ManifestError, match="BENCHMARK.json says"):
        manifest.load_cell("higgs-gpu63-int8.train", str(root))


def test_a_missing_limit_is_refused(root):
    _edit(root / "benchmarks" / "workloads" / "higgs-gpu63-int8.train.json",
          lambda obj: obj["limits"].pop("score_gap"))
    with pytest.raises(manifest.ManifestError, match="missing key"):
        manifest.load_cell("higgs-gpu63-int8.train", str(root))
