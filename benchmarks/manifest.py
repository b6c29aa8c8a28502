"""Loads ``BENCHMARK.json`` and the data files a cell is made of.

A cell is found by name, and every piece of it is a file of its own, so
that a later PR adds a configuration, a traffic mix, a cell or a per-layer
metric by adding files and entries and editing none:

- ``benchmarks/configs/<config>.json``   the configuration as it is run
- ``benchmarks/traffic/<traffic>.json``  the traffic mix (rows, warm-up)
- ``benchmarks/workloads/<cell>.json``   the cell: its configuration, its
  traffic, its chips, what ``correct`` follows, its control and each
  number's limit
- ``benchmarks/metrics/<metric>.py``     one reader per per-layer metric

A key this loader does not know is an error and never ignored: a later
cell cannot silently run as one of today's.
"""

from __future__ import annotations

import json
import os
import re
from dataclasses import dataclass

import reference

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")

CONFIG_KEYS = {"name", "source", "cited_in", "params", "num_features",
               "published_rows", "precision", "data", "assumed", "reduced",
               "scaled"}
DATA_KEYS = {"generator"}
TRAFFIC_KEYS = {"name", "rows", "warmup_iterations", "eval", "bagging",
                "scaled"}
CHECK_KEYS = {"trees", "score_rows", "split_nodes", "split_rows"}
WORKLOAD_KEYS = {"name", "config", "traffic", "chips", "why", "check",
                 "control", "limits"}
# the numbers `correct` compares; a limits table names each of them
NUMBERS = reference.NUMBERS


class ManifestError(ValueError):
    pass


def check_name(name, what: str) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise ManifestError(f"{what} {name!r} is not a name (letters, digits, "
                            "'_', '.', '-', at most 64, no leading '.' or '-')")
    return name


def _read_json(path: str) -> dict:
    try:
        with open(path, encoding="utf-8") as fh:
            obj = json.load(fh)
    except OSError as exc:
        raise ManifestError(f"cannot read {path}: {exc}") from None
    except json.JSONDecodeError as exc:
        raise ManifestError(f"{path} is not JSON: {exc}") from None
    if not isinstance(obj, dict):
        raise ManifestError(f"{path} does not hold a JSON object")
    return obj


def _only_keys(obj: dict, known: set, required: set, where: str) -> None:
    unknown = sorted(set(obj) - known)
    if unknown:
        raise ManifestError(f"{where}: unknown key(s) {unknown}; "
                            f"known: {sorted(known)}")
    missing = sorted(required - set(obj))
    if missing:
        raise ManifestError(f"{where}: missing key(s) {missing}")


@dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file
    traffic: dict         # the traffic file
    check: dict           # what the comparison follows
    control: str          # the control of ``reference.CONTROLS`` it is held to
    limits: dict          # number -> limit
    end_to_end: list      # names of the end-to-end metrics this cell reports
    per_layer: list       # names of the per-layer metrics this cell reports
    units: dict           # metric name -> unit


def load_benchmark(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def load_config(path: str) -> dict:
    cfg = _read_json(path)
    _only_keys(cfg, CONFIG_KEYS,
               {"name", "source", "params", "num_features", "published_rows",
                "data"}, path)
    check_name(cfg["name"], "configuration")
    _only_keys(cfg["data"], DATA_KEYS, {"generator"}, path + ":data")
    if not isinstance(cfg["params"], dict) or not cfg["params"]:
        raise ManifestError(f"{path}: params must be a non-empty object")
    if int(cfg["num_features"]) < 5:
        raise ManifestError(f"{path}: the generator's label reads features "
                            "0 to 4, so num_features is at least 5")
    return cfg


def load_traffic(path: str) -> dict:
    tr = _read_json(path)
    _only_keys(tr, TRAFFIC_KEYS,
               {"name", "rows", "warmup_iterations", "eval", "bagging"}, path)
    check_name(tr["name"], "traffic")
    if not isinstance(tr["rows"], int) or tr["rows"] < 1000:
        raise ManifestError(f"{path}: rows must be a whole number >= 1000")
    if tr["warmup_iterations"] != 1:
        raise ManifestError(f"{path}: warmup_iterations is 1: the first "
                            "iteration compiles and belongs to set-up")
    # the training job as run (valid sets, bagging) is a later cell: the
    # harness refuses what it would otherwise silently not do
    if tr["eval"] != "none" or tr["bagging"] != "none":
        raise ManifestError(f"{path}: only eval 'none' and bagging 'none' "
                            "are implemented")
    return tr


def _metric_cells(metric: dict, all_cells: list) -> list:
    return metric.get("workloads", all_cells)


def load_cell(name: str, root: str = ROOT) -> Cell:
    """Everything one cell runs with, found by its name."""
    check_name(name, "workload")
    bench = load_benchmark(root)
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise ManifestError(f"BENCHMARK.json names no workload {name!r}; "
                            f"it has {sorted(entries)}")
    entry = entries[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if entry["config"] not in configs:
        raise ManifestError(f"workload {name}: unknown configuration "
                            f"{entry['config']!r}")
    check_name(entry["traffic"], "traffic")
    config = load_config(os.path.join(root, configs[entry["config"]]["file"]))
    if config["name"] != entry["config"]:
        raise ManifestError(f"{configs[entry['config']]['file']} is the file "
                            f"of {config['name']!r}, not {entry['config']!r}")
    bench_dir = os.path.join(root, bench["paths"][0])
    traffic = load_traffic(
        os.path.join(bench_dir, "traffic", entry["traffic"] + ".json"))
    wpath = os.path.join(bench_dir, "workloads", name + ".json")
    wl = _read_json(wpath)
    _only_keys(wl, WORKLOAD_KEYS, WORKLOAD_KEYS, wpath)
    for key in ("name", "config", "traffic", "chips"):
        want = name if key == "name" else entry[key]
        if wl[key] != want:
            raise ManifestError(f"{wpath}: {key} is {wl[key]!r}, "
                                f"BENCHMARK.json says {want!r}")
    if wl["chips"] not in (1, 4):
        raise ManifestError(f"{wpath}: chips is 1 or 4")
    _only_keys(wl["check"], CHECK_KEYS, CHECK_KEYS, wpath + ":check")
    _only_keys(wl["limits"], set(NUMBERS), set(NUMBERS), wpath + ":limits")
    if wl["control"] not in reference.CONTROLS:
        raise ManifestError(f"{wpath}: control is one of "
                            f"{list(reference.CONTROLS)}")
    cells = sorted(entries)
    e2e = [m["name"] for m in bench["end_to_end"]
           if name in _metric_cells(m, cells)]
    layer = [m["name"] for m in bench["per_layer"]
             if name in _metric_cells(m, cells)]
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    for metric in e2e + layer:
        check_name(metric, "metric")
    return Cell(name=name, chips=int(wl["chips"]), config=config,
                traffic=traffic, check=wl["check"], control=wl["control"],
                limits=wl["limits"],
                end_to_end=e2e, per_layer=layer, units=units)
