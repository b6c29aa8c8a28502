"""Warm start everywhere (ISSUE 14): persistent compile-cache policy +
serialized AOT serving artifacts.

Covers:
- ``compile_cache`` policy semantics: auto respects an existing
  configuration (the conftest's), off never touches jax config, on
  forces a directory, JAX_COMPILATION_CACHE_DIR outranks all of them;
  the donation policy (``donation_allowed``: on unless env force-off);
- cache hygiene: the LRU prune caps the directory, oldest entries
  first, env-tunable, unbounded = no-op;
- serialized artifacts (serve/artifacts.py): export/restore round trip
  is bit-identical with ZERO serve/lowlat compiles, warm() is
  idempotent per (bucket, width), a foreign fingerprint or a corrupt
  artifact transparently falls back to a fresh compile (counted), and
  predictions are bit-identical either way;
- second-process warm start: the same small train in two fresh
  interpreters sharing a fresh cache dir — the warm rerun HITS the
  persistent cache and its real compile seconds collapse (obs/xla
  attributes cache hits to ``cache_load_s_total``);
- the quick-tier tools: perf-gate check 10 units + the
  tools/check_coldstart.py validator wiring.
"""

import json
import os
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import compile_cache
from lightgbm_tpu.config import Config
from lightgbm_tpu.obs.metrics import global_metrics
from lightgbm_tpu.serve import (ModelRegistry, SERVE_LOWLAT_TAG,
                                serialize_available)
from lightgbm_tpu.serve import artifacts as artifacts_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")
for _p in (REPO, TOOLS):
    if _p not in sys.path:
        sys.path.insert(0, _p)

_F = 6


@pytest.fixture(scope="module")
def model_str():
    r = np.random.RandomState(3)
    X = r.randn(500, _F)
    y = (X[:, 0] + 0.4 * X[:, 1] ** 2 > 0.2).astype(np.float32)
    params = dict(objective="binary", num_leaves=7, max_bin=31,
                  min_data_in_leaf=5, verbosity=-1)
    ds = lgb.Dataset(X, label=y, params=params)
    ds.construct()
    return lgb.train(params, ds, num_boost_round=3).model_to_string()


class TestCompileCachePolicy:
    def test_auto_respects_existing_configuration(self):
        # conftest armed the cache for the whole test process; auto at
        # a later entry (every Booster/train call) must be a no-op
        import jax
        before = jax.config.jax_compilation_cache_dir
        assert before, "test process should run with the conftest cache"
        assert compile_cache.configure("auto") is True
        assert jax.config.jax_compilation_cache_dir == before

    def test_off_never_touches(self):
        import jax
        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.configure("off") is False
        assert jax.config.jax_compilation_cache_dir == before

    def test_on_forces_dir(self, tmp_path):
        import jax
        before = jax.config.jax_compilation_cache_dir
        try:
            assert compile_cache.configure("on", str(tmp_path)) is True
            assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        finally:
            compile_cache.configure("on", before)

    def test_unknown_mode_treated_as_auto(self):
        import jax
        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.configure("bogus") is True
        assert jax.config.jax_compilation_cache_dir == before

    def test_cache_active_reports_jax_config(self):
        assert compile_cache.cache_active() is True  # conftest armed it

    @pytest.mark.parametrize("cache_on", [True, False])
    @pytest.mark.parametrize("env_off", [True, False])
    def test_donation_policy(self, monkeypatch, cache_on, env_off):
        # donation no longer depends on the cache (the jaxlib<=0.4.36
        # crash it was gated on is gone with that jaxlib): only the env
        # force-off drops it
        monkeypatch.setattr(compile_cache, "cache_active",
                            lambda: cache_on)
        if env_off:
            monkeypatch.setenv("LGBM_TPU_NO_DONATE", "1")
        else:
            monkeypatch.delenv("LGBM_TPU_NO_DONATE", raising=False)
        assert compile_cache.donation_allowed() is (not env_off)

    def test_default_dir_resolution(self, monkeypatch):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/tmp/xyz_cc")
        assert compile_cache.default_cache_dir() == "/tmp/xyz_cc"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert compile_cache.default_cache_dir() == \
            compile_cache.repo_cache_dir()

    def test_env_dir_outranks_knob_and_mode(self, monkeypatch, tmp_path):
        # where JAX_COMPILATION_CACHE_DIR is set, that directory is the
        # cache: neither "on" + tpu_compile_cache_dir nor an earlier
        # jax.config setting may place it elsewhere
        import jax
        before = jax.config.jax_compilation_cache_dir
        env_dir = str(tmp_path / "from_env")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        try:
            for mode, knob in (("on", str(tmp_path / "knob")),
                               ("auto", None)):
                assert compile_cache.configure(mode, knob) is True
                assert jax.config.jax_compilation_cache_dir == env_dir
            assert not (tmp_path / "knob").exists()
        finally:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
            compile_cache.configure("on", before)

    def test_conftest_cache_is_not_a_temp_path(self):
        # the test cache is the env's directory or the checkout-local
        # one — never derived from a temp name, pid or time
        import jax
        assert jax.config.jax_compilation_cache_dir == \
            compile_cache.default_cache_dir()

    def test_knob_aliases(self):
        cfg = Config.from_params({"compile_cache": "off",
                                  "compile_cache_dir": "/tmp/d",
                                  "artifact_dir": "/tmp/a"})
        assert cfg.tpu_compile_cache == "off"
        assert cfg.tpu_compile_cache_dir == "/tmp/d"
        assert cfg.serve_artifact_dir == "/tmp/a"
        assert Config.from_params({}).tpu_compile_cache == "auto"


class TestCachePrune:
    def _fill(self, root, sizes):
        os.makedirs(root, exist_ok=True)
        paths = []
        for i, size in enumerate(sizes):
            p = os.path.join(root, f"f{i}.bin")
            with open(p, "wb") as fh:
                fh.write(b"x" * size)
            # strictly increasing mtimes: f0 oldest
            os.utime(p, (1000 + i, 1000 + i))
            paths.append(p)
        return paths

    def test_prune_caps_and_removes_oldest_first(self, tmp_path):
        root = str(tmp_path / "cache")
        paths = self._fill(root, [100, 100, 100, 100])
        removed = compile_cache.prune_cache(root, max_bytes=250)
        assert removed == 200
        assert not os.path.exists(paths[0])
        assert not os.path.exists(paths[1])
        assert os.path.exists(paths[2]) and os.path.exists(paths[3])
        assert compile_cache.cache_size_bytes(root) == 200

    def test_prune_unbounded_is_noop(self, tmp_path):
        root = str(tmp_path / "cache")
        paths = self._fill(root, [100, 100])
        assert compile_cache.prune_cache(root, max_bytes=0) == 0
        assert all(os.path.exists(p) for p in paths)

    def test_prune_under_cap_is_noop(self, tmp_path):
        root = str(tmp_path / "cache")
        self._fill(root, [100])
        assert compile_cache.prune_cache(root, max_bytes=1000) == 0

    def test_prune_env_tunable(self, tmp_path, monkeypatch):
        root = str(tmp_path / "cache")
        self._fill(root, [100, 100])
        monkeypatch.setenv("LGBM_TPU_COMPILE_CACHE_MAX_BYTES", "150")
        assert compile_cache.prune_cache(root) == 100

    def test_prune_missing_dir_is_safe(self, tmp_path):
        assert compile_cache.prune_cache(str(tmp_path / "nope"),
                                         max_bytes=1) == 0


@pytest.mark.skipif(not serialize_available(),
                    reason="no executable serialization on this jax")
class TestArtifactStore:
    def test_roundtrip_after_eviction_zero_compiles(self, tmp_path,
                                                    model_str):
        reg = ModelRegistry(artifact_dir=str(tmp_path))
        entry = reg.load("m", model_str=model_str)
        n = entry.lowlat.warm(_F)
        assert n == len(entry.lowlat.buckets())
        assert len(os.listdir(str(tmp_path))) == n
        req = np.random.RandomState(0).randn(5, _F)
        ref = entry.lowlat(req)

        entry.drop_packs()  # LRU eviction drops packs + executables
        c0 = global_metrics.recompiles(SERVE_LOWLAT_TAG)
        loads0 = global_metrics.counters.get("serve/aot_loads", 0)
        entry.lowlat.warm(_F)
        assert global_metrics.recompiles(SERVE_LOWLAT_TAG) - c0 == 0
        assert global_metrics.counters.get("serve/aot_loads",
                                           0) - loads0 == n
        assert np.array_equal(ref, entry.lowlat(req))

    def test_fresh_registry_restores_from_disk(self, tmp_path, model_str):
        reg_a = ModelRegistry(artifact_dir=str(tmp_path))
        entry_a = reg_a.load("m", model_str=model_str)
        entry_a.lowlat.warm(_F)
        req = np.random.RandomState(1).randn(3, _F)
        ref = entry_a.lowlat(req)
        # the replica-restart twin: nothing shared but the directory
        reg_b = ModelRegistry(artifact_dir=str(tmp_path))
        entry_b = reg_b.load("m", model_str=model_str)
        c0 = global_metrics.recompiles(SERVE_LOWLAT_TAG)
        entry_b.lowlat.warm(_F)
        assert global_metrics.recompiles(SERVE_LOWLAT_TAG) - c0 == 0
        assert np.array_equal(ref, entry_b.lowlat(req))

    def test_warm_is_idempotent(self, tmp_path, model_str):
        reg = ModelRegistry(artifact_dir=str(tmp_path))
        entry = reg.load("m", model_str=model_str)
        entry.lowlat.warm(_F)
        c0 = global_metrics.recompiles(SERVE_LOWLAT_TAG)
        loads0 = global_metrics.counters.get("serve/aot_loads", 0)
        entry.lowlat.warm(_F)  # everything resident: no compile, no load
        assert global_metrics.recompiles(SERVE_LOWLAT_TAG) - c0 == 0
        assert global_metrics.counters.get("serve/aot_loads",
                                           0) - loads0 == 0

    def test_warm_idempotent_without_store_too(self, model_str):
        reg = ModelRegistry()  # no artifact dir
        entry = reg.load("m", model_str=model_str)
        entry.lowlat.warm(_F)
        c0 = global_metrics.recompiles(SERVE_LOWLAT_TAG)
        entry.lowlat.warm(_F)
        assert global_metrics.recompiles(SERVE_LOWLAT_TAG) - c0 == 0

    def test_export_artifacts_explicit(self, tmp_path, model_str):
        reg = ModelRegistry(artifact_dir=str(tmp_path))
        entry = reg.load("m", model_str=model_str)
        n = entry.lowlat.export_artifacts(_F)
        assert n == len(entry.lowlat.buckets())
        assert len([f for f in os.listdir(str(tmp_path))
                    if f.endswith(".aotx")]) == n

    def test_no_store_without_dir(self):
        assert artifacts_mod.open_store("") is None
        assert artifacts_mod.open_store(None) is None

    def test_fingerprint_mismatch_recompiles_bit_identical(
            self, tmp_path, model_str):
        reg_a = ModelRegistry(artifact_dir=str(tmp_path))
        entry_a = reg_a.load("m", model_str=model_str)
        entry_a.lowlat.warm(_F)
        req = np.random.RandomState(2).randn(4, _F)
        ref = entry_a.lowlat(req)
        orig = artifacts_mod.ARTIFACT_VERSION
        artifacts_mod.ARTIFACT_VERSION = orig + 1  # "new jaxlib" replica
        try:
            reg_b = ModelRegistry(artifact_dir=str(tmp_path))
            entry_b = reg_b.load("m", model_str=model_str)
            c0 = global_metrics.recompiles(SERVE_LOWLAT_TAG)
            entry_b.lowlat.warm(_F)
            assert global_metrics.recompiles(SERVE_LOWLAT_TAG) - c0 > 0
            assert np.array_equal(ref, entry_b.lowlat(req))
        finally:
            artifacts_mod.ARTIFACT_VERSION = orig

    def test_corrupt_artifact_falls_back(self, tmp_path, model_str):
        reg_a = ModelRegistry(artifact_dir=str(tmp_path))
        entry_a = reg_a.load("m", model_str=model_str)
        entry_a.lowlat.warm(_F)
        req = np.random.RandomState(4).randn(2, _F)
        ref = entry_a.lowlat(req)
        for name in os.listdir(str(tmp_path)):
            with open(os.path.join(str(tmp_path), name), "wb") as fh:
                fh.write(b"not an artifact")
        fails0 = global_metrics.counters.get("serve/aot_load_failures", 0)
        reg_b = ModelRegistry(artifact_dir=str(tmp_path))
        entry_b = reg_b.load("m", model_str=model_str)
        c0 = global_metrics.recompiles(SERVE_LOWLAT_TAG)
        entry_b.lowlat.warm(_F)
        assert global_metrics.recompiles(SERVE_LOWLAT_TAG) - c0 > 0
        assert global_metrics.counters.get("serve/aot_load_failures",
                                           0) > fails0
        assert np.array_equal(ref, entry_b.lowlat(req))

    def test_mutated_model_digest_never_loads_stale(self, tmp_path):
        from lightgbm_tpu.serve.lowlat import LowLatencyPredictor
        import bench as bench_mod
        rng = np.random.RandomState(5)
        trees = bench_mod._random_trees(rng, 4, 7, _F)
        p1 = LowLatencyPredictor(trees, 1, artifact_dir=str(tmp_path))
        p1.warm(_F)
        # a retrained twin: same shapes, different leaf values
        trees2 = bench_mod._random_trees(np.random.RandomState(6), 4, 7,
                                         _F)
        p2 = LowLatencyPredictor(trees2, 1, artifact_dir=str(tmp_path))
        c0 = global_metrics.recompiles(SERVE_LOWLAT_TAG)
        p2.warm(_F)
        assert global_metrics.recompiles(SERVE_LOWLAT_TAG) - c0 > 0, \
            "a different model's artifacts must never be loaded"


class TestSecondProcessWarmStart:
    def test_warm_rerun_hits_cache_and_compiles_near_zero(self, tmp_path):
        import bench as bench_mod
        os.environ["COLDSTART_ITERS"] = "2"
        os.environ["COLDSTART_LEAVES"] = "15"
        try:
            cold = bench_mod._coldstart_child_run(str(tmp_path), 3000)
            warm = bench_mod._coldstart_child_run(str(tmp_path), 3000)
        finally:
            os.environ.pop("COLDSTART_ITERS", None)
            os.environ.pop("COLDSTART_LEAVES", None)
        assert cold["compile_s_total"] > 0
        assert cold.get("n_cache_hits", 0) == 0
        assert warm.get("n_cache_hits", 0) > 0, \
            f"warm rerun never hit the persistent cache: {warm}"
        # "compile_s_total ~ 0": everything the warm process acquired
        # came off disk (attributed to cache_load_s_total instead)
        assert warm["compile_s_total"] <= \
            max(0.2 * cold["compile_s_total"], 0.05), (cold, warm)

    def test_bench_mode_registered(self):
        import bench as bench_mod
        assert bench_mod.parse_bench_mode(["--coldstart"]) == "coldstart"
        assert "coldstart" in bench_mod._MODE_MEASURE

    def test_parent_imports_initialise_no_backend(self):
        """A chip belongs to one process at a time, so the --coldstart
        parent must reach its two children without having initialised a
        backend: everything _measure_coldstart imports first is
        import-only (bench.py says so in a comment; this holds it)."""
        import subprocess
        from lightgbm_tpu.hostenv import cpu_child_env
        code = (
            "import sys; sys.path.insert(0, sys.argv[1])\n"
            "import jax, bench\n"
            "from lightgbm_tpu.model_io import LoadedModel\n"
            "from lightgbm_tpu.obs.metrics import global_metrics\n"
            "from lightgbm_tpu.serve import (ModelRegistry, ModelServer,"
            " SERVE_LOWLAT_TAG, serialize_available)\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, xla_bridge._backends\n")
        out = subprocess.run([sys.executable, "-c", code, REPO],
                             env=cpu_child_env(), capture_output=True,
                             text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]


class TestGateCheck10:
    def _floor(self):
        return {"coldstart": {"min_compile_reduction": 5.0,
                              "max_warm_acquire_s": 5.0,
                              "max_restore_lowlat_compiles": 0}}

    def _candidate(self, tmp_path, cold=10.0, warm=0.1, load=1.0,
                   restore_compiles=0, bit_identical=True,
                   serialize=True):
        rec = {"metric": "coldstart_compile_reduction", "value": 1.0,
               "unit": "x (platform=cpu)", "vs_baseline": 1.0,
               "coldstart": {
                   "cold_compile_s": cold, "warm_compile_s": warm,
                   "warm_cache_load_s": load,
                   "artifact_serialize_available": serialize,
                   "restore_lowlat_compiles": restore_compiles,
                   "restore_aot_loads": 7,
                   "restore_bit_identical": bit_identical}}
        p = tmp_path / "BENCH_cand.json"
        p.write_text(json.dumps(rec))
        return str(p)

    def test_gate_passes(self, tmp_path):
        import check_perf_gate
        failures = []
        check_perf_gate.check_coldstart(self._floor(), failures,
                                        self._candidate(tmp_path))
        assert failures == []

    def test_gate_fails_weak_reduction(self, tmp_path):
        import check_perf_gate
        failures = []
        check_perf_gate.check_coldstart(
            self._floor(), failures,
            self._candidate(tmp_path, cold=2.0, warm=1.0))
        assert any("not biting" in f for f in failures)

    def test_gate_fails_acquire_ceiling(self, tmp_path):
        import check_perf_gate
        failures = []
        check_perf_gate.check_coldstart(
            self._floor(), failures,
            self._candidate(tmp_path, cold=100.0, warm=0.5, load=6.0))
        assert any("ratchet ceiling" in f for f in failures)

    def test_gate_fails_restore_compiles(self, tmp_path):
        import check_perf_gate
        failures = []
        check_perf_gate.check_coldstart(
            self._floor(), failures,
            self._candidate(tmp_path, restore_compiles=7))
        assert any("not restoring" in f for f in failures)

    def test_gate_fails_parity(self, tmp_path):
        import check_perf_gate
        failures = []
        check_perf_gate.check_coldstart(
            self._floor(), failures,
            self._candidate(tmp_path, bit_identical=False))
        assert any("bit-identical" in f for f in failures)

    def test_gate_skips_restore_without_serialization(self, tmp_path):
        import check_perf_gate
        failures = []
        check_perf_gate.check_coldstart(
            self._floor(), failures,
            self._candidate(tmp_path, restore_compiles=7,
                            serialize=False))
        assert failures == []

    def test_gate_skips_without_floor_or_bench(self, tmp_path):
        import check_perf_gate
        failures = []
        check_perf_gate.check_coldstart({}, failures, None)
        empty = tmp_path / "BENCH_none.json"
        empty.write_text(json.dumps({"metric": "x"}))
        check_perf_gate.check_coldstart(self._floor(), failures,
                                        str(empty))
        assert failures == []


class TestObsSplit:
    def test_summary_separates_compiles_from_cache_hits(self):
        from lightgbm_tpu.obs.xla import XlaIntrospector
        reg = XlaIntrospector()
        reg.note_compile("t", "train", "s", 2.0, object(), trace_s=1.0)
        reg.note_compile("t", "train", "s", 0.5, object(), trace_s=1.0,
                         cache_hit=True)
        s = reg.summary()
        assert s["compile_s_total"] == 2.0
        assert s["cache_load_s_total"] == 0.5
        assert s["n_cache_hits"] == 1
        assert s["trace_s_total"] == 2.0
        assert s["by_tag"]["t"]["compile_s"] == 2.0
        assert s["by_tag"]["t"]["cache_load_s"] == 0.5


class TestToolsWiring:
    @pytest.mark.slow
    def test_check_coldstart_tool(self):
        import check_coldstart
        assert check_coldstart.main() == 0
