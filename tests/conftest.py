"""Test configuration: force an 8-device virtual CPU mesh so sharding
paths are exercised without TPU hardware (chip_smoke.py --chips 4 is the
run on real chips)."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()
# The eight virtual devices share the CPU client's thread pools, which are
# sized to the host's cores (8 here): a collective blocks one pool thread
# per device in its rendezvous, and whatever else the program needs a
# thread for (a second independent collective, an intra-op parallel loop)
# then waits forever; XLA aborts the process after 40 s ("Expected 8
# threads to join the rendezvous"). Seen on
# test_scatter_bit_parity_uneven_features, 2 runs in 3 in isolation, at
# the parent of PR 21 too; it killed two xdist workers of a whole run.
# PJRT_NPROC sizes those pools; the extra threads only ever wait. Real
# chips do not share a thread pool.
os.environ.setdefault("PJRT_NPROC", "32")

import jax  # noqa: E402

# Persistent XLA compilation cache, keyed on HLO: every Booster builds
# fresh jit partials, so identical programs recompile once per TEST
# without it. The disk cache dedupes them within one pytest run (the
# in-memory jit cache is per-callable and can't) and across runs — a
# warm cache cuts JAX-heavy files by ~40-50% (measured on
# test_quantized: 75s cold/uncached -> 39s warm), which is what lets
# the full tier-1 sweep fit its timeout. Same directory as every other
# entry point (compile_cache.default_cache_dir: the environment's
# JAX_COMPILATION_CACHE_DIR, else the checkout-local .jax_cache).
# Opt out: LGBM_TPU_NO_JAX_CACHE=1.
if not os.environ.get("LGBM_TPU_NO_JAX_CACHE"):
    from lightgbm_tpu.compile_cache import default_cache_dir
    jax.config.update("jax_compilation_cache_dir", default_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def release_executables():
    """Drop the process's compiled programs after the test. An
    interpret-mode run of the multi-leaf histogram kernels compiles to an
    XLA:CPU executable of some 1,100 memory mappings, and a process may
    hold 65,530 (`vm.max_map_count`): tests/test_waved.py's step cases
    alone came to 46k, and the worker that had run them died in the next
    file's compiles (a segmentation fault inside XLA's compile, serialize
    or deserialize; PR 33). `jax.clear_caches()` gives the mappings back;
    what is needed again comes from the persistent cache."""
    yield
    jax.clear_caches()


# Quick tier (VERDICT r4 #9): `pytest -m quick` runs a <=15-min subset —
# one config per family + the semantics/unit tests — so verification
# stops competing with development; the full 2h+ grid stays the default
# `pytest tests/` (plus LGBM_TPU_FULL_CONSISTENCY=1 for the stochastic
# tier). Membership is per-module: every test in these files is cheap.
QUICK_FILES = {
    "test_binning.py", "test_bundling.py", "test_sparse.py",
    "test_native.py", "test_param_honesty.py", "test_objectives.py",
    "test_metrics.py", "test_model_io.py", "test_learner.py",
    "test_booster_surface.py", "test_ingestion.py", "test_waved.py",
    "test_predict_engine.py", "test_serve.py", "test_codegen.py",
    "test_bin_pack.py", "test_perf_gate.py", "test_memory_model.py",
    "test_obs_export.py", "test_health.py", "test_resilience.py",
    "test_stream.py", "test_coldstart.py", "test_profile.py",
    "test_fleet.py", "test_watchdog.py", "test_shap.py",
    "test_scatter.py", "test_partition_kernel.py",
}


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running test")
    config.addinivalue_line(
        "markers", "quick: <=15-min verification tier (see QUICK_FILES)")


def pytest_collection_modifyitems(config, items):
    import pytest as _pytest
    for item in items:
        if os.path.basename(str(item.fspath)) in QUICK_FILES:
            item.add_marker(_pytest.mark.quick)


@pytest.fixture
def rng():
    return np.random.RandomState(42)


def make_regression(n=1000, f=8, seed=0):
    r = np.random.RandomState(seed)
    X = r.randn(n, f)
    y = (X[:, 0] * 2.0 - X[:, 1] + 0.5 * X[:, 2] ** 2
         + 0.1 * r.randn(n)).astype(np.float32)
    return X, y


def make_binary(n=1000, f=8, seed=0):
    r = np.random.RandomState(seed)
    X = r.randn(n, f)
    logit = X[:, 0] + 0.5 * X[:, 1] ** 2 + 0.3 * X[:, 2] * X[:, 3]
    y = (logit + 0.2 * r.randn(n) > 0.5).astype(np.float32)
    return X, y


def make_multiclass(n=1200, f=8, k=4, seed=0):
    r = np.random.RandomState(seed)
    X = r.randn(n, f)
    centers = r.randn(k, f) * 2.0
    d = ((X[:, None, :] - centers[None]) ** 2).sum(-1)
    y = np.argmin(d + 0.5 * r.randn(n, k), axis=1).astype(np.float32)
    return X, y


def make_ranking(num_queries=50, docs_per_query=20, f=6, seed=0):
    r = np.random.RandomState(seed)
    n = num_queries * docs_per_query
    X = r.randn(n, f)
    rel = X[:, 0] + 0.5 * X[:, 1] + 0.3 * r.randn(n)
    y = np.zeros(n, np.float32)
    for q in range(num_queries):
        s = q * docs_per_query
        seg = rel[s:s + docs_per_query]
        qs = np.quantile(seg, [0.5, 0.75, 0.9])
        y[s:s + docs_per_query] = np.digitize(seg, qs)
    group = np.full(num_queries, docs_per_query)
    return X, y, group
