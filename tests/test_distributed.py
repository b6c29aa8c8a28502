"""Distributed (data-parallel) training over the virtual 8-device CPU mesh
(ref strategy: tests/distributed/_test_distributed.py DistributedMockup —
there via N localhost CLI processes + sockets; here via jax.sharding over
a forced multi-device host platform, which exercises the same program the
TPU mesh runs)."""

import numpy as np
import jax
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.metrics import _auc
from tests.conftest import make_binary, make_regression


@pytest.fixture(autouse=True)
def _require_multi_device():
    if len(jax.devices()) < 2:
        pytest.skip("needs multi-device (XLA_FLAGS host platform count)")


def test_mesh_has_8_devices():
    assert len(jax.devices()) == 8


def test_data_parallel_binary_quality():
    X, y = make_binary(2000)
    dtrain = lgb.Dataset(X, label=y)
    bst = lgb.train({"objective": "binary", "tree_learner": "data",
                     "num_leaves": 15, "min_data_in_leaf": 5,
                     "verbosity": -1},
                    dtrain, num_boost_round=20)
    assert _auc(y, bst.predict(X)) > 0.9


def test_data_parallel_matches_serial():
    """Distributed vs single-device training must agree (ref:
    _test_distributed.py:168 accuracy + prediction agreement check)."""
    X, y = make_regression(1024)
    params = {"objective": "regression", "num_leaves": 15,
              "min_data_in_leaf": 5, "verbosity": -1, "seed": 7}
    serial = lgb.train(dict(params), lgb.Dataset(X, label=y),
                       num_boost_round=10)
    parallel = lgb.train({**params, "tree_learner": "data"},
                         lgb.Dataset(X, label=y), num_boost_round=10)
    ps = serial.predict(X)
    pp = parallel.predict(X)
    # identical math; tolerance covers cross-shard reduction order
    np.testing.assert_allclose(pp, ps, rtol=1e-3, atol=1e-3)


def test_data_parallel_sharded_arrays():
    X, y = make_binary(512)
    dtrain = lgb.Dataset(X, label=y)
    bst = lgb.Booster({"objective": "binary", "tree_learner": "data",
                       "num_leaves": 7, "verbosity": -1}, dtrain)
    gbdt = bst._gbdt
    assert gbdt.mesh.size == 8
    # bins sharded along rows (axis 1)
    sharding = gbdt.bins_fm.sharding
    spec = sharding.spec
    assert spec[1] == "data"
    bst.update()
    assert bst.current_iteration() == 1


def test_data_parallel_num_shards_param():
    X, y = make_binary(512)
    bst = lgb.Booster({"objective": "binary", "tpu_num_shards": 4,
                       "num_leaves": 7, "verbosity": -1},
                      lgb.Dataset(X, label=y))
    assert bst._gbdt.mesh.size == 4
    bst.update()


def test_voting_and_feature_learner_accepted():
    X, y = make_binary(512)
    for tl in ("voting", "feature"):
        bst = lgb.train({"objective": "binary", "tree_learner": tl,
                         "num_leaves": 7, "verbosity": -1},
                        lgb.Dataset(X, label=y), num_boost_round=3)
        assert bst.num_trees() == 3


def test_voting_parallel_matches_serial_when_topk_covers_features():
    """With top_k >= F the voting filter keeps every feature, so PV-tree
    must reproduce the serial learner exactly."""
    X, y = make_regression(1024, 8)
    params = {"objective": "regression", "num_leaves": 15,
              "min_data_in_leaf": 5, "verbosity": -1, "top_k": 20,
              # the sharded learners grow exact leaf-wise; compare
              # against the serial EXACT grower, not the waved default
              "tpu_wave_max": 0}
    serial = lgb.train(dict(params), lgb.Dataset(X, label=y),
                       num_boost_round=10)
    voting = lgb.train({**params, "tree_learner": "voting"},
                       lgb.Dataset(X, label=y), num_boost_round=10)
    np.testing.assert_allclose(voting.predict(X), serial.predict(X),
                               rtol=1e-3, atol=1e-3)


def test_voting_parallel_small_topk_still_learns():
    """top_k < F: the candidate filter is actually binding (ref: PV-tree
    accuracy claim — voting loses little quality)."""
    X, y = make_binary(2048, 12)
    bst = lgb.train({"objective": "binary", "tree_learner": "voting",
                     "top_k": 2, "num_leaves": 15, "verbosity": -1},
                    lgb.Dataset(X, label=y), num_boost_round=15)
    assert bst._gbdt.mesh.size == 8
    assert _auc(y, bst.predict(X)) > 0.9


def test_feature_parallel_matches_serial_exactly():
    """Feature-parallel is exact: same candidate set, sharded search."""
    X, y = make_regression(1024, 10)
    params = {"objective": "regression", "num_leaves": 15,
              "min_data_in_leaf": 5, "verbosity": -1, "seed": 3,
              # serial baseline must be the EXACT grower (see above)
              "tpu_wave_max": 0}
    serial = lgb.train(dict(params), lgb.Dataset(X, label=y),
                       num_boost_round=10)
    fpar = lgb.train({**params, "tree_learner": "feature"},
                     lgb.Dataset(X, label=y), num_boost_round=10)
    np.testing.assert_allclose(fpar.predict(X), serial.predict(X),
                               rtol=1e-4, atol=1e-4)


def test_feature_parallel_uneven_feature_count():
    """F=13 not divisible by 8 shards: overlapping slices must stay
    correct."""
    X, y = make_binary(1024, 13)
    bst = lgb.train({"objective": "binary", "tree_learner": "feature",
                     "num_leaves": 15, "verbosity": -1},
                    lgb.Dataset(X, label=y), num_boost_round=10)
    assert _auc(y, bst.predict(X)) > 0.9


def test_voting_program_contains_collectives():
    """The compiled voting program must actually communicate: psum
    (all-reduce) for candidate histograms, all-gather for votes."""
    import jax.numpy as jnp
    from lightgbm_tpu.parallel.voting import make_sharded_voting_grow
    from lightgbm_tpu.parallel import mesh as mesh_lib

    X, y = make_binary(512, 8)
    bst = lgb.Booster({"objective": "binary", "tree_learner": "voting",
                       "num_leaves": 7, "verbosity": -1, "top_k": 2},
                      lgb.Dataset(X, label=y))
    g = bst._gbdt
    mesh = mesh_lib.get_mesh(8)
    grow = make_sharded_voting_grow(mesh, num_leaves=7,
                                    max_bins=g._static["max_bins"],
                                    top_k=2)
    hlo = grow.lower(
        g.bins_fm, jnp.zeros(512, jnp.float32), jnp.ones(512, jnp.float32),
        jnp.ones(512, jnp.float32), jnp.ones(8, bool), g.feature_meta,
        g.hp, jnp.int32(-1)).compile().as_text()
    assert "all-reduce" in hlo or "all-gather" in hlo, \
        "voting program lost its collectives"


def test_mesh_pallas_hist_matches_serial():
    """tpu_hist_impl=pallas under the 8-device mesh (interpret mode on
    CPU): the shard_map per-shard kernel + psum wrapper must reproduce
    single-device training (VERDICT r4 #5 — the flagship kernel on the
    flagship multi-chip configuration). N=1003 exercises the row padding
    to a mesh multiple."""
    X, y = make_regression(1003)
    params = {"objective": "regression", "num_leaves": 15,
              "min_data_in_leaf": 5, "verbosity": -1, "seed": 7,
              "tpu_hist_precision": "highest"}
    serial = lgb.train(dict(params), lgb.Dataset(X, label=y),
                       num_boost_round=8)
    mesh_pallas = lgb.train({**params, "tree_learner": "data",
                             "tpu_hist_impl": "pallas"},
                            lgb.Dataset(X, label=y), num_boost_round=8)
    np.testing.assert_allclose(mesh_pallas.predict(X), serial.predict(X),
                               rtol=1e-3, atol=1e-3)


def test_mesh_pallas_exact_grower_matches_serial():
    """Same check for the exact (per-split) grower path."""
    X, y = make_binary(1003)
    params = {"objective": "binary", "num_leaves": 15, "tpu_wave_max": 0,
              "min_data_in_leaf": 5, "verbosity": -1,
              "tpu_hist_precision": "highest"}
    serial = lgb.train(dict(params), lgb.Dataset(X, label=y),
                       num_boost_round=8)
    mesh_pallas = lgb.train({**params, "tree_learner": "data",
                             "tpu_hist_impl": "pallas"},
                            lgb.Dataset(X, label=y), num_boost_round=8)
    np.testing.assert_allclose(mesh_pallas.predict(X), serial.predict(X),
                               rtol=1e-3, atol=1e-3)


def test_mesh_quantized_int8_psum_matches_serial():
    """use_quantized_grad on the mesh: the int8 kernel runs per shard and
    the psum reduces INT32 histograms (exact integer accumulation across
    shards — ref: data_parallel_tree_learner.cpp:290-297 reduces integer
    bins). Same quantization RNG on both sides -> near-identical models."""
    X, y = make_binary(1003)
    params = {"objective": "binary", "num_leaves": 15,
              "min_data_in_leaf": 5, "verbosity": -1,
              "use_quantized_grad": True, "tpu_hist_impl": "pallas",
              "tpu_hist_precision": "highest"}
    serial = lgb.train(dict(params), lgb.Dataset(X, label=y),
                       num_boost_round=8)
    meshq = lgb.train({**params, "tree_learner": "data"},
                      lgb.Dataset(X, label=y), num_boost_round=8)
    np.testing.assert_allclose(meshq.predict(X), serial.predict(X),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("reduce,collective", [
    ("psum", "all_reduce"), ("scatter", "reduce_scatter")])
def test_mesh_quantized_reduce_is_integer_typed(reduce, collective):
    """Compiled-program proof that the quantized mesh reduction moves
    int32 histograms, not dequantized f32 (VERDICT r4 #8): the program's
    cross-shard collective — all_reduce for the psum oracle,
    reduce_scatter for the feature-sharded default — must carry s32
    operands."""
    import functools
    import jax.numpy as jnp

    X, y = make_binary(1024)
    bst = lgb.Booster({"objective": "binary", "tree_learner": "data",
                       "num_leaves": 7, "verbosity": -1,
                       "use_quantized_grad": True,
                       "tpu_hist_impl": "pallas",
                       "tpu_hist_reduce": reduce},
                      lgb.Dataset(X, label=y))
    g = bst._gbdt
    assert g._hist_reduce == reduce
    n = g.num_data
    grow = g._grow_partial()
    quant = (jnp.zeros(n, jnp.float32), jnp.ones(n, jnp.float32),
             jnp.float32(0.5), jnp.float32(0.5))
    lowered = jax.jit(functools.partial(grow, quant=quant)).lower(
        g.bins_fm, jnp.zeros(n, jnp.float32), jnp.ones(n, jnp.float32),
        jnp.ones(n, jnp.float32), jnp.ones(X.shape[1], bool),
        g.feature_meta, g.hp, jnp.int32(-1), None, None)
    # assert on the lowered program (CPU backend optimizations may later
    # rewrite the collective): the histogram collective must consume the
    # int8 kernel's output and reduce i32 tensors, with the f32
    # dequantize AFTER it
    shlo = lowered.as_text()
    assert collective in shlo, \
        f"quantized mesh grower lost its {collective}"
    assert "hist_pallas_multi_int8" in shlo, \
        "quantized mesh grower dropped the int8 pallas kernel"
    import re
    ar_types = []
    for chunk in shlo.split('stablehlo.' + collective)[1:]:
        m = re.search(r'\^bb0\(%\w+: tensor<(\w+)>', chunk)
        if m:
            ar_types.append(m.group(1))
    assert ar_types and all(t == "i32" for t in ar_types), \
        f"expected i32 {collective} reductions, got {ar_types}"


@pytest.mark.slow
def test_mono_pairwise_parallel_learners_match_serial():
    """monotone_constraints_method=advanced under all three parallel
    learners (VERDICT r4 #7): the pairwise leaf-box state is replicated
    and deterministic, so each learner must reproduce its serial-strategy
    result; previously these downgraded to the basic method with a
    warning. Ref: monotone_constraints.hpp:330 (the reference's factory
    is learner-agnostic too)."""
    X, y = make_regression(1024)
    params = {"objective": "regression", "num_leaves": 15,
              "min_data_in_leaf": 5, "verbosity": -1,
              "monotone_constraints": "1,-1,0,0,0,0,0,0",
              "monotone_constraints_method": "advanced",
              # the sharded voting/feature learners grow exact leaf-wise;
              # compare against the serial EXACT grower
              "tpu_wave_max": 0}
    serial = lgb.train(dict(params), lgb.Dataset(X, label=y),
                       num_boost_round=10)
    ps = serial.predict(X)
    # data-parallel: identical grower math under GSPMD
    dp = lgb.train({**params, "tree_learner": "data"},
                   lgb.Dataset(X, label=y), num_boost_round=10)
    np.testing.assert_allclose(dp.predict(X), ps, rtol=1e-3, atol=1e-3)
    # feature-parallel: exact same split sequence
    fp = lgb.train({**params, "tree_learner": "feature"},
                   lgb.Dataset(X, label=y), num_boost_round=10)
    np.testing.assert_allclose(fp.predict(X), ps, rtol=1e-4, atol=1e-4)
    # voting with top_k covering all features degenerates to data-parallel
    vp = lgb.train({**params, "tree_learner": "voting", "top_k": 8},
                   lgb.Dataset(X, label=y), num_boost_round=10)
    np.testing.assert_allclose(vp.predict(X), ps, rtol=1e-3, atol=1e-3)
    # and no downgrade warning fires
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        lgb.Booster({**params, "tree_learner": "voting", "top_k": 8,
                     "verbosity": -1}, lgb.Dataset(X, label=y))


def test_int8_mesh_psum_exact_parity():
    """The promoted-to-default int8 histogram path on the mesh: the
    per-shard int8 kernel + INT32 psum must reproduce the single-device
    int8 kernel EXACTLY — integer accumulation commutes across shards,
    unlike f32 (the point of reducing quantized histograms, ref:
    data_parallel_tree_learner.cpp:290-297)."""
    import jax.numpy as jnp
    from lightgbm_tpu.learner import _sharded_pallas_multi
    from lightgbm_tpu.ops.pallas_histogram import (hist_multi_int8_xla,
                                                   hist_pallas_multi_int8)
    from lightgbm_tpu.parallel import mesh as mesh_lib

    r = np.random.RandomState(4)
    n, f, b, slots = 1003, 5, 15, 8  # n not a mesh multiple: pads rows
    bins = jnp.asarray(r.randint(0, b, (f, n)), jnp.uint8)
    mask = (r.rand(n) < 0.8).astype(np.int8)
    ghT_i8 = jnp.asarray(np.stack([(r.randint(-3, 4, n) * mask),
                                   (r.randint(0, 5, n) * mask), mask],
                                  axis=1), jnp.int8)
    row_leaf = jnp.asarray(r.randint(0, slots, n), jnp.int32)
    ids = jnp.asarray(np.arange(slots, dtype=np.int32))

    mesh = mesh_lib.get_mesh(8)
    sharded = _sharded_pallas_multi(mesh, max_bins=b, precision="highest",
                                    int8=True)
    out_mesh = sharded(bins, ghT_i8, row_leaf, ids)
    out_single, _ = hist_pallas_multi_int8(bins, ghT_i8, row_leaf, ids,
                                           max_bins=b, num_slots=slots,
                                           interpret=True)
    out_xla = hist_multi_int8_xla(bins, ghT_i8, row_leaf, ids,
                                  max_bins=b, num_slots=slots)
    assert out_mesh.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(out_mesh),
                                  np.asarray(out_single))
    np.testing.assert_array_equal(np.asarray(out_mesh),
                                  np.asarray(out_xla))


def test_deterministic_hist_under_sharding():
    """deterministic_hist (Kahan fixed-chunk accumulation) must make
    data-parallel training track serial training TIGHTER than the
    plain-f32 1e-3 gate above — the reorders-safely-under-sharding
    property at the training level."""
    X, y = make_regression(1024)
    params = {"objective": "regression", "num_leaves": 15,
              "min_data_in_leaf": 5, "verbosity": -1, "seed": 7,
              "deterministic_hist": True}
    serial = lgb.train(dict(params), lgb.Dataset(X, label=y),
                       num_boost_round=10)
    parallel = lgb.train({**params, "tree_learner": "data"},
                         lgb.Dataset(X, label=y), num_boost_round=10)
    np.testing.assert_allclose(parallel.predict(X), serial.predict(X),
                               rtol=1e-4, atol=1e-4)


class TestInitDistributedRetry:
    """init_distributed's connect retry/backoff (ISSUE 17 satellite):
    the fleet-restart race — every worker execs at once, the
    coordinator binds last — must be absorbed by retries, and a dead
    coordinator must surface as a structured DistributedInitError a
    supervisor can match on."""

    @pytest.fixture(autouse=True)
    def _fresh_module_state(self, monkeypatch):
        from lightgbm_tpu.parallel import distributed as dist
        monkeypatch.setattr(dist, "_initialized", False)
        import time
        monkeypatch.setattr(time, "sleep", lambda s: None)
        yield
        dist._initialized = False

    def _patch_initialize(self, monkeypatch, fn):
        monkeypatch.setattr(jax.distributed, "initialize", fn)

    def test_retries_until_coordinator_comes_up(self, monkeypatch):
        from lightgbm_tpu.parallel import distributed as dist
        calls = []

        def flaky(**kwargs):
            calls.append(kwargs)
            if len(calls) < 3:
                raise RuntimeError("connection refused")

        self._patch_initialize(monkeypatch, flaky)
        dist.init_distributed("10.0.0.1:1234", num_processes=2,
                              process_id=0, connect_retries=4,
                              connect_backoff_s=0.0)
        assert len(calls) == 3
        assert dist.is_initialized()
        assert calls[0]["coordinator_address"] == "10.0.0.1:1234"

    def test_exhausted_retries_raise_structured_error(self,
                                                      monkeypatch):
        from lightgbm_tpu.parallel import distributed as dist
        from lightgbm_tpu.resilience.errors import DistributedInitError

        def dead(**kwargs):
            raise RuntimeError("connection refused")

        self._patch_initialize(monkeypatch, dead)
        with pytest.raises(DistributedInitError) as ei:
            dist.init_distributed("10.0.0.1:1234", num_processes=2,
                                  process_id=0, connect_retries=2,
                                  connect_backoff_s=0.0)
        assert ei.value.attempts == 3
        assert isinstance(ei.value.last_error, RuntimeError)
        assert not dist.is_initialized()

    def test_misconfiguration_is_not_retried(self, monkeypatch):
        from lightgbm_tpu.parallel import distributed as dist
        calls = []

        def misconfigured(**kwargs):
            calls.append(kwargs)
            raise ValueError("bad coordinator address")

        self._patch_initialize(monkeypatch, misconfigured)
        with pytest.raises(ValueError, match="bad coordinator"):
            dist.init_distributed("nonsense", num_processes=2,
                                  process_id=0, connect_retries=5)
        assert len(calls) == 1  # retrying cannot fix a config error

    def test_already_initialized_runtime_is_adopted(self, monkeypatch):
        from lightgbm_tpu.parallel import distributed as dist

        def already(**kwargs):
            raise RuntimeError(
                "Distributed runtime is already initialized")

        self._patch_initialize(monkeypatch, already)
        dist.init_distributed("10.0.0.1:1234", num_processes=2,
                              process_id=0)
        assert dist.is_initialized()

    def test_second_call_is_idempotent(self, monkeypatch):
        from lightgbm_tpu.parallel import distributed as dist
        calls = []
        self._patch_initialize(
            monkeypatch, lambda **kw: calls.append(kw))
        dist.init_distributed("10.0.0.1:1234", num_processes=2,
                              process_id=0)
        dist.init_distributed("10.0.0.1:1234", num_processes=2,
                              process_id=0)
        assert len(calls) == 1

    def test_backoff_schedule_is_deterministic_and_capped(self):
        from lightgbm_tpu.resilience.degrade import backoff_delays
        assert backoff_delays(4, 0.5, cap_s=10.0) == \
            [0.5, 1.0, 2.0, 4.0]
        assert backoff_delays(6, 0.5, cap_s=2.0) == \
            [0.5, 1.0, 2.0, 2.0, 2.0, 2.0]
