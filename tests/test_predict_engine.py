"""Tree-parallel inference engine (ops/predict.py): parity, chunk-shape
recompile stability, incremental packing, sharded predict, knob plumbing.

Parity tiers:
- vmapped/batched traversal vs the per-tree scan it replaced must be
  BIT-identical (same f32 accumulation order by construction)
- save/load round trips run the identical XLA program -> bit-equal
- predict_leaf_index vs the pure-NumPy host traversal oracle
  (tree.py Tree.predict_leaf)
"""

import numpy as np
import jax.numpy as jnp
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs.metrics import global_metrics
from lightgbm_tpu.ops import predict as pred_ops
from lightgbm_tpu.ops.predict import (
    EnsemblePacker, PREDICT_TRACE_TAG, pack_ensemble, predict_leaf_index,
    predict_raw_multiclass, predict_raw_scan)

pytestmark = pytest.mark.quick


def _data(n=400, f=8, seed=0, nans=False, zeros=False, cats=False):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, f)
    if cats:
        x[:, 0] = rng.randint(0, 12, n)  # categorical columns
        x[:, 1] = rng.randint(0, 5, n)
    if nans:
        x[::7, 2] = np.nan
    if zeros:
        x[::5, 3] = 0.0
    y = ((np.nan_to_num(x[:, 2]) + x[:, 4]
          + (x[:, 0] % 3 == 1) * 2.0 + (x[:, 1] == 2) * 1.5)
         > 1.0).astype(np.float64)
    return x, y


def _train(x, y, extra=None, rounds=10, categorical=None):
    params = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
              "verbosity": -1}
    params.update(extra or {})
    ds = lgb.Dataset(x, label=y, params=params,
                     categorical_feature=categorical or "auto")
    return lgb.train(params, ds, num_boost_round=rounds)


def _trees(bst):
    return [t for it in bst._gbdt.models for t in it]


# ----------------------------------------------------------------------
# parity: engine vs the per-tree scan path it replaced
class TestTraversalParity:
    def test_binary_bit_identical_to_scan(self):
        x, y = _data(nans=True)
        bst = _train(x, y)
        ens = pack_ensemble(_trees(bst))
        xb = jnp.asarray(x, jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(predict_raw_multiclass(ens, xb)),
            np.asarray(predict_raw_scan(ens, xb)))

    def test_categorical_bit_identical_to_scan(self):
        x, y = _data(cats=True, nans=True)
        bst = _train(x, y, {"min_data_per_group": 2, "cat_smooth": 1.0},
                     categorical=[0, 1])
        trees = _trees(bst)
        assert any(t.num_cat > 0 for t in trees), "no categorical splits"
        ens = pack_ensemble(trees)
        assert ens.has_categorical
        xb = jnp.asarray(x, jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(predict_raw_multiclass(ens, xb)),
            np.asarray(predict_raw_scan(ens, xb)))

    @pytest.mark.slow
    def test_multiclass_single_program_bit_identical(self):
        x, _ = _data(n=600)
        rng = np.random.RandomState(3)
        y = rng.randint(0, 3, 600).astype(np.float64)
        bst = _train(x, y, {"objective": "multiclass", "num_class": 3,
                            "num_leaves": 7}, rounds=6)
        trees = _trees(bst)
        ens = pack_ensemble(trees, 3)
        xb = jnp.asarray(x, jnp.float32)
        out = np.asarray(predict_raw_multiclass(ens, xb))
        assert out.shape == (600, 3)
        np.testing.assert_array_equal(out,
                                      np.asarray(predict_raw_scan(ens, xb)))

    def test_leaf_index_vs_numpy_host_oracle(self):
        x, y = _data(cats=True, nans=True, zeros=True)
        bst = _train(x, y, {"min_data_per_group": 2}, categorical=[0, 1])
        trees = _trees(bst)
        ens = pack_ensemble(trees)
        leaves = np.asarray(predict_leaf_index(ens,
                                               jnp.asarray(x, jnp.float32)))
        oracle = np.stack([t.predict_leaf(np.asarray(x, np.float64))
                           for t in trees], axis=1)
        np.testing.assert_array_equal(leaves, oracle)


# ----------------------------------------------------------------------
# save/load bit-equality through the shared engine
class TestSaveLoadParity:
    @pytest.mark.parametrize("variant", ["missing_none", "missing_nan",
                                         "missing_zero"])
    def test_roundtrip_bit_equal_all_missing_types(self, variant):
        x, y = _data(cats=True, nans=variant == "missing_nan")
        extra = {"min_data_per_group": 2}
        if variant == "missing_zero":
            extra["zero_as_missing"] = True
        elif variant == "missing_none":
            extra["use_missing"] = False
        bst = _train(x, y, extra, categorical=[0, 1])
        assert any(t.num_cat > 0 for t in _trees(bst))
        loaded = lgb.Booster(model_str=bst.model_to_string())
        xq = np.ascontiguousarray(x[::3])
        np.testing.assert_array_equal(bst.predict(xq, raw_score=True),
                                      loaded.predict(xq, raw_score=True))

    def test_engine_output_unchanged_by_chunking(self):
        x, y = _data(n=700)
        bst = _train(x, y)
        full = bst.predict(x, raw_score=True)
        for chunk in (64, 100, 1024):
            np.testing.assert_array_equal(
                full, bst.predict(x, raw_score=True,
                                  tpu_predict_chunk=chunk))


# ----------------------------------------------------------------------
# chunk-shape stability: uneven N must never trigger a fresh JIT
class TestRecompileStability:
    def test_no_recompile_across_chunk_shapes(self):
        from lightgbm_tpu.ops.predict import _row_bucket
        chunk = 256
        x, y = _data(n=1200)
        bst = _train(x, y, {"tpu_predict_chunk": chunk})
        xt = np.random.RandomState(5).randn(1600, x.shape[1])
        # warm the (small, bounded) bucket set by predicting once at
        # each bucket size — exactly what the first requests of a
        # serving process do
        uneven = (257, 300, 511, 700, 1000, 1023, 777, 1500, 41, 39)
        buckets = {_row_bucket(n % chunk or chunk, chunk, None)
                   for n in uneven} | {chunk}
        for b in sorted(buckets):
            bst.predict(xt[:b], raw_score=True)
        warm = global_metrics.recompiles(PREDICT_TRACE_TAG)
        out_even = bst.predict(xt[:1024], raw_score=True)
        # every N here is NOT divisible by the 256-row chunk; none may
        # compile a fresh traversal program
        for n in uneven:
            bst.predict(xt[:n], raw_score=True)
        assert global_metrics.recompiles(PREDICT_TRACE_TAG) == warm, \
            "uneven chunk tails recompiled the traversal program"
        # and the outputs stay bit-stable while shapes bucket
        np.testing.assert_array_equal(out_even,
                                      bst.predict(xt[:1024], raw_score=True))

    def test_bucket_count_is_bounded(self):
        from lightgbm_tpu.ops.predict import _row_bucket
        buckets = {_row_bucket(r, 1 << 20, None) for r in
                   range(1, 1 << 20, 997)}
        assert len(buckets) <= 4 + 16 + 16  # pow2 tiers + grain multiples


# ----------------------------------------------------------------------
# incremental packing: per-iteration eval must not repack all T trees
class TestIncrementalPacking:
    def test_training_eval_packs_linear_not_quadratic(self):
        x, y = _data(n=800)
        params = {"objective": "binary", "num_leaves": 15,
                  "min_data_in_leaf": 5, "verbosity": -1}
        bst = lgb.Booster(params, lgb.Dataset(x, label=y, params=params))
        iters = 24
        xq = x[:64]
        for _ in range(iters):
            bst.update()
            bst.predict(xq, raw_score=True)  # per-iteration eval predict
        packers = list(bst._gbdt._packers.values())
        assert len(packers) == 1
        pk = packers[0]
        quadratic = iters * (iters + 1) // 2
        # amortized-doubling bound: ~3T packs total, nowhere near O(T^2)
        assert pk.trees_packed <= 4 * iters < quadratic
        # steady state appends exactly the K new trees per iteration
        before = pk.trees_packed
        bst.update()
        bst.predict(xq, raw_score=True)
        assert pk.trees_packed - before == 1

    def test_packer_detects_mutation_and_rollback(self):
        x, y = _data()
        bst = _train(x, y, rounds=6)
        p0 = bst.predict(x, raw_score=True)
        gbdt = bst._gbdt
        # rollback truncates the packed tail rather than serving it stale
        gbdt.rollback_one_iter()
        p1 = bst.predict(x, raw_score=True)
        assert not np.array_equal(p0, p1)
        # in-place leaf mutation (the DART-normalize shape: past trees
        # rescaled while the model keeps evolving) bumps pack_version,
        # so the next key change repacks the mutated prefix instead of
        # incrementally appending past it
        tree = gbdt.models[0][0]
        v0 = tree.pack_version
        tree.apply_shrinkage(0.5)
        assert tree.pack_version == v0 + 1
        host_expect = gbdt._predict_raw_host(np.asarray(x, np.float64), 0,
                                             len(gbdt.models))
        gbdt._packed_key = None  # out-of-band edit -> capi invalidation
        p2 = bst.predict(x, raw_score=True)
        assert not np.array_equal(p1, p2)
        np.testing.assert_allclose(p2, host_expect[:, 0], rtol=1e-6,
                                   atol=1e-7)

    def test_one_shot_pack_is_exact_shape(self):
        x, y = _data()
        bst = _train(x, y, rounds=5)
        trees = _trees(bst)
        ens = pack_ensemble(trees)
        assert ens.split_feature.shape[0] == len(trees) == ens.num_trees
        packer = EnsemblePacker()
        padded = packer.update(trees, 1)  # serving packer: exact first pack
        assert padded.split_feature.shape[0] == len(trees)


# ----------------------------------------------------------------------
# mesh-sharded predict
class TestShardedPredict:
    def test_sharded_bit_identical(self):
        import jax
        if len(jax.devices()) < 2:
            pytest.skip("needs multi-device (XLA_FLAGS host platform count)")
        x, y = _data(n=900)
        bst = _train(x, y)
        xt = np.random.RandomState(7).randn(1003, x.shape[1])  # odd N
        p_serial = bst.predict(xt, raw_score=True)
        bst._gbdt.config.tpu_num_shards = 4
        bst._gbdt._packed_key = None  # drop the serial-program cache
        try:
            p_sharded = bst.predict(xt, raw_score=True)
        finally:
            bst._gbdt.config.tpu_num_shards = 0
        np.testing.assert_array_equal(p_serial, p_sharded)


# ----------------------------------------------------------------------
# knob plumbing + serving telemetry + backend sniff
class TestPlumbingAndTelemetry:
    def test_chunk_knob_param_and_alias(self):
        x, y = _data(n=500)
        bst = _train(x, y, {"tpu_predict_chunk": 128})
        assert bst._gbdt.config.tpu_predict_chunk == 128
        alias = _train(x, y, {"predict_chunk": 99})
        assert alias._gbdt.config.tpu_predict_chunk == 99
        np.testing.assert_array_equal(bst.predict(x, raw_score=True),
                                      alias.predict(x, raw_score=True))

    def test_chunk_knob_reaches_loaded_model(self):
        x, y = _data()
        bst = _train(x, y)
        loaded = lgb.Booster({"tpu_predict_chunk": 77},
                             model_str=bst.model_to_string())
        assert loaded._loaded.predict_chunk == 77
        np.testing.assert_array_equal(bst.predict(x, raw_score=True),
                                      loaded.predict(x, raw_score=True))

    def test_sklearn_predict_kwarg_passthrough(self):
        from lightgbm_tpu.sklearn import LGBMClassifier
        x, y = _data()
        clf = LGBMClassifier(n_estimators=5, num_leaves=7).fit(x, y)
        np.testing.assert_array_equal(
            clf.predict_proba(x),
            clf.predict_proba(x, tpu_predict_chunk=64))

    def test_predict_rows_per_sec_accumulates(self):
        x, y = _data()
        bst = _train(x, y, rounds=3)
        rows0 = global_metrics.predict_rows_total
        bst.predict(x, raw_score=True)
        assert global_metrics.predict_rows_total == rows0 + len(x)
        assert global_metrics.predict_rows_per_sec() > 0

    def test_cpu_backend_sniff_never_hides_a_failed_backend(self,
                                                            monkeypatch):
        import jax
        from lightgbm_tpu.ops import histogram as hist_ops
        from lightgbm_tpu.ops import pallas_histogram as ph

        def boom():
            raise RuntimeError("Unable to initialize backend 'tpu'")

        monkeypatch.setattr(jax, "default_backend", boom)
        # a backend that failed to initialise must not read as "CPU",
        # which would select the XLA twin and Pallas interpret mode
        for sniff in (hist_ops.cpu_backend, hist_ops.default_impl,
                      lambda: ph._resolve_interpret(None)):
            with pytest.raises(RuntimeError, match="Unable to initialize"):
                sniff()

    def test_f32_floor_is_the_largest_float32_below(self):
        from lightgbm_tpu.ops.predict import _f32_floor
        one_up = np.nextafter(np.float32(1.0), np.float32(2.0))
        two_up = np.nextafter(one_up, np.float32(2.0))
        mid = (float(one_up) + float(two_up)) / 2  # rounds UP to two_up
        assert np.float32(mid) == two_up
        t = np.array([mid, 1.0, -mid, 1e300, -1e30, np.inf, 0.1])
        f = _f32_floor(t)
        assert f.dtype == np.float32
        assert np.all(f.astype(np.float64) <= t)
        fin = np.isfinite(f)  # nothing lies above +inf
        with np.errstate(over="ignore"):  # float32 max steps up to inf
            above = np.nextafter(f[fin], np.float32(np.inf))
        assert np.all(above.astype(np.float64) > t[fin])
        assert f[0] == one_up and f[1] == np.float32(1.0)
        assert f[3] == np.finfo(np.float32).max and f[5] == np.inf

    def test_row_on_a_threshold_routes_as_the_host_does(self):
        """A float32 row equal to the upper of two adjacent float32
        values, split at their midpoint: the midpoint rounds UP in
        float32, so a nearest-rounded device threshold sent the row
        left while the model (value <= threshold in float64) sends it
        right."""
        lo = np.nextafter(np.float32(1.0), np.float32(2.0))
        hi = np.nextafter(lo, np.float32(2.0))
        x = np.where(np.arange(400) % 2 == 0, lo, hi).astype(
            np.float32)[:, None]
        y = (x[:, 0] == hi).astype(np.float64)
        bst = lgb.train({"objective": "regression", "num_leaves": 2,
                         "min_data_in_leaf": 1, "min_data_in_bin": 1,
                         "learning_rate": 1.0, "verbosity": -1},
                        lgb.Dataset(x, label=y), num_boost_round=1)
        tree = bst._gbdt.models[0][0]
        assert tree.num_leaves == 2
        assert float(lo) < tree.threshold[0] < float(hi)
        assert np.float32(tree.threshold[0]) == hi  # nearest rounds up
        probe = np.array([[lo], [hi]], np.float32)
        dev = bst.predict(probe, raw_score=True)
        host = bst._gbdt._predict_raw_host(
            probe.astype(np.float64), 0, 1)[:, 0]
        assert dev[0] != dev[1]
        np.testing.assert_allclose(dev, host, atol=1e-6)
