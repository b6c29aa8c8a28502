"""Bit-packed bin storage, the fused gradient/histogram wave, the
subtraction-aware wave schedule, and deterministic histogram
accumulation (ISSUE 7 / ROADMAP item 3).

Parity strategy: the packed layout and the fused-gradient kernel are
pure re-encodings — same values, same accumulation order — so packed
vs unpacked (and fused vs pre-built ghT) must agree BITWISE, end to
end through training, on the quantized fixture and on float data
alike. The no-subtraction oracle reorders f32 accumulation, so its
gate is tolerance-based (documented in config.tpu_wave_subtract).
"""

import functools

import numpy as np
import jax.numpy as jnp
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.ops.bin_pack import (PACK_ALIGN, PackedBins,
                                       pack_bins_host, pack_vpb,
                                       to_device, unpack_bins,
                                       unpack_feature, unpack_rows)


def strip_params(model_str: str) -> str:
    """Model string minus the echoed parameters block — knob values
    legitimately differ between the compared configs; everything else
    (trees, thresholds, leaf values) must match exactly."""
    out, skip = [], False
    for line in model_str.splitlines():
        if line.startswith("parameters:"):
            skip = True
        elif skip and line.startswith("end of parameters"):
            skip = False
            continue
        if not skip:
            out.append(line)
    return "\n".join(out)


def _binary(n=3000, f=8, seed=0):
    r = np.random.RandomState(seed)
    X = r.randn(n, f)
    logit = X[:, 0] + 0.5 * X[:, 1] ** 2 + 0.3 * X[:, 2] * X[:, 3]
    y = (logit + 0.2 * r.randn(n) > 0.5).astype(np.float32)
    return X, y


BASE = {"objective": "binary", "num_leaves": 31, "learning_rate": 0.1,
        "min_data_in_leaf": 5, "verbosity": -1, "max_bin": 15}


def _train(X, y, rounds=5, **extra):
    return lgb.train({**BASE, **extra}, lgb.Dataset(X, label=y),
                     num_boost_round=rounds)


# ---------------------------------------------------------------------------
# pack/unpack roundtrip property (satellite: max_bin in {2,15,16,63,255})
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("max_bins", [2, 15, 16, 63, 255])
@pytest.mark.parametrize("n", [1, 700, 2048, 4097])
def test_pack_roundtrip(max_bins, n):
    r = np.random.RandomState(max_bins + n)
    f = 5
    bins = r.randint(0, max_bins, (f, n)).astype(np.uint8)
    pb = pack_bins_host(bins, max_bins)
    if max_bins > 15:
        assert pb is None and pack_vpb(max_bins) == 1
        return
    assert pb.vpb == (4 if max_bins <= 3 else 2)
    assert pb.section % PACK_ALIGN == 0
    assert pb.shape == (f, n)
    # packed bytes are the point: <= ceil(N/vpb) per feature (padded)
    assert pb.nbytes <= f * (pb.section)
    dev = to_device(pb)
    np.testing.assert_array_equal(np.asarray(unpack_bins(dev)), bins)
    # per-row unpack (the partition path)
    feat = r.randint(0, f, n).astype(np.int32)
    np.testing.assert_array_equal(
        np.asarray(unpack_rows(dev, jnp.asarray(feat))),
        bins[feat, np.arange(n)])
    np.testing.assert_array_equal(np.asarray(unpack_feature(dev, 0)),
                                  bins[0])


# ---------------------------------------------------------------------------
# kernel bit-parity: packed vs unpacked on the quantized (integer) fixture
# ---------------------------------------------------------------------------
def _quant_fixture(n=3000, f=7, b=15, seed=3):
    r = np.random.RandomState(seed)
    bins = r.randint(0, b, (f, n)).astype(np.uint8)
    mask = (r.rand(n) < 0.8).astype(np.int8)
    g_int = (r.randint(-3, 4, n) * mask).astype(np.int8)
    h_int = (r.randint(0, 5, n) * mask).astype(np.int8)
    row_leaf = r.randint(0, 6, n).astype(np.int32)
    return bins, g_int, h_int, mask, row_leaf


def test_packed_hist_bit_parity_quantized():
    from lightgbm_tpu.ops.pallas_histogram import (
        hist_multi_xla, hist_multi_int8_xla, hist_pallas_multi,
        hist_pallas_multi_int8, hist_pallas)
    b, slots = 15, 42
    bins, g_int, h_int, mask, row_leaf = _quant_fixture(b=b)
    pb = to_device(pack_bins_host(bins, b))
    rl = jnp.asarray(row_leaf)
    ids = jnp.asarray([0, 2, 5, 1] + [-2] * (slots - 4), jnp.int32)
    ghT = jnp.asarray(np.stack([g_int, h_int, mask], axis=1), jnp.float32)
    ref = hist_multi_xla(jnp.asarray(bins), ghT, rl, ids,
                         max_bins=b, num_slots=slots)
    # f32 multi kernel, packed: exact integer sums -> bitwise
    pal = hist_pallas_multi(pb, ghT, rl, ids, max_bins=b, num_slots=slots,
                            interpret=True)
    np.testing.assert_array_equal(np.asarray(pal), np.asarray(ref))
    # int8 kernel: packed pallas == unpacked pallas == XLA int32 twin
    ghT_i8 = jnp.asarray(np.stack([g_int, h_int, mask], axis=1), jnp.int8)
    ref_i = hist_multi_int8_xla(jnp.asarray(bins), ghT_i8, rl, ids,
                                max_bins=b, num_slots=slots)
    for bins_arg in (pb, jnp.asarray(bins)):
        pal_i = hist_pallas_multi_int8(bins_arg, ghT_i8, rl, ids,
                                       max_bins=b, num_slots=slots,
                                       interpret=True)
        assert pal_i.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(pal_i), np.asarray(ref_i))
    # single-leaf kernel + XLA build path on PackedBins
    from lightgbm_tpu.ops.histogram import build_histogram
    g = jnp.asarray(g_int, jnp.float32)
    h = jnp.asarray(h_int, jnp.float32)
    m = jnp.asarray(mask, jnp.float32)
    ref_s = build_histogram(jnp.asarray(bins), g, h, m, max_bins=b,
                            impl="xla")
    np.testing.assert_array_equal(
        np.asarray(build_histogram(pb, g, h, m, max_bins=b, impl="xla")),
        np.asarray(ref_s))
    gh3 = jnp.stack([g * m, h * m, m]).astype(jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(hist_pallas(pb, gh3, max_bins=b, interpret=True)),
        np.asarray(ref_s))


def test_packed_partition_bit_parity():
    """feature_bins / apply_split / apply_wave_splits on PackedBins must
    reproduce the dense uint8 layout exactly (incl. categorical bitsets,
    NaN default-left routing, invalid wave entries)."""
    from lightgbm_tpu.ops import partition as part_ops
    rng = np.random.RandomState(0)
    N, F, B, L, W = 500, 6, 15, 15, 5
    for trial in range(4):
        bins = rng.randint(0, B, (F, N)).astype(np.uint8)
        pb = to_device(pack_bins_host(bins, B))
        row_leaf = rng.randint(0, 8, N).astype(np.int32)
        leaves = rng.permutation(8)[:W].astype(np.int32)
        rights = (8 + np.arange(W)).astype(np.int32)
        feats = rng.randint(0, F, W).astype(np.int32)
        thrs = rng.randint(0, B - 1, W).astype(np.int32)
        dlefts = rng.rand(W) > 0.5
        cmasks = rng.rand(W, B) > 0.5
        valid = np.ones(W, bool)
        valid[-1] = False
        num_bins = np.full(F, B, np.int32)
        missing = rng.randint(0, 3, F).astype(np.int32)
        is_cat = rng.rand(F) > 0.7
        args = (jnp.asarray(leaves), jnp.asarray(rights),
                jnp.asarray(feats), jnp.asarray(thrs),
                jnp.asarray(dlefts), jnp.asarray(cmasks),
                jnp.asarray(valid), jnp.asarray(num_bins),
                jnp.asarray(missing), jnp.asarray(is_cat), L)
        dense = part_ops.apply_wave_splits(
            jnp.asarray(row_leaf), jnp.asarray(bins), *args)
        packed = part_ops.apply_wave_splits(
            jnp.asarray(row_leaf), pb, *args)
        np.testing.assert_array_equal(np.asarray(dense), np.asarray(packed))
        s_dense = part_ops.apply_split(
            jnp.asarray(row_leaf), jnp.asarray(bins), jnp.int32(leaves[0]),
            jnp.int32(rights[0]), jnp.int32(feats[0]), jnp.int32(thrs[0]),
            jnp.bool_(dlefts[0]), jnp.asarray(cmasks[0]),
            jnp.asarray(num_bins), jnp.asarray(missing),
            jnp.asarray(is_cat), jnp.bool_(True))
        s_packed = part_ops.apply_split(
            jnp.asarray(row_leaf), pb, jnp.int32(leaves[0]),
            jnp.int32(rights[0]), jnp.int32(feats[0]), jnp.int32(thrs[0]),
            jnp.bool_(dlefts[0]), jnp.asarray(cmasks[0]),
            jnp.asarray(num_bins), jnp.asarray(missing),
            jnp.asarray(is_cat), jnp.bool_(True))
        np.testing.assert_array_equal(np.asarray(s_dense),
                                      np.asarray(s_packed))


# ---------------------------------------------------------------------------
# end-to-end training parity
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_packed_training_bit_identical():
    """tpu_bin_pack=auto (packed) vs off (uint8 oracle): the full waved
    training loop must produce bit-identical models — the packed layout
    is a re-encoding, not an approximation."""
    X, y = _binary()
    m_off = strip_params(_train(X, y, tpu_bin_pack="off",
                                tpu_fused_grad="off").model_to_string())
    m_on = strip_params(_train(X, y,
                               tpu_fused_grad="off").model_to_string())
    assert m_on == m_off


@pytest.mark.slow
def test_packed_training_bit_identical_quantized():
    """The acceptance fixture: quantized gradients + packed bins vs the
    unpacked oracle — bit-identical (int32 histogram sums are exact)."""
    X, y = _binary()
    m_off = strip_params(_train(X, y, use_quantized_grad=True,
                                tpu_bin_pack="off").model_to_string())
    m_on = strip_params(_train(X, y,
                               use_quantized_grad=True).model_to_string())
    assert m_on == m_off


@pytest.mark.slow
def test_packed_2bit_training():
    """max_bin=3 engages the 2-bit pair layout end to end."""
    X, y = _binary(2000)
    bst_off = _train(X, y, max_bin=3, tpu_bin_pack="off")
    bst_on = _train(X, y, max_bin=3)
    assert lgb.Booster(model_str=bst_on.model_to_string())  # round-trips
    np.testing.assert_array_equal(bst_on.predict(X), bst_off.predict(X))


def test_packed_disabled_when_ineligible():
    X, y = _binary(1500)
    # too many bins
    bst = lgb.Booster({**BASE, "max_bin": 63}, lgb.Dataset(X, label=y))
    assert bst._gbdt._bin_pack_vpb == 1
    # knob off
    bst2 = lgb.Booster({**BASE, "tpu_bin_pack": "off"},
                       lgb.Dataset(X, label=y))
    assert bst2._gbdt._bin_pack_vpb == 1
    # eligible default
    bst3 = lgb.Booster(BASE, lgb.Dataset(X, label=y))
    assert bst3._gbdt._bin_pack_vpb == 2


def test_packed_with_valid_sets_and_exact_grower():
    """Valid-set replay and the exact (tpu_wave_max=0) grower both
    traverse PackedBins; parity vs the unpacked oracle."""
    X, y = _binary(2500)
    Xv, yv = _binary(800, seed=9)
    evals = {}
    preds = {}
    for pack in ("off", "auto"):
        ev = {}
        bst = lgb.train({**BASE, "tpu_bin_pack": pack, "tpu_wave_max": 0,
                         "metric": "auc"},
                        lgb.Dataset(X, label=y), num_boost_round=5,
                        valid_sets=[lgb.Dataset(Xv, label=yv)],
                        valid_names=["v"], callbacks=[
                            lgb.record_evaluation(ev)])
        evals[pack] = ev["v"]["auc"]
        preds[pack] = bst.predict(Xv)
    assert evals["auto"] == evals["off"]
    np.testing.assert_array_equal(preds["auto"], preds["off"])


# ---------------------------------------------------------------------------
# fused gradient/histogram wave
# ---------------------------------------------------------------------------
def test_fused_grad_bit_identical_binary():
    X, y = _binary()
    m_off = strip_params(_train(X, y, tpu_fused_grad="off",
                                tpu_bin_pack="off").model_to_string())
    m_on = strip_params(_train(X, y, tpu_bin_pack="off").model_to_string())
    assert m_on == m_off


@pytest.mark.slow
def test_fused_grad_bit_identical_weighted_regression():
    r = np.random.RandomState(1)
    n = 2500
    X = r.randn(n, 6)
    y = (X[:, 0] * 2 - X[:, 1] + 0.1 * r.randn(n)).astype(np.float32)
    w = np.abs(r.randn(n)).astype(np.float32) + 0.5
    params = {"objective": "regression", "num_leaves": 31, "max_bin": 15,
              "min_data_in_leaf": 5, "verbosity": -1}
    outs = {}
    for mode in ("off", "auto"):
        bst = lgb.train({**params, "tpu_fused_grad": mode},
                        lgb.Dataset(X, label=y, weight=w),
                        num_boost_round=6)
        outs[mode] = strip_params(bst.model_to_string())
    assert outs["auto"] == outs["off"]


@pytest.mark.slow
def test_fused_grad_in_kernel_pallas_bit_identical():
    """The pallas path computes gradients INSIDE the multi kernel
    (interpret mode on CPU): must bit-match the pre-built-ghT pallas
    path — same dots, same order, gh computed in VMEM instead of HBM."""
    X, y = _binary()
    m_off = strip_params(_train(X, y, tpu_hist_impl="pallas",
                                tpu_fused_grad="off").model_to_string())
    m_on = strip_params(_train(X, y,
                               tpu_hist_impl="pallas").model_to_string())
    assert m_on == m_off


def test_fused_grad_wide_bins_stay_off_kernel_path():
    """max_bin > 256 stores uint16 bin ids, which the byte-sectioned
    fused kernel cannot represent: the waved grower must fall back to
    the materialized-ghT pallas path (still bit-identical to
    tpu_fused_grad=off) instead of silently aliasing ids & 255."""
    r = np.random.RandomState(3)
    n = 1200
    X = np.repeat(r.randn(n // 4, 4), 4, axis=0) + 0.01 * r.randn(n, 4)
    y = (X[:, 0] + 0.3 * X[:, 1] > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 15, "max_bin": 300,
              "min_data_in_leaf": 5, "verbosity": -1,
              "tpu_hist_impl": "pallas"}
    outs = {}
    for mode in ("off", "auto"):
        bst = lgb.train({**params, "tpu_fused_grad": mode},
                        lgb.Dataset(X, label=y), num_boost_round=3)
        outs[mode] = strip_params(bst.model_to_string())
    assert outs["auto"] == outs["off"]


def test_fused_grad_resolution_gates():
    """GOSS / quantized / multiclass / unsupported objectives keep the
    materialized-gradient path."""
    X, y = _binary(1200)
    assert lgb.Booster(BASE, lgb.Dataset(X, label=y)) \
        ._gbdt._fused_grad_fn is not None
    assert lgb.Booster({**BASE, "tpu_fused_grad": "off"},
                       lgb.Dataset(X, label=y))._gbdt._fused_grad_fn is None
    assert lgb.Booster({**BASE, "data_sample_strategy": "goss"},
                       lgb.Dataset(X, label=y))._gbdt._fused_grad_fn is None
    assert lgb.Booster({**BASE, "use_quantized_grad": True},
                       lgb.Dataset(X, label=y))._gbdt._fused_grad_fn is None
    assert lgb.Booster({**BASE, "objective": "quantile"},
                       lgb.Dataset(X, label=y))._gbdt._fused_grad_fn is None


def test_pointwise_grad_fn_matches_get_gradients():
    """The pointwise forms must be BITWISE equal to get_gradients."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.dataset import Metadata
    r = np.random.RandomState(2)
    n = 1000
    label = (r.rand(n) > 0.5).astype(np.float32)
    weight = np.abs(r.randn(n)).astype(np.float32)
    score = jnp.asarray(r.randn(n), jnp.float32)
    for name, use_w in (("binary", False), ("binary", True),
                        ("regression", False), ("regression", True)):
        cfg = Config.from_params({"objective": name})
        obj = create_objective(cfg)
        md = Metadata(n)
        md.set_label(label)
        if use_w:
            md.set_weight(weight)
        obj.init(md, n)
        fn = obj.pointwise_grad_fn()
        assert fn is not None
        g_ref, h_ref = obj.get_gradients(score)
        g_fn, h_fn = fn(score, obj.label, obj.weight)
        np.testing.assert_array_equal(np.asarray(g_fn), np.asarray(g_ref))
        np.testing.assert_array_equal(np.asarray(h_fn), np.asarray(h_ref))


# ---------------------------------------------------------------------------
# subtraction-aware wave schedule
# ---------------------------------------------------------------------------
def test_wave_schedule_subtraction_awareness():
    from lightgbm_tpu.learner import _wave_schedule
    sub = _wave_schedule(255, 42, 42, 1)
    nosub = _wave_schedule(255, 42, 42, 2)
    assert sum(sub) == sum(nosub) == 254
    assert max(sub) == 42        # one slot per split
    assert max(nosub) == 21      # two slots per split
    assert len(nosub) > len(sub)  # the oracle pays more full-data passes
    # regression guard on the cost model's headline numbers
    assert len(sub) == 13 and len(nosub) == 17


def test_subtract_oracle_training_parity():
    """tpu_wave_subtract=False (both children built, no subtraction)
    agrees with the subtraction path within documented f32 cancellation
    tolerance, and trains the same tree STRUCTURE on this fixture."""
    X, y = _binary()
    b_sub = _train(X, y)
    b_oracle = _train(X, y, tpu_wave_subtract=False)
    np.testing.assert_allclose(b_oracle.predict(X), b_sub.predict(X),
                               rtol=1e-3, atol=1e-3)


def test_hist_traffic_model_counters():
    """The static traffic model: per-wave counters, and >= 1.8x byte
    reduction on the quantized packed fixture shape (the acceptance
    number for ISSUE 7; packing x2 on bins + int8 gh x4 + the
    subtraction-aware 13-vs-17-pass schedule)."""
    from lightgbm_tpu.learner import hist_traffic_model
    kw = dict(num_data=10_500_000, storage_features=28, max_bins=15,
              num_leaves=255, wave_max=42)
    actual = hist_traffic_model(**kw, pack_vpb=2, gh_read_bytes=3,
                                subtract=True, fused_grad=False)
    oracle = hist_traffic_model(**kw, pack_vpb=1, gh_read_bytes=12,
                                subtract=False, fused_grad=False)
    assert len(actual["wave_rows_scanned"]) == actual["passes"]
    assert actual["rows_scanned_per_iter"] == \
        actual["passes"] * kw["num_data"]
    reduction = oracle["hist_bytes_per_iter"] / actual["hist_bytes_per_iter"]
    assert reduction >= 1.8, f"traffic reduction {reduction:.2f} < 1.8"


def test_traffic_meta_reaches_obs_and_model_consistency():
    from lightgbm_tpu.obs.metrics import global_metrics
    X, y = _binary(1500)
    bst = lgb.Booster(BASE, lgb.Dataset(X, label=y))
    ht = global_metrics.meta.get("hist_traffic")
    assert ht is not None and ht["pack_vpb"] == 2 and ht["fused_grad"]
    assert global_metrics.meta["hist_bytes_per_iter"] == \
        ht["hist_bytes_per_iter"]
    assert global_metrics.meta["hist_bytes_reduction"] > 1.0
    assert bst._gbdt._bin_pack_vpb == 2


@pytest.mark.parametrize("kind", ["int8", "float"])
def test_published_geometry_at_the_benchmark_shape(kind):
    """The geometry each traced multi-leaf kernel took is published under
    ``global_metrics.meta["hist_geometry"]`` beside ``hist_traffic``. At
    the benchmark cells' 63M x 28 (traced abstractly: nothing runs) both
    operand types take one feature block and under 20,000 grid steps a
    pass, where the parent took 123,048 and 246,096."""
    import jax
    from lightgbm_tpu.obs.metrics import global_metrics
    from lightgbm_tpu.ops import pallas_histogram as ph
    n, f, slots = 63_000_000, 28, 42

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    vec, rl, ids = s((n,), jnp.float32), s((n,), jnp.int32), s((slots,),
                                                              jnp.int32)
    kw = dict(max_bins=63, num_slots=slots, interpret=True)
    if kind == "int8":
        jax.eval_shape(functools.partial(ph.hist_pallas_multi_int8, **kw),
                       s((f, n), jnp.uint8), s((n, 3), jnp.int8), rl, ids)
    else:
        jax.eval_shape(
            functools.partial(ph.hist_pallas_multi_fused, precise="default",
                              grad_fn=lambda sc, lb, w: (sc - lb, sc * sc),
                              **kw),
            s((f, n), jnp.uint8), vec, vec, None, vec, rl, ids)
    operand = "int8" if kind == "int8" else "bfloat16"
    got, = [g for g in global_metrics.meta["hist_geometry"]
            if g["rows"] >= n and g["operand"] == operand]
    assert got["kernel"] == ("lgbm_hist_multi_int8" if kind == "int8"
                             else "lgbm_hist_multi_packed")
    assert (got["features"], got["max_bins"], got["bp"]) == (f, 63, 64)
    assert got["features_per_step"] == 32 and got["pack_factor"] == 1
    assert got["row_chunk"] >= 4096 and got["features_per_dot"] * 64 >= 512
    assert got["grid_steps"] == -(-n // got["row_chunk"]) < 20_000
    # 28 real features of the block's 32: three dots of 8 and one of 4
    assert (got["dots_per_step"], got["tail_features"]) == (4, 4)
    assert got["row_chunk"] % got["k_tile"] == 0 == \
        got["row_chunk"] % got["root_tile"]


# ---------------------------------------------------------------------------
# deterministic histogram accumulation (satellite)
# ---------------------------------------------------------------------------
def test_deterministic_hist_tightens_accumulation():
    """Kahan-compensated fixed-chunk accumulation must stay inside the
    1e-4 parity band vs the f64 ground truth on cancellation-heavy
    gradients (at this N both modes are near noise level — the
    compensation's growth-with-N advantage is asserted structurally by
    the shard-regrouping test below, not by racing two tiny errors)."""
    from lightgbm_tpu.ops.histogram import build_histogram
    r = np.random.RandomState(5)
    n, f, b = 200_000, 3, 15
    bins = jnp.asarray(r.randint(0, b, (f, n)), jnp.uint8)
    # huge magnitude spread -> naive f32 accumulation error is visible
    grad = jnp.asarray((r.randn(n) * 10.0 ** r.randint(-3, 4, n))
                       .astype(np.float32))
    hess = jnp.asarray(np.abs(r.randn(n)).astype(np.float32))
    mask = jnp.ones(n, jnp.float32)
    ref64 = np.zeros((f, b, 3))
    bn = np.asarray(bins)
    g64 = np.asarray(grad, np.float64)
    h64 = np.asarray(hess, np.float64)
    for j in range(f):
        for c, v in enumerate((g64, h64, np.ones(n))):
            ref64[j, :, c] = np.bincount(bn[j], weights=v, minlength=b)
    plain = np.asarray(build_histogram(bins, grad, hess, mask, max_bins=b,
                                       impl="xla", row_chunk=8192),
                       np.float64)
    det = np.asarray(build_histogram(bins, grad, hess, mask, max_bins=b,
                                     impl="xla", deterministic=True),
                     np.float64)
    err_plain = np.max(np.abs(plain - ref64) / np.maximum(np.abs(ref64), 1))
    err_det = np.max(np.abs(det - ref64) / np.maximum(np.abs(ref64), 1))
    assert err_det < 1e-4  # the ROADMAP parity target
    assert err_det < 10 * max(err_plain, 1e-9)  # never much worse


def test_deterministic_hist_shard_regrouping():
    """Per-shard deterministic builds summed together (the psum shape)
    must agree with the whole-data deterministic build within the 1e-4
    parity band — the reorders-safely-under-sharding property."""
    from lightgbm_tpu.ops.pallas_histogram import hist_multi_xla
    r = np.random.RandomState(6)
    n, f, b, slots = 50_000, 4, 15, 8
    bins = r.randint(0, b, (f, n)).astype(np.uint8)
    ghT = np.stack([(r.randn(n) * 10.0 ** r.randint(-2, 3, n)),
                    np.abs(r.randn(n)), np.ones(n)],
                   axis=1).astype(np.float32)
    rl = r.randint(0, slots, n).astype(np.int32)
    ids = jnp.asarray(np.arange(slots, dtype=np.int32))

    def det(bv, gv, rv):
        return hist_multi_xla(jnp.asarray(bv), jnp.asarray(gv),
                              jnp.asarray(rv), ids, max_bins=b,
                              num_slots=slots, deterministic=True)

    whole = np.asarray(det(bins, ghT, rl))
    shards = 8
    step = n // shards
    parts = sum(np.asarray(det(bins[:, s * step:(s + 1) * step],
                               ghT[s * step:(s + 1) * step],
                               rl[s * step:(s + 1) * step]))
                for s in range(shards))
    np.testing.assert_allclose(parts, whole,
                               rtol=5e-4, atol=5e-4)
    denom = np.maximum(np.abs(whole), 1.0)
    assert np.max(np.abs(parts - whole) / denom) < 1e-3


def test_deterministic_hist_trains():
    X, y = _binary(2000)
    bst = _train(X, y, deterministic_hist=True, max_bin=63)
    from lightgbm_tpu.metrics import _auc
    assert _auc(y, bst.predict(X)) > 0.9
    # the knob forces the XLA impl
    bst2 = lgb.Booster({**BASE, "deterministic_hist": True,
                        "tpu_hist_impl": "pallas"}, lgb.Dataset(X, label=y))
    assert bst2._gbdt._hist_impl == "xla"


# ---------------------------------------------------------------------------
# int8 promoted to default-capable (satellite)
# ---------------------------------------------------------------------------
def test_int8_xla_matches_pallas_bitwise():
    from lightgbm_tpu.ops.pallas_histogram import (hist_multi_int8,
                                                   hist_multi_int8_xla,
                                                   hist_pallas_multi_int8)
    b, slots = 15, 42
    bins, g_int, h_int, mask, row_leaf = _quant_fixture(b=b)
    ghT_i8 = jnp.asarray(np.stack([g_int, h_int, mask], axis=1), jnp.int8)
    rl = jnp.asarray(row_leaf)
    ids = jnp.asarray([0, 3, 5, 1] + [-2] * (slots - 4), jnp.int32)
    x = hist_multi_int8_xla(jnp.asarray(bins), ghT_i8, rl, ids,
                            max_bins=b, num_slots=slots)
    p = hist_pallas_multi_int8(jnp.asarray(bins), ghT_i8, rl, ids,
                               max_bins=b, num_slots=slots, interpret=True)
    assert x.dtype == p.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(x), np.asarray(p))
    d = hist_multi_int8(jnp.asarray(bins), ghT_i8, rl, ids, max_bins=b,
                        num_slots=slots, impl="xla")
    np.testing.assert_array_equal(np.asarray(d), np.asarray(x))


@pytest.mark.slow
def test_quantized_waved_runs_int8_on_xla():
    """use_quantized_grad on the default (XLA) backend now runs the
    exact-integer int8 histogram — same int32 sums as the device kernel
    — instead of f32 histograms of dequantized values."""
    X, y = _binary()
    m_xla = strip_params(_train(X, y, use_quantized_grad=True,
                                tpu_bin_pack="off").model_to_string())
    m_pal = strip_params(_train(X, y, use_quantized_grad=True,
                                tpu_bin_pack="off",
                                tpu_hist_impl="pallas").model_to_string())
    assert m_xla == m_pal


# ---------------------------------------------------------------------------
# the packer and its record at the benchmark's 28 features (PR 35)
# ---------------------------------------------------------------------------
def test_pack_section_no_multiple_of_the_largest_chunk():
    """`pack_bins_host` on [28, N] at an N whose section (24,576 bytes =
    3 x 8,192) the kernels' 16,384-row chunk does not divide, as at the
    benchmark's 84M rows (42,000,384 = 5,127 x 8,192): section 0 is whole,
    the last one short, the bytes past it bin 0 in the high nibble; and
    the step `_fb_geometry` gives that section: 8,192-row chunks, all 28
    features in one dot of 28 x 16 one-hot rows. On the float path it is
    VMEM and not the alignment that keeps the 16,384-row chunk out at two
    values a byte (the row operands' blocks double): a section it divides
    gets 8,192 too, so the packer's alignment stays PACK_ALIGN. (The
    int8 operand's step would fit at 16,384, with a 2,048-row root tile:
    not a benchmark cell, not measured.)"""
    from lightgbm_tpu.ops import pallas_histogram as ph
    f, section = 28, 3 * 8192
    n = 2 * section - 2048 - 7
    r = np.random.RandomState(3)
    bins = r.randint(0, 15, (f, n)).astype(np.uint8)
    pb = pack_bins_host(bins, 15)
    assert (pb.vpb, pb.section, pb.shape) == (2, section, (f, n))
    assert pb.section % PACK_ALIGN == 0 and pb.section % 16384
    short = n - section
    np.testing.assert_array_equal(pb.data & 0xF, bins[:, :section])
    np.testing.assert_array_equal(pb.data[:, :short] >> 4,
                                  bins[:, section:])
    assert not (pb.data[:, short:] >> 4).any()
    np.testing.assert_array_equal(np.asarray(unpack_bins(to_device(pb))),
                                  bins)
    for itemsize in (1, 2):
        g = ph._fb_geometry(f, 15, 2, itemsize, section=pb.section)
        assert g.row_chunk == 8192 and pb.section % g.row_chunk == 0
        assert g.f_blk == 32 and g.dot_feats * g.bp >= 512
        assert ph._step_vmem_bytes(g, 2, itemsize) <= ph._VMEM_LIMIT
    assert ph._fb_geometry(f, 15, 2, 2, section=4 * 16384).row_chunk == 8192
    assert ph._fb_geometry(f, 15, 2, 2, section=pb.section).bp == 16


@pytest.mark.parametrize("max_bin,packs", [(15, True), (63, False)])
def test_bin_pack_record_and_span(max_bin, packs):
    """``global_metrics.meta["bin_pack"]`` holds one record a packed
    matrix with the tracer off (``benchmarks/metrics/bins_pack_s.py``
    reads its seconds), and the pack and upload are the tracer's span
    ``data/pack_bins`` when it is on; at 63 bins nothing is packed and
    neither appears."""
    from lightgbm_tpu.obs.metrics import global_metrics
    from lightgbm_tpu.obs.trace import global_tracer
    X, y = _binary(3000, f=28)
    params = {**BASE, "max_bin": max_bin}
    assert not global_tracer.enabled
    before = len(global_metrics.meta.get("bin_pack", []))
    bst = lgb.Booster(params, lgb.Dataset(X, label=y))
    records = global_metrics.meta.get("bin_pack", [])
    assert len(records) == before + int(packs)
    assert isinstance(bst._gbdt.bins_fm, PackedBins) == packs
    if packs:
        rec = records[-1]
        assert rec["seconds"] > 0
        assert (rec["rows"], rec["features"], rec["vpb"]) == (3000, 28, 2)
        assert rec["section"] == 2048 == bst._gbdt.bins_fm.section
        assert rec["bytes_raw"] == 28 * 3000
        assert rec["bytes_packed"] == 28 * 2048 == bst._gbdt.bins_fm.nbytes
    global_tracer.enable()
    try:
        global_tracer.reset()
        lgb.Booster(params, lgb.Dataset(X, label=y))
        spans = global_tracer.summary()
    finally:
        global_tracer.disable()
        global_tracer.reset()
    assert ("data/pack_bins" in spans) == packs
    assert len(global_metrics.meta.get("bin_pack", [])) == \
        before + 2 * int(packs)
