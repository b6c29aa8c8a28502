"""Waved (batched-histogram) tree growth: quality parity vs the exact
per-split grower, feature coverage (categorical, monotone), and the
multi-leaf histogram kernel (Pallas, run in interpreter mode so CI
executes it on CPU) vs the XLA reference implementation.

Ref strategy: the reference gates its GPU learner on CPU/GPU output
agreement (tests/python_package_test/test_dual.py:19); waved-vs-exact is
the analogous gate for the batched TPU grower.
"""

import contextlib
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import lightgbm_tpu as lgb
from tests.conftest import make_binary, make_regression


def _auc(y, p):
    order = np.argsort(p)
    ranks = np.empty(len(y))
    ranks[order] = np.arange(1, len(y) + 1)
    pos = y > 0
    n_pos, n_neg = pos.sum(), (~pos).sum()
    return (ranks[pos].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg)


def _train(X, y, wave_max, **extra):
    params = {"objective": "binary", "num_leaves": 63, "learning_rate": 0.1,
              "min_data_in_leaf": 5, "verbosity": -1,
              "tpu_wave_max": wave_max, **extra}
    return lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=20)


def test_waved_default_is_auto():
    """tpu_wave_max=-1 (auto): waved for single-output objectives, exact
    for multiclass (softmax calibration is split-order-sensitive; the
    waved path at wave size 1 is bit-identical to exact, batching >= 2
    drifts multiclass logloss — see config.py tpu_wave_max)."""
    from lightgbm_tpu.config import Config
    assert Config().tpu_wave_max == -1
    X, y = make_binary(400)
    bst = lgb.Booster({"objective": "binary", "num_leaves": 7,
                       "verbosity": -1}, lgb.Dataset(X, label=y))
    assert bst._gbdt._use_waved()
    from tests.conftest import make_multiclass
    Xm, ym = make_multiclass(400)
    bstm = lgb.Booster({"objective": "multiclass", "num_class": 4,
                        "num_leaves": 7, "verbosity": -1},
                       lgb.Dataset(Xm, label=ym))
    assert not bstm._gbdt._use_waved()
    # explicit setting overrides auto in both directions
    bstm2 = lgb.Booster({"objective": "multiclass", "num_class": 4,
                         "num_leaves": 7, "verbosity": -1,
                         "tpu_wave_max": 42}, lgb.Dataset(Xm, label=ym))
    assert bstm2._gbdt._use_waved()
    # OVA trains independent per-class binary trees (no softmax
    # coupling), so auto keeps the waved default there
    bsto = lgb.Booster({"objective": "multiclassova", "num_class": 4,
                        "num_leaves": 7, "verbosity": -1},
                       lgb.Dataset(Xm, label=ym))
    assert bsto._gbdt._use_waved()


@pytest.mark.slow
def test_waved_quality_parity_binary():
    X, y = make_binary(4000)
    auc_exact = _auc(y, _train(X, y, 0).predict(X))
    auc_waved = _auc(y, _train(X, y, 32).predict(X))
    # waved defers within-wave children to the wave boundary; with
    # boosting on top the quality gap must stay small
    assert auc_waved > auc_exact - 0.02
    assert auc_waved > 0.9


@pytest.mark.slow
def test_waved_quality_parity_regression():
    # held-out comparison: exact leaf-wise overfits deeper at equal
    # rounds, so train-set error would mis-rank the growers
    X, y = make_regression(6000)
    Xtr, ytr, Xte, yte = X[:4000], y[:4000], X[4000:], y[4000:]
    params = {"objective": "regression", "num_leaves": 63,
              "min_data_in_leaf": 5, "verbosity": -1}
    preds = {}
    for wave in (0, 32):
        bst = lgb.train({**params, "tpu_wave_max": wave},
                        lgb.Dataset(Xtr, label=ytr), num_boost_round=20)
        preds[wave] = bst.predict(Xte)
    mse_exact = np.mean((preds[0] - yte) ** 2)
    mse_waved = np.mean((preds[32] - yte) ** 2)
    assert mse_waved < mse_exact * 1.15
    assert mse_waved < np.var(yte) * 0.2


def test_waved_first_splits_match_exact():
    """Wave sizes start at 1, 1 — so a 3-leaf tree (two splits, each in
    its own wave) must be IDENTICAL to the exact grower's."""
    X, y = make_binary(2000)
    m_exact = _train(X, y, 0, num_leaves=3).model_to_string()
    m_waved = _train(X, y, 32, num_leaves=3).model_to_string()

    def first_split(text):
        for line in text.splitlines():
            if line.startswith("split_feature="):
                return line
        return None

    assert first_split(m_exact) == first_split(m_waved)


@pytest.mark.slow
def test_waved_categorical():
    r = np.random.RandomState(7)
    n = 3000
    cat = r.randint(0, 40, n)
    num = r.randn(n)
    logit = np.where(np.isin(cat, [3, 7, 11, 22, 35]), 1.5, -0.8) + num
    y = (logit + 0.3 * r.randn(n) > 0).astype(np.float32)
    X = np.column_stack([cat.astype(np.float64), num])
    params = {"objective": "binary", "num_leaves": 31, "verbosity": -1,
              "min_data_in_leaf": 5, "tpu_wave_max": 32,
              "categorical_feature": [0]}
    bst = lgb.train(params, lgb.Dataset(X, label=y,
                                        categorical_feature=[0]),
                    num_boost_round=20)
    auc = _auc(y, bst.predict(X))
    assert auc > 0.85
    # round-trip: categorical bitsets survive serialization
    loaded = lgb.Booster(model_str=bst.model_to_string())
    np.testing.assert_allclose(loaded.predict(X), bst.predict(X), rtol=1e-9)


@pytest.mark.slow
def test_waved_monotone():
    r = np.random.RandomState(3)
    n = 3000
    X = r.randn(n, 4)
    y = (2.0 * X[:, 0] + np.sin(X[:, 1]) * 2 + 0.5 * X[:, 2]
         + 0.2 * r.randn(n)).astype(np.float32)
    params = {"objective": "regression", "num_leaves": 63, "verbosity": -1,
              "min_data_in_leaf": 5, "tpu_wave_max": 32,
              "monotone_constraints": [1, 0, 0, 0]}
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=30)
    # sweep feature 0 over its range with the others pinned: prediction
    # must be non-decreasing at every probed point
    base = np.tile(np.median(X, axis=0), (200, 1))
    base[:, 0] = np.linspace(X[:, 0].min(), X[:, 0].max(), 200)
    p = bst.predict(base)
    assert np.all(np.diff(p) >= -1e-10)


def test_waved_with_bagging_and_feature_fraction():
    X, y = make_binary(3000)
    bst = _train(X, y, 32, bagging_fraction=0.7, bagging_freq=1,
                 feature_fraction=0.8)
    assert _auc(y, bst.predict(X)) > 0.85


# (max_bins, values a byte, features, slots, rows): every branch of
# pallas_histogram._fb_geometry: a slab of one tile and of several (63 and
# 64 bins give 64 rows, 255 and 256 give 256, 300 gives 304 or 320 and
# uint16 ids; 15/16 and 3/4 bins the 4-bit and 2-bit PackedBins), a
# feature block whose last dot is cut or not issued (5, 28, 70) or whole
# (32), more than one block (70 features at 255 bins with a tail, 2000 at
# 63 without), a row count no chunk divides, a section only the smallest
# chunk divides (9000 rows at 2 a byte: 6144 bytes)
STEP_CASES = [(63, 1, 28, 42, 5000), (63, 1, 5, 1, 2500),
              (64, 1, 70, 8, 2500), (255, 1, 5, 8, 2500),
              (255, 1, 70, 42, 2100), (256, 1, 28, 1, 2500),
              (300, 1, 5, 8, 2500), (300, 1, 28, 42, 2100),
              (15, 2, 28, 8, 9000), (16, 2, 5, 42, 4100),
              (3, 4, 28, 8, 9000), (4, 4, 70, 1, 8200),
              (63, 1, 32, 8, 2500),
              # the wide cell's shape (PR 32): many feature blocks a row
              # chunk, the last one padded
              (63, 1, 2000, 42, 2100)]
# the live-row cases of the same check (which rows of a pass are live) are
# tests/test_hist_live_rows.py's: another file, so another worker


def _binary_grad(score, label, weight):
    p = jax.nn.sigmoid(score)
    return p - label, p * (1.0 - p)


def _step_rows(r, live, slots, n):
    """(row_leaf [n], leaf_ids [slots], live slots) of a `live` case:
    "some": a few leaves of many; "none": no slot matches; "one": one row
    a 2048-row chunk; a share; "dense": over 7/8, the chunk that is not
    squeezed; "root": every row in the one slot, told at the call site;
    "end": the live rows at the end of each chunk. What the column
    squeeze (squeeze stage 7) can get wrong and the full network could
    not, in 2048-row chunks of 16 vregs: "stride": live rows 128 apart,
    one lane column full, so the chunk is not squeezed; "column": the
    same in every other chunk, and between them 127 columns full and
    one empty; "last_vreg": live rows in a chunk's last 128 lanes only;
    "tile_edge": the tallest column exactly one sub-tile of 8 vregs
    high, the others 3 or none."""
    if live == "root":
        return np.full(n, 9), np.array([9] + [-2] * (slots - 1)), 1
    if live == "some":
        k = min(slots, 4)
        ids = list(r.permutation(slots + 3)[:k]) + [-2] * (slots - k)
        return r.randint(0, slots + 3, n), np.array(ids), k
    ids = np.arange(slots) + 5
    row = np.arange(n)
    col, vreg = row % 128, row % 2048 // 128
    on = {"none": np.zeros(n, bool), "one": row % 2048 == 7,
          "dense": r.rand(n) < 0.95, "end": row % 2048 >= 1748,
          "stride": col == 5,
          "column": (col == 3) ^ (row // 2048 % 2 == 1),
          "last_vreg": vreg == 15,
          "tile_edge": ((col == 77) & (vreg % 2 == 1))
          | ((col % 3 == 1) & np.isin(vreg, (2, 9, 12)))}.get(
              live, r.rand(n) < (live if isinstance(live, float) else 0))
    return np.where(on, r.randint(5, slots + 5, n),
                    r.randint(100, 120, n)), ids, slots


@pytest.mark.usefixtures("release_executables")
@pytest.mark.parametrize("kind", ["int8", "float", "fused"])
@pytest.mark.parametrize("max_bins,vpb,f,slots,n", STEP_CASES)
def test_shared_step_matches_xla_twins(kind, max_bins, vpb, f, slots, n):
    """The one step every multi-leaf Pallas kernel runs (`_multi_step`
    with `_accum_section_dots`, interpret mode), under each of its three
    operand readers, against the XLA twins: bit for bit on int8, to
    float32 rounding on float (tpu_hist_precision=highest: three bf16
    passes over the leaf operand); padded slots stay empty, and so do a
    padded feature's slab and a slab's rows at max_bins and beyond. Rows
    of a few leaves of many are live here (tests/test_hist_live_rows.py
    has the other cases)."""
    check_shared_step(kind, "some", max_bins, vpb, f, slots, n)


@contextlib.contextmanager
def squeeze_stage(stage):
    """`stage` in the place of the geometry's rule
    (pallas_histogram._squeeze_stage), for the kernels traced inside."""
    from lightgbm_tpu.ops import pallas_histogram as ph
    jitted = (ph._multi_slabs, ph.hist_pallas_multi,
              ph.hist_pallas_multi_int8, ph.hist_pallas_multi_fused)
    was = ph._squeeze_stage
    ph._squeeze_stage = lambda *a: stage
    try:
        for fn in jitted:
            fn.clear_cache()
        yield
    finally:
        ph._squeeze_stage = was
        for fn in jitted:
            fn.clear_cache()


def check_shared_step(kind, live, max_bins, vpb, f, slots, n, stage=None):
    """The multi-leaf kernels' step against the XLA twins with the rows
    of a `live` case (_step_rows) live; with `stage`, under that squeeze
    whatever the geometry's rule says. Returns the histograms."""
    if stage is not None:
        with squeeze_stage(stage):
            return check_shared_step(kind, live, max_bins, vpb, f, slots, n)
    from lightgbm_tpu.ops import pallas_histogram as ph
    from lightgbm_tpu.ops.bin_pack import pack_bins_host, to_device
    r = np.random.RandomState(max_bins + f + n)
    bins = r.randint(0, max_bins, (f, n)).astype(
        np.uint8 if max_bins <= 256 else np.uint16)
    # 16 and 4 bins have no sentinel left in their bits: the program
    # leaves them unpacked, the kernels take them (test_chip_compile)
    arg = (to_device(pack_bins_host(bins, {2: 15, 4: 3}[vpb])) if vpb > 1
           else jnp.asarray(bins))
    assert getattr(arg, "vpb", 1) == vpb
    mask = r.rand(n) < 0.8
    row_leaf, leaf_ids, n_live = _step_rows(r, live, slots, n)
    row_leaf = jnp.asarray(row_leaf, jnp.int32)
    leaf_ids = jnp.asarray(leaf_ids, jnp.int32)
    kw = dict(max_bins=max_bins, num_slots=slots)
    run = dict(interpret=True, all_live=live == "root", **kw)
    m = jnp.asarray(mask, jnp.float32)
    if kind == "int8":
        gh = jnp.asarray(np.stack([r.randint(-63, 64, n) * mask,
                                   r.randint(0, 64, n) * mask, mask], 1),
                         jnp.int8)
        want = ph.hist_multi_int8_xla(jnp.asarray(bins), gh, row_leaf,
                                      leaf_ids, **kw)
        vecs, reader = [(gh, 0)], {}
        got, counts = ph.hist_pallas_multi_int8(arg, gh, row_leaf, leaf_ids,
                                                **run)
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        score = jnp.asarray(r.randn(n), jnp.float32)
        label = jnp.asarray(r.rand(n) < 0.5, jnp.float32)
        g, h = _binary_grad(score, label, None)
        gh = jnp.stack([g * m, h * m, m], 1)
        want = ph.hist_multi_xla(jnp.asarray(bins), gh, row_leaf, leaf_ids,
                                 **kw)
        if kind == "float":
            vecs, reader = [(gh, 0.0)], {}
            got, counts = ph.hist_pallas_multi(arg, gh, row_leaf, leaf_ids,
                                               **run)
        else:
            vecs = [(score, 0.0), (label, 0.0), (m, 0.0)]
            reader = dict(grad_fn=_binary_grad, has_weight=False)
            got, counts = ph.hist_pallas_multi_fused(
                arg, score, label, None, m, row_leaf, leaf_ids,
                grad_fn=_binary_grad, **run)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-6, atol=2e-5)
    assert np.all(np.asarray(got[n_live:]) == 0)
    if live == "none":
        assert not np.asarray(got).any()
    else:
        assert np.asarray(got).any()
    vecs.append((row_leaf, -1))
    slabs, slab_counts = ph._multi_slabs(
        arg, tuple(v for v, _ in vecs),
        jnp.pad(leaf_ids, (0, ph._MAX_SLOTS - slots), constant_values=-2),
        pads=tuple(p for _, p in vecs), max_bins=max_bins,
        int8=kind == "int8", precise="highest", interpret=True,
        all_live=live == "root", name="lgbm_hist_multi", **reader)
    slabs = np.asarray(slabs)
    from lightgbm_tpu.obs.metrics import global_metrics
    # one record a squeeze stage this shape was traced with: the one the
    # kernels ran with here
    ran = ph._fb_geometry(f, max_bins, vpb, 1 if kind == "int8" else 2,
                          **({"section": arg.section} if vpb > 1
                             else {"rows": n}))
    rows = vpb * arg.section if vpb > 1 else -(-n // ran.row_chunk) \
        * ran.row_chunk
    geom, = [g for g in global_metrics.meta["hist_geometry"]
             if (g["kernel"], g["features"], g["max_bins"], g["pack_factor"],
                 g["operand"] == "int8", g["squeeze_stage"], g["rows"]) == (
                 "lgbm_hist_multi", f, max_bins, vpb, kind == "int8",
                 ran.squeeze_stage, rows)]
    assert slabs.shape[0] % geom["features_per_step"] == 0
    assert slabs.shape[0] >= f and slabs.shape[1] == geom["bp"] >= max_bins
    # the dots cover the real features only: a padded feature's slab is
    # never written, a real one's rows past max_bins never hit
    assert not slabs[:, max_bins:].any() and not slabs[f:].any()
    last = f - (-(-f // geom["features_per_step"]) - 1) \
        * geom["features_per_step"]
    assert geom["dots_per_step"] == -(-last // geom["features_per_dot"])
    assert geom["tail_features"] == (last - 1) % geom["features_per_dot"] + 1
    np.testing.assert_array_equal(
        slabs[:f, :max_bins, :3 * slots].reshape(f, max_bins, slots, 3),
        np.moveaxis(np.asarray(got), 0, 2))
    want = step_counts(np.asarray(row_leaf), np.asarray(leaf_ids), geom,
                       all_live=live == "root")
    assert dict(zip(ph.PASS_COUNTS, np.asarray(counts).tolist())) == want
    np.testing.assert_array_equal(np.asarray(slab_counts), np.asarray(counts))
    return np.asarray(got)


def step_counts(row_leaf, leaf_ids, geom, *, all_live=False):
    """What the steps of one multi-leaf pass count (ops/pallas_histogram.
    PASS_COUNTS), counted here in NumPy on the same rows: a chunk is
    `row_chunk` rows of ONE bit-section (byte j of a PackedBins feature
    holds rows j, j + section, ...), padded rows (leaf -1) are dead, the
    lane columns of a chunk are its lanes modulo 2^`squeeze_stage`, the
    loop runs ceil(tallest column x columns / k_tile) turns and the chunk
    is squeezed when that is fewer than all of them; every feature block
    runs the same."""
    cb, stage = geom["row_chunk"], geom["squeeze_stage"]
    t = geom["root_tile"] if all_live else geom["k_tile"]
    blocks = -(-geom["features"] // geom["features_per_step"])
    leaf = np.full(geom["rows"], -1)
    leaf[:len(row_leaf)] = row_leaf
    turns = squeezed = 0
    # [sections, chunks a section, lanes a column, columns]
    live = np.isin(leaf, leaf_ids[leaf_ids >= 0]).reshape(
        geom["pack_factor"], -1, cb >> stage, 1 << stage)
    for chunk in live.reshape(-1, cb >> stage, 1 << stage):
        n = (cb // t if all_live else
             -(-int(chunk.sum(0).max()) * (1 << stage) // t))
        turns += n
        squeezed += n < cb // t
    return {"rows_live": 0 if all_live else int(live.sum()),
            "k_tiles": blocks * turns, "chunks_squeezed": blocks * squeezed,
            "k_tiles_full": blocks * geom["rows"] // t, "k_tile": t,
            "feature_blocks": blocks}


def test_waved_quantized_grad_trains():
    """use_quantized_grad + waved growth end-to-end (CPU falls back to the
    XLA f32 hist on dequantized values — numerically identical to the
    int8 device path, which sums the same integers)."""
    X, y = make_binary(3000)
    bst = _train(X, y, 32, use_quantized_grad=True,
                 quant_train_renew_leaf=True)
    assert _auc(y, bst.predict(X)) > 0.85


def test_hist_pallas_single_matches_xla():
    from lightgbm_tpu.ops.histogram import build_histogram
    from lightgbm_tpu.ops.pallas_histogram import hist_pallas
    r = np.random.RandomState(1)
    n, f, b = 900, 11, 32
    bins = jnp.asarray(r.randint(0, b, (f, n)), jnp.uint8)
    grad = jnp.asarray(r.randn(n), jnp.float32)
    hess = jnp.asarray(np.abs(r.randn(n)), jnp.float32)
    mask = jnp.asarray((r.rand(n) < 0.9), jnp.float32)
    ref = build_histogram(bins, grad, hess, mask, max_bins=b, impl="xla")
    gh3 = jnp.stack([grad * mask, hess * mask, mask]).astype(jnp.float32)
    pal = hist_pallas(bins, gh3, max_bins=b, interpret=True)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def _wave_case(rng, N, F, B, L, W, *, live=8, all_invalid=False,
               has_categorical=True, bins_dtype=np.uint8):
    """One random wave over `live` current leaves: W distinct split
    leaves (the last step invalid, or all of them), right ids counting
    down from L - 1."""
    assert W <= live and live + W <= L + 1
    c = dict(
        bins=rng.randint(0, B, (F, N)).astype(bins_dtype),
        row_leaf=rng.randint(0, live, N).astype(np.int32),
        leaves=rng.permutation(live)[:W].astype(np.int32),
        rights=(L - 1 - np.arange(W)).astype(np.int32),
        feats=rng.randint(0, F, W).astype(np.int32),
        thrs=rng.randint(0, B - 1, W).astype(np.int32),
        dlefts=rng.rand(W) > 0.5,
        cmasks=rng.rand(W, B) > 0.5,
        valid=np.ones(W, bool),
        num_bins=rng.randint(max(B // 2, 2), B + 1, F).astype(np.int32),
        missing=rng.randint(0, 3, F).astype(np.int32),
        is_cat=(rng.rand(F) > 0.7) & has_categorical)
    c["num_bins"][0] = B
    c["valid"][-1] = False
    if all_invalid:
        c["valid"][:] = False
    return c


def _sequential(part_ops, c, bins, bundle=None):
    seq = jnp.asarray(c["row_leaf"])
    for w in range(len(c["leaves"])):
        seq = part_ops.apply_split(
            seq, bins, jnp.int32(c["leaves"][w]), jnp.int32(c["rights"][w]),
            jnp.int32(c["feats"][w]), jnp.int32(c["thrs"][w]),
            jnp.bool_(c["dlefts"][w]), jnp.asarray(c["cmasks"][w]),
            jnp.asarray(c["num_bins"]), jnp.asarray(c["missing"]),
            jnp.asarray(c["is_cat"]), jnp.bool_(c["valid"][w]), bundle)
    return np.asarray(seq)


def _batched(part_ops, c, bins, L, bundle=None, has_categorical=True,
             form=None):
    return np.asarray(part_ops.apply_wave_splits(
        jnp.asarray(c["row_leaf"]), bins, jnp.asarray(c["leaves"]),
        jnp.asarray(c["rights"]), jnp.asarray(c["feats"]),
        jnp.asarray(c["thrs"]), jnp.asarray(c["dlefts"]),
        jnp.asarray(c["cmasks"]), jnp.asarray(c["valid"]),
        jnp.asarray(c["num_bins"]), jnp.asarray(c["missing"]),
        jnp.asarray(c["is_cat"]), L, bundle, has_categorical, form=form))


# the cases apply_wave_splits branches on (static shapes and storage
# class): N, F, B, L, W and what differs from the dense mix
WAVE_CASES = {
    "dense-mix": dict(N=500, F=6, B=16, L=15, W=5),
    "no-categorical": dict(N=500, F=6, B=16, L=15, W=5,
                           has_categorical=False),
    # a full wave of the flagship tree: right ids up to 254, two records
    "W42-L255": dict(N=3000, F=28, B=63, L=255, W=42, live=213),
    # 8 mask words; a categorical split on the last bin
    "B255-cat-bin254": dict(N=2000, F=6, B=255, L=31, W=5, cat_last=True),
    "F33": dict(N=700, F=33, B=16, L=15, W=5),
    # the wide cell's shape (PR 32): a full wave over 2000 stored rows
    "F2000-W42-L255": dict(N=3000, F=2000, B=63, L=255, W=42, live=213,
                           has_categorical=False),
    "all-invalid": dict(N=500, F=6, B=16, L=15, W=5, all_invalid=True),
    # uint16 bins: thresholds and NaN codes wider than a byte
    "B300-uint16": dict(N=1500, F=5, B=300, L=15, W=4,
                        bins_dtype=np.uint16),
    # records wider than one int32: feature ids above 2^15, leaf ids
    # above 2^16
    "wide-ids": dict(N=300, F=40000, B=16, L=70000, W=3),
    "efb-bundle": dict(N=900, F=7, B=16, L=15, W=5, bundle=True),
    "packed-4bit": dict(N=2500, F=6, B=15, L=15, W=5, packed=True),
    "packed-2bit": dict(N=4500, F=6, B=3, L=15, W=5, packed=True),
    # the storage the Pallas kernel takes on the chip, each held
    # to the kernel too: uint8 at the flagship tree, both packings
    "kernel-W42-L255": dict(N=3000, F=28, B=63, L=255, W=42, live=213,
                            has_categorical=False),
    "kernel-packed-4bit": dict(N=2500, F=6, B=15, L=15, W=5, packed=True,
                               has_categorical=False),
    "kernel-packed-2bit": dict(N=4500, F=6, B=3, L=15, W=5, packed=True,
                               has_categorical=False),
}


@pytest.mark.usefixtures("release_executables")
@pytest.mark.parametrize("case", list(WAVE_CASES))
def test_apply_wave_splits_matches_sequential(case):
    """The wave partition must be BIT-equal to the sequential
    apply_split chain it stands for: NaN default routing, invalid
    steps, categorical masks, every storage class and every record
    width it packs; where the storage admits the Pallas kernel
    (`ops/pallas_partition`, interpret mode here), the kernel too."""
    from lightgbm_tpu.ops import partition as part_ops
    from lightgbm_tpu.ops.bin_pack import pack_bins_host, to_device

    kw = dict(WAVE_CASES[case])
    bundle_case = kw.pop("bundle", False)
    packed = kw.pop("packed", False)
    cat_last = kw.pop("cat_last", False)
    L, B = kw["L"], kw["B"]
    has_cat = kw.get("has_categorical", True)
    rng = np.random.RandomState(len(case))
    for trial in range(2 if kw["F"] > 1000 else 6):
        c = _wave_case(rng, **kw)
        if cat_last:
            f = c["feats"][0]
            c["is_cat"][f] = True
            c["num_bins"][f] = B
            c["cmasks"][0, B - 1] = trial % 2 == 0
            rows = np.flatnonzero(c["row_leaf"] == c["leaves"][0])[::2]
            c["bins"][f, rows] = B - 1
        bins, bundle = jnp.asarray(c["bins"]), None
        if packed:
            bins = to_device(pack_bins_host(c["bins"], B))
        if bundle_case:
            # 7 logical features in 3 stored columns: logical bin b >= 1
            # of feature f is stored as offset_of[f] + b - 1, 0 is shared
            group_of = np.array([0, 0, 0, 1, 1, 2, 2], np.int32)
            nb = np.array([5, 4, 6, 7, 3, 9, 8], np.int32)
            offset_of = np.array([1, 5, 8, 1, 7, 1, 9], np.int32)
            owner = rng.randint(0, 7, kw["N"])
            stored = np.zeros((3, kw["N"]), np.uint8)
            for f in range(7):
                mine = owner == f
                logical = rng.randint(0, nb[f], kw["N"])
                stored[group_of[f], mine] = np.where(
                    logical[mine] > 0, offset_of[f] + logical[mine] - 1, 0)
            c["num_bins"] = nb
            c["thrs"] = np.minimum(c["thrs"], nb[c["feats"]] - 1)
            bins = jnp.asarray(stored)
            bundle = (jnp.asarray(group_of), jnp.asarray(offset_of),
                      jnp.asarray(nb))
        seq = _sequential(part_ops, c, bins, bundle)
        got = _batched(part_ops, c, bins, L, bundle, has_cat)
        np.testing.assert_array_equal(seq, got)
        if case.startswith("kernel"):
            np.testing.assert_array_equal(seq, _batched(
                part_ops, c, bins, L, bundle, has_cat, form="kernel"))
        if not c["valid"].any():
            np.testing.assert_array_equal(got, c["row_leaf"])
        else:
            assert (got != c["row_leaf"]).any()


def _grower_data(case, rng, n):
    """(X, y, dataset params, categorical columns) of one storage class."""
    if case == "bundle":
        # one-hot-ish mutually exclusive features so EFB actually bundles
        hot = rng.randint(0, 6, n)
        X = np.zeros((n, 6))
        X[np.arange(n), hot] = rng.rand(n) * 3 + 0.5
        return X, np.isin(hot, [1, 4]).astype(np.float32), {"max_bin": 15}, []
    X = rng.randn(n, 6)
    X[rng.rand(n) < 0.1, 2] = np.nan            # a NaN bin to route
    y = (X[:, 0] + 0.5 * X[:, 1] ** 2 > 0.4).astype(np.float32)
    if case == "categorical":
        X[:, 3] = rng.randint(0, 12, n)
        y = np.where(np.isin(X[:, 3], [2, 5, 7]), 1.0, y).astype(np.float32)
        return X, y, {"max_bin": 63}, [3]
    return X, y, {"max_bin": 15 if case == "packed" else 63}, []


@pytest.mark.parametrize("case", [
    "dense", "categorical", "packed",
    pytest.param("bundle", marks=pytest.mark.slow)])
def test_batched_partition_through_grower(case):
    """Force the wave partition (the TPU default) through the FULL waved
    grower on CPU and require the tree and every row's leaf of the
    per-split partition (the CPU default, apply_split): the call-site
    wiring, has_categorical, and each storage class end to end (dense,
    a categorical feature, PackedBins, an EFB bundle)."""
    from lightgbm_tpu import Dataset
    from lightgbm_tpu.basic import Booster
    from lightgbm_tpu.learner import grow_tree_waved
    from lightgbm_tpu.ops.bin_pack import PackedBins

    n = 1500
    X, y, ds_params, cats = _grower_data(case, np.random.RandomState(9), n)
    ds = Dataset(X, label=y, categorical_feature=cats or "auto",
                 params={**ds_params, "verbosity": -1}).construct()
    binned = ds._binned
    bst = Booster({"objective": "binary", "num_leaves": 15,
                   "min_data_in_leaf": 5, "verbosity": -1, **ds_params}, ds)
    g = bst._gbdt
    assert (binned.bundle_info is not None) == (case == "bundle")
    assert isinstance(g.bins_fm, PackedBins) == (case == "packed")
    assert g._has_categorical == (case == "categorical")
    grad = jnp.asarray(y - 0.5, jnp.float32)
    hess = jnp.full(n, 0.25, jnp.float32)
    mask = jnp.ones(n, jnp.float32)
    fmask = jnp.ones(binned.num_features, bool)
    kw = dict(g._grow_kwargs(), hist_dtype=jnp.float32, hist_impl="xla",
              hist_precision="highest",
              has_categorical=g._has_categorical)
    outs = {}
    for batched in (False, True):
        rec, row_leaf = grow_tree_waved(
            g.bins_fm, grad, hess, mask, fmask, g.feature_meta, g.hp,
            g.max_depth, None, None, batched_partition=batched, **kw)
        outs[batched] = (np.asarray(row_leaf), np.asarray(rec.leaf_count),
                        np.asarray(rec.split_feature),
                        np.asarray(rec.split_cat_mask))
    assert int(np.asarray(rec.num_leaves)) >= (3 if case == "bundle" else 8)
    if case == "categorical":
        assert 3 in outs[True][2]
    for a, b in zip(outs[False], outs[True]):
        np.testing.assert_array_equal(a, b)


def small_children_rows(tree, schedule):
    """The child-count walk: the rows of each wave pass's smaller
    children (the last wave has no pass), from the model's own child
    pointers: what a bag-free tree's wave passes find live."""
    def rows(child):
        return (tree.leaf_count[~child] if child < 0
                else tree.internal_count[child])

    small = [min(rows(tree.left_child[s]), rows(tree.right_child[s]))
             for s in range(tree.num_leaves - 1)]
    out, at = [], 0
    for w in schedule[:-1]:
        out.append(int(round(sum(small[at:at + w]))))
        at += w
    return out


def test_hist_live_rows_counter():
    """`global_metrics.meta["hist_live_rows"]`: each tree whose passes
    ran on the Mosaic kernels (interpret mode here) leaves, a pass, what
    the kernel's steps counted, with no tracer session: every row for the
    root, then each wave's rows in a slot's leaf, which on a bag-free
    tree are the smaller children's rows counted from the model's own
    child pointers. An iteration fetches nothing for it: the trees stay
    on the device until the model is asked for. Passes on the XLA twins
    are counted by nobody, and nothing is recorded."""
    from lightgbm_tpu.learner import HIST_SLOTS, _wave_schedule
    from lightgbm_tpu.obs.metrics import global_metrics
    from lightgbm_tpu.obs.trace import global_tracer
    assert not global_tracer.enabled
    X, y = make_binary(3000)
    params = {"objective": "binary", "num_leaves": 31, "verbosity": -1,
              "min_data_in_leaf": 5}
    global_metrics.meta.pop("hist_live_rows", None)
    bst = lgb.Booster(params, lgb.Dataset(X, label=y))
    bst.update()
    bst.model_to_string()
    assert "hist_live_rows" not in global_metrics.meta

    bst = lgb.Booster({**params, "tpu_hist_impl": "pallas"},
                      lgb.Dataset(X, label=y))
    bst.update()
    bst.update()
    # the counter reads a tree as it reaches the host, and an iteration
    # brings none there
    assert not bst._gbdt._host_models and len(bst._gbdt._device_records) == 2
    assert "hist_live_rows" not in global_metrics.meta
    bst.model_to_string()
    trees = [t for it in bst._gbdt._host_models for t in it]
    recorded = global_metrics.meta["hist_live_rows"]
    assert len(recorded) == len(trees) == 2
    schedule = _wave_schedule(31, bst._gbdt._resolved_wave_max(),
                              HIST_SLOTS)
    for tree, passes in zip(trees, recorded):
        assert tree.num_leaves == 31
        assert [p["pass"] for p in passes] == ["root"] + [
            f"w{i:02d}" for i in range(len(schedule) - 1)]
        assert passes[0]["rows_live"] == passes[0]["rows_passed"] == 3000
        assert passes[0]["k_tiles"] == passes[0]["k_tiles_full"]
        assert passes[0]["chunks_squeezed"] == 0
        assert [p["rows_live"] for p in passes[1:]] == \
            small_children_rows(tree, schedule)
        for p, w in zip(passes[1:], schedule):
            assert p["slots"] == w and p["rows_passed"] == 3000
            assert 0 < p["rows_live"] <= 1500
            # a chunk's live rows need a turn at least; none needs more
            # than all of them
            assert (p["rows_live"] * p["feature_blocks"]
                    <= p["k_tiles"] * p["k_tile"])
            assert p["k_tiles"] <= p["k_tiles_full"]
    # a pass's counts to its record: a three-leaf tree of 100 rows, one
    # wave pass (the last wave has none)
    from lightgbm_tpu.learner import hist_live_rows
    counts = np.array([[0, 16, 0, 16, 8, 1], [50, 8, 1, 16, 8, 1]])
    assert hist_live_rows(counts, num_data=100, num_leaves=3,
                          wave_max=42) == [
        {"pass": "root", "slots": 1, "rows_passed": 100, "rows_live": 100,
         "k_tiles": 16, "chunks_squeezed": 0, "k_tiles_full": 16,
         "k_tile": 8, "feature_blocks": 1},
        {"pass": "w00", "slots": 1, "rows_passed": 100, "rows_live": 50,
         "k_tiles": 8, "chunks_squeezed": 1, "k_tiles_full": 16,
         "k_tile": 8, "feature_blocks": 1}]
