"""The wide shape on the normal path (PR 32): 2000 dense features, 63
bins, ``num_leaves`` 255, through ``lgb.Dataset`` and ``lgb.train`` with
every ``tpu_*`` parameter at its default, on the float path and with
``use_quantized_grad``, against a plain NumPy float64 reference written
here: the gradient of binary log loss at the initial scores, a node's
histogram by ``np.add.at`` over its own rows, the gain
``GL^2/HL + GR^2/HR - G^2/H`` of every (feature, bin) that leaves both
sides ``min_sum_hessian_in_leaf``, Newton leaf values.

The reference follows the program's tree node by node (a leaf-wise tree
whose leaf budget does not bind is the same tree in any leaf order, and
the waved grower's order is not the exact one): at every node the
program split, the split it took must be the reference's best, or tied
with it; at every leaf the reference must find nothing left to split.
Only the chip runs the Mosaic kernels and the bfloat16 operand; here the
histograms are the XLA twin's exact float32 sums
(``benchmarks/tests/test_epsilon_gpu63.py`` and the cell itself hold the
chip's).

Also here: the kernel step's geometry at this width, and the entry
layer's promise that fitting the bin mappers on worker threads changes
no bit of them or of the binned matrix.
"""

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import dataset as dataset_mod
from lightgbm_tpu.binning import BinMapper
from lightgbm_tpu.config import Config
from lightgbm_tpu.dataset import BinnedDataset

N, F, BINS, LEAVES = 6000, 2000, 63, 255
# The cell states 100 for 1.2M rows; 6000 rows hold 1400. At 40 the tree
# ends at some 25 leaves and a depth the waved grower reaches long before
# its 13th and last wave: it is then complete, and every leaf order grows it.
MIN_HESS = 40.0
WAVES = 13         # learner._wave_schedule(255, ...): 1, 2, 4, 8, 8, 8, 15, ...
LEARNING_RATE = 0.1
PATHS = {"float": {},
         "int8": {"use_quantized_grad": True, "num_grad_quant_bins": 126}}


def _data():
    """The benchmark generator's shape: N(0.26, 1) features, the label
    from features 0-4, 1995 columns of noise; initial scores that give
    every row its own gradient and hessian."""
    r = np.random.RandomState(32)
    x = r.standard_normal((N, F))
    logit = (x[:, 0] + 0.6 * x[:, 1] ** 2 + 0.4 * x[:, 2] * x[:, 3]
             - 0.3 * np.abs(x[:, 4]) + 0.5 * r.standard_normal(N))
    y = (logit > 0.2).astype(np.float64)
    return x + 0.26, y, 0.5 * r.standard_normal(N)


@pytest.fixture(scope="module")
def trained():
    """{path: (tree, bins [F, N], g, h)}: one tree of each path, the bins
    the program made, the float64 gradients at the initial scores."""
    x, y, init = _data()
    p = 1.0 / (1.0 + np.exp(-init))
    out = {}
    for path, extra in PATHS.items():
        ds = lgb.Dataset(x, label=y, init_score=init)
        bst = lgb.train({"objective": "binary", "num_leaves": LEAVES,
                         "max_bin": BINS, "learning_rate": LEARNING_RATE,
                         "min_data_in_leaf": 1,
                         "min_sum_hessian_in_leaf": MIN_HESS,
                         "verbosity": -1, **extra}, ds, num_boost_round=1)
        bins = ds._binned.bins_fm
        assert bins.shape == (F, N) and ds._binned.bundle_info is None
        out[path] = (bst._gbdt.models[0][0], bins, p - y, p * (1.0 - p))
    return out


def _gains(bins, rows, g, h):
    """(gain [F, BINS - 1], G, H) of the node holding ``rows``: candidate
    (f, t) sends bins <= t left; -inf where a side falls under MIN_HESS."""
    hist = np.zeros((2, F, BINS))
    at = (np.arange(F)[:, None], bins[:, rows])
    np.add.at(hist[0], at, g[rows][None, :])
    np.add.at(hist[1], at, h[rows][None, :])
    gl, hl = np.cumsum(hist, axis=2)[:, :, :-1]
    gt, ht = g[rows].sum(), h[rows].sum()
    ok = (hl >= MIN_HESS) & (ht - hl >= MIN_HESS)
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = gl ** 2 / hl + (gt - gl) ** 2 / (ht - hl) - gt ** 2 / ht
    return np.where(ok, gain, -np.inf), gt, ht


def _follow(tree, bins, g, h):
    """Walks the program's tree from the root with the reference beside
    it. Returns a record per internal node (the reference's best gain,
    the gain of the split taken, whether that split is the best one) and
    per leaf (rows, G, H, the best gain still on offer)."""
    nodes, leaves = [], []
    stack = [(0, np.arange(N), 0)] if tree.num_leaves > 1 else []
    while stack:
        node, rows, depth = stack.pop()
        if node < 0:
            gain, gt, ht = _gains(bins, rows, g, h)
            leaves.append(dict(leaf=~node, rows=len(rows), G=gt, H=ht,
                               on_offer=gain.max(), depth=depth))
            continue
        gain, _, _ = _gains(bins, rows, g, h)
        f, t = tree.split_feature_inner[node], tree.threshold_bin[node]
        best = np.unravel_index(np.argmax(gain), gain.shape)
        second = np.partition(gain.ravel(), -2)[-2]
        nodes.append(dict(node=node, best=gain[best], taken=gain[f, t],
                          same=(f, t) == tuple(best), second=second,
                          recorded=tree.split_gain[node]))
        left = bins[f, rows] <= t
        stack.append((tree.left_child[node], rows[left], depth + 1))
        stack.append((tree.right_child[node], rows[~left], depth + 1))
    return nodes, leaves


# What a gain may be off by. float: the program sums float32 gradients
# in float32, a node's G to 1e-6 of the sum of |g| (up to sqrt(n) eps
# over 6000 rows), and a gain is a difference of squares of such sums
# that are themselves far larger than the gain at a deep node: 2e-4 of
# the best gain plus 2e-3 absolute covers it, far under the gap between
# a signal split and the noise columns. int8: each row's g is rounded
# stochastically to a multiple of max|g| / 63 (h: max h / 126), an
# unbiased error of variance at most a quarter step squared a row, so a
# side's G is off by up to 4 x step x sqrt(rows) / 2 at four standard
# deviations and its gain by 2 G dG / H; the reference's exact gains
# then rank the noise columns' candidates (gains of 5-15 among 124,000)
# differently, so the split taken is held to a share of the best gain
# measured against the noise floor 2 ln(F x 62), the benchmark's
# ``split_gain_shortfall`` form.
NOISE_FLOOR = 2.0 * np.log(F * (BINS - 1))
GAIN_TOL = {"float": lambda best: 2e-4 * best + 2e-3,
            "int8": lambda best: 0.05 * max(best, 8 * NOISE_FLOOR)}


@pytest.mark.parametrize("path", list(PATHS))
def test_train_takes_the_reference_splits(trained, path):
    tree, bins, g, h = trained[path]
    nodes, leaves = _follow(tree, bins, g, h)
    # neither the leaf budget nor the number of waves binds
    assert 16 <= tree.num_leaves < LEAVES // 4
    assert max(leaf["depth"] for leaf in leaves) < WAVES - 2
    assert len(nodes) == tree.num_leaves - 1 and len(leaves) == len(nodes) + 1
    tol = GAIN_TOL[path]
    for n in nodes:
        assert n["taken"] >= n["best"] - tol(n["best"]), n
        # the same feature and bin, where the runner-up is not a tie
        if n["best"] - n["second"] > 2 * tol(n["best"]):
            assert n["same"], n
    # read here: all 29 splits the reference's own on both paths; the
    # int8 path is allowed the splits its rounding may tie differently
    assert nodes[0]["same"] and sum(n["same"] for n in nodes) >= {
        "float": len(nodes) - 2, "int8": len(nodes) - len(nodes) // 4}[path]
    # nothing left to split, by the reference's own sums (int8: a leaf's
    # hessian by its rows may stand a step or two over the minimum that
    # its quantised bins fell under)
    for leaf in leaves:
        assert leaf["on_offer"] <= tol(0.0) + (
            0 if path == "float" else 8 * NOISE_FLOOR), leaf


@pytest.mark.parametrize("path", list(PATHS))
def test_leaf_counts_and_values(trained, path):
    tree, bins, g, h = trained[path]
    _, leaves = _follow(tree, bins, g, h)
    g_step, h_step = np.abs(g).max() / 63, h.max() / 126
    for leaf in leaves:
        i = leaf["leaf"]
        assert tree.leaf_count[i] == leaf["rows"]        # exact, both paths
        want = -leaf["G"] / leaf["H"] * LEARNING_RATE
        if path == "float":
            # float32 sums of 6000 rows or fewer: 1e-5 of the value, and
            # of sum |g| / H where G cancels to near nothing
            tol = 1e-5 * (abs(want) + LEARNING_RATE)
        else:
            # four standard deviations of the stochastic rounding of the
            # leaf's rows (see GAIN_TOL), through -G / H
            dg = 4 * g_step * np.sqrt(leaf["rows"]) / 2
            dh = 4 * h_step * np.sqrt(leaf["rows"]) / 2
            tol = LEARNING_RATE * (dg + abs(leaf["G"] / leaf["H"]) * dh) \
                / (leaf["H"] - dh)
        assert abs(tree.leaf_value[i] - want) <= tol, (leaf, want)
        assert leaf["H"] >= MIN_HESS - (0 if path == "float" else 4 * h_step
                                        * np.sqrt(leaf["rows"]) / 2)


def test_recorded_gains_are_the_reference_gains(trained):
    tree, bins, g, h = trained["float"]
    nodes, _ = _follow(tree, bins, g, h)
    for n in nodes:
        assert n["recorded"] == pytest.approx(
            n["taken"], rel=2e-4, abs=2e-3), n


@pytest.mark.parametrize("itemsize", [1, 2], ids=["int8", "bf16"])
def test_the_step_takes_many_feature_blocks(itemsize):
    """At 28 features a grid step holds every feature; at 2000 the
    accumulator leaves room for 72-80 a step, so the leaf operand is
    built once a row chunk for each of 25-28 blocks
    (``tests/test_waved.py::test_shared_step_matches_xla_twins`` runs that
    step against its XLA twin at this width)."""
    from lightgbm_tpu.ops.pallas_histogram import _fb_geometry
    geom = _fb_geometry(F, BINS, itemsize=itemsize, rows=1_200_000)
    blocks = -(-F // geom.f_blk)
    assert geom.bp == 64 and 1 < blocks <= 32 and geom.f_blk % 8 == 0
    assert _fb_geometry(28, BINS, itemsize=itemsize,
                        rows=63_000_000).f_blk == 32


# ---------------------------------------------------------------------------
# the entry layer: threads change nothing

def _mapper_facts(m: BinMapper):
    return (m.num_bins, m.is_categorical, m.missing_type, m.default_bin,
            m.most_freq_bin, m.min_value, m.max_value, m.is_trivial,
            None if m.bin_upper_bound is None else m.bin_upper_bound.tobytes(),
            None if m.cat_bin_to_value is None
            else np.asarray(m.cat_bin_to_value).tobytes())


def _serial_mappers(x, cfg, cats=(), forced=None):
    """The plain loop ``from_matrix`` ran before PR 32: one column after
    another, each a strided read of the sampled rows."""
    n, f = x.shape
    sample_cnt = min(n, int(cfg.bin_construct_sample_cnt))
    sample = x
    if sample_cnt < n:
        rng = np.random.RandomState(cfg.data_random_seed)
        sample = x[np.sort(rng.choice(n, sample_cnt, replace=False))]
    by_feature = cfg.max_bin_by_feature
    return [BinMapper().fit(
        np.asarray(sample[:, col], dtype=np.float64),
        max_bin=int(by_feature[col]) if by_feature is not None
        and len(by_feature) == f else int(cfg.max_bin),
        min_data_in_bin=int(cfg.min_data_in_bin),
        use_missing=bool(cfg.use_missing),
        zero_as_missing=bool(cfg.zero_as_missing),
        is_categorical=col in cats,
        forced_bounds=(forced or {}).get(col)) for col in range(f)]


def _entry_cases():
    r = np.random.RandomState(5)
    n, f = 3000, 70
    x = r.standard_normal((n, f))
    nan = x.copy()
    nan[r.rand(n, f) < 0.1] = np.nan
    zeros = x.copy()
    zeros[r.rand(n, f) < 0.6] = 0.0
    cat = x.copy()
    cat[:, 3], cat[:, 40] = r.randint(0, 12, n), r.randint(0, 300, n)
    one_hot = np.zeros((n, 60))
    one_hot[np.arange(n), r.randint(0, 20, n)] = 1
    one_hot[np.arange(n), 20 + r.randint(0, 20, n)] = r.randint(1, 4, n)
    one_hot[:, 40:] = np.abs(r.standard_normal((n, 20))) * (
        r.rand(n, 20) < 0.02)
    return {
        "numerical": (x, {}, {}),
        "sampled": (r.standard_normal((9000, 40)),
                    {"bin_construct_sample_cnt": 2500, "max_bin": 63}, {}),
        "categorical": (cat, {}, {"categorical_features": [3, 40]}),
        "nan": (nan, {}, {}),
        "no_missing": (nan, {"use_missing": False}, {}),
        "zero_as_missing": (zeros, {"zero_as_missing": True}, {}),
        "forced_bins": (x, {}, {"forced_bins": {2: [-0.5, 0.1, 0.7],
                                                69: [0.0]}}),
        "max_bin_by_feature": (x, {"max_bin_by_feature": [
            int(v) for v in r.randint(4, 200, f)]}, {}),
        "float32_column_major": (np.asfortranarray(x.astype(np.float32)),
                                 {"max_bin": 15}, {}),
        # EFB: columns that bundle, columns too dense to (the early return)
        "one_hot_bundles": (one_hot, {}, {}),
        "bundles_with_conflicts": (one_hot, {"max_conflict_rate": 0.05}, {}),
        "dense_default_bin_0": (np.abs(x) * (r.rand(n, f) < 0.97), {}, {}),
        "dense_within_budget": (np.abs(x) * (r.rand(n, f) < 0.6),
                                {"max_conflict_rate": 0.3}, {}),
    }


ENTRY_CASES = _entry_cases()


@pytest.mark.parametrize("case", list(ENTRY_CASES))
def test_threaded_binning_is_bit_for_bit_the_serial_one(case, monkeypatch):
    """Mappers from the worker threads (forced to 4 here, blocks of 8
    columns, whatever this machine has) equal the serial loop's, field
    for field and bit for bit; so does the binned matrix, the bundles,
    and a second data set binned by reference to the first. EFB's early
    returns give what the whole conflict search gives."""
    from lightgbm_tpu import bundling
    x, params, kw = ENTRY_CASES[case]
    cfg = Config(dict(params, verbosity=-1))
    monkeypatch.setattr(dataset_mod, "_fit_workers", lambda columns: 4)
    monkeypatch.setattr(dataset_mod, "_FIT_BLOCK", 8)
    got = BinnedDataset.from_matrix(x, cfg, **kw)

    want = _serial_mappers(x, cfg, set(kw.get("categorical_features", ())),
                           kw.get("forced_bins"))
    used = [i for i, m in enumerate(want) if not m.is_trivial]
    assert got.used_features == used
    assert [_mapper_facts(m) for m in got.mappers] == [
        _mapper_facts(want[i]) for i in used]

    # the binned matrix: every column by its mapper alone, then the whole
    # conflict search with no early return
    dtype = np.uint8 if max(m.num_bins for m in got.mappers) <= 256 \
        else np.uint16
    plain = np.stack([want[i].transform(np.asarray(x[:, i], np.float64))
                      for i in used]).astype(dtype)
    nb = np.array([want[i].num_bins for i in used], np.int64)
    default = np.array([want[i].default_bin for i in used], np.int64)
    bundles = bundling.find_bundles(
        plain != default[:, None].astype(dtype), nb,
        max_conflict_rate=float(cfg.max_conflict_rate),
        max_bundle_bins=max(int(nb.max()), 256), bundleable=default == 0)
    if bundling.should_bundle(bundles, len(used)):
        plain, info = bundling.build_bundled_matrix(plain, nb, bundles)
        assert got.bundle_info.bundles == info.bundles
    else:
        assert got.bundle_info is None
    assert case not in ("one_hot_bundles", "bundles_with_conflicts") \
        or got.bundle_info is not None
    assert got.bins_fm.dtype == plain.dtype
    np.testing.assert_array_equal(got.bins_fm, plain)

    again = BinnedDataset.from_matrix(x[:500], cfg, reference=got)
    np.testing.assert_array_equal(again.bins_fm, got.bins_fm[:, :500])


def test_binning_counters_are_always_on():
    """``data/binning`` and its three parts are counted with the tracer
    off (``benchmarks/metrics/dataset_bin_s.py`` reads them), and are
    spans of the tracer when it is on."""
    from lightgbm_tpu.obs.metrics import global_metrics
    from lightgbm_tpu.obs.trace import global_tracer
    r = np.random.RandomState(0)
    x = r.standard_normal((2000, 40))
    y = (x[:, 0] > 0).astype(np.float64)
    assert not global_tracer.enabled
    before = len(global_metrics.meta.get("data_binning", []))
    lgb.Dataset(x, label=y).construct()
    records = global_metrics.meta["data_binning"]
    assert len(records) == before + 1
    rec = records[-1]
    assert rec["columns"] == 40 and rec["sample_rows"] == 2000
    assert rec["workers"] >= 1
    parts = rec["find_bins_s"] + rec["transform_s"] + rec["bundle_s"]
    assert 0 < parts <= rec["seconds"]
    global_tracer.enable()
    try:
        global_tracer.reset()
        lgb.Dataset(x, label=y).construct()
        spans = global_tracer.summary()
    finally:
        global_tracer.disable()
        global_tracer.reset()
    assert {"data/binning", "data/binning/find_bins",
            "data/binning/transform", "data/binning/bundle"} <= set(spans)
    assert spans["data/binning"]["self_seconds"] < \
        spans["data/binning"]["seconds"]
