"""The multi-leaf histogram kernels' step multiplies the live rows of a
pass only (ops/pallas_histogram._multi_step: live mask, squeeze of the
chunk's live lanes, a loop over its sub-tiles), and gives the XLA twins'
histograms whichever rows are live. The check is tests/test_waved.py's
(`check_shared_step`); its live-row cases are here so that they run on
another worker than that file's."""

import numpy as np
import pytest

from tests.test_waved import check_shared_step

# (kind, live, max_bins, values a byte, features, slots, rows): no slot
# matches; one live row a chunk; shares of 0.1 and 0.5; over 7/8 (the
# chunk that is not squeezed); the root (told at the call site); live rows
# at the end of each chunk; on raw bins under each operand reader, on
# 4-bit PackedBins (two bit-sections, each squeezed with its own rows) and
# on 2-bit ones (four); 42, 8 and 1 slots; 5000 and 9000 rows divide into
# no chunk (-1 pads)
LIVE_CASES = [(kind, live, 63, 1, 28, 42, 5000)
              for kind in ("int8", "float", "fused")
              for live in ("none", "one", 0.1, 0.5, "dense", "root", "end")]
LIVE_CASES += [("int8", live, 15, 2, 28, 8, 9000)
               for live in ("none", "one", 0.1, "dense", "root", "end")]
LIVE_CASES += [("fused", live, 3, 4, 28, 1, 9000) for live in (0.5, "end")]

# what the column squeeze can get wrong and the full network could not
# (test_waved._step_rows), under each reader, raw and 4-bit packed
COLUMN_CASES = [(kind, live, max_bins, vpb, 28, 8, n)
                for kind in ("int8", "float", "fused")
                for max_bins, vpb, n in ((63, 1, 5000), (15, 2, 9000))
                for live in ("stride", "column", "last_vreg", "tile_edge")]


@pytest.mark.usefixtures("release_executables")
@pytest.mark.parametrize("kind,live,max_bins,vpb,f,slots,n", LIVE_CASES)
def test_step_multiplies_live_rows_only(kind, live, max_bins, vpb, f, slots,
                                        n):
    check_shared_step(kind, live, max_bins, vpb, f, slots, n)


@pytest.mark.usefixtures("release_executables")
@pytest.mark.parametrize("kind,live,max_bins,vpb,f,slots,n", COLUMN_CASES)
def test_column_squeeze_multiplies_live_rows_only(kind, live, max_bins, vpb,
                                                  f, slots, n):
    """The step under the column squeeze (squeeze stage 7, whatever the
    rule gives this shape): columns that end at different heights, a
    chunk that is not squeezed because one column is full, a tallest
    column that ends on a sub-tile's edge."""
    check_shared_step(kind, live, max_bins, vpb, f, slots, n, stage=7)


@pytest.mark.usefixtures("release_executables")
@pytest.mark.parametrize("stage", [1, 3, 4, 5, 6])
@pytest.mark.parametrize("live", ["tile_edge", 0.1, "end"])
def test_squeeze_from_any_stage(stage, live):
    """The network started between the two ends: 2^stage lane columns,
    several lanes of a vreg in each."""
    check_shared_step("int8", live, 63, 1, 28, 8, 5000, stage=stage)


@pytest.mark.usefixtures("release_executables")
@pytest.mark.parametrize("max_bins,vpb,n", [(63, 1, 5000), (15, 2, 9000)])
def test_every_squeeze_gives_the_same_int8_histograms(max_bins, vpb, n):
    """Each end of the static rule's range and what the rule gives this
    shape, on the same input: the full network (stage 0), the column
    squeeze (stage 7) and the one between sum the same integers."""
    from lightgbm_tpu.ops import pallas_histogram as ph
    from lightgbm_tpu.ops.bin_pack import section_len
    rule = ph._fb_geometry(28, max_bins, vpb, 1, **(
        {"rows": n} if vpb == 1 else {"section": section_len(n, vpb)})
    ).squeeze_stage
    assert 0 < rule < 7
    full, columns, between = (
        check_shared_step("int8", 0.3, max_bins, vpb, 28, 42, n, stage=stage)
        for stage in (0, 7, rule))
    assert full.any()
    np.testing.assert_array_equal(full, columns)
    np.testing.assert_array_equal(full, between)


def test_squeeze_tiles_model_against_counted_tallest_columns():
    """`squeeze_tiles` (the expected tallest of 2^stage binomial lane
    columns, in sub-tiles: what the geometry's rule weighs the stages
    by) against a count on random rows, at the cells' two chunk sizes;
    and the counts of a pass (what the kernel's steps count, by the
    NumPy count the step is held to exactly: test_waved.step_counts)
    against the model, and as `learner.hist_live_rows` publishes them."""
    from lightgbm_tpu.learner import hist_live_rows
    from lightgbm_tpu.ops.pallas_histogram import PASS_COUNTS, squeeze_tiles
    from tests.test_waved import step_counts
    r = np.random.RandomState(36)
    for row_chunk in (16384, 8192):
        for share in (0.5, 0.1, 0.01):
            live = r.rand(1500, row_chunk) < share
            lanes = np.ceil(live.sum(1) / 1024).mean()
            for stage in (0, 4, 6, 7):
                cols = 1 << stage
                tallest = live.reshape(1500, -1, cols).sum(1).max(1)
                counted = np.ceil(tallest * cols / 1024).mean()
                model = squeeze_tiles(share, row_chunk, 1024, stage)
                assert abs(model - counted) < 0.03 * counted, (
                    row_chunk, share, stage)
                # more columns, a taller tallest: never under the lanes'
                assert model >= lanes - 0.03 * lanes
    assert squeeze_tiles(0.0, 16384, 1024, 7) == 0.0
    assert squeeze_tiles(1.0, 16384, 1024, 7) == 16.0
    # a three-leaf tree of 262,144 rows in sixteen 16,384-row chunks, in
    # random order: the wave's pass has a quarter of them live
    n = 1 << 18
    row_leaf = np.where(r.rand(n) < 0.25, 1, 2)
    geom = dict(row_chunk=16384, k_tile=1024, root_tile=2048, rows=n,
                features=28, features_per_step=32, pack_factor=1)
    root = step_counts(row_leaf, np.array([0]), dict(geom, squeeze_stage=0),
                       all_live=True)
    assert (root["k_tiles"], root["k_tiles_full"]) == (128, 128)
    turns = []
    for stage in (0, 4, 7):
        wave = step_counts(row_leaf, np.array([1, -2]),
                           dict(geom, squeeze_stage=stage))
        assert wave["rows_live"] == (row_leaf == 1).sum()
        turns.append(wave["k_tiles"])
        # the model's expectation, counted on one tree's rows
        assert abs(wave["k_tiles"] - 16 * squeeze_tiles(
            0.25, 16384, 1024, stage)) <= 0.1 * wave["k_tiles"]
        assert wave["chunks_squeezed"] == 16 and wave["k_tiles_full"] == 256
        passes = hist_live_rows(
            np.array([[root[k] for k in PASS_COUNTS],
                      [wave[k] for k in PASS_COUNTS]]),
            num_data=n, num_leaves=3, wave_max=42)
        assert [p["pass"] for p in passes] == ["root", "w00"]
        assert passes[0]["rows_live"] == n
        assert {k: passes[1][k] for k in PASS_COUNTS} == wave
    # one column: a chunk's live rows in whole sub-tiles; more columns,
    # a taller tallest
    live = (row_leaf == 1).reshape(16, -1).sum(1)
    assert turns[0] == np.ceil(live / 1024).sum()
    assert turns == sorted(turns)


def test_live_rows_of_a_packed_pass_count_bit_sections():
    """The counts of a pass on PackedBins: each bit-section's chunk is
    squeezed and multiplied on its own, so the turns of a pass are
    counted over `pack_factor` x (section / row_chunk) chunks, not over
    the byte blocks and not over `num_data` rounded up. 9,000 rows at
    two values a byte: sections of 6,144 bytes (three 2,048-row chunks
    each, six chunk-sections where 9,000 rows rounded up give five), two
    sub-tiles a chunk. Through an untraced booster on the Mosaic kernels
    (interpret mode): the wave passes' live rows are the smaller
    children's of the tree (no bagging)."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.learner import HIST_SLOTS, _wave_schedule
    from lightgbm_tpu.obs.metrics import global_metrics
    from lightgbm_tpu.ops import pallas_histogram as ph
    from lightgbm_tpu.ops.bin_pack import pack_bins_host
    from tests.test_waved import small_children_rows
    n = 9000
    pb = pack_bins_host(np.zeros((28, n), np.uint8), 15)
    geom = ph._fb_geometry(28, 15, pb.vpb, 2, section=pb.section)
    assert (pb.section, geom.row_chunk, geom.k_tile) == (6144, 2048, 1024)

    r = np.random.RandomState(0)
    x = r.randn(n, 28)
    y = (x[:, 0] + 0.5 * x[:, 1] > 0).astype(np.float64)
    global_metrics.meta.pop("hist_geometry", None)
    bst = lgb.Booster({"objective": "binary", "num_leaves": 7,
                       "max_bin": 15, "min_data_in_leaf": 5,
                       "verbosity": -1, "tpu_hist_impl": "pallas"},
                      lgb.Dataset(x, label=y))
    bst.update()
    bst.model_to_string()
    step = global_metrics.meta["hist_geometry"][-1]
    assert (step["pack_factor"], step["section"], step["rows"]) == \
        (2, 6144, 12288)
    passes = global_metrics.meta["hist_live_rows"][-1]
    assert {p["k_tiles_full"] for p in passes if p["pass"] != "root"} == {12}
    tree, = bst._gbdt._host_models[-1]
    schedule = _wave_schedule(7, 42, HIST_SLOTS)
    assert [p["rows_live"] for p in passes[1:]] == \
        small_children_rows(tree, schedule)
    # the root's pass multiplies whole chunks: `root_tile` rows a turn
    assert passes[0]["k_tiles"] == passes[0]["k_tiles_full"] == \
        step["rows"] // step["root_tile"]


@pytest.mark.usefixtures("release_executables")
def test_counting_kernel_leaves_the_model_alone():
    """The kernel counts beside its histograms and changes none of
    them: int8 gradients sum integers, so a booster on the Mosaic
    kernels (interpret mode) writes the model text of one on the XLA
    twins, bit for bit, and its passes' counts are published."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs.metrics import global_metrics
    r = np.random.RandomState(5)
    x = r.randn(4000, 8)
    y = (x[:, 0] - x[:, 2] ** 2 + 0.3 * r.randn(4000) > 0).astype(float)
    texts = {}
    for impl in ("xla", "pallas"):
        global_metrics.meta.pop("hist_live_rows", None)
        bst = lgb.train({"objective": "binary", "num_leaves": 15,
                         "use_quantized_grad": True, "verbosity": -1,
                         "tpu_hist_impl": impl},
                        lgb.Dataset(x, label=y), num_boost_round=3)
        texts[impl] = bst.model_to_string().split("parameters:")[0]
        assert ("hist_live_rows" in global_metrics.meta) == (impl == "pallas")
    assert texts["pallas"] == texts["xla"]
