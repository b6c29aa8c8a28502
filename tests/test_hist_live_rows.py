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
    columns, in sub-tiles: what the geometry's rule weighs the stages by
    and `learner.hist_live_rows` reckons a pass's K-sub-tiles with)
    against a count on random rows, at the cells' two chunk sizes; and
    the counter names the squeeze it reckoned with."""
    from lightgbm_tpu.learner import hist_live_rows
    from lightgbm_tpu.ops.pallas_histogram import squeeze_tiles
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
    # a three-leaf tree of 65,536 rows in four 16,384-row chunks: the
    # wave's pass has a quarter of the rows live
    rec = {"split_leaf": np.array([0, 0]), "num_leaves": 3,
           "leaf_count": np.array([8192.0, 16384.0, 40960.0])}
    kw = dict(num_data=65536, num_leaves=3, wave_max=42, row_chunk=16384,
              k_tile=1024)
    lanes = hist_live_rows(rec, **kw)
    columns = hist_live_rows(rec, **kw, squeeze_stage=7)
    assert [p["squeeze"] for p in lanes] == ["none", "lanes"]
    assert [p["squeeze"] for p in columns] == ["none", "columns"]
    assert lanes[0] == columns[0] and lanes[0]["k_tiles"] == 64
    assert (lanes[1]["k_tiles"], columns[1]["k_tiles"]) == (
        16, round(4 * squeeze_tiles(0.25, 16384, 1024, 7))) == (16, 24)
    assert hist_live_rows(rec, **kw, squeeze_stage=4)[1]["k_tiles"] == 20


def test_live_rows_of_a_packed_pass_count_bit_sections():
    """`learner.hist_live_rows` on PackedBins: a pass squeezes and
    multiplies each bit-section's chunk on its own, so its K-sub-tiles
    are counted over `pack_factor` x (section / row_chunk) chunks
    (hist_geometry's padded `rows` over `row_chunk`), not over the byte
    blocks and not over `num_data` rounded up. 9,000 rows at two values a
    byte: sections of 6,144 bytes (three 2,048-row chunks each, six
    chunk-sections where 9,000 rows rounded up give five). And through a
    traced booster: the record takes the padded rows of the kernel the
    booster traced."""
    import numpy as np

    import lightgbm_tpu as lgb
    from lightgbm_tpu.learner import hist_live_rows
    from lightgbm_tpu.obs.metrics import global_metrics
    from lightgbm_tpu.obs.trace import global_tracer
    from lightgbm_tpu.ops import pallas_histogram as ph
    from lightgbm_tpu.ops.bin_pack import pack_bins_host
    n = 9000
    pb = pack_bins_host(np.zeros((28, n), np.uint8), 15)
    geom = ph._fb_geometry(28, 15, pb.vpb, 2, section=pb.section)
    assert (pb.section, geom.row_chunk, geom.k_tile) == (6144, 2048, 1024)
    # a three-leaf tree: leaf 0 -> 4,500 + 4,500, then 900 + 3,600
    rec = {"split_leaf": np.array([0, 0]), "num_leaves": 3,
           "leaf_count": np.array([900.0, 4500.0, 3600.0])}
    kw = dict(num_data=n, num_leaves=3, wave_max=42,
              row_chunk=geom.row_chunk, k_tile=geom.k_tile)
    packed = hist_live_rows(rec, **kw, rows_padded=pb.vpb * pb.section)
    assert packed == [
        {"pass": "root", "slots": 1, "rows_live": n, "rows_passed": n,
         "k_tiles": 12, "k_tiles_full": 12, "squeeze": "none"},
        # 4,500 live rows over six chunk-sections: 750 each, one sub-tile
        {"pass": "w00", "slots": 1, "rows_live": 4500, "rows_passed": n,
         "k_tiles": 6, "k_tiles_full": 12, "squeeze": "lanes"}]
    by_rows = hist_live_rows(rec, **kw)
    assert [p["k_tiles_full"] for p in by_rows] == [10, 10]

    r = np.random.RandomState(0)
    x = r.randn(n, 28)
    y = (x[:, 0] + 0.5 * x[:, 1] > 0).astype(np.float64)
    was = global_tracer.enabled
    global_tracer.enable()
    try:
        global_metrics.meta.pop("hist_geometry", None)
        bst = lgb.Booster({"objective": "binary", "num_leaves": 7,
                           "max_bin": 15, "min_data_in_leaf": 5,
                           "verbosity": -1, "tpu_hist_impl": "pallas"},
                          lgb.Dataset(x, label=y))
        bst.update()
        bst.model_to_string()
    finally:
        global_tracer.enabled = was
    step = global_metrics.meta["hist_geometry"][-1]
    assert (step["pack_factor"], step["section"], step["rows"]) == \
        (2, 6144, 12288)
    passes = global_metrics.meta["hist_live_rows"][-1]
    full = step["rows"] // step["row_chunk"] * (
        step["row_chunk"] // step["k_tile"])
    assert {p["k_tiles_full"] for p in passes} == {full} == {12}
