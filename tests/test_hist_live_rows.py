"""The multi-leaf histogram kernels' step multiplies the live rows of a
pass only (ops/pallas_histogram._multi_step: live mask, squeeze of the
chunk's live lanes, a loop over its sub-tiles), and gives the XLA twins'
histograms whichever rows are live. The check is tests/test_waved.py's
(`check_shared_step`); its live-row cases are here so that they run on
another worker than that file's."""

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


@pytest.mark.usefixtures("release_executables")
@pytest.mark.parametrize("kind,live,max_bins,vpb,f,slots,n", LIVE_CASES)
def test_step_multiplies_live_rows_only(kind, live, max_bins, vpb, f, slots,
                                        n):
    check_shared_step(kind, live, max_bins, vpb, f, slots, n)


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
         "k_tiles": 12, "k_tiles_full": 12},
        # 4,500 live rows over six chunk-sections: 750 each, one sub-tile
        {"pass": "w00", "slots": 1, "rows_live": 4500, "rows_passed": n,
         "k_tiles": 6, "k_tiles_full": 12}]
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
