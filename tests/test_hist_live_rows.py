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
