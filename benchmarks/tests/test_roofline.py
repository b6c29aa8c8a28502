"""The work function checked by hand for one shape, and the peaks table."""

import pytest

import roofline


def test_work_by_hand_for_one_shape():
    # N = 1000 rows, F = 4 features, B = 63 bins (6 bits), L = 255 leaves:
    # depth 8, rows touched 1000 * (1 + 7 / 2) = 4500
    assert roofline.rows_touched(1000, 255) == 4500.0
    hist = roofline.histogram_work(1000, 4, 63, 255)
    assert hist["bytes"] == 4500 * (4 * 6 / 8 + 8)      # 49,500
    assert hist["ops"] == 4500 * 4 * 63 * 2 * 2         # 4,536,000
    it = roofline.iteration_work(1000, 4, 63, 255)
    assert it["bytes"] == hist["bytes"] + 1000 * 12
    assert it["ops"] == hist["ops"]


def test_least_time_names_its_bound():
    peaks = roofline.device_peaks("TPU v5 lite")
    assert peaks["bytes_per_s"] == 8.19e11 and peaks["flops_per_s"] == 1.97e14
    least = roofline.least_seconds({"bytes": 8.19e11, "ops": 1.0}, peaks)
    assert least["bound"] == "bytes" and least["seconds"] == 1.0
    least = roofline.least_seconds({"bytes": 1.0, "ops": 3.94e14}, peaks)
    assert least["bound"] == "ops" and least["seconds"] == 2.0
    # a configuration that sums int8 gradients is held to the int8 peak
    least = roofline.least_seconds({"bytes": 1.0, "ops": 3.93e14}, peaks, True)
    assert least["bound"] == "ops" and least["seconds"] == 1.0


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.device_peaks("TPU v9 imaginary")


@pytest.mark.parametrize("n,f", [(63_000_000, 28), (1_200_000, 2000)])
def test_the_cells_least_times_are_milliseconds(n, f):
    least = roofline.least_seconds(roofline.iteration_work(n, f, 63, 255),
                                   roofline.device_peaks("TPU v5 lite"))
    assert 0.005 < least["seconds"] < 0.05


def test_the_steps_share_is_read_against_the_traces_busy_seconds():
    """``iter_roofline_mfu_pct`` is a device_trace metric: the host clock's
    reading of the traced iteration (``window_s``) does not move it."""
    import run
    read = run.load_reader("iter_roofline_mfu_pct")
    peaks = roofline.device_peaks("TPU v5 lite")
    shapes = {"rows": 63_000_000, "features": 28, "max_bin": 63,
              "num_leaves": 255}
    least = roofline.least_seconds(
        roofline.iteration_work(63_000_000, 28, 63, 255), peaks)["seconds"]
    ctx = {"busy_s": 25.0, "window_s": 26.0, "shapes": shapes, "peaks": peaks}
    assert read(ctx) == pytest.approx(100.0 * least / 25.0)
    assert read(dict(ctx, window_s=99.0)) == read(ctx)
    assert read(dict(ctx, busy_s=0.0)) is None
