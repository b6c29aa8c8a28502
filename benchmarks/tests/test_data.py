"""The generator: the same seed gives the same bytes whatever the threads,
a large seed is taken, and the shift moves the features, not the label."""

import numpy as np
import pytest

import data as D

CFG = {"generator": "gaussian-logit"}


def test_same_seed_same_bytes_whatever_the_threads(monkeypatch):
    a = D.make_data(30_000, 300, 2**31 + 11, CFG)       # several chunks
    monkeypatch.setattr(D, "host_threads", lambda: 1)
    b = D.make_data(30_000, 300, 2**31 + 11, CFG)
    assert a[0].dtype == np.float64 and a[0].flags["C_CONTIGUOUS"]
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])


def test_another_seed_gives_other_rows():
    a = D.make_data(5_000, 28, 1, CFG)
    b = D.make_data(5_000, 28, 2, CFG)
    assert not np.array_equal(a[0], b[0])
    assert 0.40 < a[1].mean() < 0.55 and set(np.unique(a[1])) == {0.0, 1.0}


def test_the_shift_moves_every_feature_and_leaves_the_label(monkeypatch):
    a = D.make_data(5_000, 28, 3, CFG)
    monkeypatch.setattr(D, "FEATURE_SHIFT", 0.0)
    b = D.make_data(5_000, 28, 3, CFG)
    assert np.allclose(a[0] - b[0], 0.26)
    assert abs(a[0].mean() - 0.26) < 0.01
    assert np.array_equal(a[1], b[1])


def test_an_unknown_generator_is_refused():
    with pytest.raises(ValueError):
        D.make_data(100, 28, 1, {"generator": "uniform"})
