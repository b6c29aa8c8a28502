"""Seeded synthetic training data, made straight into one float64 array.

A copy of ``bench.py::_measure``'s generator (Gaussian features, a label
from five of them plus noise, about 47% positive), because the machine
with the chip has no network and no data set. The rows are filled in fixed
chunks, each from its own stream keyed by (seed, chunk), so the same seed
gives the same bytes whatever the number of threads.

Every feature is shifted by ``FEATURE_SHIFT`` after the label is taken.
With unshifted Gaussians
the value 0.0 sits on the boundary of bins 31 and 32 of 63, a feature's
zero bin falls on either side by sampling noise, and the program bakes
the zero bins into its iteration program as a constant: every new seed
then compiles a new program (57 s at 63M x 28, PERF.md PR 25). At 0.26
the zero bin is 25 for every feature and seed tried (the flips are at
0.24 and 0.28, seven standard deviations of the sampling noise away).

float64 and C-contiguous, because that is what ``lgb.Dataset`` holds
(``basic._to_2d``): any other type is copied to it first, 19 GB and most
of a minute for 1.2M x 2000. This array goes in as it is and then
zero-copy into the native transform.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

_CHUNK_VALUES = 1 << 22   # values per chunk: 32 MiB of float64
LABEL_NOISE = 0.5         # of the label's logit, as in bench.py
LABEL_THRESHOLD = 0.2     # about 47% positive
FEATURE_SHIFT = 0.26      # every feature is N(0.26, 1)


def host_threads() -> int:
    return max(1, min(16, os.cpu_count() or 1))


def _check(data_cfg: dict) -> None:
    if data_cfg["generator"] != "gaussian-logit":
        raise ValueError(f"unknown generator {data_cfg['generator']!r}")


def chunk_rows(features: int) -> int:
    return max(1, _CHUNK_VALUES // features)


def num_chunks(rows: int, features: int) -> int:
    return -(-rows // chunk_rows(features))


def fill_chunk(c: int, rows: int, features: int, seed: int, data_cfg: dict,
               xb=None):
    """Chunk ``c`` of the data: (first row, one past the last, x, y). ``xb``
    is where the features go (a [b - a, features] float64 block), made here
    if not given."""
    step = chunk_rows(features)
    a, b = c * step, min(rows, (c + 1) * step)
    rng = np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([int(seed), c])))
    if xb is None:
        xb = np.empty((b - a, features), np.float64)
    rng.standard_normal(out=xb)
    eps = rng.standard_normal(b - a)
    logit = (xb[:, 0] + 0.6 * xb[:, 1] ** 2 + 0.4 * xb[:, 2] * xb[:, 3]
             - 0.3 * np.abs(xb[:, 4]) + LABEL_NOISE * eps)
    yb = (logit > LABEL_THRESHOLD).astype(np.float32)
    xb += FEATURE_SHIFT
    return a, b, xb, yb


def make_data(rows: int, features: int, seed: int, data_cfg: dict):
    """(x [rows, features] float64 C-contiguous, y [rows] float32 in {0,1})."""
    _check(data_cfg)
    x = np.empty((rows, features), np.float64)
    y = np.empty(rows, np.float32)
    step = chunk_rows(features)

    def fill(c: int) -> None:
        a, b = c * step, min(rows, (c + 1) * step)
        y[a:b] = fill_chunk(c, rows, features, seed, data_cfg, x[a:b])[3]

    with ThreadPoolExecutor(host_threads()) as pool:
        list(pool.map(fill, range(num_chunks(rows, features))))
    return x, y


class Chunks:
    """The same data, chunk by chunk and never whole: what the reference
    reads after the window, when the raw rows are long gone (19 GB at
    1.2M x 2000, on a machine that ends a run at 40 GiB)."""

    def __init__(self, rows: int, features: int, seed: int, data_cfg: dict):
        _check(data_cfg)
        self.rows, self.features = int(rows), int(features)
        self.seed, self.data_cfg = int(seed), data_cfg

    def __len__(self) -> int:
        return num_chunks(self.rows, self.features)

    def __getitem__(self, c: int):
        return fill_chunk(c, self.rows, self.features, self.seed,
                          self.data_cfg)
