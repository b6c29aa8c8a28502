"""Binned dataset core + metadata.

TPU-native analog of the reference Dataset / Metadata / CUDARowData
(ref: include/LightGBM/dataset.h:492,49; cuda/cuda_row_data.hpp:33).
Host side: per-feature BinMappers over (sampled) raw data, a dense
feature-major bin matrix, and label/weight/group metadata. Device side:
the bin matrix as a `[F, N]` uint8/uint16 array (optionally sharded over a
mesh axis for data-parallel training).
"""

from __future__ import annotations

import contextlib
import os
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from . import binning
from .binning import BinMapper
from .config import Config
from .obs.metrics import global_metrics
from .obs.trace import global_tracer

# columns a worker takes at a time when the mappers are fitted: their
# sampled rows are gathered into one [block, S] float64 array, a
# contiguous row a column (4-51 MB at the default 200k-row sample)
_FIT_BLOCK = 32
_FIT_WORKERS_MAX = 16


@contextlib.contextmanager
def binning_span(name: Optional[str] = None, **facts):
    """The span ``data/binning`` (``name`` None) or its child
    ``data/binning/<name>``, with its seconds counted whether or not the
    tracer is on. The outer span appends one record to the list
    ``global_metrics.meta["data_binning"]`` and writes ``seconds`` into it
    when it closes; a child inside it adds ``<name>_s`` and ``facts`` to
    that record (outside any, to a record nobody keeps)."""
    records = global_metrics.meta.setdefault("data_binning", [])
    if name is None:
        records.append({})
    is_open = bool(records) and "seconds" not in records[-1]
    record = records[-1] if is_open else {}
    record.update(facts)
    t = time.perf_counter()
    try:
        with global_tracer.span("data/binning" + (f"/{name}" if name else "")):
            yield record
    finally:
        record[f"{name}_s" if name else "seconds"] = time.perf_counter() - t


def is_sparse(data) -> bool:
    """True for any scipy sparse matrix (scipy optional: dense-only
    installs never import it)."""
    try:
        import scipy.sparse as sp
        return sp.issparse(data)
    except ImportError:
        return False


def sparse_row_batches(data, budget_cells: int = 1 << 25):
    """Yield dense float64 row batches of a scipy sparse matrix, sized
    so each batch stays under ~budget_cells values — the single batching
    policy shared by every sparse prediction path (ref: c_api.cpp
    LGBM_BoosterPredictForCSR row-chunking)."""
    csr = data.tocsr()
    batch = max(1, budget_cells // max(csr.shape[1], 1))
    for i in range(0, csr.shape[0], batch):
        yield np.asarray(csr[i:i + batch].toarray(), np.float64)


def _transform_all(data: np.ndarray, mappers: List[BinMapper],
                   used: Sequence[int], dtype) -> np.ndarray:
    """Bin all used columns -> [F_used, N]. Uses the native threaded
    transform for the numerical columns when the library is available
    (ref: the reference bins in C++; here native/src LGT_TransformMatrix)."""
    n = data.shape[0]
    bins_fm = np.empty((len(used), n), dtype=dtype)
    numeric = [j for j, m in enumerate(mappers) if not m.is_categorical
               and m.bin_upper_bound is not None]
    done = set()
    if len(numeric) > 1 and n * len(numeric) >= 65536:
        from . import native as _native
        cols = [used[j] for j in numeric]
        if cols == list(range(data.shape[1])) and (
                data.flags["C_CONTIGUOUS"] or data.flags["F_CONTIGUOUS"]):
            sub = data  # all columns numeric+used: zero-copy into the kernel
        else:
            sub = data[:, cols]  # C-order gather, original dtype
        out = _native.transform_matrix(sub, [mappers[j] for j in numeric],
                                       dtype)
        if out is not None:
            if len(numeric) == len(used):
                return out  # [F_used, N] already — skip the copy
            for k, j in enumerate(numeric):
                bins_fm[j] = out[k]
            done = set(numeric)
    for j, col in enumerate(used):
        if j not in done:
            bins_fm[j] = mappers[j].transform(data[:, col])
    return bins_fm


def _fit_workers(columns: int) -> int:
    """Threads the mappers of ``columns`` columns are fitted on."""
    from . import native as _native
    if not _native.available():
        return 1
    blocks = -(-columns // _FIT_BLOCK)
    return max(1, min(blocks, _FIT_WORKERS_MAX, os.cpu_count() or 1))


def _fit_mappers(data: np.ndarray, sample_rows: Optional[np.ndarray],
                 config: Config, categorical_features: Sequence[int],
                 forced_bins: Optional[Dict[int, List[float]]],
                 workers: int) -> List[BinMapper]:
    """One fitted BinMapper a column of ``data`` [N, F], each from the
    column's values at ``sample_rows`` (all rows if None).

    The columns go in blocks of ``_FIT_BLOCK``: a block's sampled rows are
    gathered into a [block, S] float64 array, so that every ``fit`` reads
    a contiguous vector and not a strided column of the row-major matrix
    (at 2000 columns a stride of 16 kB a value). Where the native library
    finds the bounds the blocks are spread over ``workers`` threads (ctypes
    and NumPy's passes release the interpreter lock); without it the
    Python loops of ``binning._greedy_find_bin`` hold the lock, and
    ``_fit_workers`` says 1: the blocks run one after another. Each
    mapper depends on its own column's values only: the result is the
    same whatever the block size and the number of workers."""
    f = data.shape[1]
    cat_set = set(int(c) for c in categorical_features)
    max_bin_by_feature = config.max_bin_by_feature
    if max_bin_by_feature is None or len(max_bin_by_feature) != f:
        max_bin_by_feature = None

    def fit_block(c0: int) -> List[BinMapper]:
        block = data[:, c0:c0 + _FIT_BLOCK]
        if sample_rows is not None:
            block = block[sample_rows]
        values = np.ascontiguousarray(block.T, dtype=np.float64)
        return [BinMapper().fit(
            values[col - c0],
            max_bin=int(config.max_bin if max_bin_by_feature is None
                        else max_bin_by_feature[col]),
            min_data_in_bin=int(config.min_data_in_bin),
            use_missing=bool(config.use_missing),
            zero_as_missing=bool(config.zero_as_missing),
            is_categorical=col in cat_set,
            forced_bounds=forced_bins.get(col) if forced_bins else None)
            for col in range(c0, min(f, c0 + _FIT_BLOCK))]

    with ThreadPoolExecutor(workers) as pool:
        blocks = pool.map(fit_block, range(0, f, _FIT_BLOCK))
        return [m for block in blocks for m in block]


class Metadata:
    """Labels, weights, init scores, query boundaries
    (ref: include/LightGBM/dataset.h:49)."""

    def __init__(self, num_data: int):
        self.num_data = num_data
        self.label: Optional[np.ndarray] = None       # [N] f32
        self.weight: Optional[np.ndarray] = None      # [N] f32
        self.init_score: Optional[np.ndarray] = None  # [N] or [N*K] f64
        self.query_boundaries: Optional[np.ndarray] = None  # [num_queries+1]
        self.positions: Optional[np.ndarray] = None

    def set_label(self, label) -> None:
        label = np.asarray(label, dtype=np.float32).reshape(-1)
        assert len(label) == self.num_data, "label length mismatch"
        self.label = label

    def set_weight(self, weight) -> None:
        if weight is None:
            self.weight = None
            return
        weight = np.asarray(weight, dtype=np.float32).reshape(-1)
        assert len(weight) == self.num_data, "weight length mismatch"
        self.weight = weight

    def set_init_score(self, init_score) -> None:
        if init_score is None:
            self.init_score = None
            return
        self.init_score = np.asarray(init_score, dtype=np.float64)

    def set_group(self, group) -> None:
        """group: per-query sizes (like the python-package) -> boundaries."""
        if group is None:
            self.query_boundaries = None
            return
        group = np.asarray(group, dtype=np.int64).reshape(-1)
        bounds = np.zeros(len(group) + 1, dtype=np.int32)
        np.cumsum(group, out=bounds[1:])
        assert bounds[-1] == self.num_data, "sum(group) must equal num_data"
        self.query_boundaries = bounds

    def set_position(self, position) -> None:
        if position is None:
            self.positions = None
            return
        self.positions = np.asarray(position, dtype=np.int32).reshape(-1)

    @property
    def num_queries(self) -> int:
        return 0 if self.query_boundaries is None else len(self.query_boundaries) - 1


class BinnedDataset:
    """Pre-binned dataset (host arrays; `.device_bins()` ships to TPU).

    Attributes:
      bins_fm: [F_used, N] feature-major bin ids (uint8 or uint16).
      mappers: BinMapper per used feature.
      used_features: original column index per used feature.
      num_total_features: raw feature count (incl. trivial/dropped).
    """

    def __init__(self, bins_fm: np.ndarray, mappers: List[BinMapper],
                 used_features: List[int], num_total_features: int,
                 metadata: Metadata, feature_names: Optional[List[str]] = None,
                 label_idx: int = 0):
        self.bins_fm = bins_fm
        self.mappers = mappers
        self.used_features = used_features
        self.num_total_features = num_total_features
        self.metadata = metadata
        self.feature_names = feature_names or [
            f"Column_{i}" for i in range(num_total_features)]
        self.label_idx = label_idx
        self._device_cache: Dict[Any, Any] = {}
        # raw feature values [N, F_total] (reference kept for linear-tree
        # leaf fits; None for binary-loaded datasets)
        self.raw_data: Optional[np.ndarray] = None
        # EFB: when set, bins_fm holds BUNDLED columns [G, N] and
        # bundle_info maps logical features into them (ref:
        # dataset.cpp:251 FastFeatureBundling; see bundling.py)
        self.bundle_info = None
        # sparse row-wise COO storage (ref: multi_val_sparse_bin.hpp:21):
        # when set to (rows, feats, bins, zero_bins) int32 arrays,
        # bins_fm is a [1, N] placeholder and histogram/partition paths
        # run on the COO triples (ops.partition.SparseBins)
        self.sparse_coo = None

    # ------------------------------------------------------------------
    @property
    def num_data(self) -> int:
        return self.bins_fm.shape[1]

    @property
    def num_features(self) -> int:
        return len(self.mappers)

    @property
    def max_bins(self) -> int:
        return max((m.num_bins for m in self.mappers), default=1)

    def feature_meta_arrays(self):
        """Host numpy arrays for ops.split.FeatureMeta."""
        f = len(self.mappers)
        num_bins = np.array([m.num_bins for m in self.mappers], np.int32)
        missing = np.array([m.missing_type for m in self.mappers], np.int32)
        default_bin = np.array([m.default_bin for m in self.mappers], np.int32)
        is_cat = np.array([m.is_categorical for m in self.mappers], bool)
        return num_bins, missing, default_bin, is_cat

    # ------------------------------------------------------------------
    @classmethod
    def from_matrix(cls, data: np.ndarray, config: Config,
                    metadata: Optional[Metadata] = None,
                    categorical_features: Sequence[int] = (),
                    feature_names: Optional[List[str]] = None,
                    reference: Optional["BinnedDataset"] = None,
                    forced_bins: Optional[Dict[int, List[float]]] = None,
                    ) -> "BinnedDataset":
        """Bin a dense [N, F] float matrix (ref: DatasetLoader::
        ConstructFromSampleData, src/io/dataset_loader.cpp:601)."""
        data = np.asarray(data)
        if data.ndim != 2:
            raise ValueError("data must be 2-D [num_data, num_features]")
        n, f = data.shape
        metadata = metadata or Metadata(n)

        if reference is not None:
            # align binning with a reference (train) dataset
            # (ref: dataset_loader.cpp:307 LoadFromFileAlignWithOtherDataset)
            mappers = reference.mappers
            used = reference.used_features
            logical_dtype = (np.uint8 if max(
                (m.num_bins for m in mappers), default=1) <= 256
                else np.uint16)
            with binning_span("transform"):
                bins_fm = _transform_all(data, mappers, used, logical_dtype)
            if reference.bundle_info is not None:
                from .bundling import build_bundled_matrix
                nb = np.array([m.num_bins for m in mappers], np.int64)
                bins_fm, _ = build_bundled_matrix(
                    bins_fm, nb, [list(b) for b in
                                  reference.bundle_info.bundles])
            ds = cls(bins_fm, mappers, used, reference.num_total_features,
                     metadata, reference.feature_names)
            ds.bundle_info = reference.bundle_info
            ds.raw_data = data
            return ds

        # sample rows for binning (ref: bin_construct_sample_cnt)
        sample_cnt = min(n, int(config.bin_construct_sample_cnt))
        sample_rows = None
        if sample_cnt < n:
            rng = np.random.RandomState(config.data_random_seed)
            sample_rows = np.sort(rng.choice(n, sample_cnt, replace=False))
        workers = _fit_workers(f)
        with binning_span("find_bins", columns=f, sample_rows=sample_cnt,
                          workers=workers):
            mappers_all = _fit_mappers(data, sample_rows, config,
                                       categorical_features, forced_bins,
                                       workers)

        used = [i for i, m in enumerate(mappers_all)
                if not (config.feature_pre_filter and m.is_trivial)]
        if not used:
            used = [0] if f else []
        mappers = [mappers_all[i] for i in used]
        max_bins = max((m.num_bins for m in mappers), default=1)
        dtype = np.uint8 if max_bins <= 256 else np.uint16
        with binning_span("transform"):
            bins_fm = _transform_all(data, mappers, used, dtype)
        ds = cls(bins_fm, mappers, used, f, metadata, feature_names)
        ds.raw_data = data
        if config.enable_bundle and len(mappers) > 1:
            with binning_span("bundle"):
                ds._try_bundle(config)
        return ds

    @classmethod
    def from_sparse(cls, data, config: Config,
                    metadata: Optional[Metadata] = None,
                    categorical_features: Sequence[int] = (),
                    feature_names: Optional[List[str]] = None,
                    reference: Optional["BinnedDataset"] = None,
                    forced_bins: Optional[Dict[int, List[float]]] = None,
                    ) -> "BinnedDataset":
        """Bin a scipy CSR/CSC matrix WITHOUT densifying it (ref:
        LGBM_DatasetCreateFromCSR/CSC c_api.cpp:1311,1330 feeding
        SparseBin sparse_bin.hpp:74). Binning runs per CSC column on the
        explicit nonzeros + the implicit zero count; storage is emitted
        directly as the bundled [G, N] EFB matrix, so a 1M x 10k one-hot
        matrix ingests in O(nnz + G*N) host memory, never O(N*F)."""
        import scipy.sparse as sp
        from .bundling import build_bundled_from_csc, find_bundles_sparse
        if not sp.issparse(data):
            raise ValueError("from_sparse expects a scipy sparse matrix")
        if getattr(config, "linear_tree", False):
            raise ValueError(
                "linear_tree requires raw feature values; sparse input "
                "is not supported for linear trees")
        csc = data.tocsc()
        csc.sort_indices()
        n, f = csc.shape
        metadata = metadata or Metadata(n)

        if reference is not None:
            # valid set aligned with the (sparse-trained) train set
            mappers = reference.mappers
            used = reference.used_features
            nb = np.array([m.num_bins for m in mappers], np.int64)
            if reference.sparse_coo is not None:
                # mirror the COO storage layout
                zb = reference.sparse_coo[3]
                ds = cls._emit_coo(csc, mappers, used,
                                   reference.num_total_features, metadata,
                                   reference.feature_names, zb, n)
                return ds
            if reference.bundle_info is not None:
                bundles = [list(b) for b in reference.bundle_info.bundles]
            else:
                bundles = [[j] for j in range(len(mappers))]
            bins_fm, info = build_bundled_from_csc(csc, mappers, used,
                                                   bundles, nb)
            ds = cls(bins_fm, mappers, used, reference.num_total_features,
                     metadata, reference.feature_names)
            # mirror the reference dataset's storage layout exactly
            ds.bundle_info = (info if reference.bundle_info is not None
                              else None)
            ds.raw_data = csc.tocsr()
            return ds

        # --- sample rows for binning (ref: bin_construct_sample_cnt) ---
        sample_cnt = min(n, int(config.bin_construct_sample_cnt))
        if sample_cnt < n:
            rng = np.random.RandomState(config.data_random_seed)
            rows = np.sort(rng.choice(n, sample_cnt, replace=False))
            sample_csc = csc[rows, :].tocsc()
            sample_csc.sort_indices()
        else:
            sample_csc = csc

        cat_set = set(int(c) for c in categorical_features)
        max_bin_by_feature = config.max_bin_by_feature
        mappers_all: List[BinMapper] = []
        for col in range(f):
            mb = int(config.max_bin)
            if max_bin_by_feature is not None and len(max_bin_by_feature) == f:
                mb = int(max_bin_by_feature[col])
            forced = forced_bins.get(col) if forced_bins else None
            sl = slice(sample_csc.indptr[col], sample_csc.indptr[col + 1])
            nz_vals = np.asarray(sample_csc.data[sl], np.float64)
            m = BinMapper()
            if col in cat_set:
                # categorical needs exact per-category counts incl. the
                # implicit zero category: materialize ONE sampled column
                dense_col = np.zeros(sample_cnt)
                dense_col[sample_csc.indices[sl]] = nz_vals
                m.fit(dense_col, max_bin=mb,
                      min_data_in_bin=int(config.min_data_in_bin),
                      use_missing=bool(config.use_missing),
                      zero_as_missing=bool(config.zero_as_missing),
                      is_categorical=True)
            else:
                m.fit_sparse(nz_vals, sample_cnt, max_bin=mb,
                             min_data_in_bin=int(config.min_data_in_bin),
                             use_missing=bool(config.use_missing),
                             zero_as_missing=bool(config.zero_as_missing),
                             forced_bounds=forced)
            mappers_all.append(m)

        used = [i for i, m in enumerate(mappers_all)
                if not (config.feature_pre_filter and m.is_trivial)]
        if not used:
            used = [0] if f else []
        mappers = [mappers_all[i] for i in used]
        nb = np.array([m.num_bins for m in mappers], np.int64)

        # --- bundle structure from the SAMPLE's non-default rows ---
        # zero_bins[j] = the bin an implicit zero lands in (transform(0));
        # equals default_bin for numerical mappers but NOT for
        # categorical ones (category 0's bin vs the 'other' bin 0)
        zero_bins = np.array(
            [int(m.transform(np.zeros(1))[0]) for m in mappers], np.int64)
        nz_rows: List[np.ndarray] = []
        for j, col in enumerate(used):
            sl = slice(sample_csc.indptr[col], sample_csc.indptr[col + 1])
            fb = mappers[j].transform(
                np.asarray(sample_csc.data[sl], np.float64))
            nz_rows.append(sample_csc.indices[sl][fb != zero_bins[j]])
        max_bins = int(nb.max()) if len(nb) else 1
        # same learner guard as _try_bundle: the parallel growers index
        # LOGICAL [F, N] storage and have no bundle decode
        if (config.enable_bundle and len(mappers) > 1
                and config.tree_learner in ("serial",)):
            bundles = find_bundles_sparse(
                nz_rows, sample_cnt, nb,
                max_conflict_rate=float(config.max_conflict_rate),
                max_bundle_bins=max(max_bins, 256),
                bundleable=(zero_bins == 0))
        else:
            bundles = [[j] for j in range(len(mappers))]

        # --- sparse row-wise COO mode (ref: bin.h:482 MultiValBin sparse
        # variant): when bundling can't shrink the dense layout enough,
        # O(nnz) segment-sum histograms beat the O(G*N*B) dense passes.
        # Estimated from the sample's post-zero-bin-filter density.
        est_nnz = sum(len(r) for r in nz_rows) * (n / max(sample_cnt, 1))
        mode = getattr(config, "tpu_sparse_hist", "auto")
        coo_eligible = (config.tree_learner in ("serial",)
                        and not config.linear_tree and len(mappers) > 1)
        if mode == "force" and not coo_eligible:
            import warnings
            warnings.warn(
                "tpu_sparse_hist=force needs tree_learner=serial, "
                "linear_tree=false and >1 used feature; using the "
                "dense layout")
        use_coo = (coo_eligible
                   and (mode == "force"
                        or (mode == "auto"
                            # 48x compute-bias factor: a scatter-added
                            # COO element costs far more than an MXU
                            # one-hot lane; COO must be ~50x leaner
                            and 48.0 * est_nnz < len(bundles) * n)))
        if use_coo:
            ds = cls._emit_coo(csc, mappers, used, f, metadata,
                               feature_names,
                               zero_bins.astype(np.int32), n)
            return ds

        if len(bundles) == len(mappers):
            # nothing bundled: emit the plain [F, N] layout in FEATURE
            # order (find_bundles returns nnz-descending order) and skip
            # the bundle decode indirection entirely
            bundles = [[j] for j in range(len(mappers))]
        bins_fm, info = build_bundled_from_csc(csc, mappers, used,
                                               bundles, nb)
        ds = cls(bins_fm, mappers, used, f, metadata, feature_names)
        if len(bundles) < len(mappers):
            ds.bundle_info = info
        # the sparse matrix itself serves as raw_data: prediction paths
        # densify in batches, continued training fast-forwards through
        # predict_raw (linear trees are rejected above)
        ds.raw_data = csc.tocsr()
        return ds

    @classmethod
    def _emit_coo(cls, csc, mappers, used, num_total_features, metadata,
                  feature_names, zero_bins: np.ndarray,
                  n: int) -> "BinnedDataset":
        """Emit COO sparse storage: per used feature, bin the explicit
        nonzeros and keep only entries off the implicit-zero bin (their
        mass is recovered from leaf totals at histogram time)."""
        rows_l, feats_l, bins_l = [], [], []
        for j, col in enumerate(used):
            sl = slice(csc.indptr[col], csc.indptr[col + 1])
            fb = mappers[j].transform(
                np.asarray(csc.data[sl], np.float64)).astype(np.int32)
            keep = fb != zero_bins[j]
            rows_l.append(csc.indices[sl][keep].astype(np.int32))
            feats_l.append(np.full(int(keep.sum()), j, np.int32))
            bins_l.append(fb[keep])
        ds = cls(np.zeros((1, n), np.uint8), mappers, used,
                 num_total_features, metadata, feature_names)
        ds.sparse_coo = (
            np.concatenate(rows_l) if rows_l else np.zeros(0, np.int32),
            np.concatenate(feats_l) if feats_l else np.zeros(0, np.int32),
            np.concatenate(bins_l) if bins_l else np.zeros(0, np.int32),
            np.asarray(zero_bins, np.int32))
        ds.raw_data = csc.tocsr()
        return ds

    def _try_bundle(self, config: Config) -> None:
        """EFB: merge mutually exclusive features into bundled storage
        columns when that shrinks the bin matrix (ref: dataset.cpp:112
        FindGroups, :251 FastFeatureBundling). Logical semantics are
        unchanged — histograms/partitions decode through bundle_info."""
        from .bundling import (build_bundled_matrix, find_bundles,
                               should_bundle)
        if config.tree_learner not in ("serial",):
            return  # parallel learners shard logical features directly
        nb = np.array([m.num_bins for m in self.mappers], np.int64)
        default_bins = np.array([m.default_bin for m in self.mappers],
                                np.int64)
        # the offset encoding represents "default" as stored bin 0, so
        # only default-bin-0 features can share a bundle; others are
        # stored verbatim as singletons
        bundleable = default_bins == 0
        if np.count_nonzero(bundleable) < 2:
            return  # nothing to pair: every bundle would be a singleton
        # conflict detection on a row SAMPLE (ref: FindGroups samples too)
        # — a full scan would cost O(F*G*N) host time on exactly the
        # wide-sparse data EFB exists for
        n = self.bins_fm.shape[1]
        sample_cnt = min(n, int(config.bin_construct_sample_cnt))
        if sample_cnt < n:
            rng = np.random.RandomState(config.data_random_seed)
            rows = np.sort(rng.choice(n, sample_cnt, replace=False))
            sample = self.bins_fm[:, rows]
        else:
            sample = self.bins_fm
        nonzero = sample != default_bins[:, None].astype(self.bins_fm.dtype)
        max_conflict_rate = float(config.max_conflict_rate)
        # Two columns with a and b non-default rows of the S sampled share
        # at least a + b - S of them, and `find_bundles` joins a column to
        # a bundle only while the shared rows stay within the budget. If
        # the two sparsest bundleable columns already exceed it, so does
        # every pair (dense data): the search would end with singletons.
        fewest = np.sort(nonzero.sum(axis=1)[bundleable])[:2]
        if int(fewest.sum()) - sample.shape[1] > int(
                max_conflict_rate * sample.shape[1]):
            return
        bundles = find_bundles(
            nonzero, nb,
            max_conflict_rate=max_conflict_rate,
            max_bundle_bins=max(int(self.max_bins), 256),
            bundleable=bundleable)
        if not should_bundle(bundles, len(self.mappers)):
            return
        bundled, info = build_bundled_matrix(self.bins_fm, nb, bundles)
        self.bins_fm = bundled
        self.bundle_info = info
        self._device_cache.clear()

    # ------------------------------------------------------------------
    def device_bins(self):
        """Bin matrix as a device array (cached). Bundled storage when
        bundle_info is set — pair with device_bundle(). COO SparseBins
        pytree when sparse_coo is set."""
        import jax.numpy as jnp
        key = "bins"
        if key not in self._device_cache:
            if self.sparse_coo is not None:
                from .ops.partition import SparseBins
                rows, feats, bins, zb = self.sparse_coo
                self._device_cache[key] = SparseBins(
                    jnp.asarray(rows), jnp.asarray(feats),
                    jnp.asarray(bins), jnp.asarray(zb))
            else:
                self._device_cache[key] = jnp.asarray(self.bins_fm)
        return self._device_cache[key]

    def host_feature_bins(self, j: int) -> np.ndarray:
        """One logical feature's [N] bin column on host (dense slice, or
        COO materialization for sparse storage). Bundled datasets decode
        through bundle_info."""
        if self.sparse_coo is not None:
            rows, feats, bins, zb = self.sparse_coo
            out = np.full(self.num_data, zb[j], np.int32)
            sel = feats == j
            out[rows[sel]] = bins[sel]
            return out
        if self.bundle_info is not None:
            from .bundling import decode_stored_host
            return decode_stored_host(
                self.bins_fm[self.bundle_info.group_of[j]].astype(np.int32),
                self.bundle_info.offset_of[j],
                self.mappers[j].num_bins - 1)
        return self.bins_fm[j].astype(np.int32)

    def device_bundle(self):
        """(group_of, offset_of, num_bins) device triple for EFB decode,
        or None for unbundled storage."""
        if self.bundle_info is None:
            return None
        import jax.numpy as jnp
        key = "bundle"
        if key not in self._device_cache:
            nb = np.array([m.num_bins for m in self.mappers], np.int32)
            self._device_cache[key] = (
                jnp.asarray(self.bundle_info.group_of),
                jnp.asarray(self.bundle_info.offset_of),
                jnp.asarray(nb))
        return self._device_cache[key]

    def feature_infos(self) -> List[str]:
        """Per raw feature info strings for the model header."""
        infos = []
        used_map = {c: j for j, c in enumerate(self.used_features)}
        for col in range(self.num_total_features):
            if col in used_map:
                infos.append(self.mappers[used_map[col]].feature_info_str())
            else:
                infos.append("none")
        return infos
