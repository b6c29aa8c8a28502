"""In-process backend of the C-ABI shim.

`native/src/lgbm_tpu_capi.cpp` embeds a CPython interpreter, imports this
module, and forwards every `LGBM_*` call here with raw pointers passed as
integers. This module wraps those pointers with ctypes/NumPy, drives the
ordinary Python API (`basic.Dataset`/`basic.Booster`), and returns
primitive values the C side can marshal back — giving reference harnesses
and third-party tooling the familiar `lib_lightgbm` calling convention
(ref: include/LightGBM/c_api.h; internal Booster wrapper c_api.cpp:170).

Handles are small integers into a registry (the C side casts them to the
opaque `DatasetHandle`/`BoosterHandle` pointers the reference API uses).
"""

from __future__ import annotations

import ctypes
import os
from typing import Dict

import numpy as np

from .basic import Booster, Dataset
from .config import Config

# C_API_DTYPE_* (ref: c_api.h:36-39)
_DTYPES = {0: ctypes.c_float, 1: ctypes.c_double,
           2: ctypes.c_int32, 3: ctypes.c_int64}
_NP_DTYPES = {0: np.float32, 1: np.float64, 2: np.int32, 3: np.int64}

# C_API_PREDICT_* (ref: c_api.h:41-44)
_PREDICT_NORMAL, _PREDICT_RAW, _PREDICT_LEAF, _PREDICT_CONTRIB = range(4)

_registry: Dict[int, object] = {}
_next_handle = [1]


def _new_handle(obj) -> int:
    h = _next_handle[0]
    _next_handle[0] += 1
    _registry[h] = obj
    return h


def _get(handle: int):
    try:
        return _registry[handle]
    except KeyError:
        raise ValueError(f"invalid handle {handle}")


def _array_from_ptr(ptr: int, count: int, dtype: int) -> np.ndarray:
    if count == 0:
        return np.empty(0, _NP_DTYPES[dtype])
    ct = _DTYPES[dtype]
    buf = (ct * count).from_address(ptr)
    return np.asarray(np.ctypeslib.as_array(buf), _NP_DTYPES[dtype]).copy()


def _write_doubles(ptr: int, values: np.ndarray) -> int:
    values = np.ascontiguousarray(values, np.float64)
    ctypes.memmove(ptr, values.ctypes.data, values.nbytes)
    return int(values.size)


def _parse_params(parameters: str) -> Dict[str, str]:
    return Config.kv2map((parameters or "").split())


# -- dataset ---------------------------------------------------------------
def dataset_create_from_mat(data_ptr: int, data_type: int, nrow: int,
                            ncol: int, is_row_major: int, parameters: str,
                            reference: int) -> int:
    """(ref: LGBM_DatasetCreateFromMat c_api.cpp:1311)"""
    flat = _array_from_ptr(data_ptr, nrow * ncol, data_type)
    mat = (flat.reshape(nrow, ncol) if is_row_major
           else flat.reshape(ncol, nrow).T)
    ref = _resolve_ds(_get(reference)) if reference else None
    ds = Dataset(np.asarray(mat, np.float64), reference=ref,
                 params=_parse_params(parameters))
    return _new_handle(ds)


def _csr_from_ptrs(indptr_ptr: int, indptr_type: int, indices_ptr: int,
                   data_ptr: int, data_type: int, nindptr: int,
                   nelem: int, num_col: int):
    from scipy import sparse
    indptr = _array_from_ptr(indptr_ptr, nindptr, indptr_type)
    indices = _array_from_ptr(indices_ptr, nelem, 2)  # int32
    data = _array_from_ptr(data_ptr, nelem, data_type)
    return sparse.csr_matrix(
        (np.asarray(data, np.float64), indices, indptr),
        shape=(nindptr - 1, num_col))


def dataset_create_from_csr(indptr_ptr: int, indptr_type: int,
                            indices_ptr: int, data_ptr: int,
                            data_type: int, nindptr: int, nelem: int,
                            num_col: int, parameters: str,
                            reference: int) -> int:
    """(ref: LGBM_DatasetCreateFromCSR c_api.cpp:1311) — feeds the
    densification-free sparse ingestion path."""
    csr = _csr_from_ptrs(indptr_ptr, indptr_type, indices_ptr, data_ptr,
                         data_type, nindptr, nelem, num_col)
    ref = _resolve_ds(_get(reference)) if reference else None
    ds = Dataset(csr, reference=ref, params=_parse_params(parameters))
    return _new_handle(ds)


def _predict_into(bst, matrix, predict_type: int, start_iteration: int,
                  num_iteration: int, out_ptr: int) -> int:
    """Shared predict dispatch + result write for the dense and CSR
    entry points."""
    pred = bst.predict(matrix, start_iteration=start_iteration,
                       num_iteration=num_iteration,
                       raw_score=predict_type == _PREDICT_RAW,
                       pred_leaf=predict_type == _PREDICT_LEAF,
                       pred_contrib=predict_type == _PREDICT_CONTRIB)
    return _write_doubles(out_ptr, np.asarray(pred).reshape(-1))


def booster_predict_for_csr(handle: int, indptr_ptr: int, indptr_type: int,
                            indices_ptr: int, data_ptr: int,
                            data_type: int, nindptr: int, nelem: int,
                            num_col: int, predict_type: int,
                            start_iteration: int, num_iteration: int,
                            out_ptr: int) -> int:
    """(ref: LGBM_BoosterPredictForCSR c_api.cpp)"""
    csr = _csr_from_ptrs(indptr_ptr, indptr_type, indices_ptr, data_ptr,
                         data_type, nindptr, nelem, num_col)
    return _predict_into(_get(handle), csr, predict_type, start_iteration,
                         num_iteration, out_ptr)


def dataset_create_from_file(filename: str, parameters: str,
                             reference: int) -> int:
    """(ref: LGBM_DatasetCreateFromFile c_api.cpp:1044)"""
    ref = _resolve_ds(_get(reference)) if reference else None
    ds = Dataset(filename, reference=ref, params=_parse_params(parameters))
    return _new_handle(ds)


def dataset_set_field(handle: int, field: str, ptr: int, count: int,
                      dtype: int) -> None:
    """(ref: LGBM_DatasetSetField c_api.cpp)"""
    ds = _resolve_ds(_get(handle))
    values = _array_from_ptr(ptr, count, dtype)
    if field == "label":
        ds.set_label(values)
    elif field == "weight":
        ds.set_weight(values)
    elif field in ("group", "query"):
        ds.set_group(values)
    elif field == "init_score":
        ds.set_init_score(values)
    else:
        raise ValueError(f"unknown field {field}")


def dataset_num_data(handle: int) -> int:
    obj = _get(handle)
    if isinstance(obj, _StreamingDataset):
        return obj.num_total_row
    return int(obj.num_data())


def dataset_num_feature(handle: int) -> int:
    obj = _get(handle)
    if isinstance(obj, _StreamingDataset):
        return obj.ncol
    return int(obj.num_feature())


def handle_free(handle: int) -> None:
    _registry.pop(handle, None)
    _eval_counts.pop(handle, None)
    _field_cache.pop(handle, None)


# -- booster ---------------------------------------------------------------
def booster_create(train_handle: int, parameters: str) -> int:
    """(ref: LGBM_BoosterCreate c_api.cpp:1998)"""
    bst = Booster(_parse_params(parameters), _resolve_ds(_get(train_handle)))
    return _new_handle(bst)


def booster_create_from_modelfile(filename: str) -> tuple:
    """(ref: LGBM_BoosterCreateFromModelfile)"""
    bst = Booster(model_file=filename)
    return _new_handle(bst), int(bst.num_trees())


def booster_add_valid_data(handle: int, valid_handle: int) -> None:
    bst = _get(handle)
    bst.add_valid(_resolve_ds(_get(valid_handle)),
                  f"valid_{len(bst._name_valid_sets)}")


def booster_update_one_iter(handle: int) -> int:
    """Returns 1 when training is finished
    (ref: LGBM_BoosterUpdateOneIter c_api.cpp:2121)."""
    return int(bool(_get(handle).update()))


def booster_current_iteration(handle: int) -> int:
    return int(_get(handle).current_iteration())


_eval_counts: Dict[int, int] = {}


def booster_get_eval_counts(handle: int) -> int:
    # the metric set is fixed after Booster creation; cache so harnesses
    # polling the count each iteration don't pay a full evaluation
    if handle not in _eval_counts:
        _eval_counts[handle] = len(_get(handle).eval_train())
    return _eval_counts[handle]


def booster_get_eval(handle: int, data_idx: int, out_ptr: int) -> int:
    """data_idx 0 = train, 1.. = valid sets (ref: LGBM_BoosterGetEval)."""
    bst = _get(handle)
    if data_idx == 0:
        results = bst.eval_train()
    else:
        name = bst._name_valid_sets[data_idx - 1]
        results = [r for r in bst.eval_valid() if r[0] == name]
    return _write_doubles(out_ptr, np.asarray([r[2] for r in results]))


def booster_predict_for_mat(handle: int, data_ptr: int, data_type: int,
                            nrow: int, ncol: int, is_row_major: int,
                            predict_type: int, start_iteration: int,
                            num_iteration: int, out_ptr: int) -> int:
    """(ref: LGBM_BoosterPredictForMat c_api.cpp:2558)"""
    flat = _array_from_ptr(data_ptr, nrow * ncol, data_type)
    mat = (flat.reshape(nrow, ncol) if is_row_major
           else flat.reshape(ncol, nrow).T)
    return _predict_into(_get(handle), np.asarray(mat, np.float64),
                         predict_type, start_iteration, num_iteration,
                         out_ptr)


def booster_save_model(handle: int, start_iteration: int,
                       num_iteration: int, importance_type: int,
                       filename: str) -> None:
    """(ref: LGBM_BoosterSaveModel)"""
    _get(handle).save_model(
        filename, num_iteration=num_iteration,
        start_iteration=start_iteration,
        importance_type="gain" if importance_type == 1 else "split")


def booster_save_model_to_string(handle: int, start_iteration: int,
                                 num_iteration: int,
                                 importance_type: int) -> str:
    return _get(handle).model_to_string(
        num_iteration=num_iteration, start_iteration=start_iteration,
        importance_type="gain" if importance_type == 1 else "split")


def booster_num_feature(handle: int) -> int:
    return int(_get(handle).num_feature())


# -- streaming dataset construction ----------------------------------------
# (ref: c_api.cpp:1330 LGBM_DatasetPushRows* + chunked_array.hpp; scenario
# coverage modeled on tests/cpp_tests/test_stream.cpp:253,304)
class _StreamingDataset:
    """A fixed-size dataset being filled by PushRows calls. Auto-finishes
    when pushed rows reach num_total_row (unless wait_manual), after which
    `built` holds the constructed Dataset."""

    def __init__(self, num_total_row: int, ncol: int, params, reference):
        self.num_total_row = int(num_total_row)
        self.ncol = int(ncol)
        self.params = params
        self.reference = reference
        self.X = np.zeros((self.num_total_row, self.ncol), np.float64)
        self.label = np.zeros(self.num_total_row, np.float32)
        self.weight = None
        self.init_score = None
        self.query = None
        self.nclasses = 1
        self.pushed = 0
        self.wait_manual = False
        self.built = None

    def init_streaming(self, has_weights, has_init_scores, has_queries,
                       nclasses):
        if has_weights:
            self.weight = np.zeros(self.num_total_row, np.float32)
        if has_init_scores:
            self.nclasses = max(int(nclasses), 1)
            self.init_score = np.zeros(
                self.num_total_row * self.nclasses, np.float64)
        if has_queries:
            self.query = np.zeros(self.num_total_row, np.int32)
        # InitStreaming implies the manual-finish contract
        # (ref: test_stream.cpp streaming flow step 4: MarkFinished)
        self.wait_manual = True

    def push(self, rows: np.ndarray, start_row: int, label=None,
             weight=None, init_score=None, query=None):
        if self.built is not None:
            raise ValueError("dataset already finished")
        n = rows.shape[0]
        if start_row + n > self.num_total_row:
            raise ValueError(
                f"push of {n} rows at {start_row} exceeds num_total_row="
                f"{self.num_total_row}")
        self.X[start_row:start_row + n] = rows
        if label is not None:
            self.label[start_row:start_row + n] = label
        if weight is not None and self.weight is not None:
            self.weight[start_row:start_row + n] = weight
        if init_score is not None and self.init_score is not None:
            # column-format [nclasses x nrow] slices (ref: c_api.h:259)
            for c in range(self.nclasses):
                dst = c * self.num_total_row + start_row
                self.init_score[dst:dst + n] = init_score[c * n:(c + 1) * n]
        if query is not None and self.query is not None:
            self.query[start_row:start_row + n] = query
        self.pushed += n
        if not self.wait_manual and self.pushed >= self.num_total_row:
            self.finish()

    def finish(self):
        if self.built is not None:
            return self.built
        group = None
        if self.query is not None:
            # per-row query ids -> group sizes (run-length; the reference
            # metadata does the same boundary conversion)
            _, counts = np.unique(self.query, return_counts=True)
            # np.unique sorts; queries arrive contiguous, so preserve
            # first-appearance order via index of first occurrence
            _, first = np.unique(self.query, return_index=True)
            order = np.argsort(first)
            group = counts[order]
        # init_score stays in class-major (column) format: both the C API
        # contract (c_api.h:259) and GBDT's consumer
        # (boosting.py init.reshape(K, N)) use [class * num_row + row]
        init_score = self.init_score
        ds = Dataset(self.X, label=self.label, weight=self.weight,
                     init_score=init_score, group=group,
                     reference=self.reference, params=dict(self.params))
        self.built = ds.construct()
        return self.built


def _resolve_ds(obj):
    if isinstance(obj, _StreamingDataset):
        if obj.built is None:
            raise ValueError("streaming dataset is not finished yet "
                             "(push all rows or call MarkFinished)")
        return obj.built
    return obj


def dataset_create_by_reference(ref_handle: int, num_total_row: int) -> int:
    """(ref: LGBM_DatasetCreateByReference c_api.cpp:1245)"""
    ref = _get(ref_handle)
    ref.construct()
    sd = _StreamingDataset(num_total_row, ref.num_feature(),
                           dict(ref.params or {}), ref)
    return _new_handle(sd)


def dataset_create_from_sampled_column(sample_data_ptr: int,
                                       sample_indices_ptr: int, ncol: int,
                                       num_per_col_ptr: int,
                                       num_sample_row: int,
                                       num_local_row: int,
                                       parameters: str) -> int:
    """Build the dataset 'schema' (bin mappers) from per-column sampled
    values, sized for num_local_row pushed rows
    (ref: LGBM_DatasetCreateFromSampledColumn c_api.cpp:1112; the
    streaming flow of test_stream.cpp:253 step 1)."""
    num_per_col = _array_from_ptr(num_per_col_ptr, ncol, 2)
    dptrs = _array_from_ptr(sample_data_ptr, ncol, 3)   # double* per col
    iptrs = _array_from_ptr(sample_indices_ptr, ncol, 3)  # int* per col
    S = np.zeros((num_sample_row, ncol), np.float64)
    for j in range(ncol):
        cnt = int(num_per_col[j])
        if cnt == 0:
            continue
        vals = _array_from_ptr(int(dptrs[j]), cnt, 1)
        rows = _array_from_ptr(int(iptrs[j]), cnt, 2)
        S[rows, j] = vals
    params = _parse_params(parameters)
    schema = Dataset(S, params=dict(params)).construct()
    sd = _StreamingDataset(num_local_row, ncol, params, schema)
    return _new_handle(sd)


def dataset_init_streaming(handle: int, has_weights: int,
                           has_init_scores: int, has_queries: int,
                           nclasses: int) -> None:
    sd = _get(handle)
    if not isinstance(sd, _StreamingDataset):
        raise ValueError("InitStreaming requires a streaming dataset "
                         "(CreateByReference/CreateFromSampledColumn)")
    sd.init_streaming(has_weights, has_init_scores, has_queries, nclasses)


def dataset_push_rows(handle: int, data_ptr: int, data_type: int,
                      nrow: int, ncol: int, start_row: int) -> None:
    """(ref: LGBM_DatasetPushRows c_api.cpp:1330)"""
    sd = _get(handle)
    flat = _array_from_ptr(data_ptr, nrow * ncol, data_type)
    sd.push(flat.reshape(nrow, ncol), start_row)


def dataset_push_rows_with_metadata(handle: int, data_ptr: int,
                                    data_type: int, nrow: int, ncol: int,
                                    start_row: int, label_ptr: int,
                                    weight_ptr: int, init_score_ptr: int,
                                    query_ptr: int) -> None:
    sd = _get(handle)
    flat = _array_from_ptr(data_ptr, nrow * ncol, data_type)
    label = _array_from_ptr(label_ptr, nrow, 0) if label_ptr else None
    weight = _array_from_ptr(weight_ptr, nrow, 0) if weight_ptr else None
    init_score = (_array_from_ptr(init_score_ptr, nrow * sd.nclasses, 1)
                  if init_score_ptr else None)
    query = _array_from_ptr(query_ptr, nrow, 2) if query_ptr else None
    sd.push(flat.reshape(nrow, ncol), start_row, label, weight,
            init_score, query)


def dataset_push_rows_by_csr(handle: int, indptr_ptr: int, indptr_type: int,
                             indices_ptr: int, data_ptr: int,
                             data_type: int, nindptr: int, nelem: int,
                             num_col: int, start_row: int) -> None:
    """(ref: LGBM_DatasetPushRowsByCSR c_api.cpp:1383)"""
    sd = _get(handle)
    ncol = int(num_col) if num_col > 0 else sd.ncol
    csr = _csr_from_ptrs(indptr_ptr, indptr_type, indices_ptr, data_ptr,
                         data_type, nindptr, nelem, ncol)
    sd.push(np.asarray(csr.todense()), start_row)


def dataset_push_rows_by_csr_with_metadata(
        handle: int, indptr_ptr: int, indptr_type: int, indices_ptr: int,
        data_ptr: int, data_type: int, nindptr: int, nelem: int,
        start_row: int, label_ptr: int, weight_ptr: int,
        init_score_ptr: int, query_ptr: int) -> None:
    sd = _get(handle)
    nrow = nindptr - 1
    csr = _csr_from_ptrs(indptr_ptr, indptr_type, indices_ptr, data_ptr,
                         data_type, nindptr, nelem, sd.ncol)
    label = _array_from_ptr(label_ptr, nrow, 0) if label_ptr else None
    weight = _array_from_ptr(weight_ptr, nrow, 0) if weight_ptr else None
    init_score = (_array_from_ptr(init_score_ptr, nrow * sd.nclasses, 1)
                  if init_score_ptr else None)
    query = _array_from_ptr(query_ptr, nrow, 2) if query_ptr else None
    sd.push(np.asarray(csr.todense()), start_row, label, weight,
            init_score, query)


def dataset_set_wait_for_manual_finish(handle: int, wait: int) -> None:
    sd = _get(handle)
    if isinstance(sd, _StreamingDataset):
        sd.wait_manual = bool(wait)


def dataset_mark_finished(handle: int) -> None:
    """(ref: LGBM_DatasetMarkFinished -> Dataset::FinishLoad)"""
    sd = _get(handle)
    if isinstance(sd, _StreamingDataset):
        sd.finish()


def get_sample_count(num_total_row: int, parameters: str) -> int:
    """(ref: LGBM_GetSampleCount c_api.cpp)"""
    params = _parse_params(parameters)
    cnt = int(params.get("bin_construct_sample_cnt", 200000))
    return min(max(cnt, 1), int(num_total_row))


def sample_indices(num_total_row: int, parameters: str, out_ptr: int) -> int:
    """Sorted uniform sample without replacement, seeded by
    data_random_seed (ref: LGBM_SampleIndices -> CreateSampleIndices)."""
    params = _parse_params(parameters)
    cnt = get_sample_count(num_total_row, parameters)
    seed = int(params.get("data_random_seed", 1))
    rng = np.random.RandomState(seed & 0x7FFFFFFF)
    idx = np.sort(rng.choice(num_total_row, size=cnt,
                             replace=False).astype(np.int32))
    ctypes.memmove(out_ptr, idx.ctypes.data, idx.nbytes)
    return int(idx.size)


# -- dataset field access / utilities --------------------------------------
# GetField returns a pointer into a buffer we must keep alive for the
# handle's lifetime (the reference returns pointers into Metadata's own
# vectors, c_api.cpp LGBM_DatasetGetField)
_field_cache: Dict[int, Dict[str, np.ndarray]] = {}


def dataset_get_field(handle: int, field: str) -> tuple:
    """Returns (ptr, len, dtype_code) (ref: LGBM_DatasetGetField)."""
    ds = _resolve_ds(_get(handle))
    if field == "label":
        arr, code = np.ascontiguousarray(ds.get_label(), np.float32), 0
    elif field == "weight":
        w = ds.get_weight()
        if w is None:
            return 0, 0, 0
        arr, code = np.ascontiguousarray(w, np.float32), 0
    elif field in ("group", "query"):
        g = ds.get_group()
        if g is None:
            return 0, 0, 2
        # boundaries, not sizes (ref: Metadata::query_boundaries_)
        arr = np.concatenate([[0], np.cumsum(np.asarray(g))]).astype(
            np.int32)
        code = 2
    elif field == "init_score":
        s = ds.get_init_score()
        if s is None:
            return 0, 0, 1
        arr, code = np.ascontiguousarray(s, np.float64).reshape(-1), 1
    else:
        raise ValueError(f"unknown field {field}")
    _field_cache.setdefault(handle, {})[field] = arr
    return int(arr.ctypes.data), int(arr.size), code


def dataset_get_feature_names(handle: int) -> list:
    return list(_resolve_ds(_get(handle)).get_feature_name())


def dataset_set_feature_names(handle: int, names: list) -> None:
    ds = _resolve_ds(_get(handle))
    ds.feature_name = [str(n) for n in names]


def dataset_get_feature_num_bin(handle: int, feature: int) -> int:
    """(ref: LGBM_DatasetGetFeatureNumBin -> FeatureNumBin)"""
    ds = _resolve_ds(_get(handle)).construct()
    binned = ds._binned
    for j, raw in enumerate(binned.used_features):
        if raw == feature:
            return int(binned.mappers[j].num_bins)
    return 1  # trivial (unused) feature: single bin


def dataset_save_binary(handle: int, filename: str) -> None:
    _resolve_ds(_get(handle)).construct().save_binary(filename)


def dataset_dump_text(handle: int, filename: str) -> None:
    """(ref: LGBM_DatasetDumpText c_api.cpp)"""
    ds = _resolve_ds(_get(handle)).construct()
    X = np.asarray(ds.get_data(), np.float64)
    lab = ds.get_label()
    with open(filename, "w") as fh:
        names = ds.get_feature_name()
        fh.write("\t".join(["label"] + list(names)) + "\n")
        for i in range(X.shape[0]):
            row = [repr(float(lab[i]))] if lab is not None else []
            row += [repr(float(v)) for v in X[i]]
            fh.write("\t".join(row) + "\n")


def dataset_get_subset(handle: int, indices_ptr: int, num_indices: int,
                       parameters: str) -> int:
    """(ref: LGBM_DatasetGetSubset c_api.cpp)"""
    ds = _resolve_ds(_get(handle))
    idx = _array_from_ptr(indices_ptr, num_indices, 2)
    sub = ds.subset(idx, params=_parse_params(parameters))
    return _new_handle(sub)


def dataset_update_param_checking(old_parameters: str,
                                  new_parameters: str) -> None:
    """(ref: LGBM_DatasetUpdateParamChecking — raises when a
    dataset-affecting parameter changed)."""
    old = _parse_params(old_parameters)
    new = _parse_params(new_parameters)
    binning_keys = ("max_bin", "min_data_in_bin", "bin_construct_sample_cnt",
                    "categorical_feature", "use_missing", "zero_as_missing",
                    "feature_pre_filter")
    for k in binning_keys:
        if k in new and old.get(k) != new.get(k):
            raise ValueError(
                f"cannot change {k} after constructing Dataset")


# -- booster extras --------------------------------------------------------
def booster_load_model_from_string(model_str: str) -> tuple:
    """(ref: LGBM_BoosterLoadModelFromString)"""
    bst = Booster(model_str=model_str)
    return _new_handle(bst), int(bst.num_trees())


def booster_reset_parameter(handle: int, parameters: str) -> None:
    """(ref: LGBM_BoosterResetParameter c_api.cpp:2095)"""
    _get(handle).reset_parameter(_parse_params(parameters))


def booster_reset_training_data(handle: int, train_handle: int) -> None:
    """(ref: LGBM_BoosterResetTrainingData c_api.cpp:2086): swap the
    training data, keep the model — no extra boosting iteration."""
    _get(handle).reset_train_set(_resolve_ds(_get(train_handle)))


def booster_rollback_one_iter(handle: int) -> None:
    _get(handle).rollback_one_iter()


def booster_get_num_classes(handle: int) -> int:
    bst = _get(handle)
    if bst._gbdt is not None:
        cfg = bst._gbdt.config
        return int(getattr(cfg, "num_class", 1))
    return max(int(bst._loaded.num_tree_per_iteration), 1)


def booster_num_model_per_iteration(handle: int) -> int:
    bst = _get(handle)
    if bst._gbdt is not None:
        return int(bst._gbdt.num_tree_per_iteration)
    return max(int(bst._loaded.num_tree_per_iteration), 1)


def booster_number_of_total_model(handle: int) -> int:
    return _booster_total_models(_get(handle))


def _booster_total_models(bst) -> int:
    if bst._gbdt is not None:
        return sum(len(it) for it in bst._gbdt.models)
    return len(bst._loaded.trees)


def booster_get_eval_names(handle: int) -> list:
    """Metric names WITHOUT evaluating (ref: LGBM_BoosterGetEvalNames —
    the reference lists name strings only)."""
    bst = _get(handle)
    if bst._gbdt is None or bst.train_set is None:
        return []
    metrics = bst._metrics_for(bst.train_set._binned,
                               bst._gbdt.num_data)
    return [m.name for m in metrics]


def booster_get_feature_names(handle: int) -> list:
    return list(_get(handle).feature_name())


def booster_get_linear(handle: int) -> int:
    bst = _get(handle)
    if bst._gbdt is not None:
        return int(bool(bst._gbdt.config.linear_tree))
    return 0


def booster_calc_num_predict(handle: int, num_row: int, predict_type: int,
                             start_iteration: int,
                             num_iteration: int) -> int:
    """(ref: LGBM_BoosterCalcNumPredict c_api.cpp:2585)"""
    bst = _get(handle)
    k = booster_num_model_per_iteration(handle)
    total_iter = _booster_total_models(bst) // max(k, 1)
    start = max(int(start_iteration), 0)
    iters = total_iter - start if num_iteration <= 0 else \
        min(int(num_iteration), total_iter - start)
    if predict_type == _PREDICT_LEAF:
        return int(num_row) * k * max(iters, 0)
    if predict_type == _PREDICT_CONTRIB:
        return int(num_row) * k * (int(booster_num_feature(handle)) + 1)
    return int(num_row) * k


def booster_get_num_predict(handle: int, data_idx: int) -> int:
    bst = _get(handle)
    k = booster_num_model_per_iteration(handle)
    if data_idx == 0:
        n = bst._gbdt.num_data
    else:
        n = bst._valid_sets[data_idx - 1].num_data()
    return int(n) * k


def booster_get_predict(handle: int, data_idx: int, out_ptr: int) -> int:
    """Current (transformed) scores for train (0) or valid set idx
    (ref: LGBM_BoosterGetPredict -> GBDT::GetPredictAt)."""
    bst = _get(handle)
    gbdt = bst._gbdt
    if data_idx == 0:
        raw = np.asarray(gbdt.scores).T       # [N, K]
    else:
        raw = np.asarray(gbdt.valid_raw_scores(data_idx - 1))  # [N, K]
    obj = gbdt.objective
    out = obj.convert_output(raw) if obj is not None else raw
    return _write_doubles(out_ptr, np.asarray(out).reshape(-1))


def booster_predict_for_file(handle: int, data_filename: str,
                             data_has_header: int, predict_type: int,
                             start_iteration: int, num_iteration: int,
                             parameter: str, result_filename: str) -> None:
    """(ref: LGBM_BoosterPredictForFile c_api.cpp:2496 -> Predictor)"""
    from .io.text_loader import load_svmlight_or_csv
    params = _parse_params(parameter)
    params.setdefault("header", str(bool(data_has_header)).lower())
    X, _y, _w, _g = load_svmlight_or_csv(data_filename, params)
    bst = _get(handle)
    canon = {Config.canonical_key(pk): pv for pk, pv in params.items()}
    # per-call serving override; also caps the SHAP row chunks when
    # predict_type is contribution (ops/shap.py)
    chunk = canon.get("tpu_predict_chunk")
    pred = bst.predict(X, start_iteration=start_iteration,
                       num_iteration=num_iteration,
                       raw_score=predict_type == _PREDICT_RAW,
                       pred_leaf=predict_type == _PREDICT_LEAF,
                       pred_contrib=predict_type == _PREDICT_CONTRIB,
                       tpu_predict_chunk=int(chunk) if chunk else None)
    pred = np.asarray(pred)
    if pred.ndim == 1:
        pred = pred[:, None]
    with open(result_filename, "w") as fh:
        for row in pred:
            fh.write("\t".join(repr(float(v)) for v in row) + "\n")


def booster_dump_model(handle: int, start_iteration: int,
                       num_iteration: int) -> str:
    """(ref: LGBM_BoosterDumpModel — JSON text)"""
    import json
    return json.dumps(_get(handle).dump_model(
        num_iteration=num_iteration, start_iteration=start_iteration))


def booster_feature_importance(handle: int, num_iteration: int,
                               importance_type: int, out_ptr: int) -> int:
    """(ref: LGBM_BoosterFeatureImportance c_api.cpp:2933)"""
    imp = _get(handle).feature_importance(
        "gain" if importance_type == 1 else "split",
        iteration=num_iteration if num_iteration > 0 else -1)
    return _write_doubles(out_ptr, np.asarray(imp, np.float64))


def _all_trees(bst):
    if bst._gbdt is not None:
        return [t for it in bst._gbdt.models for t in it]
    return list(bst._loaded.trees)


def booster_get_leaf_value(handle: int, tree_idx: int,
                           leaf_idx: int) -> float:
    trees = _all_trees(_get(handle))
    return float(trees[tree_idx].leaf_value[leaf_idx])


def _invalidate_packed(bst) -> None:
    """Drop the packed device-ensemble cache after structural edits
    (ops/predict.py predict_raw_cached keys on owner._packed_key; the
    incremental EnsemblePackers identify trees by (id, pack_version)
    tokens, which in-place leaf edits don't change — so they must be
    dropped wholesale too)."""
    for owner in (bst._gbdt, getattr(bst, "_loaded", None)):
        if owner is not None and hasattr(owner, "_packed_key"):
            owner._packed_key = None
        if owner is not None and hasattr(owner, "_packers"):
            owner._packers = {}


def booster_set_leaf_value(handle: int, tree_idx: int, leaf_idx: int,
                           val: float) -> None:
    """(ref: LGBM_BoosterSetLeafValue -> Tree::SetLeafOutput)"""
    bst = _get(handle)
    trees = _all_trees(bst)
    trees[tree_idx].leaf_value[leaf_idx] = val
    _invalidate_packed(bst)


def booster_get_upper_bound_value(handle: int) -> float:
    """(ref: LGBM_BoosterGetUpperBoundValue -> GBDT::GetUpperBoundValue)"""
    bst = _get(handle)
    total = sum(float(np.max(t.leaf_value[:max(t.num_leaves, 1)]))
                for t in _all_trees(bst))
    return total


def booster_get_lower_bound_value(handle: int) -> float:
    bst = _get(handle)
    return sum(float(np.min(t.leaf_value[:max(t.num_leaves, 1)]))
               for t in _all_trees(bst))


def booster_shuffle_models(handle: int, start_iter: int,
                           end_iter: int) -> None:
    _get(handle).shuffle_models(start_iter, end_iter)


def booster_merge(handle: int, other_handle: int) -> None:
    """(ref: LGBM_BoosterMerge — appends other's models)"""
    bst, other = _get(handle), _get(other_handle)
    if bst._gbdt is None or other._gbdt is None:
        raise ValueError("merge requires trained boosters")
    bst._gbdt.models = bst._gbdt.models + other._gbdt.models
    _invalidate_packed(bst)


def booster_update_one_iter_custom(handle: int, grad_ptr: int,
                                   hess_ptr: int) -> int:
    """(ref: LGBM_BoosterUpdateOneIterCustom c_api.cpp:2140)"""
    bst = _get(handle)
    gbdt = bst._gbdt
    n = gbdt.num_data * gbdt.num_tree_per_iteration
    grad = _array_from_ptr(grad_ptr, n, 0)
    hess = _array_from_ptr(hess_ptr, n, 0)
    return int(bool(bst.update(fobj=lambda _scores, _ds: (grad, hess))))


def booster_refit(handle: int, leaf_preds_ptr: int, nrow: int,
                  ncol: int) -> None:
    """(ref: LGBM_BoosterRefit c_api.cpp:2109 -> GBDT::RefitTree).

    The booster's current train set supplies features/labels (the
    python-package flow resets training data first, then calls this);
    the refitted model replaces the handle's booster in the registry.
    leaf_preds is accepted for signature parity — refit.py re-derives
    leaf assignments from the train features, which is equivalent for
    data that produced those leaf predictions."""
    bst = _get(handle)
    _array_from_ptr(leaf_preds_ptr, nrow * ncol, 2)  # validate readable
    ds = bst.train_set
    if ds is None or ds.data is None:
        raise ValueError("refit requires a booster with raw train data")
    new = bst.refit(np.asarray(ds.get_data(), np.float64),
                    np.asarray(ds.get_label(), np.float32))
    _registry[handle] = new


# -- single-row / fast-path prediction -------------------------------------
class _FastConfig:
    """Pre-bound prediction configuration (ref: FastConfigHandle,
    c_api.cpp FastConfig + LGBM_BoosterPredictForMatSingleRowFastInit
    c_api.cpp:2605-2625). Binding booster + predict params once lets the
    per-call path skip parameter parsing; repeated single-row predicts
    also reuse the jitted packed-ensemble program (shape-stable)."""

    def __init__(self, booster, predict_type, start_iteration,
                 num_iteration, data_type, ncol):
        self.booster = booster
        self.predict_type = int(predict_type)
        self.start_iteration = int(start_iteration)
        self.num_iteration = int(num_iteration)
        self.data_type = int(data_type)
        self.ncol = int(ncol)


def booster_predict_for_mat_single_row(handle: int, data_ptr: int,
                                       data_type: int, ncol: int,
                                       predict_type: int,
                                       start_iteration: int,
                                       num_iteration: int,
                                       out_ptr: int) -> int:
    """(ref: LGBM_BoosterPredictForMatSingleRow c_api.cpp:2558)"""
    row = _array_from_ptr(data_ptr, ncol, data_type).reshape(1, ncol)
    return _predict_into(_get(handle), np.asarray(row, np.float64),
                         predict_type, start_iteration, num_iteration,
                         out_ptr)


def fast_config_init(handle: int, predict_type: int, start_iteration: int,
                     num_iteration: int, data_type: int, ncol: int) -> int:
    """Shared by the Mat and CSR FastInit entry points."""
    fc = _FastConfig(_get(handle), predict_type, start_iteration,
                     num_iteration, data_type, ncol)
    return _new_handle(fc)


def booster_predict_single_row_fast(fc_handle: int, data_ptr: int,
                                    out_ptr: int) -> int:
    """(ref: LGBM_BoosterPredictForMatSingleRowFast c_api.cpp:2625)"""
    fc = _get(fc_handle)
    row = _array_from_ptr(data_ptr, fc.ncol, fc.data_type).reshape(
        1, fc.ncol)
    return _predict_into(fc.booster, np.asarray(row, np.float64),
                         fc.predict_type, fc.start_iteration,
                         fc.num_iteration, out_ptr)


def booster_predict_csr_single_row_fast(fc_handle: int, indptr_ptr: int,
                                        indptr_type: int, indices_ptr: int,
                                        data_ptr: int, nindptr: int,
                                        nelem: int, out_ptr: int) -> int:
    """(ref: LGBM_BoosterPredictForCSRSingleRowFast c_api.cpp:2651)"""
    fc = _get(fc_handle)
    csr = _csr_from_ptrs(indptr_ptr, indptr_type, indices_ptr, data_ptr,
                         fc.data_type, nindptr, nelem, fc.ncol)
    return _predict_into(fc.booster, csr, fc.predict_type,
                         fc.start_iteration, fc.num_iteration, out_ptr)


def booster_predict_csr_single_row(handle: int, indptr_ptr: int,
                                   indptr_type: int, indices_ptr: int,
                                   data_ptr: int, data_type: int,
                                   nindptr: int, nelem: int, num_col: int,
                                   predict_type: int, start_iteration: int,
                                   num_iteration: int, out_ptr: int) -> int:
    """(ref: LGBM_BoosterPredictForCSRSingleRow)"""
    csr = _csr_from_ptrs(indptr_ptr, indptr_type, indices_ptr, data_ptr,
                         data_type, nindptr, nelem, num_col)
    return _predict_into(_get(handle), csr, predict_type, start_iteration,
                         num_iteration, out_ptr)


def booster_predict_for_mats(handle: int, row_ptrs_ptr: int,
                             data_type: int, nrow: int, ncol: int,
                             predict_type: int, start_iteration: int,
                             num_iteration: int, out_ptr: int) -> int:
    """(ref: LGBM_BoosterPredictForMats — array of row pointers)"""
    ptrs = _array_from_ptr(row_ptrs_ptr, nrow, 3)  # void* per row
    mat = np.empty((nrow, ncol), np.float64)
    for i in range(nrow):
        mat[i] = _array_from_ptr(int(ptrs[i]), ncol, data_type)
    return _predict_into(_get(handle), mat, predict_type, start_iteration,
                         num_iteration, out_ptr)


# -- global utilities ------------------------------------------------------
_max_threads = [-1]


def set_max_threads(n: int) -> None:
    """(ref: LGBM_SetMaxThreads — bounds the native thread pool; XLA
    device parallelism is unaffected, like the reference's CUDA path).
    Any negative value resets to the 'use default' sentinel -1, exactly
    like the reference (tests/c_api_test/test_.py
    test_max_thread_control pins this contract)."""
    _max_threads[0] = int(n) if n > 0 else -1
    os.environ["LGBM_TPU_NUM_THREADS"] = str(n if n > 0 else 0)


def get_max_threads() -> int:
    return _max_threads[0]


def dump_param_aliases() -> str:
    """(ref: LGBM_DumpParamAliases c_api.cpp — JSON alias map)"""
    import json
    from .config import _ALIAS_TO_CANONICAL
    out: Dict[str, list] = {}
    for alias, canonical in _ALIAS_TO_CANONICAL.items():
        if alias != canonical:
            out.setdefault(canonical, []).append(alias)
    return json.dumps(out, indent=2)


_log_callback = [None]


def register_log_callback(ptr: int) -> None:
    """Route framework logging through a C callback
    (ref: LGBM_RegisterLogCallback c_api.cpp:90)."""
    from . import log as log_mod
    cb = ctypes.CFUNCTYPE(None, ctypes.c_char_p)(ptr)
    _log_callback[0] = cb

    class _CallbackLogger:
        @staticmethod
        def info(msg: str) -> None:
            cb(str(msg).encode("utf-8"))

        warning = info

    log_mod.register_logger(_CallbackLogger())


_network_conf = [None]


def network_init(machines: str, local_listen_port: int, listen_time_out: int,
                 num_machines: int) -> None:
    """API-parity seam for LGBM_NetworkInit (c_api.cpp:2845). The socket
    machine list is recorded but collectives ride the jax.distributed /
    ICI mesh (parallel/distributed.py) rather than reference TCP — use
    lightgbm_tpu.cluster / jax.distributed.initialize for real
    multi-host runs."""
    _network_conf[0] = {"machines": machines,
                       "local_listen_port": int(local_listen_port),
                       "listen_time_out": int(listen_time_out),
                       "num_machines": int(num_machines)}


def network_free() -> None:
    _network_conf[0] = None


def booster_validate_feature_names(handle: int, names: list) -> None:
    """(ref: LGBM_BoosterValidateFeatureNames c_api.cpp)"""
    model_names = booster_get_feature_names(handle)
    data_names = [str(n) for n in names]
    if len(model_names) != len(data_names) or any(
            a != b for a, b in zip(model_names, data_names)):
        raise ValueError(
            f"feature names mismatch: model has {model_names}, "
            f"data has {data_names}")


# -- serialized dataset reference + ByteBuffer ------------------------------
# (ref: LGBM_DatasetSerializeReferenceToBinary c_api.cpp +
#  LGBM_DatasetCreateFromSerializedReference — ship the dataset SCHEMA
#  (bin mappers, used features) to another process, which then fills a
#  same-aligned dataset via the streaming push API; ByteBufferHandle is
#  the transport, c_api.h:117)
def dataset_serialize_reference(handle: int) -> int:
    """Returns a ByteBuffer handle whose bytes encode the schema."""
    import json as _json
    from .io.binary_format import _mapper_state
    ds = _resolve_ds(_get(handle)).construct()
    binned = ds._binned
    payload = {
        "num_total_features": binned.num_total_features,
        "used_features": [int(c) for c in binned.used_features],
        "feature_names": list(binned.feature_names),
        "mappers": [_mapper_state(m) for m in binned.mappers],
    }
    buf = _json.dumps(payload).encode("utf-8")
    return _new_handle(buf)


def byte_buffer_size(handle: int) -> int:
    return len(_get(handle))


def byte_buffer_get_at(handle: int, index: int) -> int:
    return _get(handle)[index]


def dataset_create_from_serialized_reference(buf_ptr: int, buf_size: int,
                                             num_row: int,
                                             num_classes: int,
                                             parameters: str) -> int:
    """(ref: LGBM_DatasetCreateFromSerializedReference c_api.cpp:1245)"""
    import json as _json
    from .dataset import BinnedDataset, Metadata
    from .io.binary_format import _mapper_from_state
    raw = ctypes.string_at(buf_ptr, buf_size)
    payload = _json.loads(raw.decode("utf-8"))
    mappers = [_mapper_from_state(s) for s in payload["mappers"]]
    used = payload["used_features"]
    ref_binned = BinnedDataset(
        np.zeros((1, 0), np.uint8), mappers, used,
        payload["num_total_features"], Metadata(0),
        feature_names=payload["feature_names"])
    from .io.binary_format import make_dataset_shell
    ref = make_dataset_shell(ref_binned, _parse_params(parameters))
    sd = _StreamingDataset(num_row, payload["num_total_features"],
                           _parse_params(parameters), ref)
    if num_classes > 1:
        sd.nclasses = int(num_classes)
    return _new_handle(sd)


def booster_get_loaded_param(handle: int) -> str:
    """(ref: LGBM_BoosterGetLoadedParam — JSON of the model's stored
    parameters block)."""
    import json as _json
    bst = _get(handle)
    params = dict(getattr(bst, "_loaded", None) and bst._loaded.params
                  or bst.params or {})
    return _json.dumps(params)


# -- sparse (CSR) prediction output ----------------------------------------
def booster_predict_sparse_output(handle: int, indptr_ptr: int,
                                  indptr_type: int, indices_ptr: int,
                                  data_ptr: int, data_type: int,
                                  nindptr: int, nelem: int, num_col: int,
                                  predict_type: int, start_iteration: int,
                                  num_iteration: int) -> tuple:
    """Feature contributions as CSR (ref: LGBM_BoosterPredictSparseOutput
    c_api.cpp — contrib matrices are mostly zero on sparse input).
    Returns (indptr_bytes, indices_bytes, data_bytes, out_nindptr,
    out_nelem); the C side copies into malloc'd buffers the caller
    frees with LGBM_BoosterFreePredictSparse."""
    from scipy import sparse
    if predict_type != _PREDICT_CONTRIB:
        raise ValueError(
            "sparse output is defined for contribution prediction")
    csr = _csr_from_ptrs(indptr_ptr, indptr_type, indices_ptr, data_ptr,
                         data_type, nindptr, nelem, num_col)
    bst = _get(handle)
    contrib = np.asarray(bst.predict(
        csr, start_iteration=start_iteration, num_iteration=num_iteration,
        pred_contrib=True), np.float64)
    k = booster_num_model_per_iteration(handle)
    if k > 1:
        # reference layout: num_class * num_data rows x (num_feature + 1)
        # cols, class-major like its other multi-output surfaces
        # (c_api.h:1092)
        n = contrib.shape[0]
        contrib = contrib.reshape(n, k, -1).transpose(1, 0, 2).reshape(
            n * k, -1)
    out = sparse.csr_matrix(contrib)
    # outputs carry the CALLER's indptr/data element types, like the
    # reference's allocation (FreePredictSparse takes both types)
    indptr = np.ascontiguousarray(out.indptr, _NP_DTYPES[indptr_type])
    indices = np.ascontiguousarray(out.indices, np.int32)
    vals = np.ascontiguousarray(out.data, _NP_DTYPES[data_type])
    return (indptr.tobytes(), indices.tobytes(), vals.tobytes(),
            int(len(indptr)), int(len(vals)))


# -- Arrow C-data entry points (raw struct pointers) ------------------------
# The PyCapsule-protocol ingestion in io/arrow_ingest.py does all the
# work; these shims wrap the C API's raw ArrowArray/ArrowSchema/
# ArrowArrayStream pointers in no-destructor capsules so the same
# (dependency-free) reader consumes them (ref: c_api.cpp
# LGBM_DatasetCreateFromArrow* family via nanoarrow).
_PyCapsule_New = ctypes.pythonapi.PyCapsule_New
_PyCapsule_New.restype = ctypes.py_object
_PyCapsule_New.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                           ctypes.c_void_p]


class _RawArrowArray:
    def __init__(self, schema_ptr: int, array_ptr: int):
        self._schema_ptr = schema_ptr
        self._array_ptr = array_ptr

    def __arrow_c_array__(self, requested_schema=None):
        return (_PyCapsule_New(self._schema_ptr, b"arrow_schema", None),
                _PyCapsule_New(self._array_ptr, b"arrow_array", None))


class _RawArrowStream:
    def __init__(self, stream_ptr: int):
        self._stream_ptr = stream_ptr

    def __arrow_c_stream__(self, requested_schema=None):
        return _PyCapsule_New(self._stream_ptr, b"arrow_array_stream",
                              None)


def _arrow_chunks_matrix(n_chunks: int, chunks_ptr: int, schema_ptr: int):
    from .io.arrow_ingest import ArrowArray, arrow_to_matrix
    if n_chunks <= 0 or not chunks_ptr or not schema_ptr:
        raise ValueError("empty Arrow chunked array")
    sz = ctypes.sizeof(ArrowArray)
    mats, names = [], None
    for i in range(int(n_chunks)):
        m, names = arrow_to_matrix(
            _RawArrowArray(schema_ptr, chunks_ptr + i * sz))
        mats.append(m)
    return (np.concatenate(mats, axis=0) if len(mats) > 1 else mats[0],
            names)


def dataset_create_from_arrow(n_chunks: int, chunks_ptr: int,
                              schema_ptr: int, parameters: str,
                              reference: int) -> int:
    mat, names = _arrow_chunks_matrix(n_chunks, chunks_ptr, schema_ptr)
    ref = _resolve_ds(_get(reference)) if reference else None
    ds = Dataset(np.asarray(mat, np.float64), reference=ref,
                 feature_name=names or "auto",
                 params=_parse_params(parameters))
    return _new_handle(ds)


def dataset_create_from_arrow_stream(stream_ptr: int, parameters: str,
                                     reference: int) -> int:
    from .io.arrow_ingest import arrow_to_matrix
    mat, names = arrow_to_matrix(_RawArrowStream(stream_ptr))
    ref = _resolve_ds(_get(reference)) if reference else None
    ds = Dataset(np.asarray(mat, np.float64), reference=ref,
                 feature_name=names or "auto",
                 params=_parse_params(parameters))
    return _new_handle(ds)


def _set_field_values(handle: int, field: str, values: np.ndarray) -> None:
    ds = _resolve_ds(_get(handle))
    if field == "label":
        ds.set_label(values)
    elif field == "weight":
        ds.set_weight(values)
    elif field in ("group", "query"):
        ds.set_group(values)
    elif field == "init_score":
        ds.set_init_score(values)
    else:
        raise ValueError(f"unknown field {field}")


def dataset_set_field_from_arrow(handle: int, field: str, n_chunks: int,
                                 chunks_ptr: int, schema_ptr: int) -> None:
    from .io.arrow_ingest import ArrowArray, arrow_to_vector
    if n_chunks <= 0 or not chunks_ptr or not schema_ptr:
        raise ValueError("empty Arrow chunked array")
    sz = ctypes.sizeof(ArrowArray)
    parts = [arrow_to_vector(_RawArrowArray(schema_ptr,
                                            chunks_ptr + i * sz))
             for i in range(int(n_chunks))]
    _set_field_values(handle, field,
                      np.concatenate(parts) if len(parts) > 1 else parts[0])


def dataset_set_field_from_arrow_stream(handle: int, field: str,
                                        stream_ptr: int) -> None:
    from .io.arrow_ingest import _iter_stream, _primitive_to_numpy
    parts = []
    for schema, array, _keep in _iter_stream(_RawArrowStream(stream_ptr)):
        parts.append(_primitive_to_numpy(schema, array))
    if not parts:
        raise ValueError("empty Arrow stream")
    _set_field_values(handle, field,
                      np.concatenate(parts) if len(parts) > 1 else parts[0])


def booster_predict_for_arrow(handle: int, n_chunks: int, chunks_ptr: int,
                              schema_ptr: int, predict_type: int,
                              start_iteration: int, num_iteration: int,
                              out_ptr: int) -> int:
    mat, _names = _arrow_chunks_matrix(n_chunks, chunks_ptr, schema_ptr)
    return _predict_into(_get(handle), np.asarray(mat, np.float64),
                         predict_type, start_iteration, num_iteration,
                         out_ptr)


def booster_predict_for_arrow_stream(handle: int, stream_ptr: int,
                                     predict_type: int,
                                     start_iteration: int,
                                     num_iteration: int,
                                     out_ptr: int) -> int:
    from .io.arrow_ingest import arrow_to_matrix
    mat, _names = arrow_to_matrix(_RawArrowStream(stream_ptr))
    return _predict_into(_get(handle), np.asarray(mat, np.float64),
                         predict_type, start_iteration, num_iteration,
                         out_ptr)


# -- CSC / multi-matrix creation -------------------------------------------
def _csc_from_ptrs(col_ptr: int, col_ptr_type: int, indices_ptr: int,
                   data_ptr: int, data_type: int, ncol_ptr: int,
                   nelem: int, num_row: int):
    from scipy import sparse
    colptr = _array_from_ptr(col_ptr, ncol_ptr, col_ptr_type)
    indices = _array_from_ptr(indices_ptr, nelem, 2)
    data = _array_from_ptr(data_ptr, nelem, data_type)
    return sparse.csc_matrix(
        (np.asarray(data, np.float64), indices, colptr),
        shape=(num_row, ncol_ptr - 1))


def dataset_create_from_csc(col_ptr: int, col_ptr_type: int,
                            indices_ptr: int, data_ptr: int,
                            data_type: int, ncol_ptr: int, nelem: int,
                            num_row: int, parameters: str,
                            reference: int) -> int:
    """(ref: LGBM_DatasetCreateFromCSC c_api.cpp — the col-wise twin)"""
    csc = _csc_from_ptrs(col_ptr, col_ptr_type, indices_ptr, data_ptr,
                         data_type, ncol_ptr, nelem, num_row)
    ref = _resolve_ds(_get(reference)) if reference else None
    ds = Dataset(csc, reference=ref, params=_parse_params(parameters))
    return _new_handle(ds)


def booster_predict_for_csc(handle: int, col_ptr: int, col_ptr_type: int,
                            indices_ptr: int, data_ptr: int,
                            data_type: int, ncol_ptr: int, nelem: int,
                            num_row: int, predict_type: int,
                            start_iteration: int, num_iteration: int,
                            out_ptr: int) -> int:
    csc = _csc_from_ptrs(col_ptr, col_ptr_type, indices_ptr, data_ptr,
                         data_type, ncol_ptr, nelem, num_row)
    return _predict_into(_get(handle), csc.tocsr(), predict_type,
                         start_iteration, num_iteration, out_ptr)


def dataset_create_from_mats(nmat: int, data_ptrs_ptr: int, data_type: int,
                             nrow_ptr: int, ncol: int,
                             is_row_major_ptr: int, parameters: str,
                             reference: int) -> int:
    """(ref: LGBM_DatasetCreateFromMats — stacked sub-matrices)"""
    ptrs = _array_from_ptr(data_ptrs_ptr, nmat, 3)
    nrows = _array_from_ptr(nrow_ptr, nmat, 2)
    majors = _array_from_ptr(is_row_major_ptr, nmat, 2)
    mats = []
    for i in range(nmat):
        n = int(nrows[i])
        flat = _array_from_ptr(int(ptrs[i]), n * ncol, data_type)
        mats.append(flat.reshape(n, ncol) if majors[i]
                    else flat.reshape(ncol, n).T)
    mat = np.concatenate(mats, axis=0) if len(mats) > 1 else mats[0]
    ref = _resolve_ds(_get(reference)) if reference else None
    ds = Dataset(np.asarray(mat, np.float64), reference=ref,
                 params=_parse_params(parameters))
    return _new_handle(ds)


def _as_dense(ds) -> np.ndarray:
    data = ds.get_data()
    if hasattr(data, "todense"):
        return np.asarray(data.todense(), np.float64)
    return np.asarray(data, np.float64)


def dataset_add_features_from(target: int, source: int) -> None:
    """(ref: LGBM_DatasetAddFeaturesFrom dataset.cpp:1437 — append the
    source dataset's features to the target). Requires raw data on both
    (re-bins the combined matrix; the reference splices bin mappers).
    The target's metadata (label/weight/group/init_score/position) and
    both sides' feature names are preserved."""
    tgt = _resolve_ds(_get(target))
    src = _resolve_ds(_get(source))
    if tgt.data is None or src.data is None:
        raise ValueError("AddFeaturesFrom requires raw data on both "
                         "datasets")
    names = None
    tn, sn = tgt.get_feature_name(), src.get_feature_name()
    if tn and sn:
        names = list(tn) + list(sn)
    merged = Dataset(np.hstack([_as_dense(tgt), _as_dense(src)]),
                     label=tgt.get_label(), weight=tgt.get_weight(),
                     group=tgt.get_group(),
                     init_score=tgt.get_init_score(),
                     feature_name=names or "auto",
                     params=dict(tgt.params or {}))
    merged.position = getattr(tgt, "position", None)
    merged.construct()
    _registry[target] = merged


def network_init_with_functions(num_machines: int, rank: int,
                                reduce_scatter_ptr: int,
                                allgather_ptr: int) -> None:
    """API-parity seam for LGBM_NetworkInitWithFunctions
    (c_api.cpp:2867): external collective callbacks are recorded but
    collectives ride XLA over the jax mesh (see network_init)."""
    _network_conf[0] = {"machines": "<external-functions>",
                        "num_machines": int(num_machines),
                        "rank": int(rank),
                        "reduce_scatter_ext": int(reduce_scatter_ptr),
                        "allgather_ext": int(allgather_ptr)}
