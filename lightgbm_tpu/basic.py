"""User-facing Dataset / Booster API.

Mirrors the reference python-package surface
(ref: python-package/lightgbm/basic.py:1692 Dataset, :3495 Booster) with
lazy Dataset construction, aligned validation binning via `reference=`,
and a Booster wrapping the TPU boosting engine instead of ctypes into
lib_lightgbm.so.
"""

from __future__ import annotations

import abc
import json
from copy import deepcopy
from pathlib import Path
from typing import Any, Dict, List, Optional, Union
from typing import Sequence as _SequenceT

import numpy as np

from .boosting import GBDT, create_boosting
from .config import Config
from .dataset import BinnedDataset, Metadata
from .metrics import create_metrics
from .model_io import (dump_model_to_json, load_model_from_string,
                       save_model_to_string, LoadedModel)
from .objectives import create_objective


class LightGBMError(Exception):
    """(ref: basic.py LightGBMError)"""


from .dataset import is_sparse as _is_sparse


def _to_2d(data):
    if _is_sparse(data):
        # kept sparse end-to-end (see BinnedDataset.from_sparse);
        # normalized to CSR so row slicing (subset, cv folds) works
        return data.tocsr()
    arr = np.asarray(data, dtype=np.float64)
    if arr.ndim == 1:
        arr = arr.reshape(-1, 1)
    return arr


class Sequence(abc.ABC):
    """Generic batched data-access interface (ref: basic.py:841
    lightgbm.Sequence): subclasses provide random row access
    (``seq[i]`` -> 1D row, ``seq[a:b]`` -> 2D batch) and ``len(seq)``;
    ``batch_size`` bounds how many rows are read per range access.
    A Dataset accepts one Sequence or a list of them (row-concatenated)
    and reads through them in batches, so producers never hand over one
    giant in-memory matrix."""

    batch_size: int = 4096

    @abc.abstractmethod
    def __getitem__(self, idx):
        raise NotImplementedError("scikit-learn requires __getitem__")

    @abc.abstractmethod
    def __len__(self) -> int:
        raise NotImplementedError


def _materialize_sequences(seqs) -> np.ndarray:
    """Batched read-through of one or more Sequence objects -> [N, F]."""
    parts = []
    for seq in seqs:
        n = len(seq)
        bs = max(int(getattr(seq, "batch_size", 4096) or 4096), 1)
        for lo in range(0, n, bs):
            batch = np.asarray(seq[lo:min(lo + bs, n)], np.float64)
            parts.append(batch if batch.ndim == 2 else batch[None, :])
    if not parts:
        raise LightGBMError("empty Sequence data")
    return np.concatenate(parts, axis=0)


class Dataset:
    """Lazily-constructed training dataset (ref: basic.py:1692)."""

    def __init__(self, data, label=None, reference: Optional["Dataset"] = None,
                 weight=None, group=None, init_score=None,
                 feature_name: Union[str, List[str]] = "auto",
                 categorical_feature: Union[str, List] = "auto",
                 params: Optional[Dict[str, Any]] = None,
                 free_raw_data: bool = False, position=None):
        if isinstance(data, Sequence):
            data = _materialize_sequences([data])
        elif isinstance(data, (list, tuple)) and data and any(
                isinstance(s, Sequence) for s in data):
            if not all(isinstance(s, Sequence) for s in data):
                raise TypeError(
                    "a chunked Dataset input must be a list of Sequence "
                    "objects only (mixed Sequence/array lists are not "
                    "supported)")
            data = _materialize_sequences(data)
        if isinstance(data, (str, Path)):
            path = str(data)
            with open(path, "rb") as fh:
                magic = fh.read(2)
            loaded = None
            if magic == b"PK":  # zip container: try the binary-dataset path
                from .io.binary_format import load_dataset_binary
                try:
                    loaded = load_dataset_binary(path)
                except Exception:
                    loaded = None  # not ours — fall through to text parsing
            if loaded is not None:
                self.__dict__.update(loaded.__dict__)
                # user-supplied metadata overrides the stored copy
                for value, setter in ((label, self.set_label),
                                      (weight, self.set_weight),
                                      (group, self.set_group),
                                      (init_score, self.set_init_score)):
                    if value is not None:
                        setter(value)
                if reference is not None:
                    # stored bins must match the reference's mappers, or
                    # eval would silently run on mis-binned data
                    reference.construct()
                    ref_b, own_b = reference._binned, self._binned
                    same = (len(ref_b.mappers) == len(own_b.mappers) and all(
                        rm.num_bins == om.num_bins and
                        rm.is_categorical == om.is_categorical and
                        (rm.bin_upper_bound is None or
                         om.bin_upper_bound is None or
                         np.array_equal(rm.bin_upper_bound,
                                        om.bin_upper_bound))
                        for rm, om in zip(ref_b.mappers, own_b.mappers)))
                    if not same:
                        raise LightGBMError(
                            f"binary dataset {path} was binned differently "
                            "from the reference dataset; rebuild it with "
                            "save_binary against the same training data, "
                            "or pass the text file instead")
                    self.reference = reference
                _DATASET_PARAM_KEYS = {
                    "max_bin", "max_bin_by_feature", "min_data_in_bin",
                    "bin_construct_sample_cnt", "use_missing",
                    "zero_as_missing", "feature_pre_filter",
                    "categorical_feature", "forcedbins_filename"}
                dropped = _DATASET_PARAM_KEYS & set(params or {})
                if dropped:
                    import warnings
                    warnings.warn(
                        f"dataset params {sorted(dropped)} are ignored when "
                        "loading a binary dataset file (binning is fixed)")
                return
            from .io.text_loader import (load_svmlight_or_csv,
                                         sidecar_init_score,
                                         sidecar_position)
            data, file_label, file_weight, file_group = \
                load_svmlight_or_csv(path, params or {})
            if label is None:
                label = file_label
            if weight is None:
                weight = file_weight
            if group is None:
                group = file_group
            if init_score is None:
                init_score = sidecar_init_score(path)
            if position is None:
                position = sidecar_position(path)
        from .io.arrow_ingest import arrow_to_matrix, arrow_to_vector, is_arrow
        if is_arrow(data):
            # Arrow table via the PyCapsule C-ABI protocol — no pyarrow
            # needed (ref: arrow.h:34, LGBM_DatasetCreateFromArrow)
            data, arrow_names = arrow_to_matrix(data)
            if feature_name == "auto" and arrow_names:
                feature_name = arrow_names
        if label is not None and is_arrow(label):
            label = arrow_to_vector(label)
        if weight is not None and is_arrow(weight):
            weight = arrow_to_vector(weight)
        if init_score is not None and is_arrow(init_score):
            init_score = arrow_to_vector(init_score)
        if group is not None and is_arrow(group):
            group = arrow_to_vector(group)
        self.data = _to_2d(data)
        self.label = label
        self.weight = weight
        self.group = group
        self.init_score = init_score
        self.position = position
        self.reference = reference
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.params = dict(params or {})
        self.free_raw_data = free_raw_data
        self._binned: Optional[BinnedDataset] = None
        self.used_indices = None

    # ------------------------------------------------------------------
    def construct(self) -> "Dataset":
        if self._binned is not None:
            return self
        cfg = Config.from_params(self.params)
        meta = Metadata(self.data.shape[0])
        if self.label is not None:
            meta.set_label(self.label)
        else:
            meta.set_label(np.zeros(self.data.shape[0]))
        meta.set_weight(self.weight)
        if self.group is not None:
            meta.set_group(self.group)
        meta.set_init_score(self.init_score)
        if self.position is not None:
            meta.set_position(self.position)

        cat_indices: List[int] = []
        names = self._feature_names()
        if isinstance(self.categorical_feature, (list, tuple)):
            for c in self.categorical_feature:
                if isinstance(c, str) and c in names:
                    cat_indices.append(names.index(c))
                elif isinstance(c, (int, np.integer)):
                    cat_indices.append(int(c))

        ref_binned = None
        if self.reference is not None:
            self.reference.construct()
            ref_binned = self.reference._binned

        forced_bins = None
        fb_file = cfg.forcedbins_filename
        if fb_file:
            with open(fb_file) as fh:
                spec = json.load(fh)
            forced_bins = {int(e["feature"]): e["bin_upper_bound"]
                           for e in spec}

        from .dataset import binning_span
        with binning_span():
            if _is_sparse(self.data):
                self._binned = BinnedDataset.from_sparse(
                    self.data, cfg, metadata=meta,
                    categorical_features=cat_indices,
                    feature_names=names, reference=ref_binned,
                    forced_bins=forced_bins)
            else:
                self._binned = BinnedDataset.from_matrix(
                    self.data, cfg, metadata=meta,
                    categorical_features=cat_indices,
                    feature_names=names, reference=ref_binned,
                    forced_bins=forced_bins)
        return self

    def _feature_names(self) -> List[str]:
        if isinstance(self.feature_name, list):
            return list(self.feature_name)
        return [f"Column_{i}" for i in range(self.data.shape[1])]

    # ------------------------------------------------------------------
    def set_label(self, label) -> "Dataset":
        self.label = label
        if self._binned is not None:
            self._binned.metadata.set_label(label)
        return self

    def set_weight(self, weight) -> "Dataset":
        self.weight = weight
        if self._binned is not None:
            self._binned.metadata.set_weight(weight)
        return self

    def set_group(self, group) -> "Dataset":
        self.group = group
        if self._binned is not None:
            self._binned.metadata.set_group(group)
        return self

    def set_init_score(self, init_score) -> "Dataset":
        self.init_score = init_score
        if self._binned is not None:
            self._binned.metadata.set_init_score(init_score)
        return self

    def get_label(self):
        return self.label

    def get_weight(self):
        return self.weight

    def get_group(self):
        return self.group

    def get_init_score(self):
        return self.init_score

    def get_data(self):
        return self.data

    def num_data(self) -> int:
        if self.data is None and self._binned is not None:
            return self._binned.num_data
        return self.data.shape[0]

    def num_feature(self) -> int:
        if self.data is None and self._binned is not None:
            return self._binned.num_total_features
        return self.data.shape[1]

    def get_feature_name(self) -> List[str]:
        return self._feature_names()

    def subset(self, used_indices: _SequenceT[int],
               params: Optional[Dict] = None) -> "Dataset":
        """Row-subset view (ref: basic.py Dataset.subset)."""
        if self.data is None:
            raise LightGBMError(
                "cannot subset a dataset loaded from a binary file "
                "(raw feature values are not stored)")
        idx = np.asarray(used_indices)
        sub = Dataset(
            self.data[idx],
            label=None if self.label is None else np.asarray(self.label)[idx],
            weight=None if self.weight is None else np.asarray(self.weight)[idx],
            init_score=None if self.init_score is None
            else np.asarray(self.init_score)[idx],
            feature_name=self.feature_name,
            categorical_feature=self.categorical_feature,
            params=params or self.params,
            reference=self if self._binned is not None else None)
        sub.used_indices = idx
        return sub

    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, params=None) -> "Dataset":
        return Dataset(data, label=label, weight=weight, group=group,
                       init_score=init_score, reference=self,
                       params=params or self.params)

    def save_binary(self, filename) -> "Dataset":
        """Binary serialization of the binned dataset
        (ref: Dataset::SaveBinaryFile dataset.h:710)."""
        self.construct()
        from .io.binary_format import save_dataset_binary
        save_dataset_binary(self, filename)
        return self


class Booster:
    """Training/prediction handle (ref: basic.py:3495)."""

    def __init__(self, params: Optional[Dict[str, Any]] = None,
                 train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None):
        self.params = dict(params or {})
        self.best_iteration = -1
        self.best_score: Dict = {}
        self._loaded: Optional[LoadedModel] = None
        self._gbdt: Optional[GBDT] = None
        self.train_set: Optional[Dataset] = None
        self._valid_sets: List[Dataset] = []
        self._name_valid_sets: List[str] = []
        self._metrics_cache: Dict[int, list] = {}
        self._network_params = None

        if model_file is not None:
            with open(model_file) as fh:
                self._loaded = load_model_from_string(fh.read())
            self._plumb_loaded_predict_params()
            return
        if model_str is not None:
            self._loaded = load_model_from_string(model_str)
            self._plumb_loaded_predict_params()
            return
        if train_set is None:
            raise LightGBMError(
                "Booster requires train_set, model_file or model_str")

        self.config = Config.from_params(self.params)
        from . import log
        log.set_verbosity(self.config.verbosity)
        # warm start by default: arm the persistent XLA compile cache at
        # THE training program boundary (compile_cache.py policy — a
        # second process re-running the same shapes pays ~zero compile
        # seconds). No-op when conftest/env/operator already armed one.
        from .compile_cache import configure as _configure_compile_cache
        _configure_compile_cache(self.config.tpu_compile_cache,
                                 self.config.tpu_compile_cache_dir or None)
        if self.config.trace_output:
            # param twin of LGBM_TPU_TRACE: record spans for this run and
            # write a Chrome trace at exit (obs/trace.py)
            from .obs.trace import global_tracer
            global_tracer.enable(path=self.config.trace_output)
        train_set.params = {**train_set.params, **self.params}
        train_set.construct()
        self.train_set = train_set
        objective = create_objective(self.config)
        if objective is None and self.config.objective not in ("none",):
            raise LightGBMError(f"unknown objective {self.config.objective}")
        binned = train_set._binned
        if self.config.tree_learner in ("data", "voting", "feature") or \
                self.config.num_machines > 1 or \
                int(self.params.get("tpu_num_shards", 0) or 0) > 1:
            from .parallel.data_parallel import create_parallel_boosting
            self._gbdt = create_parallel_boosting(self.config, binned,
                                                  objective)
        else:
            self._gbdt = create_boosting(self.config, binned, objective)

    def _plumb_loaded_predict_params(self) -> None:
        """Serving knobs for a loaded (file/string) model: alias-resolve
        the Booster params and hand tpu_predict_chunk / tpu_num_shards
        to the LoadedModel's streaming predict engine."""
        canon = {Config.canonical_key(k): v for k, v in self.params.items()}
        chunk = canon.get("tpu_predict_chunk")
        if chunk:
            self._loaded.predict_chunk = int(chunk)
        shards = int(canon.get("tpu_num_shards", 0) or 0)
        if shards > 1:
            self._loaded.predict_shards = shards

    # ------------------------------------------------------------------
    def _load_init_model(self, init_model) -> "Booster":
        """Continued training from a model file / string / Booster
        (ref: engine.py train init_model; boosting.cpp:74-90)."""
        if isinstance(init_model, Booster):
            loaded = load_model_from_string(init_model.model_to_string())
        elif isinstance(init_model, LoadedModel):
            loaded = init_model
        elif isinstance(init_model, str):
            with open(init_model) as fh:
                loaded = load_model_from_string(fh.read())
        else:
            raise TypeError(
                "init_model must be a Booster, LoadedModel, or filename")
        self._gbdt.init_from_loaded(loaded)
        return self

    # ------------------------------------------------------------------
    def add_valid(self, data: Dataset, name: str) -> "Booster":
        data.reference = data.reference or self.train_set
        data.construct()
        self._valid_sets.append(data)
        self._name_valid_sets.append(name)
        self._gbdt.add_valid(data._binned, data.data)
        return self

    def reset_train_set(self, train_set: Dataset) -> "Booster":
        """Replace the training data, keeping the current model
        (ref: GBDT::ResetTrainingData gbdt.cpp:214 /
        LGBM_BoosterResetTrainingData c_api.cpp:2086). The new data is
        binned against the current mappers and the existing trees'
        scores are replayed onto it."""
        if self._gbdt is None:
            raise LightGBMError(
                "reset_train_set requires a booster built on a Dataset")
        saved = None
        if any(self._gbdt.models):
            saved = load_model_from_string(self.model_to_string())
        train_set.reference = train_set.reference or self.train_set
        train_set.params = {**train_set.params, **self.params}
        train_set.construct()
        self.train_set = train_set
        self._metrics_cache.clear()
        objective = create_objective(self.config)
        binned = train_set._binned
        if self.config.tree_learner in ("data", "voting", "feature") or \
                self.config.num_machines > 1 or \
                int(self.params.get("tpu_num_shards", 0) or 0) > 1:
            from .parallel.data_parallel import create_parallel_boosting
            self._gbdt = create_parallel_boosting(self.config, binned,
                                                  objective)
        else:
            self._gbdt = create_boosting(self.config, binned, objective)
        if saved is not None:
            self._gbdt.init_from_loaded(saved)
        for ds in self._valid_sets:
            self._gbdt.add_valid(ds._binned, ds.data)
        return self

    def update(self, train_set: Optional[Dataset] = None, fobj=None) -> bool:
        """One boosting iteration; True means training should stop
        (ref: basic.py Booster.update -> LGBM_BoosterUpdateOneIter)."""
        if train_set is not None and train_set is not self.train_set:
            self.reset_train_set(train_set)
        self._ensure_network()
        if fobj is not None:
            grad, hess = fobj(self._raw_train_scores(), self.train_set)
            return self._gbdt.train_one_iter(np.asarray(grad),
                                             np.asarray(hess))
        return self._gbdt.train_one_iter()

    def _raw_train_scores(self) -> np.ndarray:
        # score storage may carry padded tail rows when sharded
        s = np.asarray(self._gbdt.scores)[:, :self._gbdt.num_data]
        return s[0] if s.shape[0] == 1 else s.T.reshape(-1)

    def rollback_one_iter(self) -> "Booster":
        self._gbdt.rollback_one_iter()
        return self

    def current_iteration(self) -> int:
        if self._loaded is not None:
            return self._loaded.num_iterations
        return self._gbdt.current_iteration()

    def num_trees(self) -> int:
        if self._loaded is not None:
            return len(self._loaded.trees)
        return self._gbdt.num_trees

    def num_feature(self) -> int:
        if self._loaded is not None:
            return self._loaded.max_feature_idx + 1
        return self.train_set.num_feature()

    def feature_name(self) -> List[str]:
        if self._loaded is not None:
            return self._loaded.feature_names
        return self.train_set.get_feature_name()

    # ------------------------------------------------------------------
    def _metrics_for(self, ds_binned, num_data: int):
        key = id(ds_binned)
        if key not in self._metrics_cache:
            names = self.config.metric or self.config.default_metric()
            ms = create_metrics(self.config, names)
            for m in ms:
                m.init(ds_binned.metadata, num_data)
            self._metrics_cache[key] = ms
        return self._metrics_cache[key]

    def _eval_scores(self, raw: np.ndarray, binned, name: str):
        obj = self._gbdt.objective
        raw2 = raw if raw.ndim == 2 else raw[:, None]
        squeezed = raw2[:, 0] if raw2.shape[1] == 1 else raw2
        prob = obj.convert_output(squeezed) if obj is not None else squeezed
        out = []
        for metric in self._metrics_for(binned, binned.num_data):
            for mname, value, hib in metric.eval(prob, squeezed):
                out.append((name, mname, value, hib))
        return out

    def eval_train(self, feval=None):
        raw = np.asarray(self._gbdt.scores)[:, :self._gbdt.num_data].T
        # [N, K]; padded tail rows (sharded storage) dropped above
        res = self._eval_scores(raw, self.train_set._binned, "training")
        if feval is not None:
            res += _call_feval(feval, raw, self.train_set, "training")
        return res

    def eval_valid(self, feval=None):
        out = []
        for i, (vs, name) in enumerate(zip(self._valid_sets,
                                           self._name_valid_sets)):
            raw = self._gbdt.valid_raw_scores(i)  # [N, K]
            out += self._eval_scores(raw, vs._binned, name)
            if feval is not None:
                out += _call_feval(feval, raw, vs, name)
        return out

    def eval(self, data: Dataset, name: str, feval=None):
        for i, vs in enumerate(self._valid_sets):
            if vs is data:
                raw = self._gbdt.valid_raw_scores(i)
                res = self._eval_scores(raw, vs._binned, name)
                if feval is not None:
                    res += _call_feval(feval, raw, vs, name)
                return res
        raw = self._gbdt.predict_raw(data.data)
        res = self._eval_scores(raw, data.construct()._binned, name)
        if feval is not None:
            res += _call_feval(feval, raw, data, name)
        return res

    # ------------------------------------------------------------------
    def predict(self, data, start_iteration: int = 0, num_iteration: int = -1,
                raw_score: bool = False, pred_leaf: bool = False,
                pred_contrib: bool = False, **kwargs) -> np.ndarray:
        # per-call serving-engine override (alias-aware), e.g.
        # predict(X, tpu_predict_chunk=65536). Every alias is popped by
        # MEMBERSHIP (a falsy value left behind would collide with the
        # explicit kwarg in the sparse-batch recursion below)
        predict_chunk = None
        for key in ("tpu_predict_chunk", "predict_chunk",
                    "predict_chunk_rows"):
            if key in kwargs:
                val = kwargs.pop(key)
                if val and predict_chunk is None:
                    predict_chunk = int(val)
        if _is_sparse(data):
            # tree traversal reads raw feature values: densify in
            # row batches so peak host memory stays bounded
            from .dataset import sparse_row_batches
            if data.shape[0] == 0:
                data = np.zeros(data.shape)
            else:
                outs = [self.predict(b, start_iteration=start_iteration,
                                     num_iteration=num_iteration,
                                     raw_score=raw_score,
                                     pred_leaf=pred_leaf,
                                     pred_contrib=pred_contrib,
                                     tpu_predict_chunk=predict_chunk,
                                     **kwargs)
                        for b in sparse_row_batches(data)]
                return np.concatenate(outs, axis=0)
        data = np.asarray(data, dtype=np.float64)
        if data.ndim == 1:
            data = data.reshape(1, -1)
        if self._loaded is not None:
            if pred_contrib:
                from .shap import loaded_pred_contrib
                return loaded_pred_contrib(self._loaded, data,
                                           start_iteration, num_iteration,
                                           predict_chunk=predict_chunk)
            if pred_leaf:
                return self._loaded.predict_leaf(
                    data, start_iteration=start_iteration,
                    num_iteration=num_iteration)
            return self._loaded.predict(data, raw_score=raw_score,
                                        start_iteration=start_iteration,
                                        num_iteration=num_iteration,
                                        predict_chunk=predict_chunk)
        if num_iteration < 0 and self.best_iteration > 0:
            num_iteration = self.best_iteration
        return self._gbdt.predict(data, raw_score=raw_score,
                                  start_iteration=start_iteration,
                                  num_iteration=num_iteration,
                                  pred_leaf=pred_leaf,
                                  pred_contrib=pred_contrib,
                                  predict_chunk=predict_chunk)

    def refit(self, data, label, decay_rate: float = 0.9, weight=None,
              **kwargs):
        """(ref: Booster.refit basic.py; GBDT::RefitTree gbdt.cpp:267)"""
        from .refit import refit_booster
        return refit_booster(self, data, label, decay_rate, weight=weight)

    # ------------------------------------------------------------------
    def model_to_string(self, num_iteration: int = -1,
                        start_iteration: int = 0,
                        importance_type: str = "split") -> str:
        if self._loaded is not None:
            from .model_io import loaded_model_to_string
            return loaded_model_to_string(self._loaded, num_iteration,
                                          start_iteration, importance_type)
        return save_model_to_string(self._gbdt, num_iteration,
                                    start_iteration, importance_type)

    def save_model(self, filename, num_iteration: int = -1,
                   start_iteration: int = 0,
                   importance_type: str = "split") -> "Booster":
        with open(filename, "w") as fh:
            fh.write(self.model_to_string(num_iteration, start_iteration,
                                          importance_type))
        return self

    def dump_model(self, num_iteration: int = -1, start_iteration: int = 0
                   ) -> dict:
        return dump_model_to_json(self._gbdt, num_iteration, start_iteration)

    # ------------------------------------------------------------------
    def attr(self, key: str):
        """Booster attribute by name, or None (ref: Booster.attr
        python-package basic.py / LGBM_BoosterGetAttr)."""
        return getattr(self, "_attr", {}).get(key)

    def set_attr(self, **kwargs) -> "Booster":
        """Set string attributes; a None value deletes the key
        (ref: Booster.set_attr / LGBM_BoosterSetAttr)."""
        store = getattr(self, "_attr", None)
        if store is None:
            store = self._attr = {}
        for key, value in kwargs.items():
            if value is None:
                store.pop(key, None)
            else:
                if not isinstance(value, str):
                    raise LightGBMError(
                        "Only string values are accepted as attributes")
                store[key] = value
        return self

    def trees_to_dataframe(self):
        """The fitted model as one pandas row per node, with the
        reference's column schema (ref: Booster.trees_to_dataframe,
        python-package basic.py:3775)."""
        import pandas as pd

        if self.num_trees() == 0:
            raise LightGBMError(
                "There are no trees in this Booster and thus nothing "
                "to parse")
        feature_names = self.feature_name()
        rows = []

        def walk(node, tree_index, depth, parent):
            is_split = "split_index" in node
            node_id = (f"{tree_index}-S{node['split_index']}" if is_split
                       else f"{tree_index}-L{node.get('leaf_index', 0)}")
            rec = {
                "tree_index": tree_index,
                "node_depth": depth,
                "node_index": node_id,
                "left_child": None,
                "right_child": None,
                "parent_index": parent,
                "split_feature": None,
                "split_gain": np.nan,
                "threshold": np.nan,
                "decision_type": None,
                "missing_direction": None,
                "missing_type": None,
                "value": node.get("leaf_value"),
                "weight": node.get("leaf_weight"),
                "count": node.get("leaf_count"),
            }
            if is_split:
                f = node["split_feature"]
                rec.update(
                    split_feature=(feature_names[f]
                                   if f < len(feature_names)
                                   else f"Column_{f}"),
                    split_gain=node["split_gain"],
                    threshold=node["threshold"],
                    decision_type=node["decision_type"],
                    missing_direction=("left" if node["default_left"]
                                       else "right"),
                    missing_type=node["missing_type"],
                    value=node["internal_value"],
                    weight=node["internal_weight"],
                    count=node["internal_count"],
                )
            rows.append(rec)
            if is_split:
                left, right = node["left_child"], node["right_child"]

                def child_id(c):
                    return (f"{tree_index}-S{c['split_index']}"
                            if "split_index" in c
                            else f"{tree_index}-L{c.get('leaf_index', 0)}")

                rec["left_child"] = child_id(left)
                rec["right_child"] = child_id(right)
                walk(left, tree_index, depth + 1, node_id)
                walk(right, tree_index, depth + 1, node_id)

        if self._loaded is not None:
            # text-loaded models carry Tree objects directly
            tree_infos = [t.to_json(i)
                          for i, t in enumerate(self._loaded.trees)]
        else:
            tree_infos = self.dump_model()["tree_info"]
        for t in tree_infos:
            walk(t["tree_structure"], t["tree_index"], 1, None)
        return pd.DataFrame(rows)

    def feature_importance(self, importance_type: str = "split",
                           iteration: int = -1) -> np.ndarray:
        if self._loaded is not None:
            # text-loaded model: accumulate over parsed trees, with the
            # same dtype/semantics as the live path
            # (ref: GBDT::FeatureImportance gbdt.cpp)
            n = self._loaded.max_feature_idx + 1
            out = np.zeros(n, np.float64)
            trees = self._loaded.trees
            # iteration <= 0 means all trees (ref: gbdt_model_text.cpp
            # FeatureImportance 'if (num_iteration > 0)')
            if iteration > 0:
                trees = trees[:iteration *
                              max(self._loaded.num_tree_per_iteration, 1)]
            for tree in trees:
                for i in range(tree.num_internal):
                    # the reference only counts splits with positive gain
                    # (ref: GBDT::FeatureImportance gbdt_model_text.cpp)
                    if float(tree.split_gain[i]) <= 0.0:
                        continue
                    f = int(tree.split_feature[i])
                    if importance_type == "split":
                        out[f] += 1.0
                    else:
                        out[f] += float(tree.split_gain[i])
            return out
        return self._gbdt.feature_importance(importance_type, iteration)

    def reset_parameter(self, params: Dict[str, Any]) -> "Booster":
        self.params.update(params)
        self.config.update(params)
        self._gbdt.config = self.config
        from .ops.split import SplitHyperParams
        self._gbdt.hp = SplitHyperParams.from_config(self.config)
        self._gbdt.shrinkage_rate = self.config.learning_rate
        return self

    def free_dataset(self) -> "Booster":
        return self

    def free_network(self) -> "Booster":
        self._network_params = None
        return self

    def set_network(self, machines, local_listen_port=12400,
                    listen_time_out=120, num_machines=1) -> "Booster":
        """Join the machine list's distributed runtime. The reference's
        TCP collectives become jax.distributed + XLA collectives: the
        first machine is the coordinator and this process's rank comes
        from the LGBM_TPU_RANK env var (each reference worker likewise
        locates itself in mlist.txt)."""
        from . import log
        from .parallel import distributed as dist
        if (not num_machines or int(num_machines) <= 1) and machines:
            # reference configs often leave num_machines at 1 and rely
            # on the machine list length
            num_machines = len(dist.parse_machine_list(machines))
        # Like the reference's SetNetwork, only RECORD the config here;
        # joining the runtime blocks until all ranks arrive, so it is
        # deferred to the first update() (see _ensure_network) instead of
        # hanging API-compat callers at set_network time.
        self._network_params = dict(machines=machines,
                                    local_listen_port=local_listen_port,
                                    listen_time_out=listen_time_out,
                                    num_machines=num_machines)
        import os
        if (num_machines and int(num_machines) > 1
                and os.environ.get("LGBM_TPU_RANK") is None
                and not dist.is_initialized()):
            log.warning(
                "set_network: machine list given but LGBM_TPU_RANK is "
                "unset — cannot determine this process's rank, so the "
                "distributed runtime will NOT be initialized; set "
                "LGBM_TPU_RANK or call parallel.distributed."
                "init_distributed(process_id=...) directly")
        return self

    def _ensure_network(self) -> None:
        """Join the recorded machine list at training start (deferred
        from set_network; no-op when the runtime is already up)."""
        from . import log
        from .parallel import distributed as dist
        np_ = self._network_params
        if not np_ or dist.is_initialized():
            return
        num_machines = np_.get("num_machines") or 1
        if int(num_machines) <= 1:
            return
        import os
        if os.environ.get("LGBM_TPU_RANK") is None:
            return  # already warned at set_network time
        timeout_min = np_.get("listen_time_out")
        try:
            dist.init_distributed(
                machines=np_["machines"],
                num_processes=int(num_machines),
                # listen_time_out follows the reference's unit (minutes,
                # config.h time_out); jax wants seconds
                initialization_timeout=(None if timeout_min is None
                                        else float(timeout_min) * 60.0))
        except RuntimeError as exc:
            if "already initialized" in str(exc).lower():
                # the caller brought up the JAX runtime themselves — fine
                log.warning(f"set_network: distributed init skipped: {exc}")
            else:
                raise

    def shuffle_models(self, start_iteration=0, end_iteration=-1) -> "Booster":
        models = self._gbdt.models
        end = len(models) if end_iteration < 0 else end_iteration
        seg = models[start_iteration:end]
        np.random.shuffle(seg)
        self._gbdt.models = models[:start_iteration] + list(seg) + \
            models[end:]
        return self

    def __copy__(self):
        return self

    def __deepcopy__(self, memo):
        model_str = self.model_to_string()
        return Booster(model_str=model_str)


def _call_feval(feval, raw, dataset, name):
    out = []
    fevals = feval if isinstance(feval, (list, tuple)) else [feval]
    preds = raw[:, 0] if raw.ndim == 2 and raw.shape[1] == 1 else raw
    for f in fevals:
        res = f(preds, dataset)
        if isinstance(res, list):
            for mname, value, hib in res:
                out.append((name, mname, value, hib))
        else:
            mname, value, hib = res
            out.append((name, mname, value, hib))
    return out
