"""Boosting loop: GBDT / DART / RF with bagging & GOSS sampling.

TPU-native re-architecture of the reference boosting layer
(ref: src/boosting/gbdt.cpp:60 Init, :353 TrainOneIter, :328
BoostFromAverage; dart.hpp:24; rf.hpp:26; bagging.hpp:15; goss.hpp:19).

The per-iteration pipeline (gradients -> sampling -> tree growth -> score
update) runs as XLA programs on device; tree records stay on device until
the host needs them (model save / prediction / leaf renewal), keeping the
training loop free of per-iteration synchronization — the TPU analog of
keeping boosting_on_gpu_ fully device-resident (gbdt.cpp:111).

Reference order of operations preserved (gbdt.cpp:353-461):
  BoostFromAverage -> gradients -> bagging -> Train -> RenewTreeOutput ->
  Shrinkage -> UpdateScore -> AddBias(first iteration only).
"""

from __future__ import annotations

import contextlib
import functools
import os
import time
from typing import Dict, List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from .config import Config
from .dataset import BinnedDataset
from .learner import grow_tree, grow_tree_waved, replay_tree
from .obs import health as obs_health
from .obs import xla as obs_xla
from .obs.export import global_flusher
from .obs.flightrec import global_flightrec
from .obs.profile import global_profile
from .resilience import faults as faults_mod
from .obs.metrics import global_metrics
from .obs.trace import global_tracer
from .timer import global_timer  # noqa: F401  (compat facade re-export)
from .objectives import ObjectiveFunction, create_objective
from .ops import histogram as hist_ops
from .ops.partition import per_row_lookup
from .ops.split import FeatureMeta, SplitHyperParams, leaf_output
from .tree import Tree

K_EPSILON = 1e-35


def _multi_value(value):
    """Multi-value param -> list of floats, accepting both the Python list
    form and the reference's comma-separated string form
    (ref: config.h multi-value params like monotone_constraints)."""
    if value is None:
        return None
    if isinstance(value, str):
        value = [v for v in value.split(",") if v.strip()]
    vals = [float(v) for v in value]
    return vals if vals else None


def _tree_record_to_host(record) -> Dict[str, np.ndarray]:
    return {k: np.asarray(v) for k, v in record._asdict().items()}


def _nonfinite_counts(grad, hess, scores):
    """Traced [3] int32 nonfinite-entry counts of (grad, hess, scores) —
    the per-iteration NaN/Inf sentinel payload (obs/health.py). Pure
    reductions: folding this into a fused program changes none of the
    training math, so models are bit-identical with the sentinel on."""
    def cnt(x):
        if x is None:
            return jnp.int32(0)
        return jnp.sum(~jnp.isfinite(x)).astype(jnp.int32)
    return jnp.stack([cnt(grad), cnt(hess), cnt(scores)])


@jax.named_scope("lgbm/records")
def _stack_class_records(recs):
    """[K] per-class TreeArrays -> one TreeArrays with a leading class
    axis (traced; used inside the fused programs)."""
    if len(recs) == 1:
        return jax.tree_util.tree_map(lambda x: x[None], recs[0])
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *recs)


def _records_to_host(recs):
    """List of per-iteration records -> host arrays with a leading
    iteration axis, in ONE device->host transfer set (a single-element
    list skips the device-side stack entirely)."""
    if len(recs) == 1:
        host = jax.device_get(recs[0])
        return jax.tree_util.tree_map(lambda x: x[None], host)
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *recs)
    return jax.device_get(stacked)


class _ResidentFeed:
    """Bins feed of resident training: the device tensor the booster
    holds; nothing to stage, nothing to account."""

    orchestrated = False

    def take(self, bins_fm):
        return bins_fm

    def dispatched(self) -> None:
        pass

    def done(self, scores) -> None:
        pass


class _SlabFeed:
    """Bins feed of out-of-core training (tpu_stream) over a
    HostSlabBins plan, with the streaming pipeline's accounting. One
    slab: the fused program's bins operand is the staged device copy of
    the whole (packed) bin matrix, uploaded once and cached — the bins
    are immutable, so re-staging identical bytes every iteration would
    only waste link bandwidth, and holding the one copy is exactly the
    memory the model budgeted for the slab pair. The plan degenerates to
    resident behavior with an explicit upload, which is what makes
    single-slab streamed models bit-identical. Several slabs: the
    streamed composition feeds itself (HostSlabBins.feed), wave by
    wave."""

    def __init__(self, plan):
        self._plan = plan
        self._staged = None

    @property
    def orchestrated(self) -> bool:
        return self._plan.n_slabs > 1

    def take(self, bins_fm):
        if self._staged is None:
            self._staged = self._plan.stage_noted(0)
        return self._staged

    def dispatched(self) -> None:
        """Right after the fused program dispatches (async): the overlap
        classifier's count of compute in flight (the cached single-slab
        upload needs no re-stage)."""
        self._plan.stats.note_dispatch()

    def done(self, scores) -> None:
        """End of a streamed iteration: the sync resets the overlap
        classifier's in-flight count so a later pipeline can't inherit
        stale dispatches."""
        t0 = time.perf_counter()
        jax.block_until_ready(scores)
        self._plan.stats.note_block(time.perf_counter() - t0)
        self._plan.stats.iterations_total += 1
        self.publish()

    def publish(self) -> None:
        """Publish the streaming pipeline accounting (always-on meta ->
        bench JSON `stream` field + lgbmtpu_stream_* OpenMetrics)."""
        plan = self._plan
        global_metrics.set_meta("stream", {
            **plan.stats.summary(),
            "slab_rows": int(plan.slab_rows),
            "n_slabs": int(plan.n_slabs),
            "num_data": int(plan.num_data),
            "host_bytes": int(plan.nbytes_host),
        })


class GBDT:
    """Gradient Boosted Decision Trees (ref: src/boosting/gbdt.h:38)."""

    boosting_type = "gbdt"

    def __init__(self, config: Config, train_set: BinnedDataset,
                 objective: Optional[ObjectiveFunction] = None):
        self.config = config
        self.train_set = train_set
        self.objective = objective
        self.num_data = train_set.num_data
        self.num_class = max(config.num_class, 1)
        self.num_tree_per_iteration = (
            objective.num_model_per_iteration if objective is not None
            else self.num_class)
        self.shrinkage_rate = config.learning_rate
        self.iter = 0
        # masked pad rows appended to the row tensors so they divide a
        # device mesh (parallel/data_parallel._pad_and_shard_rows);
        # num_data stays the REAL row count throughout
        self._row_pad = 0
        # host trees (materialized lazily from device records on the fast
        # path; populated directly on the slow path)
        self._host_models: List[List[Tree]] = []
        self._device_records: List = []  # per fast-path iter: TreeArrays [K,...]
        self.init_scores = [0.0] * self.num_tree_per_iteration
        self._init_done = False

        if objective is not None:
            objective.init(train_set.metadata, self.num_data)

        # device-side constants. Bit-packed bin storage (tpu_bin_pack,
        # ops/bin_pack.py): when the bin-id range fits 4-bit nibbles the
        # device tensor ships packed and every histogram/partition
        # consumer unpacks on the fly — the packed bytes are what each
        # of the ~13 per-iteration full-data passes actually reads.
        # Out-of-core streaming (tpu_stream, io/streaming.py): the bin
        # tensor instead stays HOST-resident as section-aligned slabs
        # and `bins_fm` is the HostSlabBins plan — the streamed growers
        # feed it to the device wave-by-wave, double-buffered.
        self._bin_pack_vpb = 1
        self._stream = self._resolve_stream(train_set)
        self._stream_progs: Dict = {}
        self._feed = (_SlabFeed(self._stream) if self._stream is not None
                      else _ResidentFeed())
        if self._stream is not None:
            self.bins_fm = self._stream
            self._bin_pack_vpb = self._stream.vpb
        else:
            packed = self._maybe_pack_bins(train_set)
            if packed is not None:
                self.bins_fm = packed
                self._bin_pack_vpb = packed.vpb
            else:
                self.bins_fm = train_set.device_bins()
        # EFB (ref: dataset.cpp:251): bins_fm is bundled [G, N] storage;
        # the growers decode through this triple (None when unbundled)
        self._bundle = train_set.device_bundle()
        self._num_bundle_bins = (train_set.bundle_info.num_bundle_bins
                                 if train_set.bundle_info is not None else 0)
        # sparse row-wise COO storage (multi_val_sparse_bin.hpp analog):
        # bins_fm is then a SparseBins pytree, histogram passes run
        # O(nnz) segment-sums
        self._sparse_shape = None
        self._quant_enabled = bool(config.use_quantized_grad)
        if train_set.sparse_coo is not None:
            self._sparse_shape = (train_set.num_features,
                                  train_set.num_data)
            if self._quant_enabled:
                import warnings
                warnings.warn("use_quantized_grad is not supported with "
                              "sparse COO histograms; using f32")
                self._quant_enabled = False
        num_bins, missing, default_bin, is_cat = \
            train_set.feature_meta_arrays()
        mono = np.zeros(train_set.num_features, np.int8)
        mc_vals = _multi_value(config.monotone_constraints)
        if mc_vals is not None:
            mc = np.asarray(mc_vals, np.int8)
            for j, col in enumerate(train_set.used_features):
                if col < len(mc):
                    mono[j] = mc[col]
        penalty = np.ones(train_set.num_features, np.float32)
        fc_vals = _multi_value(config.feature_contri)
        if fc_vals is not None:
            fc = np.asarray(fc_vals, np.float32)
            for j, col in enumerate(train_set.used_features):
                if col < len(fc):
                    penalty[j] = fc[col]

        # CEGB per-feature penalties (ref: cost_effective_gradient_boosting
        # .hpp DeltaGain). Coupled penalties are charged on a feature's
        # first use in the model; the used-set is refreshed between
        # iterations (the reference updates mid-tree). Lazy penalties are
        # charged per row in the leaf (upper bound of the reference's
        # per-(row, feature) first-query tracking).
        def _per_feature(cfg_list):
            out = np.zeros(train_set.num_features, np.float32)
            vals = _multi_value(cfg_list)
            if vals is not None:
                arr = np.asarray(vals, np.float32)
                for j, col in enumerate(train_set.used_features):
                    if col < len(arr):
                        out[j] = arr[col]
            return out
        self._cegb_coupled = _per_feature(config.cegb_penalty_feature_coupled)
        self._cegb_lazy = _per_feature(config.cegb_penalty_feature_lazy)
        self._cegb_used = np.zeros(train_set.num_features, bool)
        self._has_cegb_coupled = bool(np.any(self._cegb_coupled != 0))

        self.feature_meta = FeatureMeta(
            num_bins=jnp.asarray(num_bins),
            missing_type=jnp.asarray(missing),
            default_bin=jnp.asarray(default_bin),
            is_categorical=jnp.asarray(is_cat),
            monotone=jnp.asarray(mono),
            penalty=jnp.asarray(penalty),
            cegb_feat=jnp.asarray(
                config.cegb_tradeoff * self._cegb_coupled),
            cegb_lazy=jnp.asarray(config.cegb_tradeoff * self._cegb_lazy),
        )
        self.hp = SplitHyperParams.from_config(config)
        self.max_depth = jnp.asarray(config.max_depth, jnp.int32)
        self._static = dict(
            num_leaves=int(config.num_leaves),
            max_bins=int(train_set.max_bins),
            # intermediate/advanced monotone methods: exact pairwise
            # leaf-box bounds (split.compute_box_bounds) replace the
            # basic midpoint propagation
            mono_pairwise=bool(
                np.any(mono != 0)
                and str(config.monotone_constraints_method)
                in ("intermediate", "advanced")),
        )
        self._forced = self._parse_forced_splits()
        self._interaction_groups = self._parse_interaction_constraints()

        # scores [K, N] on device (ScoreUpdater analog, score_updater.hpp:22)
        scores = np.zeros((self.num_tree_per_iteration, self.num_data),
                          np.float32)
        meta_init = train_set.metadata.init_score
        self._has_init_score = meta_init is not None
        if self._has_init_score:
            init = np.asarray(meta_init, np.float64)
            if init.size == self.num_data * self.num_tree_per_iteration:
                scores += init.reshape(self.num_tree_per_iteration,
                                       self.num_data, order="C").astype(
                    np.float32)
            else:
                scores += init.reshape(1, -1).astype(np.float32)
        self.scores = jnp.asarray(scores)

        # per-iteration device records not yet materialized into host Trees
        self._pending: List[List] = []  # [(record, row_leaf), ...] per iter
        self._rng = np.random.RandomState(config.seed)
        self._feature_rng = np.random.RandomState(config.feature_fraction_seed)
        self._bagging_key = jax.random.PRNGKey(config.bagging_seed)
        self._sample_mask = jnp.ones(self.num_data, jnp.float32)
        self._grad_scale = None  # GOSS amplification, set per iter

        # training-health sentinels (obs/health.py; tpu_health knob).
        # Resolved BEFORE the grower build: the fused programs emit the
        # sentinel outputs only when armed, so the knob is a build-time
        # program-shape decision (off = byte-identical programs).
        mode = str(config.tpu_health).lower()
        if mode in ("off", "0", "false", "none", ""):
            mode = "off"
        elif mode in ("warn", "warning"):
            mode = "warn"
        elif mode in ("error", "raise", "strict"):
            mode = "error"
        else:
            raise ValueError(
                f"tpu_health={config.tpu_health!r} is not one of "
                "off/warn/error")
        self._health_mode = mode
        self._health_armed = mode != "off"
        self._health_every = max(int(config.tpu_health_every), 1)
        self._health_tick = 0

        # device-time profiling window (obs/profile.py; tpu_profile
        # knob; LGBM_TPU_PROFILE env overrides for driver-side arming)
        pmode = str(os.environ.get("LGBM_TPU_PROFILE", "")
                    or config.tpu_profile).lower()
        if pmode in ("off", "0", "false", "none", ""):
            pmode = "off"
        elif pmode not in ("window", "bench"):
            raise ValueError(
                f"tpu_profile={config.tpu_profile!r} is not one of "
                "off/window/bench")
        self._profile_mode = pmode
        self._profile_left = max(int(config.tpu_profile_window), 1)
        self._profile_started = False
        self._health_vec = None           # device [3] nonfinite counts
        self._health_pending_record = None  # slow-path replicated record

        # valid-set state precedes _build_grow: the memory model it
        # publishes accounts registered valid sets
        self._valid_sets: List = []
        self._valid_scores: List[np.ndarray] = []
        # grown-tree jit (shared across iterations; one XLA program per tree)
        self._build_grow(hist_ops.resolve_impl(config.tpu_hist_impl))
        # slow-path twin of the fused program's score update: the
        # multiply at [L], the lookup (per_row_lookup: selects over the
        # L values, no row-sized gather) and the add must live in ONE
        # program and take the fused iteration's shape, so XLA makes the
        # same FMA-contraction choice in both — split across two jits
        # the add rounds separately and the paths drift by one ulp,
        # which flips sign-function gradients (L1 family) on rows
        # sitting at score == label
        self._update_score_shrunk = jax.jit(
            lambda score, lv, lr, row_leaf:
            score + per_row_lookup(lv * lr, row_leaf))

    def _maybe_pack_bins(self, binned):
        """Bit-packed device bins for `binned`, or None when ineligible
        (knob off, bins too wide, EFB/COO storage, or a sharded layout —
        the mesh paths shard raw rows)."""
        cfg = self.config
        if str(cfg.tpu_bin_pack) in ("off", "0", "false", "False"):
            return None
        if cfg.tree_learner != "serial" or int(cfg.tpu_num_shards or 0) > 1:
            return None
        if binned.sparse_coo is not None or binned.bundle_info is not None:
            return None
        from .ops import bin_pack as bp
        if bp.pack_vpb(int(binned.max_bins)) == 1:
            return None
        # the host's pack and the upload, counted whether or not the
        # tracer is on: one record a packed matrix under
        # global_metrics.meta["bin_pack"]
        t = time.perf_counter()
        with global_tracer.span("data/pack_bins"):
            raw = np.asarray(binned.bins_fm)
            packed = bp.to_device(bp.pack_bins_host(raw,
                                                    int(binned.max_bins)))
            jax.block_until_ready(packed.data)
        global_metrics.meta.setdefault("bin_pack", []).append({
            "seconds": time.perf_counter() - t, "rows": packed.num_data,
            "features": int(raw.shape[0]), "vpb": packed.vpb,
            "section": packed.section, "bytes_raw": int(raw.nbytes),
            "bytes_packed": packed.nbytes})
        return packed

    def _stream_ineligible(self, train_set) -> Optional[str]:
        """Why out-of-core streaming cannot serve this configuration,
        or None when it can: the shared config-level gate list
        (obs/memory.stream_config_ineligible — the same predicate
        preflight's recommendation screens with) plus the storage-level
        gates only a constructed dataset knows. The streamed grower is
        the waved grower's twin over dense (optionally packed)
        serial/data-parallel storage; everything else keeps the
        resident paths."""
        if train_set.bundle_info is not None:
            return "EFB-bundled storage is not slab-sliceable"
        if train_set.sparse_coo is not None:
            return "COO sparse storage streams by nnz, not row slabs"
        from .obs import memory as obs_memory
        return obs_memory.stream_config_ineligible(
            self.config, num_class=self.num_tree_per_iteration)

    def _resolve_stream(self, train_set):
        """Resolve ``tpu_stream`` into a ``HostSlabBins`` plan or None.

        auto: stream only when the analytic memory model says resident
        training does NOT fit device capacity (ROADMAP item 1's
        "recommend streaming instead of failing"); capacity unknown
        (CPU without LGBM_TPU_HBM_BYTES) keeps resident. on: force
        streaming, raising on ineligible configurations. The slab size
        comes from ``tpu_stream_slab_rows`` or the memory model's auto
        sizing (obs/memory.stream_auto_slab_rows)."""
        cfg = self.config
        mode = str(cfg.tpu_stream).lower()
        if mode in ("off", "0", "false", "none", ""):
            return None
        if mode in ("on", "true", "1"):
            forced = True
        elif mode == "auto":
            forced = False
        else:
            raise ValueError(f"tpu_stream={cfg.tpu_stream!r} is not one "
                             "of auto/on/off")
        why = self._stream_ineligible(train_set)
        if why is not None:
            if forced:
                raise ValueError(f"tpu_stream=on: {why}")
            return None
        from .obs import memory as obs_memory
        n = int(train_set.num_data)
        f_storage = int(train_set.bins_fm.shape[0])
        kw = obs_memory._resolve_train_knobs(
            cfg, n, f_storage, self.num_tree_per_iteration)
        kw["valid_rows"] = []
        cap = obs_memory.device_capacity_bytes()
        if not forced:
            if str(cfg.tpu_preflight).lower() in ("off", "0", "false",
                                                  "none"):
                return None  # auto-streaming IS a preflight action
            if cap is None:
                return None
            resident = obs_memory.train_memory_model(**kw)
            if resident["peak_bytes"] <= cap:
                return None
            from . import log
            log.warning(
                f"memory preflight: resident training needs "
                f"{resident['peak_bytes'] / 1e9:.2f} GB against "
                f"{cap / 1e9:.2f} GB capacity; streaming host-resident "
                "bins instead (tpu_stream=auto)")
        slab_rows = int(cfg.tpu_stream_slab_rows or 0)
        if slab_rows <= 0:
            # size the slab against the STREAMED working set (gradients
            # materialized, fused components off) — stream_model applies
            # the same overrides preflight's recommendation uses, so the
            # slab the booster builds is the slab preflight projected
            slab_rows = obs_memory.stream_model(kw, cap)["slab_rows"]
        # slab packing mirrors _maybe_pack_bins' gates exactly: the mesh
        # paths (shard_map pallas histogram wrappers) assume raw
        # row-aligned [F, N] storage, so sharded streaming keeps raw
        # slabs just like sharded resident training does
        pack = (str(cfg.tpu_bin_pack) not in ("off", "0", "false",
                                              "False")
                and cfg.tree_learner == "serial"
                and int(cfg.tpu_num_shards or 0) <= 1)
        from .io.streaming import HostSlabBins
        return HostSlabBins(np.asarray(train_set.bins_fm),
                            int(train_set.max_bins), slab_rows,
                            pack=pack)

    def _parse_forced_splits(self):
        """forcedsplits_filename JSON -> (leaf, feature, threshold_bin)
        int32 arrays aligned with scan steps, or None
        (ref: serial_tree_learner.cpp:628 ForceSplits; the JSON tree is
        walked breadth-first, left child keeps the parent's leaf id,
        right child becomes leaf step+1 — the learner's numbering)."""
        fname = self.config.forcedsplits_filename
        if not fname:
            return None
        import json as _json
        with open(fname) as fh:
            spec = _json.load(fh)
        if not spec:
            return None
        L = self._static["num_leaves"]
        ts = self.train_set
        used_map = {c: j for j, c in enumerate(ts.used_features)}
        leaf_arr = np.full(L - 1, -1, np.int32)
        feat_arr = np.full(L - 1, -1, np.int32)
        thr_arr = np.full(L - 1, -1, np.int32)
        cat_arr = np.zeros(L - 1, np.bool_)
        queue = [(0, spec)]
        s = 0
        while queue and s < L - 1:
            leaf, node = queue.pop(0)
            raw_f = int(node["feature"])
            if raw_f not in used_map:
                continue  # feature dropped as trivial — skip this subtree
            j = used_map[raw_f]
            # numerical: value -> upper-bound bin; categorical: the
            # category's bin, split one-vs-rest (ref: ForceSplits
            # serial_tree_learner.cpp:628 -> Dataset::BinThreshold; the
            # forced categorical split is the single-category bitset)
            tbin = int(self.train_set.mappers[j].transform(
                np.asarray([float(node["threshold"])]))[0])
            leaf_arr[s], feat_arr[s], thr_arr[s] = leaf, j, tbin
            cat_arr[s] = ts.mappers[j].is_categorical
            if "left" in node and node["left"]:
                queue.append((leaf, node["left"]))
            if "right" in node and node["right"]:
                queue.append((s + 1, node["right"]))
            s += 1
        if s == 0:
            return None
        return (jnp.asarray(leaf_arr), jnp.asarray(feat_arr),
                jnp.asarray(thr_arr), jnp.asarray(cat_arr))

    def _parse_interaction_constraints(self):
        """interaction_constraints -> [G, F_used] bool array or None
        (ref: config.h interaction_constraints; col_sampler.hpp)."""
        ic = self.config.interaction_constraints
        if not ic:
            return None
        if isinstance(ic, str):
            import json as _json
            ic = _json.loads(f"[{ic}]" if not ic.startswith("[[") else ic)
        groups = [list(map(int, g)) for g in ic]
        if not groups:
            return None
        ts = self.train_set
        used_map = {c: j for j, c in enumerate(ts.used_features)}
        out = np.zeros((len(groups), ts.num_features), bool)
        for gi, g in enumerate(groups):
            for raw_f in g:
                if raw_f in used_map:
                    out[gi, used_map[raw_f]] = True
        return out

    def _build_grow(self, hist_impl: str, shard_mesh=None,
                    hist_reduce: str = "psum") -> None:
        if self.config.deterministic_hist:
            # Kahan-compensated accumulation lives on the XLA path; the
            # pallas kernels keep their own (non-compensated) order
            hist_impl = "xla"
        self._hist_impl = hist_impl
        self._shard_mesh = shard_mesh
        self._hist_reduce = hist_reduce if shard_mesh is not None else "psum"
        self._has_categorical = any(
            m.is_categorical for m in self.train_set.mappers)
        # per-node randomness (extra-trees thresholds, by-node feature
        # sampling; ref: config.h extra_trees, feature_fraction_bynode)
        self._use_node_rand = (self.config.extra_trees or
                               self.config.feature_fraction_bynode < 1.0)
        self._extra_key = jax.random.PRNGKey(self.config.extra_seed)
        self._fused_grad_fn = self._resolve_fused_grad()
        if self._stream is not None:
            # out-of-core streaming: the grower is host-orchestrated
            # over HostSlabBins; the slow path's `self._grow` becomes
            # the streamed adapter (same call signature, bins argument
            # carries the plan), and the fast path takes its bins from
            # the slab feed
            self._stream.mesh = shard_mesh or getattr(self, "mesh", None)
            self._stream_grower = self._make_stream_grower(hist_impl)
            self._grow = self._stream_grow_slow
        else:
            self._grow = obs_xla.instrumented_jit(
                "boosting/grow", self._grow_partial(), phase="grow")
        self._stream_progs = {}
        self._fused = None
        self._record_lrs: List[float] = []
        self._valid_bins: List = []  # device bins per valid set (fast path)
        self._note_hist_traffic()
        self._note_collective_traffic()
        self._note_memory_model()
        self._note_bin_occupancy()

    def _resolve_fused_grad(self):
        """The objective's pointwise gradient fn when the fused
        gradient/histogram wave applies (tpu_fused_grad), else None.
        Requires the waved single-output path with plain pre-computed
        sampling: GOSS reweights by |g| and quantization re-encodes gh,
        so both keep the materialized-gradient path."""
        cfg = self.config
        if str(cfg.tpu_fused_grad) in ("off", "0", "false", "False"):
            return None
        if self._stream is not None:
            # the streamed prep program materializes gradients (the
            # slab passes consume a resident ghT operand)
            return None
        if not self._use_waved() or self.num_tree_per_iteration != 1:
            return None
        if self._quant_enabled or cfg.data_sample_strategy == "goss":
            return None
        if self._sparse_shape is not None or self.objective is None:
            return None
        return self.objective.pointwise_grad_fn()

    def _resolved_hist_shape(self) -> Dict:
        """The booster's ACTUAL resolved histogram-pass shape/knobs —
        the single source both driver-visible cost models (the traffic
        model and the peak-memory model) consume, so they can never
        desynchronize on e.g. the quantization gate."""
        waved = self._use_waved()
        return dict(
            num_data=int(self.num_data),
            storage_features=int(self.train_set.bins_fm.shape[0]),
            max_bins=int(self._num_bundle_bins
                         or self._static["max_bins"]),
            num_leaves=self._static["num_leaves"],
            wave_max=max(self._resolved_wave_max(), 1),
            waved=waved,
            quant_int8=(self._quant_enabled and waved and
                        int(self.config.num_grad_quant_bins) <= 126),
        )

    def _note_hist_traffic(self) -> None:
        """Publish the static per-iteration histogram traffic model (and
        its unpacked / no-subtraction / unfused oracle) through
        obs.metrics — always-on meta, folded into bench.py's JSON line
        and checked by tools/check_perf_gate.py."""
        if self._sparse_shape is not None:
            return
        from .learner import hist_traffic_model
        kw = self._resolved_hist_shape()
        quant_int8 = kw.pop("quant_int8")
        actual = hist_traffic_model(
            **kw, pack_vpb=self._bin_pack_vpb,
            gh_read_bytes=3 if quant_int8 else 12,
            subtract=bool(self.config.tpu_wave_subtract),
            fused_grad=self._fused_grad_fn is not None)
        # oracle: unpacked f32 ghT, standalone gradient pass, and the
        # non-subtraction-aware schedule (both children built per split)
        oracle = hist_traffic_model(**kw, pack_vpb=1, gh_read_bytes=12,
                                    subtract=False, fused_grad=False)
        global_metrics.set_meta("hist_traffic", actual)
        global_metrics.set_meta("hist_traffic_oracle", oracle)
        global_metrics.set_meta("hist_bytes_per_iter",
                                actual["hist_bytes_per_iter"])
        global_metrics.set_meta(
            "hist_bytes_reduction",
            round(oracle["hist_bytes_per_iter"]
                  / max(actual["hist_bytes_per_iter"], 1), 4))

    # trees whose passes `hist_live_rows` keeps (the newest)
    _LIVE_ROWS_KEPT = 8

    def _note_hist_live_rows(self, rec) -> None:
        """While a tracer session is live (the span tracer, or a
        profiler session), publish what the histogram passes of a tree
        multiplied (learner.hist_live_rows: live rows and K-sub-tiles a
        pass): under ``global_metrics.meta["hist_live_rows"]``, the
        newest trees, and as the args of a ``hist`` program span
        (``lgbm/hist``). Computed from the tree's arrays as they reach
        the host anyway: nothing is fetched from the device for it."""
        if not self._use_waved() or self._sparse_shape is not None:
            return
        from .obs import trace as obs_trace
        if not (global_tracer.enabled
                or obs_trace._annotation().is_enabled()):
            return
        from .learner import hist_live_rows
        kw = self._resolved_hist_shape()
        step = (global_metrics.meta.get("hist_geometry") or [{}])[-1]
        passes = hist_live_rows(
            rec, num_data=kw["num_data"], num_leaves=kw["num_leaves"],
            wave_max=kw["wave_max"],
            subtract=bool(self.config.tpu_wave_subtract),
            row_chunk=step.get("row_chunk", 0),
            k_tile=step.get("k_tile", 0), rows_padded=step.get("rows", 0),
            squeeze_stage=step.get("squeeze_stage", 0))
        kept = global_metrics.meta.setdefault("hist_live_rows", [])
        kept.append(passes)
        del kept[:-self._LIVE_ROWS_KEPT]
        with global_tracer.span("hist", args={"live_rows": passes}):
            pass

    def _note_collective_traffic(self) -> None:
        """Publish the static per-iteration COLLECTIVE traffic model —
        the interconnect counterpart of ``_note_hist_traffic`` for mesh
        training (ROADMAP item 3's driver-visible counter for the
        reduce-scatter learner). Always computes the psum oracle next
        to the resolved mode so ``collective_reduction`` prices what
        ``tpu_hist_reduce=scatter`` saves: ~W-fold fewer bytes on the
        wire per iteration at equal models."""
        mesh = getattr(self, "_shard_mesh", None)
        if mesh is None or self._sparse_shape is not None:
            return
        from .learner import collective_traffic_model
        shape = self._resolved_hist_shape()
        axes = tuple(mesh.axis_names)
        width = int(mesh.shape[axes[-1]])
        dcn = int(mesh.size) // max(width, 1)
        reduction = getattr(self, "_hist_reduce", "psum")
        if self._bundle is not None:
            reduction = "psum"  # the learner demotes bundled storage
        kw = dict(num_features=int(self.train_set.num_features),
                  max_bins=int(self._static["max_bins"]),
                  num_leaves=shape["num_leaves"],
                  wave_max=shape["wave_max"], width=width, dcn=dcn,
                  subtract=bool(self.config.tpu_wave_subtract),
                  waved=shape["waved"])
        actual = collective_traffic_model(reduction=reduction, **kw)
        oracle = collective_traffic_model(reduction="psum", **kw)
        global_metrics.set_meta("collective_traffic", actual)
        global_metrics.set_meta("collective_traffic_psum", oracle)
        global_metrics.set_meta("collective_bytes_per_iter",
                                actual["collective_bytes_per_iter"])
        global_metrics.set_meta("collective_reduction", round(
            oracle["collective_bytes_per_iter"]
            / max(actual["collective_bytes_per_iter"], 1), 4))

    def _memory_model_kwargs(self) -> Dict:
        """The analytic peak-HBM model's kwargs with every knob RESOLVED
        the way this booster actually resolved it (pack factor, fused /
        quantized state, wave mode, mesh size) — obs/memory.py's
        ``preflight`` derives the same from a raw config for the
        before-any-allocation path; this is the ground truth after."""
        cfg = self.config
        shape = self._resolved_hist_shape()
        fused = self._fused_grad_fn is not None
        mesh = getattr(self, "_shard_mesh", None)
        return dict(
            num_data=shape["num_data"],
            num_features=shape["storage_features"],
            max_bins=shape["max_bins"],
            num_leaves=shape["num_leaves"],
            num_class=self.num_tree_per_iteration,
            num_iterations=int(cfg.num_iterations),
            pack_vpb=int(self._bin_pack_vpb),
            quantized=shape["quant_int8"],
            fused_grad=fused,
            kernel_fused=fused and self._hist_impl == "pallas",
            waved=shape["waved"],
            wave_max=shape["wave_max"],
            num_shards=int(mesh.size) if mesh is not None else 1,
            has_weight=self.train_set.metadata.weight is not None,
            valid_rows=[vs.num_data for vs, _ in self._valid_sets],
            stream_slab_rows=(self._stream.slab_rows
                              if self._stream is not None else 0),
        )

    def _note_memory_model(self) -> None:
        """Publish the analytic peak-HBM model through obs.metrics
        (always-on meta -> bench.py JSON -> tools/check_perf_gate.py
        ceiling) and run the capacity preflight: predicted peak vs
        device capacity, warning (tpu_preflight=warn, the default) or
        raising (=error) with concrete knob recommendations instead of
        OOMing mid-run. Capacity is unknown on CPU (no memory_stats),
        so the check is silent there unless LGBM_TPU_HBM_BYTES is set."""
        if self._sparse_shape is not None:
            return  # COO working sets are nnz-shaped, not modeled yet
        from .obs import memory as obs_memory
        kw = self._memory_model_kwargs()
        report = obs_memory.train_report(
            kw, stream_ok=self._stream_ineligible(self.train_set) is None)
        global_metrics.set_meta("mem_model", report.model)
        global_metrics.set_meta("mem_peak_model_bytes", report.peak_bytes)
        mode = str(self.config.tpu_preflight).lower()
        if mode in ("off", "0", "false", "none") or report.fits is not False:
            return
        if mode == "error":
            raise obs_memory.PreflightError(
                "memory preflight: " + report.render())
        from . import log
        log.warning("memory preflight: " + report.render())

    def _note_bin_occupancy(self) -> None:
        """Publish static bin-occupancy stats through obs meta (part of
        the obs/health model-quality diagnostics): how much of the
        [F, B] histogram capacity the binning actually uses, and how
        many features binned down to a trivial single bin — a dataset
        whose features collapse to 1-2 bins trains structurally blind
        no matter what the loss curve says. Init-time only, always-on
        like the traffic/memory models."""
        try:
            num_bins, _, _, _ = self.train_set.feature_meta_arrays()
        except Exception:
            return
        nb = np.asarray(num_bins)
        if nb.size == 0:
            return
        cap = max(int(self._static["max_bins"]), 1)
        global_metrics.set_meta("health_bins", {
            "features": int(nb.size),
            "max_bins": cap,
            "mean_bins": round(float(nb.mean()), 2),
            "min_bins": int(nb.min()),
            "bin_occupancy": round(float(nb.mean()) / cap, 4),
            "trivial_features": int(np.sum(nb <= 1)),
        })

    # ------------------------------------------------------------------
    # training-health hooks (obs/health.py; tpu_health knob)
    def _health_end_iteration(self) -> None:
        """Per-iteration health checks, run AFTER the iteration's
        programs were dispatched: read the NaN/Inf sentinel counts
        (one tiny [3] device->host transfer per check period), digest
        replicated state across the mesh (drift sentinel), and refresh
        the telemetry straggler probe. warn mode records + logs; error
        mode raises NonFiniteError / DriftError — the structured alarms
        this layer exists for."""
        self._health_tick += 1
        if self._health_tick % self._health_every:
            self._health_vec = None
            self._health_pending_record = None
            return
        gh = obs_health.global_health
        vec, self._health_vec = self._health_vec, None
        if vec is not None:
            g, h, s = (int(x) for x in np.asarray(vec))
            gh.note_sentinel(self.iter - 1, {"grad": g, "hess": h,
                                             "scores": s},
                             mode=self._health_mode)
        mesh = getattr(self, "_shard_mesh", None)
        if mesh is None:
            mesh = getattr(self, "mesh", None)
        if mesh is not None and getattr(mesh, "size", 1) > 1:
            arrays = self._health_drift_arrays(mesh)
            if arrays:
                gh.check_drift(mesh, arrays, mode=self._health_mode,
                               where=f"iteration {self.iter - 1}")
        if gh.enabled:
            gh.straggler_probe()

    def _health_drift_arrays(self, mesh) -> Dict[str, object]:
        """Replicated device state worth digest-comparing across the
        mesh: the latest tree record (fast-path device records, or the
        slow-path record stashed by _train_one_iter_impl before its
        host transfer collapsed it to one device's copy) plus any
        row-independent state the learner keeps fully replicated
        (feature-parallel scores)."""
        from .parallel.mesh import is_replicated_on
        out: Dict[str, object] = {}
        rec = None
        if self._device_records:
            rec = self._device_records[-1]
        elif self._health_pending_record is not None:
            rec = self._health_pending_record
        self._health_pending_record = None
        if rec is not None and is_replicated_on(mesh, rec.leaf_value):
            out["tree_record"] = {"leaf_value": rec.leaf_value,
                                  "leaf_count": rec.leaf_count,
                                  "num_leaves": rec.num_leaves}
        scores = self.scores
        if isinstance(scores, jax.Array) and is_replicated_on(mesh,
                                                              scores):
            out["scores"] = scores
        return out

    def _resolved_wave_max(self) -> int:
        """tpu_wave_max with -1 (auto) resolved: exact order for softmax
        multiclass (cross-class coupling makes split order
        calibration-critical — see the knob's docstring in config.py),
        waved elsewhere. multiclassova's per-class trees are independent
        binary fits, so OVA keeps the waved default."""
        wm = int(self.config.tpu_wave_max)
        if wm >= 0:
            return wm
        obj_name = getattr(self.objective, "name", "")
        coupled = (self.num_tree_per_iteration > 1
                   and obj_name != "multiclassova")
        return 0 if coupled else 42

    def _use_waved(self) -> bool:
        """Waved growth batches histogram builds of many splits into one
        multi-leaf pass (learner.grow_tree_waved); forced splits need the
        exact per-split grower."""
        return self._resolved_wave_max() > 0 and self._forced is None

    def _grow_fn(self):
        return grow_tree_waved if self._use_waved() else grow_tree

    def _grow_kwargs(self):
        kw = dict(self._static)
        if self._use_waved():
            kw["wave_max"] = self._resolved_wave_max()
            kw["subtract_siblings"] = bool(self.config.tpu_wave_subtract)
        if self._bundle is not None:
            kw["bundle"] = self._bundle
            kw["num_bundle_bins"] = self._num_bundle_bins
        if self._sparse_shape is not None:
            kw["sparse_shape"] = self._sparse_shape
        kw["hist_deterministic"] = bool(self.config.deterministic_hist)
        return kw

    # ------------------------------------------------------------------
    # fast path: one fused XLA program per iteration, zero host round-trips
    # (the TPU analog of boosting_on_gpu_, gbdt.cpp:111 — and beyond: the
    # CUDA learner still syncs once per split, this path not at all)
    @property
    def models(self) -> List[List[Tree]]:
        self._materialize_records()
        return self._host_models

    @models.setter
    def models(self, value) -> None:
        self._host_models = value

    def _fast_path_ok(self, custom_grad) -> bool:
        return self.boosting_type == "gbdt" and \
            self._fast_path_core_ok(custom_grad)

    def _fast_path_core_ok(self, custom_grad) -> bool:
        """Conditions shared by the GBDT and DART fused paths."""
        if custom_grad is not None or self.objective is None:
            return False
        if self._has_cegb_coupled:
            # coupled penalties change per iteration with the used-feature
            # set; needs the host loop
            return False
        if self.config.linear_tree:
            # per-leaf least-squares fits run on host
            return False
        # objectives that renew leaf outputs stay fused when they provide
        # the traced renewal (L1/Huber/Quantile/MAPE percentile renew);
        # only custom objectives with host-only renewal fall back. The
        # traced renewal accumulates weights in f32 (no x64 on TPU), so
        # above 2^24 rows — where unit-weight cumsums stop being exactly
        # representable — the f64 host renewal is used instead.
        renews = type(self.objective).renew_tree_output is not \
            ObjectiveFunction.renew_tree_output
        renews_traced = (type(self.objective).renew_leaves_traced is not
                         ObjectiveFunction.renew_leaves_traced
                         and self.num_data < (1 << 24))
        return not renews or renews_traced

    @jax.named_scope("lgbm/gradient")
    def _grad_fn(self, scores):
        """Traced gradient computation [K, N] (ref: GBDT::Boosting)."""
        obj = self.objective
        if hasattr(obj, "get_gradients_multi"):
            return obj.get_gradients_multi(scores)
        g, h = obj.get_gradients(scores[0])
        return g[None, :], h[None, :]

    def _pad_tail(self, x, value):
        """Pad a per-row vector back to the padded storage length.

        Sharded row storage may carry ``_row_pad`` masked tail rows (see
        DataParallelGBDT._pad_and_shard_rows). Per-row quantities drawn at
        the real length keep their bits (same key, same shape) and the
        tail gets a neutral ``value`` so the padded rows stay inert.
        """
        if self._row_pad == 0:
            return x
        return jnp.pad(x, (0, self._row_pad), constant_values=value)

    def _valid_rows(self, n):
        """Bool [n] marking real rows (False on the padded tail)."""
        return jnp.arange(n) < self.num_data

    @jax.named_scope("lgbm/sample")
    def _sampling_in_jit(self, key, it, prev_mask):
        """Bagging mask (traced; ref: bagging.hpp Bagging)."""
        cfg = self.config
        use_bagging = cfg.bagging_freq > 0 and (
            cfg.bagging_fraction < 1.0 or cfg.pos_bagging_fraction < 1.0
            or cfg.neg_bagging_fraction < 1.0)
        if not use_bagging:
            return prev_mask
        u = self._pad_tail(jax.random.uniform(key, (self.num_data,)), 2.0)
        pos_neg = (cfg.pos_bagging_fraction < 1.0 or
                   cfg.neg_bagging_fraction < 1.0) and \
            self.objective is not None and self.objective.name == "binary"
        if pos_neg:
            is_pos = self.objective.label > 0
            frac = jnp.where(is_pos, cfg.pos_bagging_fraction,
                             cfg.neg_bagging_fraction)
        else:
            frac = cfg.bagging_fraction
        fresh = (u < frac).astype(jnp.float32)
        resample = (it % cfg.bagging_freq) == 0
        return jnp.where(resample, fresh, prev_mask)

    @jax.named_scope("lgbm/sample")
    def _goss_in_jit(self, key, grad, hess):
        """(ref: goss.hpp:60-131)"""
        cfg = self.config
        n = self.num_data
        top_k = max(1, int(n * cfg.top_rate))
        other_k = max(1, int(n * cfg.other_rate))
        score = jnp.abs(grad) * jnp.abs(hess)
        if self._row_pad:
            # padded tail must not claim top-k slots or survive sampling
            score = jnp.where(self._valid_rows(score.shape[0]), score, -1.0)
        thr = -jnp.sort(-score)[top_k - 1]
        is_top = score >= thr
        u = self._pad_tail(jax.random.uniform(key, (n,)), 2.0)
        keep_rest_p = other_k / max(n - top_k, 1)
        is_other = (~is_top) & (u < keep_rest_p)
        amplify = (1.0 - cfg.top_rate) / cfg.other_rate
        mask = (is_top | is_other).astype(jnp.float32)
        scale = jnp.where(is_other, amplify, 1.0)
        return mask, scale

    @jax.named_scope("lgbm/gradient/quantize")
    def _discretize_in_jit(self, key, grad, hess):
        """Gradient quantization with stochastic rounding (traced;
        ref: gradient_discretizer.cpp DiscretizeGradients — g_scale =
        max|g| / (bins/2), h_scale = max|h| / bins (max|h| when the
        hessian is constant), int value = trunc-toward-zero of
        scaled ± uniform). Returns dequantized (grad, hess): the learner's
        f32 histograms then accumulate exact multiples of the scales, the
        same statistics the reference's integer histograms hold."""
        cfg = self.config
        bins = max(int(cfg.num_grad_quant_bins), 2)
        const_h = (self.objective is not None and
                   self.objective.is_constant_hessian)
        abs_g, abs_h = jnp.abs(grad), jnp.abs(hess)
        if self._row_pad:
            valid = self._valid_rows(abs_g.shape[0])
            abs_g = jnp.where(valid, abs_g, 0.0)
            abs_h = jnp.where(valid, abs_h, 0.0)
        max_g = jnp.maximum(jnp.max(abs_g), K_EPSILON)
        max_h = jnp.maximum(jnp.max(abs_h), K_EPSILON)
        g_scale = max_g / (bins // 2)
        h_scale = max_h if const_h else max_h / bins
        if cfg.stochastic_rounding:
            kg, kh = jax.random.split(key)
            if self._row_pad:
                # draw at the REAL length, then pad: threefry draws are
                # shape-dependent, so drawing at the padded length would
                # move every real row's rounding off the serial stream
                u_g = self._pad_tail(
                    jax.random.uniform(kg, (self.num_data,)), 0.5)
                u_h = self._pad_tail(
                    jax.random.uniform(kh, (self.num_data,)), 0.5)
            else:
                u_g = jax.random.uniform(kg, grad.shape)
                u_h = jax.random.uniform(kh, hess.shape)
        else:
            u_g = u_h = 0.5
        g_int = jnp.trunc(grad / g_scale + jnp.sign(grad) * u_g)
        h_int = jnp.trunc(hess / h_scale + u_h)
        quant = (g_int, h_int, g_scale.astype(jnp.float32),
                 h_scale.astype(jnp.float32))
        return g_int * g_scale, h_int * h_scale, quant

    @jax.named_scope("lgbm/renew")
    def _renew_leaves_in_jit(self, rec, row_leaf, true_grad, true_hess,
                             mask):
        """Recompute leaf outputs from the un-quantized gradients
        (ref: gradient_discretizer.hpp RenewIntGradTreeOutput,
        quant_train_renew_leaf)."""
        L = self._static["num_leaves"]
        w = mask
        sums_g = jnp.zeros(L, jnp.float32).at[row_leaf].add(true_grad * w)
        sums_h = jnp.zeros(L, jnp.float32).at[row_leaf].add(true_hess * w)
        renewed = leaf_output(sums_g, sums_h, self.hp)
        new_vals = jnp.where(rec.leaf_count > 0, renewed, rec.leaf_value)
        return rec._replace(leaf_value=new_vals)

    @jax.named_scope("lgbm/sample")
    def _feature_mask_in_jit(self, key):
        cfg = self.config
        f = self.train_set.num_features
        if cfg.feature_fraction >= 1.0:
            return jnp.ones(f, bool)
        k = max(1, int(f * cfg.feature_fraction))
        u = jax.random.uniform(key, (f,))
        thr = jnp.sort(u)[k - 1]
        return u <= thr

    def _obj_state(self):
        return (self.objective.device_state()
                if self.objective is not None else {"arrays": {}, "sub": {}})

    def _grow_partial(self):
        """The grower with all static parameters bound (shared by the GBDT
        and DART fused-program builders)."""
        return functools.partial(self._grow_fn(), **self._grow_kwargs(),
                                 hist_dtype=jnp.float32,
                                 hist_impl=self._hist_impl,
                                 hist_precision=self.config.tpu_hist_precision,
                                 interaction_groups=self._interaction_groups,
                                 has_categorical=self._has_categorical,
                                 extra_trees=bool(self.config.extra_trees),
                                 ff_bynode=float(
                                     self.config.feature_fraction_bynode),
                                 shard_mesh=self._shard_mesh,
                                 hist_reduce=getattr(
                                     self, "_hist_reduce", "psum"))

    # ------------------------------------------------------------------
    # One boosting iteration, traced: a head (bagging mask, gradients), a
    # step per class (sample, grow, tail) and a finish. Each stage is
    # written once, here; the programs below only compose them. The
    # resident composition is one program (_make_fused); the streamed one
    # (tpu_stream) cuts it where arrays must materialize for the
    # host-orchestrated slab grower, each stage WHOLE inside one program
    # so XLA's FMA-contraction choices cannot diverge. Same stages, same
    # RNG folds => streamed models bit-identical to resident ones
    # whenever the slab accumulation itself is exact (single slab, or
    # int8-quantized histograms at any slab count). A booster with
    # device state of its own (DART) overrides stages, never a
    # composition: `hist` are its buffers the tail rewrites, `drop` its
    # other device inputs, `carry` what its head hands to the later
    # stages; GBDT has none of the three.
    _tags = {"fused": "fused_iter", "prep": "prep", "post": "class_post"}
    _n_hist = 0
    _fused_donate = (3, 4, 5)

    @contextlib.contextmanager
    def _traced_obj_state(self, obj_state):
        """The objective's device state swapped in for the length of a
        trace. Every N-sized objective buffer (label, weight, pad arrays)
        reaches a program as an explicit argument this way — closure
        capture would bake them into the HLO as multi-hundred-MB literal
        constants and overflow compilation at Higgs scale."""
        obj = self.objective
        if obj is None:
            yield
            return
        old_state = obj.swap_device_state(obj_state)
        try:
            yield
        finally:
            obj.swap_device_state(old_state)

    def _evolved_obj_state(self):
        """Objective state a trace has updated, as program outputs:
        objectives that evolve device state across iterations (e.g.
        lambdarank position biases) assign tracers to their attributes
        during the trace; collecting the state inside the
        _traced_obj_state frame returns the updates instead of losing
        them at restore. Evolving subset only — returning the full state
        would copy every constant [N] label/weight buffer per iter."""
        return (self.objective.device_state(evolving_only=True)
                if self.objective is not None
                else {"arrays": {}, "sub": {}})

    def _iter_bagging(self, sample_mask, it):
        """The iteration's RNG key (every later fold starts from it) and
        its bagging mask."""
        key = jax.random.fold_in(self._bagging_key, it)
        return key, self._sampling_in_jit(jax.random.fold_in(key, 1), it,
                                          sample_mask)

    def _iter_head(self, scores, sample_mask, it, lr, hist, drop):
        """Stage 1, the head: bagging mask and gradients [K, N]. Returns
        (key, sample_mask, grad_all, hess_all, sentinel operands,
        carry)."""
        key, sample_mask = self._iter_bagging(sample_mask, it)
        sen = (None, None)
        if self._fused_grad_fn is not None:
            # gradients fold into the histogram waves (see _class_grow)
            # — no [N] gradient buffers in this program at all
            grad_all = hess_all = (None,)
            if self._health_armed:
                # NaN/Inf sentinel operands: the same pointwise formula
                # the grower evaluates — XLA CSEs the two, so the fused
                # path stays fused
                with jax.named_scope("lgbm/gradient"):
                    sen = self._fused_grad_fn(
                        scores[0], self.objective.label,
                        self.objective.weight)
        else:
            grad_all, hess_all = self._grad_fn(scores)
            sen = (grad_all, hess_all)
        return key, sample_mask, grad_all, hess_all, sen, None

    def _grad_scores(self, scores, carry):
        """The [K, N] scores the head took the gradients at."""
        return scores

    def _class_sample(self, k, key, grad, hess, sample_mask):
        """Stage 2a: class k's GOSS, gradient quantization and feature
        sampling, on the salts 100+k, 300+k, 200+k of the iteration key.
        Returns (mask, grad, hess, true_grad, true_hess, quant, fmask);
        grad is None where the gradient folds into the kernel."""
        mask = sample_mask
        if self.config.data_sample_strategy == "goss":
            mask, scale = self._goss_in_jit(
                jax.random.fold_in(key, 100 + k), grad, hess)
            grad, hess = grad * scale, hess * scale
        true_grad, true_hess = grad, hess
        quant = None
        if self._quant_enabled:
            grad, hess, quant = self._discretize_in_jit(
                jax.random.fold_in(key, 300 + k), grad, hess)
        fmask = self._feature_mask_in_jit(
            jax.random.fold_in(key, 200 + k))
        return mask, grad, hess, true_grad, true_hess, quant, fmask

    def _use_int8_hist(self) -> bool:
        """int8 integer-histogram passes (the exact grower consumes the
        dequantized f32 values instead). |h_int| <= bins and
        |g_int| <= bins/2+1, so the int8 cast is exact only for
        bins <= 126 — larger settings stay on the f32 hist path."""
        return (self._quant_enabled and self._use_waved() and
                int(self.config.num_grad_quant_bins) <= 126)

    def _node_key(self, k, it):
        return (jax.random.fold_in(
            self._extra_key, it * self.num_tree_per_iteration + k)
            if self._use_node_rand else None)

    def _class_grow(self, grow, bins_fm, k, it, grad, hess, mask, fmask,
                    quant, scores_k):
        """Stage 2b of the resident composition: class k's tree grown
        inside the trace. Returns (rec, row_leaf). (The streamed
        composition grows on the host, StreamTreeGrower.grow, from the
        operands _stream_operands builds of the same `quant`.)"""
        node_key = self._node_key(k, it)
        grow_kw = {}
        if quant is not None and self._use_int8_hist():
            grow_kw["quant"] = quant
        if grad is None:
            # fused gradient/histogram wave (tpu_fused_grad): the head
            # skipped _grad_fn entirely; the grower derives gh from the
            # objective's pointwise formula — in-kernel on the pallas
            # path
            grow_kw["fused_grad"] = (self._fused_grad_fn,
                                     self.objective.label,
                                     self.objective.weight, scores_k)
        return grow(bins_fm, grad, hess, mask, fmask, self.feature_meta,
                    self.hp, self.max_depth, self._forced, node_key,
                    **grow_kw)

    def _class_tail(self, k, rec, row_leaf, scores, scores_k, valid_scores,
                    valid_bins, mask, true_grad, true_hess, lr, carry, hist):
        """Stage 3, the tail: leaf renewal, then the booster's score rule
        on the training scores and on every valid set's replayed leaves
        — ONE program in every composition, so the multiply at [L], the
        per_row_lookup (selects over the L values, no row-sized gather)
        and the add keep their FMA shape. `scores_k` is class k's row of
        _grad_scores (the caller's slice: the fused program takes it
        before the sample stage, where its grower may need it). Returns
        (rec, scores, valid_scores, hist)."""
        if self._quant_enabled and self.config.quant_train_renew_leaf:
            rec = self._renew_leaves_in_jit(rec, row_leaf, true_grad,
                                            true_hess, mask)
        if self.objective is not None:
            with jax.named_scope("lgbm/renew"):
                renewed_lv = self.objective.renew_leaves_traced(
                    rec.leaf_value, row_leaf, scores_k, mask)
                if renewed_lv is not None:
                    rec = rec._replace(leaf_value=jnp.where(
                        rec.num_leaves > 1, renewed_lv, rec.leaf_value))
        scores, leaf_vals, hist = self._score_rule(
            k, rec, row_leaf, scores, lr, carry, hist)
        new_valid = list(valid_scores)
        for vi in range(len(valid_bins)):
            with jax.named_scope("lgbm/valid"):
                vleaf = replay_tree(
                    rec, valid_bins[vi], self.feature_meta, self._bundle,
                    num_data=self._valid_sets[vi][0].num_data)
                new_valid[vi], hist = self._valid_score_rule(
                    k, vi, new_valid[vi], leaf_vals, vleaf, carry, hist)
        return rec, scores, tuple(new_valid), hist

    def _score_rule(self, k, rec, row_leaf, scores, lr, carry, hist):
        """How class k's new tree lands on the training scores. Returns
        (scores, the leaf outputs the valid sets take, hist)."""
        with jax.named_scope("lgbm/score"):
            # 1-leaf trees contribute nothing (the reference stops
            # training instead, gbdt.cpp should_continue)
            leaf_vals = jnp.where(rec.num_leaves > 1,
                                  rec.leaf_value * lr, 0.0)
            scores = scores.at[k].add(per_row_lookup(leaf_vals, row_leaf))
        return scores, leaf_vals, hist

    def _valid_score_rule(self, k, vi, valid, leaf_vals, vleaf, carry,
                          hist):
        """The score rule on valid set vi (traced under lgbm/valid)."""
        return valid.at[k].add(per_row_lookup(leaf_vals, vleaf)), hist

    def _iter_finish(self, carry, drop):
        """Stage 4: what is left of the booster's device state to update
        once every class has its tree; returns it as a tuple."""
        return ()

    def _make_fused(self):
        """Build the one-XLA-program-per-iteration jit: head, sample +
        grow + tail per class, finish. All N-sized device buffers (bin
        tensor, valid bins, objective state) are explicit arguments, see
        _traced_obj_state; `rest` is (*hist, *drop, it, lr)."""
        grow = self._grow_partial()

        def fused(bins_fm, valid_bins, obj_state, scores, sample_mask,
                  valid_scores, *rest):
            hist, drop = rest[:self._n_hist], rest[self._n_hist:-2]
            it, lr = rest[-2:]
            with self._traced_obj_state(obj_state):
                key, sample_mask, grad_all, hess_all, sen, carry = \
                    self._iter_head(scores, sample_mask, it, lr, hist, drop)
                recs = []
                for k in range(self.num_tree_per_iteration):
                    grad, hess, scores_k = (
                        grad_all[k], hess_all[k],
                        self._grad_scores(scores, carry)[k])
                    mask, grad, hess, true_grad, true_hess, quant, fmask = \
                        self._class_sample(k, key, grad, hess, sample_mask)
                    rec, row_leaf = self._class_grow(
                        grow, bins_fm, k, it, grad, hess, mask, fmask,
                        quant, scores_k)
                    rec, scores, valid_scores, hist = self._class_tail(
                        k, rec, row_leaf, scores, scores_k, valid_scores,
                        valid_bins, mask, true_grad, true_hess, lr, carry,
                        hist)
                    recs.append(rec)
                state = self._iter_finish(carry, drop)
                outs = (scores, sample_mask, tuple(valid_scores),
                        _stack_class_records(recs),
                        self._evolved_obj_state(), *hist, *state)
                if self._health_armed:
                    # pure reductions as an EXTRA output: the training
                    # math is untouched, so models are bit-identical
                    # with the sentinel on vs off (tests assert)
                    outs += (_nonfinite_counts(*sen, scores),)
                return outs

        return obs_xla.instrumented_jit(
            "boosting/" + self._tags["fused"], fused, phase="train",
            donate_argnums=self._fused_donate)

    # ------------------------------------------------------------------
    # streamed composition (tpu_stream, several slabs): the stages as
    # programs of their own around the host-orchestrated slab grower
    def _make_stream_grower(self, hist_impl: str):
        from .learner import StreamTreeGrower
        mesh = self._stream.mesh
        if mesh is not None and mesh.size > 1 and hist_impl == "pallas":
            # pallas_call does not auto-partition under GSPMD and the
            # shard_map wrappers assume resident bins; sharded streaming
            # rides the XLA contraction (GSPMD inserts the psum)
            hist_impl = "xla"
        return StreamTreeGrower(
            self._stream,
            num_leaves=self._static["num_leaves"],
            max_bins=self._static["max_bins"],
            num_features=self.train_set.num_features,
            hist_impl=hist_impl,
            hist_precision=self.config.tpu_hist_precision,
            has_categorical=any(m.is_categorical
                                for m in self.train_set.mappers),
            extra_trees=bool(self.config.extra_trees),
            ff_bynode=float(self.config.feature_fraction_bynode),
            wave_max=self._resolved_wave_max(),
            subtract_siblings=bool(self.config.tpu_wave_subtract),
            hist_deterministic=bool(self.config.deterministic_hist))

    def _stream_prog(self, name: str, builder):
        prog = self._stream_progs.get(name)
        if prog is None:
            prog = self._stream_progs[name] = obs_xla.instrumented_jit(
                f"boosting/stream_{name}", builder(), phase="train")
        return prog

    def _make_stream_prep(self):
        """The head as a program."""
        def prep(obj_state, scores, sample_mask, it, lr, hist, drop):
            with self._traced_obj_state(obj_state):
                _key, sample_mask, grad_all, hess_all, _sen, carry = \
                    self._iter_head(scores, sample_mask, it, lr, hist, drop)
                return (sample_mask, grad_all, hess_all,
                        self._evolved_obj_state(), carry)
        return prep

    @jax.named_scope("lgbm/gradient")
    def _stream_operands(self, grad, hess, mask, quant):
        """The slab grower's resident operands: the pre-masked ghT
        histogram operand (int8 when the int8 wave path applies, f32
        otherwise) and its dequantization vector."""
        f32 = jnp.float32
        if self._use_int8_hist():
            g_int, h_int, g_scale, h_scale = quant
            m8 = mask.astype(jnp.int8)
            ghT = jnp.stack([g_int.astype(jnp.int8) * m8,
                             h_int.astype(jnp.int8) * m8, m8], axis=1)
            return ghT, jnp.stack([g_scale, h_scale,
                                   jnp.float32(1.0)]).astype(f32)
        ghT = jnp.stack([grad * mask, hess * mask, mask],
                        axis=1).astype(f32)
        return ghT, jnp.ones((3,), f32)

    def _make_stream_class_prep(self, k: int):
        """Class k's sample stage as a program, with the grower's
        operands."""
        def class_prep(grad, hess, sample_mask, it):
            key = jax.random.fold_in(self._bagging_key, it)
            mask, grad, hess, true_grad, true_hess, quant, fmask = \
                self._class_sample(k, key, grad, hess, sample_mask)
            ghT, hscale = self._stream_operands(grad, hess, mask, quant)
            return (ghT, hscale, fmask, true_grad, true_hess, mask)
        return class_prep

    def _make_stream_class_post(self, k: int):
        """Class k's tail as a program."""
        def class_post(obj_state, rec, row_leaf, scores, valid_scores,
                       valid_bins, mask, true_grad, true_hess, lr, carry,
                       hist):
            with self._traced_obj_state(obj_state):
                return self._class_tail(
                    k, rec, row_leaf, scores,
                    self._grad_scores(scores, carry)[k], valid_scores,
                    valid_bins, mask, true_grad, true_hess, lr, carry, hist)
        return class_post

    def _stream_grow_class(self, k: int, grad_k, hess_k, sample_mask, it):
        """Stage 2 of the streamed composition: class prep program ->
        host-orchestrated slab grower."""
        cp = self._stream_prog(f"class_prep_{k}",
                               lambda: self._make_stream_class_prep(k))
        ghT, hscale, fmask, true_grad, true_hess, mask = cp(
            grad_k, hess_k, sample_mask, it)
        rec, row_leaf = self._stream_grower.grow(
            ghT, hscale, fmask, self.feature_meta, self.hp,
            self.max_depth, self._node_key(k, self.iter))
        return rec, row_leaf, mask, true_grad, true_hess

    def _stream_grow_slow(self, bins_fm, grad, hess, mask, feature_mask,
                          meta, hp, max_depth, forced=None, node_key=None):
        """Slow-path adapter with the resident grower's signature
        (`bins_fm` carries the HostSlabBins plan): custom-gradient /
        RF / host-renewing objectives stream through the same driver
        code they use resident."""
        assert forced is None, \
            "forced splits are gated out of streaming at resolve time"

        @jax.named_scope("lgbm/gradient")
        def basic_prep(grad_, hess_, mask_):
            return jnp.stack([grad_ * mask_, hess_ * mask_, mask_],
                             axis=1).astype(jnp.float32)

        prep = self._stream_prog("slow_prep", lambda: basic_prep)
        return self._stream_grower.grow(
            prep(grad, hess, mask), jnp.ones((3,), jnp.float32),
            feature_mask, meta, hp, max_depth, node_key)

    def _train_one_iter_stream_orchestrated(self, hist, drop, it, lr):
        """The streamed composition, host-orchestrated (bit-identical to
        the resident host/slow path; int8 histograms stay bit-identical
        at any slab count). Returns what the fused program returns past
        the valid scores, less the objective state."""
        prep = self._stream_prog(self._tags["prep"], self._make_stream_prep)
        self._sample_mask, grad_all, hess_all, new_obj_state, carry = prep(
            self._obj_state(), self.scores, self._sample_mask, it, lr, hist,
            drop)
        if self.objective is not None:
            self.objective.swap_device_state(new_obj_state)
        recs = []
        for k in range(self.num_tree_per_iteration):
            rec, row_leaf, mask, true_g, true_h = self._stream_grow_class(
                k, grad_all[k], hess_all[k], self._sample_mask, it)
            post = self._stream_prog(
                f"{self._tags['post']}_{k}",
                lambda k=k: self._make_stream_class_post(k))
            rec, self.scores, valid, hist = post(
                self._obj_state(), rec, row_leaf, self.scores,
                tuple(self._valid_scores), tuple(self._valid_bins), mask,
                true_g, true_h, lr, carry, hist)
            self._valid_scores = list(valid)
            recs.append(rec)
        state = ()
        if drop:
            state = self._stream_prog(
                self._tags["finish"], lambda: self._iter_finish)(carry, drop)
        if self._health_armed:
            sen = self._stream_prog("sentinel", lambda: _nonfinite_counts)
            self._health_vec = sen(grad_all, hess_all, self.scores)
        return (_stack_class_records(recs), *hist, *state)

    # ------------------------------------------------------------------
    # the host driver of both compositions
    def _begin_iteration(self):
        """Host work before the iteration's programs. Returns (what
        _end_iteration takes, hist, drop, lr)."""
        return None, (), (), jnp.float32(self.shrinkage_rate)

    def _end_iteration(self, plan, state) -> None:
        """Host bookkeeping after the iteration's programs; `state` is
        (*hist, *_iter_finish's) as the programs left it."""
        self._record_lrs.append(self.shrinkage_rate)

    def _train_one_iter_fast(self) -> bool:
        """One iteration as device programs, no host round-trip inside.
        The bins come from the feed: resident training and a single-slab
        streamed plan (the whole matrix fits the streaming budget — every
        fits-in-HBM fixture) run the SAME fused program, the second on a
        staged-once upload: bit-identical models by construction;
        multi-slab plans run the streamed composition."""
        self._boost_from_average()
        plan, hist, drop, lr = self._begin_iteration()
        it = jnp.int32(self.iter)
        feed = self._feed
        with global_tracer.span("train/iteration",
                                block=lambda: self.scores):
            if feed.orchestrated:
                recs, *state = self._train_one_iter_stream_orchestrated(
                    hist, drop, it, lr)
            else:
                if self._fused is None:
                    self._fused = self._make_fused()
                out = self._fused(
                    feed.take(self.bins_fm), tuple(self._valid_bins),
                    self._obj_state(), self.scores, self._sample_mask,
                    tuple(self._valid_scores), *hist, *drop, it, lr)
                feed.dispatched()
                if self._health_armed:
                    out, self._health_vec = out[:-1], out[-1]
                (self.scores, self._sample_mask, valid, recs,
                 new_obj_state, *state) = out
                if self.objective is not None:
                    self.objective.swap_device_state(new_obj_state)
                self._valid_scores = list(valid)
            feed.done(self.scores)
        self._device_records.append(recs)
        self._end_iteration(plan, state)
        self.iter += 1
        return False

    def _materialize_records(self) -> None:
        if not self._device_records:
            return
        with global_tracer.span("train/materialize_trees"):
            self._materialize_records_inner()

    def _materialize_records_inner(self) -> None:
        recs, lrs = self._device_records, self._record_lrs
        self._device_records, self._record_lrs = [], []
        host = _records_to_host(recs)
        k_per = self.num_tree_per_iteration
        for i in range(len(recs)):
            first_iter = len(self._host_models) == 0
            iter_trees = []
            for k in range(k_per):
                rec = {f: np.asarray(getattr(host, f)[i][k])
                       for f in host._fields}
                tree = Tree.from_arrays(rec, self.train_set.mappers,
                                        self.train_set.used_features)
                self._note_hist_live_rows(rec)
                if tree.num_leaves > 1:
                    tree.apply_shrinkage(lrs[i])
                    if first_iter and abs(self.init_scores[k]) > K_EPSILON:
                        tree.add_bias(self.init_scores[k])
                else:
                    tree.leaf_value[:] = (self.init_scores[k]
                                          if first_iter else 0.0)
                iter_trees.append(tree)
            self._host_models.append(iter_trees)

    # ------------------------------------------------------------------
    # bagging / GOSS (ref: bagging.hpp:15, goss.hpp:19)
    def _resample_mask(self):
        cfg = self.config
        strategy = cfg.data_sample_strategy
        if strategy == "goss":
            return None  # computed per-iteration with gradients
        use_bagging = cfg.bagging_freq > 0 and cfg.bagging_fraction < 1.0
        pos_neg = (cfg.pos_bagging_fraction < 1.0 or
                   cfg.neg_bagging_fraction < 1.0) and cfg.bagging_freq > 0
        if not use_bagging and not pos_neg:
            return
        if self.iter % cfg.bagging_freq != 0:
            return  # keep previous subset (ref: bagging.hpp Bagging)
        key = jax.random.fold_in(self._bagging_key, self.iter)
        u = jax.random.uniform(key, (self.num_data,))
        if pos_neg and self.objective is not None and \
                self.objective.name == "binary":
            is_pos = jnp.asarray(self.objective.label_np > 0)
            frac = jnp.where(is_pos, cfg.pos_bagging_fraction,
                             cfg.neg_bagging_fraction)
            self._sample_mask = self._pad_tail(
                (u < frac).astype(jnp.float32), 0.0)
        else:
            self._sample_mask = self._pad_tail(
                (u < cfg.bagging_fraction).astype(jnp.float32), 0.0)

    def _goss_mask(self, grad, hess):
        """GOSS on the host loop's own key (ref: goss.hpp:60-131)."""
        return self._goss_in_jit(
            jax.random.fold_in(self._bagging_key, self.iter + (1 << 20)),
            grad, hess)

    def _feature_mask(self):
        cfg = self.config
        f = self.train_set.num_features
        if cfg.feature_fraction >= 1.0:
            return jnp.ones(f, bool)
        k = max(1, int(f * cfg.feature_fraction))
        idx = self._feature_rng.choice(f, k, replace=False)
        mask = np.zeros(f, bool)
        mask[idx] = True
        return jnp.asarray(mask)

    # ------------------------------------------------------------------
    def _sync_init_scores(self, scores: np.ndarray) -> np.ndarray:
        """Hook: distributed learners average per-machine init scores
        (ref: gbdt.cpp:322 Network::GlobalSyncUpByMean)."""
        return scores

    def _boost_from_average(self):
        """(ref: gbdt.cpp:328)"""
        if self._init_done:
            return
        self._init_done = True
        if (self.objective is None or self._has_init_score or
                not self.config.boost_from_average):
            return
        raw = self._sync_init_scores(np.asarray(
            [self.objective.boost_from_score(k)
             for k in range(self.num_tree_per_iteration)], np.float64))
        for k in range(self.num_tree_per_iteration):
            if abs(raw[k]) > K_EPSILON:
                self.init_scores[k] = float(raw[k])
        if any(abs(s) > K_EPSILON for s in self.init_scores):
            init = jnp.asarray(np.asarray(self.init_scores, np.float32)
                               [:, None])
            add = jax.jit(lambda s, i: s + i)  # jit: works on globally
            # sharded multi-host arrays too (eager ops would not)
            self.scores = add(self.scores, init)
            for vi in range(len(self._valid_scores)):
                self._valid_scores[vi] = add(self._valid_scores[vi], init)

    def _gradients(self, custom_grad=None, custom_hess=None):
        """-> grad, hess [K, N] (ref: GBDT::Boosting gbdt.cpp:229)."""
        if custom_grad is not None:
            g = jnp.asarray(np.asarray(custom_grad, np.float32).reshape(
                self.num_tree_per_iteration, self.num_data))
            h = jnp.asarray(np.asarray(custom_hess, np.float32).reshape(
                self.num_tree_per_iteration, self.num_data))
            if self._row_pad:
                pad = ((0, 0), (0, self._row_pad))
                g, h = jnp.pad(g, pad), jnp.pad(h, pad)
            return g, h
        return self._grad_fn(self.scores)

    # ------------------------------------------------------------------
    def train_one_iter(self, custom_grad=None, custom_hess=None) -> bool:
        """Returns True when training should stop (no splittable leaves),
        matching the reference return convention (gbdt.cpp:353).

        With telemetry on (obs.metrics), each call opens a per-iteration
        metrics record; disabled mode is a single attribute check."""
        if global_flusher.armed:  # LGBM_TPU_METRICS_FILE textfile egress
            global_flusher.maybe_flush()
        if faults_mod.global_faults.armed:
            # deterministic fault plan (resilience/faults.py): the
            # slow-shard fault injects its straggler delay at the
            # iteration lifecycle so skew probes see it from ANY entry
            # point (engine / capi / sklearn), not just engine.train
            faults_mod.global_faults.maybe_slow_iteration()
        if global_flightrec.armed:
            # black-box iteration marker (obs/flightrec.py): at the
            # lifecycle so every entry point records it, and BEFORE the
            # work so a crashing iteration is in the dump
            global_flightrec.record("iteration", iteration=int(self.iter),
                                    trees=len(self._device_records)
                                    + len(self._host_models))
        if self._profile_mode != "off":
            self._profile_tick()
        if not global_metrics.enabled:
            if not self._health_armed:
                return self._train_one_iter_impl(custom_grad, custom_hess)
            # tpu_health without full telemetry: the sentinels run, the
            # per-iteration metrics machinery stays off
            stop = self._train_one_iter_impl(custom_grad, custom_hess)
            self._health_end_iteration()
            return stop
        global_metrics.begin_iteration(self.iter)
        n_dev0, n_host0 = len(self._device_records), len(self._host_models)
        self._observe_safely(self._observe_gradient_metrics,
                             custom_grad, custom_hess)
        try:
            stop = self._train_one_iter_impl(custom_grad, custom_hess)
            if self._health_armed:
                # inside the try: a DriftError/NonFiniteError must
                # propagate while the finally still closes the record
                self._health_end_iteration()
            return stop
        finally:
            self._observe_safely(self._observe_tree_metrics, n_dev0, n_host0)
            global_metrics.end_iteration()
            if not self._health_armed and \
                    obs_health.global_health.enabled:
                # telemetry-only runs still get the straggler probe
                obs_health.global_health.straggler_probe()

    def _profile_tick(self) -> None:
        """tpu_profile window lifecycle (obs/profile.py), called at the
        top of each iteration. "window": opens the capture at iteration
        1 — the compile-heavy first iteration would drown the steady
        state — and closes it after tpu_profile_window iterations
        (micro-reruns + roofline happen at close). "bench": opens
        immediately and stays open; the harness reads/stops it."""
        if self._profile_mode == "bench":
            if not global_profile.capturing:
                global_profile.start_window(source="bench")
            return
        if not self._profile_started:
            if self.iter >= 1:
                self._profile_started = True
                global_profile.start_window(source="window")
        elif global_profile.capturing:
            self._profile_left -= 1
            if self._profile_left <= 0:
                global_profile.stop_window()

    @staticmethod
    def _observe_safely(fn, *args) -> None:
        """Telemetry must never kill training (e.g. eager norm ops on
        multi-host sharded arrays can be unsupported)."""
        try:
            fn(*args)
        except Exception as exc:
            from . import log
            log.debug(f"telemetry observation failed: {exc!r}")

    def _observe_gradient_metrics(self, custom_grad, custom_hess) -> None:
        """Gradient norms / clip counts for the iteration about to run
        (telemetry-enabled path only — recomputes gradients from the
        current scores, so it adds one gradient pass)."""
        m = global_metrics
        if custom_grad is not None:
            g = np.asarray(custom_grad, np.float32)
            h = np.asarray(custom_hess, np.float32)
            m.observe("grad_norm", float(np.linalg.norm(g)))
            m.observe("hess_norm", float(np.linalg.norm(h)))
            m.observe("grad_nonfinite", int(np.sum(~np.isfinite(g))))
            return
        if self.objective is None:
            return
        # iteration 0 gradients are taken AFTER the init score lands
        # (idempotent; both train paths apply it before their gradients)
        self._boost_from_average()
        with global_tracer.span("train/telemetry_gradients"):
            g, h = self._grad_fn(self.scores)
            g_abs = jnp.abs(g)
            m.observe("grad_norm", float(jnp.linalg.norm(g)))
            m.observe("hess_norm", float(jnp.linalg.norm(h)))
            m.observe("grad_nonfinite", int(jnp.sum(~jnp.isfinite(g))))
            if self._quant_enabled:
                # entries landing in the extreme quantization bin — the
                # discretizer's saturation count (ref:
                # gradient_discretizer.cpp DiscretizeGradients)
                bins = max(int(self.config.num_grad_quant_bins), 2)
                g_scale = jnp.maximum(jnp.max(g_abs), K_EPSILON) / (bins // 2)
                m.observe("grad_clipped", int(jnp.sum(
                    g_abs >= g_scale * (bins // 2 - 0.5))))

    def _observe_tree_metrics(self, n_dev0: int, n_host0: int) -> None:
        """Leaves grown / split-gain stats of the iteration that just
        finished, plus sampled-row count (telemetry-enabled path only)."""
        m = global_metrics
        gains = None
        split_leaves = leaf_counts = None
        if len(self._device_records) > n_dev0:
            rec = self._device_records[-1]  # stacked [K, ...] TreeArrays
            nl, gains, split_leaves, leaf_counts = jax.device_get(
                (rec.num_leaves, rec.split_gain, rec.split_leaf,
                 rec.leaf_count))
            m.observe("leaves_grown", int(np.sum(nl)))
            gains = np.asarray(gains).reshape(-1)
        elif len(self._host_models) > n_host0:
            trees = self._host_models[-1]
            m.observe("leaves_grown",
                      int(sum(t.num_leaves for t in trees)))
            gains = np.concatenate(
                [np.asarray(t.split_gain[:t.num_internal], np.float64)
                 for t in trees]) if trees else np.zeros(0)
            leaf_counts = np.concatenate(
                [np.asarray(t.leaf_count[:t.num_leaves], np.float64)
                 for t in trees]) if trees else None
        if gains is not None:
            pos = gains[gains > 0]
            m.observe("splits_made", int(pos.size))
            if pos.size:
                m.observe("best_gain", float(pos.max()))
                m.observe("mean_split_gain", float(pos.mean()))
                # gain DISTRIBUTION, not just the extremes: a healthy
                # iteration's gain spectrum decays smoothly; a spectrum
                # collapsing toward zero flags exhausted structure long
                # before eval loss plateaus (obs/health diagnostics)
                m.observe("gain_p50", float(np.percentile(pos, 50)))
                m.observe("gain_p90", float(np.percentile(pos, 90)))
        if split_leaves is not None:
            depth_max = 0
            for sl in np.asarray(split_leaves).reshape(
                    -1, np.asarray(split_leaves).shape[-1]):
                depths = obs_health.tree_depths(sl)
                depth_max = max(depth_max, int(depths.max()))
            m.observe("tree_depth_max", depth_max)
        if leaf_counts is not None:
            lc = np.asarray(leaf_counts, np.float64).reshape(-1)
            lc = lc[lc > 0]
            if lc.size:
                m.observe("leaf_count_min", int(lc.min()))
                m.observe("leaf_count_median", float(np.median(lc)))
                m.observe("leaf_count_max", int(lc.max()))
        m.observe("sampled_rows", int(jnp.sum(self._sample_mask)))

    def _train_one_iter_impl(self, custom_grad=None,
                             custom_hess=None) -> bool:
        if self._fast_path_ok(custom_grad):
            return self._train_one_iter_fast()
        if custom_grad is None:
            self._boost_from_average()
        with global_tracer.span("train/gradients",
                                block=lambda: grad_all):
            grad_all, hess_all = self._gradients(custom_grad, custom_hess)
        if self._health_armed:
            # NaN/Inf sentinel payload, from the gradients this
            # iteration is about to train on — no extra passes, the
            # buffers are already live (obs/health.py)
            self._health_vec = _nonfinite_counts(grad_all, hess_all,
                                                 self.scores)
        with global_tracer.span("train/sampling"):
            self._resample_mask()

        iter_trees: List[Tree] = []
        should_continue = False
        for k in range(self.num_tree_per_iteration):
            grad, hess = grad_all[k], hess_all[k]
            mask = self._sample_mask
            if self.config.data_sample_strategy == "goss" and \
                    custom_grad is None:
                with global_tracer.span("train/sampling"):
                    mask, scale = self._goss_mask(grad, hess)
                    grad, hess = grad * scale, hess * scale
            true_grad, true_hess = grad, hess
            if self._quant_enabled:
                qkey = jax.random.fold_in(self._bagging_key,
                                          self.iter + (3 << 20) + k)
                grad, hess, _quant = self._discretize_in_jit(qkey, grad, hess)
            feature_mask = self._feature_mask()

            node_key = (jax.random.fold_in(
                self._extra_key,
                self.iter * self.num_tree_per_iteration + k)
                if self._use_node_rand else None)
            with global_tracer.span("train/grow",
                                    block=lambda: record.leaf_value):
                record, row_leaf = self._grow(
                    self.bins_fm, grad, hess, mask, feature_mask,
                    self.feature_meta, self.hp, self.max_depth, self._forced,
                    node_key)
            if self._health_armed:
                # keep the REPLICATED device record alive until the
                # end-of-iteration drift digest: the host transfer
                # below reads one device's copy, which is exactly how
                # a diverged replica would go unnoticed
                self._health_pending_record = record
            if self._quant_enabled and \
                    self.config.quant_train_renew_leaf:
                record = self._renew_leaves_in_jit(
                    record, row_leaf, true_grad, true_hess, mask)

            rec_host = _tree_record_to_host(record)
            tree = Tree.from_arrays(rec_host, self.train_set.mappers,
                                    self.train_set.used_features)
            if tree.num_leaves > 1:
                should_continue = True
                # RenewTreeOutput for L1-family (ref: gbdt.cpp:420)
                if self.objective is not None:
                    # host renewal pairs these with real-length label
                    # arrays — drop the padded tail rows
                    nd = self.num_data
                    renewed = self.objective.renew_tree_output(
                        tree, np.asarray(self.scores[k])[:nd],
                        np.asarray(row_leaf)[:nd], np.asarray(mask)[:nd])
                    if renewed is not None:
                        tree = renewed
                if self.config.linear_tree:
                    raw = self.train_set.raw_data
                    if raw is None:
                        raise ValueError(
                            "linear_tree requires raw feature values "
                            "(unavailable for binary-loaded datasets)")
                    from .linear import fit_linear_models
                    fit_linear_models(
                        tree, np.asarray(raw, np.float64),
                        np.asarray(row_leaf), np.asarray(true_grad),
                        np.asarray(true_hess), np.asarray(mask),
                        self.config.linear_lambda)
                # pre-shrinkage leaf values are exactly f32 (grower
                # output / traced renewal); captured before the f64
                # host shrinkage so the score update below can multiply
                # in f32 — the SAME rounding the fused program applies
                # (rec.leaf_value * lr). A one-ulp score skew here flips
                # sign-function gradients (L1 family) on rows sitting
                # at score == label, which cascades into different
                # splits a few iterations later.
                lv32 = tree.leaf_value.astype(np.float32)
                tree.apply_shrinkage(self._tree_shrinkage())
                with global_tracer.span("train/update_score",
                                        block=lambda: self.scores):
                    if tree.is_linear:
                        # within-leaf outputs vary by row: linear outputs
                        # over the grower's row->leaf map (no re-traversal)
                        vals = tree.predict_given_leaves(
                            np.asarray(self.train_set.raw_data, np.float64),
                            np.asarray(row_leaf))
                        new_score_k = self.scores[k] + jnp.asarray(
                            vals.astype(np.float32))
                    else:
                        new_score_k = self._slow_score_update(
                            tree, lv32, row_leaf, k)
                    self.scores = self.scores.at[k].set(new_score_k)
                    self._update_valid_scores(tree, k)
                if abs(self.init_scores[k]) > K_EPSILON and \
                        len(self.models) == 0:
                    tree.add_bias(self.init_scores[k])
            else:
                # constant tree (ref: gbdt.cpp AsConstantTree): bias on
                # the first iteration, ZERO afterwards — the grower's
                # unshrunk root output must not leak into the model (it
                # was never added to the training scores, and a DART
                # drop would subtract it; the fused path stores 0 for
                # 1-leaf trees, asserted equal by TestFusedDart)
                tree.leaf_value[:] = (self.init_scores[k]
                                      if len(self.models) == 0 else 0.0)
            iter_trees.append(tree)

        self.models.append(iter_trees)
        if not should_continue:
            self.models.pop()
            return True
        if self._has_cegb_coupled:
            # refresh first-use coupled penalties
            # (ref: UpdateLeafBestSplits marks is_feature_used_in_split_)
            changed = False
            for tree in iter_trees:
                for f_inner in tree.split_feature_inner[:tree.num_internal]:
                    if not self._cegb_used[f_inner]:
                        self._cegb_used[f_inner] = True
                        changed = True
            if changed:
                new_pen = self.config.cegb_tradeoff * np.where(
                    self._cegb_used, 0.0, self._cegb_coupled)
                self.feature_meta = self.feature_meta._replace(
                    cegb_feat=jnp.asarray(new_pen.astype(np.float32)))
        if self._stream is not None:
            # slow-path streamed iterations (custom fobj / RF / CEGB /
            # host-renewing objectives) carry the same always-on stream
            # accounting as the fast twins — and the end-of-iteration
            # sync resets the overlap classifier's in-flight count so a
            # later pipeline can't inherit stale dispatches
            self._feed.done(self.scores)
        self.iter += 1
        return False

    def _tree_shrinkage(self) -> float:
        return self.shrinkage_rate

    def _slow_score_update(self, tree, lv32: np.ndarray, row_leaf, k):
        """Slow-path score update, bit-aligned with the fused program:
        f32 pre-shrinkage leaf values x f32 learning rate, multiplied
        and added in one XLA program (see _update_score_shrunk). DART
        overrides: its drop/re-add cycle subtracts f64 host leaf
        values, so its slow path must add exactly those."""
        return self._update_score_shrunk(
            self.scores[k], jnp.asarray(lv32),
            jnp.float32(self._tree_shrinkage()), row_leaf)

    # ------------------------------------------------------------------
    def add_valid(self, valid_set, raw_data: Optional[np.ndarray]) -> None:
        """Register a validation set; scores held on device [K, Nv] and
        updated incrementally (ref: GBDT::AddValidDataset gbdt.cpp)."""
        self._valid_sets.append((valid_set, raw_data))
        n = valid_set.num_data
        score = np.zeros((self.num_tree_per_iteration, n), np.float32)
        # catch up on existing model
        if self.current_iteration() > 0:
            raw = self.predict_raw(raw_data)
            score = raw.reshape(n, self.num_tree_per_iteration).T
        elif any(abs(s) > K_EPSILON for s in self.init_scores):
            score += np.asarray(self.init_scores, np.float32)[:, None]
        if valid_set.metadata.init_score is not None:
            init = np.asarray(valid_set.metadata.init_score, np.float64)
            score += (init.reshape(-1, n) if init.size != n
                      else init.reshape(1, n)).astype(np.float32)
        self._valid_scores.append(jnp.asarray(score))
        vbins = (self._maybe_pack_bins(valid_set)
                 if self._bin_pack_vpb > 1 else None)
        self._valid_bins.append(vbins if vbins is not None
                                else valid_set.device_bins())
        self._fused = None  # fused program must include the new valid set
        self._stream_progs = {}  # streamed post programs carry valid sets
        # the valid bins + scores just moved on device: refresh the
        # published peak-memory model (and re-judge the preflight) so a
        # big eval set can't silently blow past a "fits" verdict
        self._note_memory_model()

    def _valid_raw(self, i: int) -> np.ndarray:
        """Valid set i's raw features as a DENSE array — the host tree
        paths (renewing objectives, DART normalize, rollback) index raw
        values row-wise every iteration, so a sparse valid set is
        densified once and cached rather than per iteration."""
        raw = self._valid_sets[i][1]
        from .dataset import is_sparse
        if is_sparse(raw):
            cache = getattr(self, "_valid_dense", None)
            if cache is None:
                cache = self._valid_dense = {}
            if i not in cache:
                cache[i] = np.asarray(raw.toarray(), np.float64)
            return cache[i]
        return raw

    def _update_valid_scores(self, tree: Tree, class_id: int) -> None:
        for i, (vs, raw) in enumerate(self._valid_sets):
            self._valid_scores[i] = self._valid_scores[i].at[class_id].add(
                jnp.asarray(tree.predict(self._valid_raw(i))
                            .astype(np.float32)))

    def valid_raw_scores(self, idx: int) -> np.ndarray:
        return np.asarray(self._valid_scores[idx]).T

    # ------------------------------------------------------------------
    def init_from_loaded(self, loaded) -> None:
        """Continued training: seed the booster with a previously trained
        model's trees and fast-forward train/valid scores by prediction
        (ref: boosting.cpp:74-90 LoadFileToBoosting; continued-training
        init score via Predictor, application.cpp:92-100)."""
        k = self.num_tree_per_iteration
        if loaded.num_tree_per_iteration != k:
            raise ValueError(
                f"init_model has {loaded.num_tree_per_iteration} trees per "
                f"iteration, training config needs {k}")
        n_feat = self.train_set.num_total_features
        if loaded.max_feature_idx + 1 > n_feat:
            raise ValueError(
                f"init_model uses {loaded.max_feature_idx + 1} features, "
                f"train data has {n_feat}")
        if self.train_set.raw_data is None:
            raise ValueError(
                "continued training requires raw feature values to "
                "fast-forward scores (binary-loaded datasets keep none)")
        trees = list(loaded.trees)
        self._materialize_records()
        self._host_models = [trees[i:i + k]
                             for i in range(0, len(trees), k)]
        self.iter = len(self._host_models)
        # the loaded first tree already carries the boost-from-average
        # bias; never re-apply it
        self._init_done = True
        self.init_scores = [0.0] * k

        def _dataset_init_offset(meta_init, n):
            """Per-row init_score offsets a dataset contributes to its
            scores (same layout handling as __init__)."""
            off = np.zeros((k, n), np.float32)
            if meta_init is not None:
                init = np.asarray(meta_init, np.float64)
                if init.size == n * k:
                    off += init.reshape(k, n, order="C").astype(np.float32)
                else:
                    off += init.reshape(1, -1).astype(np.float32)
            return off

        raw = self.predict_raw(self.train_set.raw_data)  # [N, K]
        scores = raw.T.astype(np.float32) + _dataset_init_offset(
            self.train_set.metadata.init_score, self.num_data)
        if self._row_pad:
            scores = np.pad(scores, ((0, 0), (0, self._row_pad)))
        self.scores = jnp.asarray(scores)
        for i, (vs, raw_v) in enumerate(self._valid_sets):
            vraw = self.predict_raw(raw_v)  # handles sparse + dense
            self._valid_scores[i] = jnp.asarray(
                vraw.T.astype(np.float32) + _dataset_init_offset(
                    vs.metadata.init_score, vs.num_data))

    # ------------------------------------------------------------------
    def rollback_one_iter(self) -> None:
        """(ref: gbdt.cpp:463 RollbackOneIter)"""
        if self.iter <= 0:
            return
        trees = self.models.pop()
        for k, tree in enumerate(trees):
            if tree.num_leaves > 1:
                # recompute leaf assignment for train rows via binned predict
                leaves = self._predict_leaf_binned_train(tree)
                if tree.is_linear:
                    vals = tree.predict_given_leaves(
                        np.asarray(self.train_set.raw_data, np.float64),
                        np.asarray(leaves))
                    self.scores = self.scores.at[k].add(
                        jnp.asarray(-vals.astype(np.float32)))
                else:
                    self.scores = self.scores.at[k].add(
                        jnp.asarray((-tree.leaf_value.astype(np.float32)))
                        [leaves])
        for i, (vs, raw) in enumerate(self._valid_sets):
            for k, tree in enumerate(trees):
                self._valid_scores[i] = self._valid_scores[i].at[k].add(
                    jnp.asarray(-tree.predict(self._valid_raw(i))
                                .astype(np.float32)))
        self.iter -= 1

    def _predict_leaf_binned_train(self, tree: Tree):
        """Leaf index per train row using the binned matrix."""
        bins = self.train_set.bins_fm
        n = bins.shape[1]
        sparse_cols = None
        if self.train_set.sparse_coo is not None:
            # COO storage: materialize only the tree's split features
            uniq = np.unique(np.asarray(
                tree.split_feature_inner[:tree.num_internal], np.int64))
            sparse_cols = {int(ff): self.train_set.host_feature_bins(
                int(ff)) for ff in uniq}
        node = np.zeros(n, np.int32)
        out = np.zeros(n, np.int32)
        if tree.num_internal == 0:
            return jnp.asarray(out)
        done = np.zeros(n, bool)
        num_bins, missing, default_bin, is_cat = \
            self.train_set.feature_meta_arrays()
        # bin-level go-left lookup for categorical nodes: mapper bin ->
        # raw category value -> membership in the node's value bitset
        max_b = int(self.train_set.max_bins)
        cat_lut = np.zeros((tree.num_internal, max_b), bool)
        for nd_i in range(tree.num_internal):
            if not (tree.decision_type[nd_i] & 1):
                continue
            mapper = self.train_set.mappers[tree.split_feature_inner[nd_i]]
            cat_idx = int(tree.threshold[nd_i])
            lo, hi = (tree.cat_boundaries[cat_idx],
                      tree.cat_boundaries[cat_idx + 1])
            for b in range(1, mapper.num_bins):
                v = int(mapper.bin_to_value(b))
                if v >= 0 and v // 32 < hi - lo and \
                        (tree.cat_threshold[lo + v // 32] >> (v % 32)) & 1:
                    cat_lut[nd_i, b] = True
        bi = self.train_set.bundle_info
        for _ in range(tree.num_internal + 1):
            if done.all():
                break
            active = np.flatnonzero(~done)
            nd = node[active]
            feat = tree.split_feature_inner[nd]
            if sparse_cols is not None:
                b = np.empty(len(active), np.int32)
                for ff in np.unique(feat):
                    m = feat == ff
                    b[m] = sparse_cols[int(ff)][active[m]]
            elif bi is None:
                b = bins[feat, active].astype(np.int32)
            else:  # EFB decode
                from .bundling import decode_stored_host
                b = decode_stored_host(
                    bins[bi.group_of[feat], active].astype(np.int32),
                    bi.offset_of[feat], num_bins[feat] - 1)
            tbin = tree.threshold_bin[nd]
            nan_bin = num_bins[feat] - 1
            is_nan = (missing[feat] == 2) & (b == nan_bin)
            dleft = (tree.decision_type[nd] & 2) > 0
            cat = (tree.decision_type[nd] & 1) > 0
            go_left = np.where(cat, cat_lut[nd, b],
                               np.where(is_nan, dleft, b <= tbin))
            child = np.where(go_left, tree.left_child[nd],
                             tree.right_child[nd])
            is_leaf = child < 0
            out[active[is_leaf]] = ~child[is_leaf]
            done[active[is_leaf]] = True
            node[active[~is_leaf]] = child[~is_leaf]
        return jnp.asarray(out)

    # ------------------------------------------------------------------
    # prediction (ref: gbdt_prediction.cpp:16-91, predictor.hpp:31)
    # Default path: the streaming tree-parallel inference engine
    # (ops/predict.py) — vmapped traversal over the packed [T] trees,
    # shape-bucketed chunking, optional mesh sharding; host fallback for
    # linear trees (per-leaf models live on host).
    def predict_raw(self, data: np.ndarray, start_iteration: int = 0,
                    num_iteration: int = -1,
                    predict_chunk: Optional[int] = None) -> np.ndarray:
        from .dataset import is_sparse, sparse_row_batches
        if is_sparse(data):
            if data.shape[0] == 0:
                data = np.zeros(data.shape)
            else:
                return np.concatenate(
                    [self.predict_raw(b, start_iteration, num_iteration,
                                      predict_chunk=predict_chunk)
                     for b in sparse_row_batches(data)], axis=0)
        data = np.asarray(data, np.float64)
        end = len(self.models) if num_iteration < 0 else \
            min(len(self.models), start_iteration + num_iteration)
        trees = [t for it in self.models[start_iteration:end] for t in it]
        if not trees:
            return np.zeros((data.shape[0], self.num_tree_per_iteration))
        # classification only — regression/ranking need accurate sums
        # (ref: predictor.hpp:47 gates on !NeedAccuratePrediction)
        if self.config.pred_early_stop and self.config.objective in (
                "binary", "multiclass", "multiclassova", "cross_entropy",
                "cross_entropy_lambda"):
            return self._predict_raw_early_stop(data, start_iteration, end)
        if any(t.is_linear for t in trees):
            return self._predict_raw_host(data, start_iteration, end)
        from .ops.predict import predict_raw_cached
        key = (start_iteration, end, self.current_iteration())
        chunk = (int(predict_chunk) if predict_chunk
                 else int(self.config.tpu_predict_chunk or (1 << 20)))
        shards = int(self.config.tpu_num_shards or 0)
        with global_tracer.span("predict/raw"):
            return predict_raw_cached(self, trees,
                                      self.num_tree_per_iteration,
                                      data, key, chunk,
                                      num_shards=shards if shards > 1 else 0)

    def _predict_raw_host(self, data: np.ndarray, start_iteration: int,
                          end: int) -> np.ndarray:
        n = data.shape[0]
        k = self.num_tree_per_iteration
        out = np.zeros((n, k))
        for it in range(start_iteration, end):
            for ki, tree in enumerate(self.models[it]):
                out[:, ki] += tree.predict(data)
        return out

    def _predict_raw_early_stop(self, data: np.ndarray, start_iteration: int,
                                end: int) -> np.ndarray:
        """Row-wise prediction with early termination (ref:
        prediction_early_stop.cpp CreatePredictionEarlyStopInstance:
        binary stops when |margin| > margin_threshold, multiclass when
        top1 - top2 > threshold, checked every `freq` trees). A host
        path by design: data-dependent per-row loop exits fit the CPU;
        the device ensemble path evaluates all trees faster than it
        could branch."""
        n = data.shape[0]
        k = self.num_tree_per_iteration
        freq = max(int(self.config.pred_early_stop_freq), 1)
        margin = float(self.config.pred_early_stop_margin)
        out = np.zeros((n, k))
        active = np.ones(n, bool)
        for idx, it in enumerate(range(start_iteration, end)):
            rows = np.flatnonzero(active)
            if rows.size == 0:
                break
            sub = data[rows]
            for ki, tree in enumerate(self.models[it]):
                out[rows, ki] += tree.predict(sub)
            if (idx + 1) % freq == 0:
                if k == 1:
                    # ref: prediction_early_stop.cpp CreateBinary uses
                    # margin = 2 * |pred|
                    stop = 2.0 * np.abs(out[rows, 0]) > margin
                else:
                    part = np.partition(out[rows], k - 2, axis=1)
                    stop = (part[:, -1] - part[:, -2]) > margin
                active[rows[stop]] = False
        return out

    def predict(self, data: np.ndarray, raw_score: bool = False,
                start_iteration: int = 0, num_iteration: int = -1,
                pred_leaf: bool = False, pred_contrib: bool = False,
                predict_chunk: Optional[int] = None) -> np.ndarray:
        if pred_leaf:
            return self.predict_leaf(data, start_iteration, num_iteration)
        if pred_contrib:
            return self.predict_contrib(data, start_iteration, num_iteration,
                                        predict_chunk=predict_chunk)
        raw = self.predict_raw(data, start_iteration, num_iteration,
                               predict_chunk=predict_chunk)
        if raw.shape[1] == 1:
            raw = raw[:, 0]
        if raw_score or self.objective is None:
            return raw
        return self.objective.convert_output(raw)

    def predict_leaf(self, data: np.ndarray, start_iteration: int = 0,
                     num_iteration: int = -1) -> np.ndarray:
        data = np.asarray(data, np.float64)
        end = len(self.models) if num_iteration < 0 else \
            min(len(self.models), start_iteration + num_iteration)
        cols = []
        for it in range(start_iteration, end):
            for tree in self.models[it]:
                cols.append(tree.predict_leaf(data))
        return np.stack(cols, axis=1) if cols else \
            np.zeros((data.shape[0], 0), np.int32)

    def predict_contrib(self, data: np.ndarray, start_iteration: int = 0,
                        num_iteration: int = -1,
                        predict_chunk: Optional[int] = None) -> np.ndarray:
        """SHAP values via the tree-path algorithm (ref: tree.h
        PredictContrib). Routed through the batched device kernel
        (ops/shap.py) unless config.tpu_shap says off or the model has
        linear-tree leaves (shap.py owns the dispatch)."""
        from .shap import predict_contrib
        return predict_contrib(self, data, start_iteration, num_iteration,
                               predict_chunk=predict_chunk)

    # ------------------------------------------------------------------
    def feature_importance(self, importance_type: str = "split",
                           iteration: int = -1) -> np.ndarray:
        """(ref: GBDT::FeatureImportance gbdt.cpp — num_iteration <= 0
        means all trees)"""
        end = len(self.models) if iteration <= 0 else min(
            len(self.models), iteration)
        imp = np.zeros(self.train_set.num_total_features)
        for it in range(end):
            for tree in self.models[it]:
                for nd in range(tree.num_internal):
                    if tree.left_child[nd] == -1 and \
                            tree.right_child[nd] == -1:
                        continue
                    # only positive-gain splits count (ref:
                    # GBDT::FeatureImportance gbdt_model_text.cpp)
                    if tree.split_gain[nd] <= 0.0:
                        continue
                    f = tree.split_feature[nd]
                    if importance_type == "split":
                        imp[f] += 1
                    else:
                        imp[f] += tree.split_gain[nd]
        return imp

    @property
    def num_trees(self) -> int:
        return self.current_iteration() * self.num_tree_per_iteration

    def current_iteration(self) -> int:
        return len(self._host_models) + len(self._device_records)


class DART(GBDT):
    """Dropouts meet MART (ref: src/boosting/dart.hpp:24)."""

    boosting_type = "dart"

    def __init__(self, config, train_set, objective=None):
        super().__init__(config, train_set, objective)
        self._drop_rng = np.random.RandomState(config.drop_seed)
        # per-NEW-iteration weights used by weighted drop selection
        # (ref: dart.hpp:200 tree_weight_, :68 push_back(shrinkage_rate_))
        self._tree_weights: List[float] = []
        self._sum_tree_weight = 0.0
        self._num_init_iteration = 0
        # fused-path state: dropped-tree contributions are recomputed on
        # device from a [T, K, N] leaf-assignment history, so a DART
        # iteration stays one XLA program with zero host round-trips
        self._dart = None            # device buffers
        self._dart_t = 0             # fused iterations stored
        self._dart_base = 0          # _host_models index of first fused iter
        self._dart_unshrunk: List[dict] = []  # host unshrunk records
        self._init_vec = None        # [K] init scores, a trace constant
        self._dart_fast_disabled = False
        self._cur_shrinkage = float(config.learning_rate)
        self._dart_update_score = None  # see _slow_score_update

    def init_from_loaded(self, loaded) -> None:
        super().init_from_loaded(loaded)
        # loaded trees are never dropped (ref: dart.hpp num_init_iteration_)
        self._num_init_iteration = len(self._host_models)

    def _tree_shrinkage(self) -> float:
        # the DART shrinkage is the drop-count-dependent factor set
        # BEFORE the new tree trains (ref: dart.hpp:139-147
        # shrinkage_rate_ update in DroppingTrees); the reference's
        # Normalize never rescales the new tree — and the bias of a
        # first tree is added AFTER this shrinkage (gbdt.cpp:426)
        return self._cur_shrinkage

    def _slow_score_update(self, tree, lv32: np.ndarray, row_leaf, k):
        # bit-aligned with the fused DART program's creation add
        # (`scores_adj + old_factor*delta + new_factor*lookup(lv, leaf)`):
        # PRE-shrinkage f32 leaf values, looked up FIRST, then multiplied
        # by the f32 drop-factor and added in one XLA program — the same
        # FMA-contraction shape, so drop-free iterations are bitwise
        # identical between the paths. (The GBDT twin multiplies before
        # the gather because ITS fused program does; the shapes must
        # each match their own fused path, not each other.) Drop-cycle
        # iterations still subtract/re-add f64 host leaf values and keep
        # ulp-level drift — the multiclass knife-edge this kills is a
        # split flip born in the drop-FREE early iterations.
        if self._dart_update_score is None:
            self._dart_update_score = jax.jit(
                lambda score, lv, nf, rl:
                score + nf * per_row_lookup(lv, rl))
        return self._dart_update_score(
            self.scores[k], jnp.asarray(lv32),
            jnp.float32(self._tree_shrinkage()), row_leaf)

    # -- fused path ----------------------------------------------------
    def _fast_path_ok(self, custom_grad) -> bool:
        if self._dart_fast_disabled or \
                not self._fast_path_core_ok(custom_grad):
            return False
        cfg = self.config
        if cfg.max_drop <= 0:
            return False  # unbounded drop count has no static shape
        # new trees grown by the host loop are missing from the device
        # drop history; fused mode only starts on a clean booster
        if self._dart_t == 0 and \
                len(self._host_models) > self._num_init_iteration:
            return False
        k = self.num_tree_per_iteration
        leaves = int(cfg.num_leaves)
        t_cap = max(int(cfg.num_iterations), 64, self._dart_t * 2)
        nv = sum(vs.num_data for vs, _ in self._valid_sets)
        item = 1 if leaves <= 256 else (2 if leaves <= 65536 else 4)
        need = t_cap * k * ((self.num_data + nv) * item + leaves * 4 + 4)
        return need <= int(cfg.tpu_dart_fused_max_bytes)

    def _dart_hist_dtype(self):
        leaves = int(self.config.num_leaves)
        return (jnp.uint8 if leaves <= 256
                else jnp.uint16 if leaves <= 65536 else jnp.int32)

    def _ensure_dart_state(self) -> None:
        if self._init_vec is None:
            self._init_vec = jnp.asarray(
                np.asarray(self.init_scores, np.float32))
        k = self.num_tree_per_iteration
        leaves = self._static["num_leaves"]
        dt = self._dart_hist_dtype()
        if self._dart is None:
            t_cap = max(int(self.config.num_iterations), 64)
            self._dart_base = len(self._host_models)
            self._dart = {
                "leaf_hist": jnp.zeros((t_cap, k, self.num_data), dt),
                "vhist": [jnp.zeros((t_cap, k, vs.num_data), dt)
                          for vs, _ in self._valid_sets],
                "leaf_vals": jnp.zeros((t_cap, k, leaves), jnp.float32),
                "factors": jnp.zeros((t_cap,), jnp.float32),
            }
        elif self._dart_t >= self._dart["leaf_hist"].shape[0]:
            # double capacity (continued training past num_iterations);
            # the jit re-specializes on the new shapes automatically
            def grow_buf(b):
                pad = [(0, b.shape[0])] + [(0, 0)] * (b.ndim - 1)
                return jnp.pad(b, pad)
            st = self._dart
            st["leaf_hist"] = grow_buf(st["leaf_hist"])
            st["vhist"] = [grow_buf(v) for v in st["vhist"]]
            st["leaf_vals"] = grow_buf(st["leaf_vals"])
            st["factors"] = grow_buf(st["factors"])

    def _dart_factors(self, k_drop: int):
        """(new_factor, old_factor) as python floats
        (ref: dart.hpp:139-147 shrinkage bookkeeping + :159 Normalize)."""
        lr = float(self.config.learning_rate)
        if self.config.xgboost_dart_mode:
            new_factor = lr if k_drop == 0 else lr / (lr + k_drop)
            old_factor = k_drop / (k_drop + lr)
        else:
            new_factor = lr / (1.0 + k_drop)
            old_factor = k_drop / (k_drop + 1.0)
        return new_factor, old_factor

    def _update_drop_weights(self, drop_slots: List[int]) -> None:
        """Weighted-mode bookkeeping after renormalizing k dropped trees
        — shared by the host and fused paths so their tested exact parity
        can't desynchronize (ref: dart.hpp:159-196 Normalize, including
        the reference's xgboost-mode quirk of subtracting w/(k+lr) rather
        than the true delta w*lr/(k+lr), dart.hpp:175,193).
        `drop_slots` are NEW-tree indices (init offset excluded)."""
        if self.config.uniform_drop or not drop_slots:
            return
        k_drop = len(drop_slots)
        lr = float(self.config.learning_rate)
        _new, old_factor = self._dart_factors(k_drop)
        sub = (1.0 / (k_drop + lr) if self.config.xgboost_dart_mode
               else 1.0 / (k_drop + 1.0))
        for s in drop_slots:
            self._sum_tree_weight -= self._tree_weights[s] * sub
            self._tree_weights[s] *= old_factor

    # -- the DART iteration: GBDT's compositions over a head extended by
    # the drop, score rules of its own and a finish. Drop selection
    # happens on the host from host-held tree weights (no device data
    # involved), the dropped trees' score contributions are recomputed on
    # device by indexing the leaf-assignment history, and normalization
    # (dart.hpp:159) becomes a per-tree factor buffer update — the
    # model's trees materialize later as unshrunk records x factors.
    # hist = (leaf_hist, vhists, leaf_vals), drop = (factors, dropped,
    # n_drop, t_cur).
    _tags = {"fused": "fused_dart_iter", "prep": "dart_prep",
             "post": "dart_post", "finish": "dart_factors"}
    _n_hist = 3
    _fused_donate = (3, 4, 5, 6, 7, 8, 9)

    def _iter_head(self, scores, sample_mask, it, lr, hist, drop):
        """GBDT's head with the dropped trees taken out of the scores
        before the gradients."""
        leaf_hist, vhists, leaf_vals = hist
        factors, dropped, n_drop, t_cur = drop
        key, sample_mask = self._iter_bagging(sample_mask, it)
        live = dropped >= 0                      # [D]
        d_gather = jnp.where(live, dropped, 0)
        d_scatter = jnp.where(live, dropped, factors.shape[0])  # OOB = no-op
        fac_d = factors[d_gather] * live.astype(jnp.float32)

        def drop_delta(leaves, vals):
            h = jnp.take(leaves, d_gather, axis=0).astype(jnp.int32)
            v = jnp.take(vals, d_gather, axis=0) * fac_d[:, None, None]
            return jnp.take_along_axis(v, h, axis=2).sum(axis=0)

        with jax.named_scope("lgbm/score/drop"):
            delta = drop_delta(leaf_hist, leaf_vals)  # [K, N]
            scores_adj = scores - delta
        with jax.named_scope("lgbm/valid/drop"):
            deltas_v = tuple(drop_delta(vh, leaf_vals) for vh in vhists)
        grad_all, hess_all = self._grad_fn(scores_adj)
        kd = n_drop.astype(jnp.float32)
        if self.config.xgboost_dart_mode:
            new_factor = jnp.where(n_drop > 0, lr / (lr + kd), lr)
            old_factor = kd / (kd + lr)
        else:
            new_factor = lr / (1.0 + kd)
            old_factor = kd / (kd + 1.0)
        carry = dict(scores_adj=scores_adj, delta=delta, deltas_v=deltas_v,
                     new_factor=new_factor, old_factor=old_factor,
                     d_scatter=d_scatter, t_cur=t_cur)
        return (key, sample_mask, grad_all, hess_all,
                (grad_all, hess_all), carry)

    def _grad_scores(self, scores, carry):
        return carry["scores_adj"]

    def _score_rule(self, k, rec, row_leaf, scores, lr, carry, hist):
        leaf_hist, vhists, leaf_vals = hist
        t_cur, new_factor = carry["t_cur"], carry["new_factor"]
        with jax.named_scope("lgbm/score"):
            lv = jnp.where(rec.num_leaves > 1, rec.leaf_value, 0.0)
            scores = scores.at[k].set(
                carry["scores_adj"][k]
                + carry["old_factor"] * carry["delta"][k]
                + new_factor * per_row_lookup(lv, row_leaf))
            leaf_hist = leaf_hist.at[t_cur, k].set(
                row_leaf.astype(leaf_hist.dtype))
            lv_store = lv
            # the reference bakes the boost-from-average bias into the
            # first tree AFTER its score update (gbdt.cpp:426 AddBias),
            # so dropped first trees carry the bias and later
            # normalizations scale it. The history buffer therefore
            # stores lv + bias/creation_factor for iteration 0:
            # factor[t] * buffer then reproduces the reference's current
            # leaf values at every later point in time.
            if self._dart_base == 0 and any(
                    abs(s) > K_EPSILON for s in self.init_scores):
                # bias applies to 1-LEAF first-iteration trees too: the
                # reference's constant tree carries leaf_value == init
                # (AsConstantTree), and a drop must subtract it — a
                # class with (near-) empty data keeps a 1-leaf tree
                # whose bias the history would otherwise lose
                # (multiclass DART parity, tests/test_engine.py)
                lv_store = lv + jnp.where(
                    t_cur == 0, self._init_vec[k] / new_factor, 0.0)
            leaf_vals = leaf_vals.at[t_cur, k].set(lv_store)
        return scores, lv, (leaf_hist, vhists, leaf_vals)

    def _valid_score_rule(self, k, vi, valid, leaf_vals, vleaf, carry,
                          hist):
        valid = valid.at[k].set(
            valid[k] - (1.0 - carry["old_factor"]) * carry["deltas_v"][vi][k]
            + carry["new_factor"] * per_row_lookup(leaf_vals, vleaf))
        leaf_hist, vhists, leaf_vals_hist = hist
        vhists = list(vhists)
        vhists[vi] = vhists[vi].at[carry["t_cur"], k].set(
            vleaf.astype(vhists[vi].dtype))
        return valid, (leaf_hist, tuple(vhists), leaf_vals_hist)

    @jax.named_scope("lgbm/score")
    def _iter_finish(self, carry, drop):
        """Normalize as a factor-buffer update: the dropped trees scaled
        by old_factor, the new tree entered at new_factor."""
        factors = drop[0].at[carry["d_scatter"]].multiply(
            carry["old_factor"])
        return (factors.at[carry["t_cur"]].set(carry["new_factor"]),)

    # -- the two hooks around GBDT's driver
    def _begin_iteration(self):
        self._ensure_dart_state()
        drop_slots = self._select_drop(self._dart_t)
        n_drop = len(drop_slots)
        global_metrics.observe("dart_dropped_trees", n_drop)
        dropped = np.full(max(int(self.config.max_drop), 1), -1, np.int32)
        dropped[:n_drop] = drop_slots
        st = self._dart
        return (drop_slots,
                (st["leaf_hist"], tuple(st["vhist"]), st["leaf_vals"]),
                (st["factors"], jnp.asarray(dropped), jnp.int32(n_drop),
                 jnp.int32(self._dart_t)),
                jnp.float32(self.config.learning_rate))

    def _end_iteration(self, drop_slots, state) -> None:
        st = self._dart
        st["leaf_hist"], vhist, st["leaf_vals"], st["factors"] = state
        st["vhist"] = list(vhist)
        self._dart_t += 1
        # host weight bookkeeping — uses only host-known values (drop
        # count), so no device sync happens
        new_factor, _old = self._dart_factors(len(drop_slots))
        self._update_drop_weights(drop_slots)
        self._tree_weights.append(new_factor)
        self._sum_tree_weight += new_factor

    def _materialize_records_inner(self) -> None:
        if self._dart is None:
            return super()._materialize_records_inner()
        # fused DART: records hold UNSHRUNK leaf values; the applied
        # factors evolve retroactively (Normalize rescales dropped trees),
        # so all fused-born trees are rebuilt from the kept unshrunk
        # records x the factor buffer's current snapshot.
        recs = self._device_records
        self._device_records, self._record_lrs = [], []
        if recs:
            host = _records_to_host(recs)
            for i in range(len(recs)):
                self._dart_unshrunk.append(
                    {f: np.asarray(getattr(host, f)[i])
                     for f in host._fields})
        factors = np.asarray(jax.device_get(self._dart["factors"]))
        # leaf values come from the history buffer (unshrunk + the first
        # iteration's bias/creation_factor term) x current factor — the
        # exact quantity the device drop path subtracts, and the
        # reference's post-Normalize leaf values (dart.hpp:159)
        buf_vals = np.asarray(jax.device_get(self._dart["leaf_vals"]))
        k_per = self.num_tree_per_iteration
        base = self._dart_base
        # incremental rebuild: only trees whose factor changed since the
        # last snapshot (the dropped ones) plus the not-yet-built tail —
        # a per-iteration predict() loop stays O(drops), not O(T^2)
        prev = getattr(self, "_dart_factor_snapshot", None)
        built = len(self._host_models) - base
        for i, rec_all in enumerate(self._dart_unshrunk):
            if i < built and prev is not None and i < len(prev) and \
                    factors[i] == prev[i]:
                continue
            first_iter = (base + i) == 0
            iter_trees = []
            for k in range(k_per):
                rec = {f: rec_all[f][k] for f in rec_all}
                tree = Tree.from_arrays(rec, self.train_set.mappers,
                                        self.train_set.used_features)
                if tree.num_leaves > 1 or first_iter:
                    # constant FIRST-iteration trees rebuild from the
                    # history buffer too: their bias rides it
                    # (init/creation_factor), so factor x buffer
                    # reproduces the reference's post-Normalize value
                    # when the tree has been dropped/rescaled
                    if tree.num_leaves > 1:
                        tree.apply_shrinkage(float(factors[i]))
                    tree.leaf_value[:] = (
                        factors[i] * buf_vals[i][k][:len(tree.leaf_value)]
                    ).astype(tree.leaf_value.dtype)
                else:
                    tree.leaf_value[:] = 0.0
                iter_trees.append(tree)
            if i < built:
                self._host_models[base + i] = iter_trees
            else:
                self._host_models.append(iter_trees)
        self._dart_factor_snapshot = factors.copy()

    def _freeze_dart_fused(self) -> None:
        """Materialize fused-born trees with their final factors and hand
        authority to the host Tree objects (after this, Normalize mutates
        them directly and the records must never be re-applied)."""
        self._materialize_records()
        self._dart_unshrunk = []
        self._dart = None
        self._fused = None  # _score_rule reads _dart_base while it traces

    def add_valid(self, valid_set, raw_data) -> None:
        super().add_valid(valid_set, raw_data)
        if self._dart_t > 0:
            # past trees have no leaf history on the new valid set
            self._freeze_dart_fused()
            self._dart_fast_disabled = True
        else:
            self._dart = None

    def rollback_one_iter(self) -> None:
        if self.iter <= 0:
            return
        if self._dart_t > 0 or self._device_records:
            # factor rewind isn't representable in the fused buffers
            self._freeze_dart_fused()
            self._dart_fast_disabled = True
        super().rollback_one_iter()
        if self._tree_weights:
            w = self._tree_weights.pop()
            self._sum_tree_weight -= w

    def _train_one_iter_impl(self, custom_grad=None,
                             custom_hess=None) -> bool:
        if self._fast_path_ok(custom_grad):
            return self._train_one_iter_fast()
        if self._dart_t > 0 or self._device_records:
            self._freeze_dart_fused()
        self._dart_fast_disabled = True
        drop_idx = [self._num_init_iteration + i for i in self._select_drop(
            len(self.models) - self._num_init_iteration)]
        global_metrics.observe("dart_dropped_trees", len(drop_idx))
        # subtract dropped trees from scores (dart.hpp DroppingTrees)
        for di in drop_idx:
            self._add_tree_scores(self.models[di], sign=-1.0)

        new_factor, _old = self._dart_factors(len(drop_idx))
        self._cur_shrinkage = new_factor
        stop = super()._train_one_iter_impl(custom_grad, custom_hess)
        if not stop:
            self._normalize(drop_idx)
            # the new tree's weight is its actual applied factor
            # (ref: dart.hpp:68 push_back(shrinkage_rate_) where
            # shrinkage_rate_ was updated by DroppingTrees :139-147)
            self._tree_weights.append(new_factor)
            self._sum_tree_weight += new_factor
        for di in drop_idx:
            self._add_tree_scores(self.models[di], sign=1.0)
        return stop

    def _add_tree_scores(self, trees, sign: float) -> None:
        for k, tree in enumerate(trees):
            leaves = self._predict_leaf_binned_train(tree)
            self.scores = self.scores.at[k].add(jnp.asarray(
                (sign * tree.leaf_value).astype(np.float32))[leaves])
        for i, (vs, raw) in enumerate(self._valid_sets):
            for k, tree in enumerate(trees):
                self._valid_scores[i] = self._valid_scores[i].at[k].add(
                    jnp.asarray(sign * tree.predict(self._valid_raw(i))
                                .astype(np.float32)))

    def _select_drop(self, n_new: int) -> List[int]:
        """Select NEW-tree indices (0-based, init offset excluded) to drop
        (ref: dart.hpp:98 DroppingTrees). Weighted mode drops tree i with
        probability proportional to its current weight (ref:
        dart.hpp:104-116); weights shrink as trees get renormalized away
        (Normalize), so frequently-dropped trees become less likely to be
        dropped again. Host-only inputs (RNG + weight floats), so the
        fused path calls this without any device sync."""
        cfg = self.config
        if n_new == 0:
            return []
        if self._drop_rng.rand() < cfg.skip_drop:
            return []
        drop_rate = cfg.drop_rate
        sel: List[int] = []
        if not cfg.uniform_drop:
            sum_w = max(self._sum_tree_weight, 1e-30)
            inv_avg = n_new / sum_w
            if cfg.max_drop > 0:
                drop_rate = min(drop_rate, cfg.max_drop * inv_avg / sum_w)
            for i in range(n_new):
                if self._drop_rng.rand() < \
                        drop_rate * self._tree_weights[i] * inv_avg:
                    sel.append(i)
                    if cfg.max_drop > 0 and len(sel) >= cfg.max_drop:
                        break
        else:
            if cfg.max_drop > 0:
                drop_rate = min(drop_rate, cfg.max_drop / n_new)
            for i in range(n_new):
                if self._drop_rng.rand() < drop_rate:
                    sel.append(i)
                    if cfg.max_drop > 0 and len(sel) >= cfg.max_drop:
                        break
        return sel

    def _normalize(self, drop_idx: List[int]) -> None:
        """Scale the DROPPED trees to k/(k+1) (or k/(k+lr) in xgboost
        mode) of their old weight (ref: dart.hpp:159 Normalize — the new
        tree was already created at its final factor, like the
        reference's Shrinkage(shrinkage_rate_) at gbdt.cpp:423)."""
        _new_factor, old_factor = self._dart_factors(len(drop_idx))
        for di in drop_idx:
            for tree in self.models[di]:
                tree.apply_shrinkage(old_factor)
        self._update_drop_weights(
            [di - self._num_init_iteration for di in drop_idx])


class RF(GBDT):
    """Random forest mode (ref: src/boosting/rf.hpp:26): bagging required,
    no shrinkage, gradients always computed at the constant init score,
    output averaged over iterations."""

    boosting_type = "rf"

    def __init__(self, config, train_set, objective=None):
        if not (config.bagging_freq > 0 and
                (config.bagging_fraction < 1.0 or
                 config.feature_fraction < 1.0)):
            raise ValueError(
                "RF mode requires bagging (bagging_freq > 0 and "
                "bagging_fraction < 1) or feature_fraction < 1")
        super().__init__(config, train_set, objective)
        self._base_grad = None

    def _tree_shrinkage(self) -> float:
        return 1.0

    def _gradients(self, custom_grad=None, custom_hess=None):
        if custom_grad is not None:
            return super()._gradients(custom_grad, custom_hess)
        if self._base_grad is None:
            self._boost_from_average()
            init = jnp.asarray(
                np.asarray(self.init_scores, np.float32)[:, None])
            base_score = jnp.broadcast_to(
                init, (self.num_tree_per_iteration, self.num_data))
            self._base_grad = self._grad_fn(base_score)
        return self._base_grad

    def predict_raw(self, data, start_iteration=0, num_iteration=-1,
                    predict_chunk=None):
        out = super().predict_raw(data, start_iteration, num_iteration,
                                  predict_chunk=predict_chunk)
        end = len(self.models) if num_iteration < 0 else \
            min(len(self.models), start_iteration + num_iteration)
        cnt = max(end - start_iteration, 1)
        return out / cnt


def create_boosting(config: Config, train_set: BinnedDataset,
                    objective: Optional[ObjectiveFunction] = None) -> GBDT:
    """Factory (ref: Boosting::CreateBoosting src/boosting/boosting.cpp:42)."""
    cls = {"gbdt": GBDT, "dart": DART, "rf": RF}.get(config.boosting)
    if cls is None:
        raise ValueError(f"Unknown boosting type: {config.boosting}")
    return cls(config, train_set, objective)
