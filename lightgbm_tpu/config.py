"""Parameter / config system.

TPU-native re-implementation of the reference config layer
(ref: include/LightGBM/config.h:41, src/io/config_auto.cpp alias tables).
A single flat dict of canonical parameters with alias resolution, typed
defaults, and `key=value` string parsing for CLI/config-file use
(ref: Config::KV2Map include/LightGBM/config.h:101).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

# ---------------------------------------------------------------------------
# Alias table (canonical name -> aliases), mirroring the semantics of the
# generated table in the reference (src/io/config_auto.cpp).
# ---------------------------------------------------------------------------
_ALIASES: Dict[str, List[str]] = {
    "config": ["config_file"],
    "task": ["task_type"],
    "objective": ["objective_type", "app", "application", "loss"],
    "boosting": ["boosting_type", "boost"],
    "data_sample_strategy": [],
    "data": ["train", "train_data", "train_data_file", "data_filename"],
    "valid": ["test", "valid_data", "valid_data_file", "test_data", "test_data_file", "valid_filenames"],
    "num_iterations": [
        "num_iteration", "n_iter", "num_tree", "num_trees", "num_round", "num_rounds",
        "nrounds", "num_boost_round", "n_estimators", "max_iter",
    ],
    "learning_rate": ["shrinkage_rate", "eta"],
    "num_leaves": ["num_leaf", "max_leaves", "max_leaf", "max_leaf_nodes"],
    "tree_learner": ["tree", "tree_type", "tree_learner_type"],
    "num_threads": ["num_thread", "nthread", "nthreads", "n_jobs"],
    "device_type": ["device"],
    "seed": ["random_seed", "random_state"],
    "deterministic": [],
    "force_col_wise": [],
    "force_row_wise": [],
    "histogram_pool_size": ["hist_pool_size"],
    "max_depth": [],
    "min_data_in_leaf": ["min_data_per_leaf", "min_data", "min_child_samples", "min_samples_leaf"],
    "min_sum_hessian_in_leaf": [
        "min_sum_hessian_per_leaf", "min_sum_hessian", "min_hessian", "min_child_weight",
    ],
    "bagging_fraction": ["sub_row", "subsample", "bagging"],
    "pos_bagging_fraction": ["pos_sub_row", "pos_subsample", "pos_bagging"],
    "neg_bagging_fraction": ["neg_sub_row", "neg_subsample", "neg_bagging"],
    "bagging_freq": ["subsample_freq"],
    "bagging_seed": ["bagging_fraction_seed"],
    "bagging_by_query": [],
    "feature_fraction": ["sub_feature", "colsample_bytree"],
    "feature_fraction_bynode": ["sub_feature_bynode", "colsample_bynode"],
    "feature_fraction_seed": [],
    "extra_trees": ["extra_tree"],
    "extra_seed": [],
    "early_stopping_round": [
        "early_stopping_rounds", "early_stopping", "n_iter_no_change",
    ],
    "early_stopping_min_delta": [],
    "first_metric_only": [],
    "max_delta_step": ["max_tree_output", "max_leaf_output"],
    "lambda_l1": ["reg_alpha", "l1_regularization"],
    "lambda_l2": ["reg_lambda", "lambda", "l2_regularization"],
    "linear_lambda": [],
    "min_gain_to_split": ["min_split_gain"],
    "drop_rate": ["rate_drop"],
    "max_drop": [],
    "skip_drop": [],
    "xgboost_dart_mode": [],
    "uniform_drop": [],
    "drop_seed": [],
    "top_rate": [],
    "other_rate": [],
    "min_data_per_group": [],
    "max_cat_threshold": [],
    "cat_l2": [],
    "cat_smooth": [],
    "max_cat_to_onehot": [],
    "top_k": ["topk"],
    "monotone_constraints": ["mc", "monotone_constraint", "monotonic_cst"],
    "monotone_constraints_method": ["monotone_constraining_method", "mc_method"],
    "monotone_penalty": ["monotone_splits_penalty", "ms_penalty", "mc_penalty"],
    "feature_contri": ["feature_contrib", "fc", "fp", "feature_penalty"],
    "forcedsplits_filename": ["fs", "forced_splits_filename", "forced_splits_file", "forced_splits"],
    "refit_decay_rate": [],
    "cegb_tradeoff": [],
    "cegb_penalty_split": [],
    "cegb_penalty_feature_lazy": [],
    "cegb_penalty_feature_coupled": [],
    "path_smooth": [],
    "interaction_constraints": [],
    "verbosity": ["verbose"],
    "input_model": ["model_input", "model_in"],
    "output_model": ["model_output", "model_out"],
    "saved_feature_importance_type": [],
    "snapshot_freq": ["save_period"],
    "linear_tree": ["linear_trees"],
    "max_bin": ["max_bins"],
    "max_bin_by_feature": [],
    "min_data_in_bin": [],
    "bin_construct_sample_cnt": ["subsample_for_bin"],
    "data_random_seed": ["data_seed"],
    "is_enable_sparse": ["is_sparse", "enable_sparse", "sparse"],
    "enable_bundle": ["is_enable_bundle", "bundle"],
    "max_conflict_rate": [],
    "use_missing": [],
    "zero_as_missing": [],
    "feature_pre_filter": [],
    "pre_partition": ["is_pre_partition"],
    "two_round": ["two_round_loading", "use_two_round_loading"],
    "header": ["has_header"],
    "label_column": ["label"],
    "weight_column": ["weight"],
    "group_column": ["group", "group_id", "query_column", "query", "query_id"],
    "ignore_column": ["ignore_feature", "blacklist"],
    "categorical_feature": ["cat_feature", "categorical_column", "cat_column"],
    "forcedbins_filename": [],
    "save_binary": ["is_save_binary", "is_save_binary_file"],
    "precise_float_parser": [],
    "parser_config_file": [],
    "start_iteration_predict": [],
    "num_iteration_predict": [],
    "predict_raw_score": ["is_predict_raw_score", "predict_rawscore", "raw_score"],
    "predict_leaf_index": ["is_predict_leaf_index", "leaf_index"],
    "predict_contrib": ["is_predict_contrib", "contrib"],
    "predict_disable_shape_check": [],
    "pred_early_stop": [],
    "pred_early_stop_freq": [],
    "pred_early_stop_margin": [],
    "output_result": ["predict_result", "prediction_result", "predict_name", "pred_name", "name_pred"],
    "convert_model_language": [],
    "convert_model": ["convert_model_file"],
    "objective_seed": [],
    "num_class": ["num_classes"],
    "is_unbalance": ["unbalance", "unbalanced_sets"],
    "scale_pos_weight": [],
    "sigmoid": [],
    "boost_from_average": [],
    "reg_sqrt": [],
    "alpha": [],
    "fair_c": [],
    "poisson_max_delta_step": [],
    "tweedie_variance_power": [],
    "lambdarank_truncation_level": [],
    "lambdarank_norm": [],
    "label_gain": [],
    "lambdarank_position_bias_regularization": [],
    "metric": ["metrics", "metric_types"],
    "metric_freq": ["output_freq"],
    "is_provide_training_metric": ["training_metric", "is_training_metric", "train_metric"],
    "eval_at": ["ndcg_eval_at", "ndcg_at", "map_eval_at", "map_at"],
    "multi_error_top_k": [],
    "auc_mu_weights": [],
    "num_machines": ["num_machine"],
    "local_listen_port": ["local_port", "port"],
    "time_out": [],
    "machine_list_filename": ["machine_list_file", "machine_list", "mlist"],
    "machines": ["workers", "nodes"],
    "gpu_platform_id": [],
    "gpu_device_id": [],
    "gpu_use_dp": [],
    "num_gpu": [],
    "use_quantized_grad": [],
    "num_grad_quant_bins": [],
    "quant_train_renew_leaf": [],
    "stochastic_rounding": [],
    # TPU-specific knobs (new in this framework)
    "trace_output": ["trace_file", "trace_path"],
    "tpu_hist_dtype": [],
    "tpu_num_shards": [],
    "tpu_donate_buffers": [],
    "tpu_wave_max": [],
    "tpu_hist_precision": [],
    "tpu_hist_impl": [],
    "tpu_hist_reduce": ["hist_reduce"],
    "tpu_sparse_hist": [],
    "tpu_bin_pack": ["bin_pack"],
    "tpu_stream": ["stream", "out_of_core"],
    "tpu_stream_slab_rows": ["stream_slab_rows", "slab_rows"],
    "tpu_fused_grad": ["fused_grad"],
    "tpu_wave_subtract": [],
    "deterministic_hist": ["tpu_deterministic_hist"],
    "tpu_dart_fused_max_bytes": [],
    "tpu_predict_chunk": ["predict_chunk", "predict_chunk_rows"],
    "tpu_shap": ["shap", "pred_contrib_device", "tpu_pred_contrib"],
    "tpu_preflight": ["preflight", "memory_preflight"],
    "tpu_health": ["health", "training_health"],
    "tpu_health_every": ["health_every", "health_check_every"],
    "tpu_compile_cache": ["compile_cache", "persistent_compile_cache"],
    "tpu_compile_cache_dir": ["compile_cache_dir"],
    "tpu_profile": ["profile", "device_profile"],
    "tpu_profile_window": ["profile_window", "profile_iters"],
    # resilience knobs (resilience/ subsystem)
    "tpu_checkpoint_every": ["checkpoint_every", "checkpoint_freq"],
    "tpu_checkpoint_path": ["checkpoint_path", "checkpoint_file"],
    "tpu_elastic_resume": ["elastic_resume"],
    "tpu_watchdog_deadline_s": ["watchdog_deadline_s", "watchdog_deadline"],
    "tpu_continual_rounds": ["continual_rounds"],
    "tpu_continual_retain": ["continual_retain", "continual_snapshots"],
    "tpu_continual_eval_fraction": ["continual_eval_fraction"],
    "tpu_continual_mode": ["continual_mode"],
    # serving knobs (serve/ subsystem)
    "serve_max_batch_rows": ["serve_max_batch"],
    "serve_max_wait_ms": ["serve_max_wait"],
    "serve_lowlat_max_rows": ["serve_lowlat_rows"],
    "serve_cache_bytes": ["serve_pack_budget_bytes"],
    "serve_request_rows": [],
    "serve_metrics_port": ["metrics_port"],
    "serve_deadline_ms": ["serve_deadline"],
    "serve_max_queue_rows": ["serve_queue_rows"],
    "serve_retry_max": ["serve_retries"],
    "serve_retry_backoff_ms": [],
    "serve_breaker_threshold": ["serve_breaker_failures"],
    "serve_breaker_reset_s": ["serve_breaker_reset"],
    "serve_artifact_dir": ["artifact_dir", "serve_artifacts_dir"],
    # serving-fleet knobs (serve/fleet.py)
    "serve_fleet_replicas": ["fleet_replicas"],
    "serve_probe_interval_ms": ["fleet_probe_interval_ms"],
    "serve_hedge_ms": ["fleet_hedge_ms"],
}

_ALIAS_TO_CANONICAL: Dict[str, str] = {}
for _canon, _al in _ALIASES.items():
    _ALIAS_TO_CANONICAL[_canon] = _canon
    for _a in _al:
        _ALIAS_TO_CANONICAL.setdefault(_a, _canon)

# keys already warned about as unsupported, process-wide (Booster and
# Dataset both build Configs from overlapping dicts; warn once per key)
_WARNED_UNSUPPORTED: set = set()

# Objective aliases (ref: config.h:136-160 objective name variants).
_OBJECTIVE_ALIASES = {
    "regression": "regression",
    "regression_l2": "regression",
    "l2": "regression",
    "mean_squared_error": "regression",
    "mse": "regression",
    "l2_root": "regression",
    "root_mean_squared_error": "regression",
    "rmse": "regression",
    "regression_l1": "regression_l1",
    "l1": "regression_l1",
    "mean_absolute_error": "regression_l1",
    "mae": "regression_l1",
    "huber": "huber",
    "fair": "fair",
    "poisson": "poisson",
    "quantile": "quantile",
    "mape": "mape",
    "mean_absolute_percentage_error": "mape",
    "gamma": "gamma",
    "tweedie": "tweedie",
    "binary": "binary",
    "multiclass": "multiclass",
    "softmax": "multiclass",
    "multiclassova": "multiclassova",
    "multiclass_ova": "multiclassova",
    "ova": "multiclassova",
    "ovr": "multiclassova",
    "cross_entropy": "cross_entropy",
    "xentropy": "cross_entropy",
    "cross_entropy_lambda": "cross_entropy_lambda",
    "xentlambda": "cross_entropy_lambda",
    "lambdarank": "lambdarank",
    "rank_xendcg": "rank_xendcg",
    "xendcg": "rank_xendcg",
    "xe_ndcg": "rank_xendcg",
    "xe_ndcg_mart": "rank_xendcg",
    "xendcg_mart": "rank_xendcg",
    "none": "none",
    "null": "none",
    "custom": "none",
    "na": "none",
}

# Metric aliases (ref: src/metric/metric.cpp:22-134).
_METRIC_ALIASES = {
    "l1": "l1", "mean_absolute_error": "l1", "mae": "l1", "regression_l1": "l1",
    "l2": "l2", "mean_squared_error": "l2", "mse": "l2", "regression": "l2", "regression_l2": "l2",
    "rmse": "rmse", "root_mean_squared_error": "rmse", "l2_root": "rmse",
    "quantile": "quantile",
    "huber": "huber",
    "fair": "fair",
    "poisson": "poisson",
    "mape": "mape", "mean_absolute_percentage_error": "mape",
    "gamma": "gamma",
    "gamma_deviance": "gamma_deviance",
    "tweedie": "tweedie",
    "ndcg": "ndcg", "lambdarank": "ndcg", "rank_xendcg": "ndcg", "xendcg": "ndcg",
    "xe_ndcg": "ndcg", "xe_ndcg_mart": "ndcg", "xendcg_mart": "ndcg",
    "map": "map", "mean_average_precision": "map",
    "auc": "auc",
    "average_precision": "average_precision",
    "binary_logloss": "binary_logloss", "binary": "binary_logloss",
    "binary_error": "binary_error",
    "auc_mu": "auc_mu",
    "multi_logloss": "multi_logloss", "multiclass": "multi_logloss",
    "softmax": "multi_logloss", "multiclassova": "multi_logloss",
    "multiclass_ova": "multi_logloss", "ova": "multi_logloss", "ovr": "multi_logloss",
    "multi_error": "multi_error",
    "cross_entropy": "cross_entropy", "xentropy": "cross_entropy",
    "cross_entropy_lambda": "cross_entropy_lambda", "xentlambda": "cross_entropy_lambda",
    "kullback_leibler": "kldiv", "kldiv": "kldiv",
    "r2": "r2",
    "none": "none", "null": "none", "custom": "none", "na": "none",
}

_BOOSTING_ALIASES = {
    "gbdt": "gbdt", "gbrt": "gbdt",
    "dart": "dart",
    "rf": "rf", "random_forest": "rf",
    "goss": "goss",  # legacy alias: boosting=goss => gbdt + goss sampling
}


@dataclasses.dataclass
class Config:
    """Canonical training configuration (ref: include/LightGBM/config.h:41)."""

    # Core
    task: str = "train"
    objective: str = "regression"
    boosting: str = "gbdt"
    data_sample_strategy: str = "bagging"
    num_iterations: int = 100
    learning_rate: float = 0.1
    num_leaves: int = 31
    tree_learner: str = "serial"
    num_threads: int = 0
    device_type: str = "tpu"
    seed: int = 0
    deterministic: bool = False

    # Learning control
    max_depth: int = -1
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    bagging_fraction: float = 1.0
    pos_bagging_fraction: float = 1.0
    neg_bagging_fraction: float = 1.0
    bagging_freq: int = 0
    bagging_seed: int = 3
    bagging_by_query: bool = False
    feature_fraction: float = 1.0
    feature_fraction_bynode: float = 1.0
    feature_fraction_seed: int = 2
    extra_trees: bool = False
    extra_seed: int = 6
    early_stopping_round: int = 0
    early_stopping_min_delta: float = 0.0
    first_metric_only: bool = False
    max_delta_step: float = 0.0
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    linear_lambda: float = 0.0
    min_gain_to_split: float = 0.0
    drop_rate: float = 0.1
    max_drop: int = 50
    skip_drop: float = 0.5
    xgboost_dart_mode: bool = False
    uniform_drop: bool = False
    drop_seed: int = 4
    top_rate: float = 0.2
    other_rate: float = 0.1
    min_data_per_group: int = 100
    max_cat_threshold: int = 32
    cat_l2: float = 10.0
    cat_smooth: float = 10.0
    max_cat_to_onehot: int = 4
    top_k: int = 20
    monotone_constraints: Any = None
    monotone_constraints_method: str = "basic"
    monotone_penalty: float = 0.0
    feature_contri: Any = None
    forcedsplits_filename: str = ""
    refit_decay_rate: float = 0.9
    cegb_tradeoff: float = 1.0
    cegb_penalty_split: float = 0.0
    cegb_penalty_feature_lazy: Any = None
    cegb_penalty_feature_coupled: Any = None
    path_smooth: float = 0.0
    interaction_constraints: Any = None
    verbosity: int = 1
    input_model: str = ""
    output_model: str = "LightGBM_model.txt"
    saved_feature_importance_type: int = 0
    snapshot_freq: int = -1
    linear_tree: bool = False

    # Dataset
    max_bin: int = 255
    max_bin_by_feature: Any = None
    min_data_in_bin: int = 3
    bin_construct_sample_cnt: int = 200000
    data_random_seed: int = 1
    is_enable_sparse: bool = True
    enable_bundle: bool = True
    max_conflict_rate: float = 0.0
    use_missing: bool = True
    zero_as_missing: bool = False
    feature_pre_filter: bool = True
    pre_partition: bool = False
    two_round: bool = False
    header: bool = False
    label_column: str = ""
    weight_column: str = ""
    group_column: str = ""
    ignore_column: str = ""
    categorical_feature: Any = ""
    forcedbins_filename: str = ""
    save_binary: bool = False
    precise_float_parser: bool = False

    # IO for train/predict
    data: str = ""
    valid: Any = None
    start_iteration_predict: int = 0
    num_iteration_predict: int = -1
    predict_raw_score: bool = False
    predict_leaf_index: bool = False
    predict_contrib: bool = False
    predict_disable_shape_check: bool = False
    pred_early_stop: bool = False
    pred_early_stop_freq: int = 10
    pred_early_stop_margin: float = 10.0
    output_result: str = "LightGBM_predict_result.txt"
    convert_model_language: str = ""
    convert_model: str = "gbdt_prediction.cpp"

    # Objective
    objective_seed: int = 5
    num_class: int = 1
    is_unbalance: bool = False
    scale_pos_weight: float = 1.0
    sigmoid: float = 1.0
    boost_from_average: bool = True
    reg_sqrt: bool = False
    alpha: float = 0.9
    fair_c: float = 1.0
    poisson_max_delta_step: float = 0.7
    tweedie_variance_power: float = 1.5
    lambdarank_truncation_level: int = 30
    lambdarank_norm: bool = True
    label_gain: Any = None
    lambdarank_position_bias_regularization: float = 0.0

    # Metric
    metric: Any = None
    metric_freq: int = 1
    is_provide_training_metric: bool = False
    eval_at: Any = None
    multi_error_top_k: int = 1
    auc_mu_weights: Any = None

    # Network
    num_machines: int = 1
    local_listen_port: int = 12400
    time_out: int = 120
    machine_list_filename: str = ""
    machines: str = ""

    # GPU compat (accepted, ignored on TPU)
    gpu_platform_id: int = -1
    gpu_device_id: int = -1
    gpu_use_dp: bool = False
    num_gpu: int = 1

    # Quantized-gradient training (ref: config.h use_quantized_grad)
    use_quantized_grad: bool = False
    num_grad_quant_bins: int = 4
    quant_train_renew_leaf: bool = False
    stochastic_rounding: bool = True

    # Observability: write a Chrome trace-event JSON of training spans
    # to this path at exit (param twin of LGBM_TPU_TRACE; obs/trace.py,
    # validated by tools/check_trace.py)
    trace_output: str = ""

    # TPU-specific
    tpu_hist_dtype: str = "float32"
    tpu_num_shards: int = 0  # 0 = use all local devices for data-parallel learner
    tpu_donate_buffers: bool = True
    # waved leaf-wise growth: batch histogram builds of up to this many
    # splits into one multi-leaf pass (0 = exact per-split builds).
    # Wave sizes follow a frontier-proportional schedule — see
    # learner._wave_schedule — so early splits stay near-exact; the cap
    # only bounds the LATE waves. 42 = the multi-leaf kernel's slot
    # count (128 MXU lanes // 3 channels); ~13 full-data histogram
    # passes per 255-leaf tree instead of 254, at quality parity on
    # binary/regression/ranking (tests/test_waved.py; parity-gated vs
    # the reference in tests/test_consistency.py's waved tier).
    #
    # Default -1 = AUTO: 42 for single-output models, 0 (exact) for
    # multiclass. Measured (round 5): the waved code path at wave size 1
    # is BIT-IDENTICAL to the exact grower, but any batching >= 2
    # perturbs softmax split order enough to drift multiclass logloss
    # calibration +0.08..+0.13 on the reference multiclass example
    # (auc_mu ordering stays better than the reference throughout) —
    # softmax's cross-class coupling makes tree structure order-critical,
    # so multiclass defaults to exact order. Set tpu_wave_max=42
    # explicitly to trade that calibration for ~20x fewer histogram
    # passes on large multiclass data.
    tpu_wave_max: int = -1
    # MXU precision of the histogram one-hot contraction: "default" =
    # one bf16 pass with f32 accumulation: the one-hot operand is exact
    # in bf16, each row's gradient and hessian are rounded to bf16 (8
    # significant bits, up to 2^-9 relative, and the same way for every
    # row that holds the same value: at a constant hessian the bins sum
    # to a total up to 0.2% off the gradients' own sum); "high" = 3
    # passes, "highest" = 6-pass f32 emulation. On the CPU (tests) every
    # mode is exact f32. The bins are therefore not sums of the
    # gradients, in any mode (the kernel also adds in its own order), and
    # a node's totals are read from its bins, never from the gradients
    # (ops/histogram.node_totals, ops/split._gain_tensors). What each
    # mode reads against the reference on the chip: PERF.md sections 2
    # and 7.1; no record compares their speed.
    tpu_hist_precision: str = "default"
    # histogram kernel implementation: "auto" = pallas on TPU backends /
    # one-hot XLA contraction elsewhere; "pallas" / "xla" force one
    # (pallas on CPU runs in interpret mode — tests use this to exercise
    # the kernel + its shard_map mesh wrapper without a chip)
    tpu_hist_impl: str = "auto"
    # data-parallel histogram reduction (parallel/scatter.py): "psum"
    # all-reduces full [F, B, 3] histograms every pass (the A/B
    # oracle); "scatter" reduce-scatters them over a static feature
    # partition — each shard aggregates + split-searches only its 1/W
    # feature slice and per-shard winners sync as ONE SplitInfo record
    # each (ref: data_parallel_tree_learner.cpp:287-297), cutting
    # collective bytes/iter ~W-fold with bit-identical models. "auto"
    # picks scatter on multi-device meshes when the feature count
    # partitions evenly (voting learner: always — it pads internally),
    # psum otherwise. EFB-bundled / COO-sparse / streamed storage and
    # single-device runs always use psum.
    tpu_hist_reduce: str = "auto"
    # sparse row-wise COO histograms for ultra-sparse non-bundleable
    # input (ref: multi_val_sparse_bin.hpp:21): "auto" picks COO when
    # the estimated O(nnz) segment-sum work beats the dense/EFB layout,
    # "force"/"off" override. Serial tree learner only.
    tpu_sparse_hist: str = "auto"
    # bit-packed bin storage (ops/bin_pack.py): "auto" packs the device
    # bin tensor to 4-bit nibbles when max_bin <= 15 (2-bit pairs when
    # <= 3), halving/quartering the dominant per-pass bin read of the
    # cost model; "off" keeps the uint8 layout (the parity oracle —
    # packed histogram + partition outputs are bit-identical to it on
    # integer-valued gradients, tests/test_bin_pack.py). Dense unbundled
    # serial storage only; EFB/COO/mesh layouts stay unpacked.
    tpu_bin_pack: str = "auto"
    # out-of-core streaming training (ROADMAP item 1; io/streaming.py +
    # learner.StreamTreeGrower): keep the [F, N] bin tensor HOST-
    # resident, cut into section-aligned row slabs that stream to the
    # device wave-by-wave, double-buffered so slab k+1 uploads while
    # the fused histogram/partition programs consume slab k. "auto"
    # streams only when lgb.preflight()'s analytic memory model says
    # resident training does NOT fit device capacity (never on CPU
    # where capacity is unknown, unless LGBM_TPU_HBM_BYTES is set);
    # "on" forces streaming (raises when the shape is ineligible:
    # EFB/COO storage, forced splits, exact-order growth, interaction
    # or pairwise-monotone constraints, linear trees); "off" never
    # streams. Single-slab streamed models are bit-identical to
    # resident ones; quantized (int8-histogram) streaming is
    # bit-identical at ANY slab count (integer partial sums); plain
    # f32 multi-slab accumulation carries ~1-ulp-per-slab float-add
    # association drift.
    tpu_stream: str = "auto"
    # streaming slab size in rows; 0 = auto (the largest
    # section-aligned slab whose double-buffered working set fits the
    # capacity left after the resident row state — obs/memory.
    # stream_auto_slab_rows). Rounded up to the slab alignment
    # (pack-factor x 2048 rows) so every full slab shares one compiled
    # program shape.
    tpu_stream_slab_rows: int = 0
    # fuse the gradient/bagging element-wise pass into the histogram
    # waves: the objective's pointwise gradient (objectives.
    # pointwise_grad_fn — binary, L2 regression) is evaluated inside the
    # waved grower — and, on the pallas path, inside the multi-leaf
    # KERNEL itself, so the [N, 3] ghT operand never round-trips through
    # HBM (~0.5 GB/iter at Higgs shape). "auto" = on whenever the
    # objective supports it on the waved single-output fast path (no
    # GOSS, no quantized gradients); "on" forces it (XLA path included —
    # bitwise-identical gradients by construction); "off" disables.
    # The in-kernel histogram accumulation order matches the unfused
    # kernel exactly; only derived root-sum reductions are subject to
    # normal f32 reduction-order tolerance.
    tpu_fused_grad: str = "auto"
    # sibling histograms by subtraction (build the smaller child, derive
    # the larger from the pooled parent — serial_tree_learner.cpp:582),
    # with the wave schedule packing ONE slot per split. False = the
    # no-subtraction oracle: both children built directly, two slots
    # per split, ~17 instead of ~13 full-data passes at 255 leaves.
    # Documented tolerance: subtraction reorders f32 accumulation
    # (parent - small vs direct build), so the two modes agree to
    # normal cancellation tolerance, not bitwise. The obs
    # `hist_traffic` counters report both cost models.
    tpu_wave_subtract: bool = True
    # opt-in deterministic histogram accumulation (ROADMAP item 4's
    # numeric-parity debt): forces the XLA histogram path with
    # fixed-size chunking and Kahan-compensated cross-chunk sums, so
    # results are insensitive (to ~1 ulp) to chunking and to how
    # sharding regroups rows. Costs the pallas kernel's bandwidth
    # advantage — a parity/debug mode, not the perf path.
    deterministic_hist: bool = False
    # DART fused-path budget: the per-tree leaf-assignment history
    # ([T, K, N] device buffer that lets dropped-tree contributions be
    # recomputed without host round-trips) is only kept below this many
    # bytes; above it DART falls back to the host loop.
    tpu_dart_fused_max_bytes: int = 2 << 30
    # serving: rows per device dispatch of the streaming prediction
    # engine (ops/predict.py predict_raw_cached). Chunks are
    # shape-bucketed — full chunks run at exactly this size, the uneven
    # tail pads up to a power-of-two bucket — so any N reuses a small
    # fixed set of compiled traversal programs.
    tpu_predict_chunk: int = 1 << 20
    # TreeSHAP routing for predict(pred_contrib=True): "auto"/"on" run
    # the batched path-decomposed device kernel (ops/shap.py) — linear-
    # tree models always take the host path, which raises the
    # reference's linear-tree restriction — "off" forces the host
    # recursion (the parity oracle). Row chunks reuse
    # tpu_predict_chunk, internally capped (the per-row working set
    # scales with paths x depth, so SHAP streams smaller blocks).
    tpu_shap: str = "auto"
    # HBM capacity preflight (obs/memory.py): the analytic peak-memory
    # model is compared against device capacity at booster construction;
    # "warn" logs the verdict plus concrete knob recommendations when it
    # doesn't fit, "error" raises PreflightError (fail fast instead of
    # OOMing mid-run), "off" publishes the model through obs meta but
    # never judges. No effect on backends that report no memory stats
    # (CPU) unless LGBM_TPU_HBM_BYTES overrides the capacity.
    tpu_preflight: str = "warn"
    # training-health sentinels (obs/health.py): per-iteration NaN/Inf
    # sentinel counts folded into the fused training programs, plus
    # cross-shard drift digests of replicated state on multi-device
    # meshes. "off" (default) = guard-check-only no-op; "warn" records
    # the finding (obs counters + a log warning) and keeps training;
    # "error" raises the structured alarm (NonFiniteError / DriftError)
    # at the iteration that produced it — a diverged or NaN-poisoned
    # model fails fast instead of surfacing as a bad eval many
    # iterations later. Trained model bytes are bit-identical on vs off
    # (the sentinel adds pure reductions as extra program outputs).
    tpu_health: str = "off"
    # check period of the tpu_health sentinels (and of the telemetry
    # straggler probe): every N iterations. 1 = every iteration; larger
    # values amortize the tiny host sync the sentinel read costs.
    tpu_health_every: int = 1
    # device-time profiling window (obs/profile.py). "off" (default) =
    # one attribute check per program dispatch. "window" opens a
    # capture window at iteration 1 (after the compile-heavy first
    # iteration) spanning tpu_profile_window iterations: with
    # LGBM_TPU_PROFILE_DIR set the real jax.profiler trace is captured
    # and parsed into per-program device-busy seconds; without it the
    # profiler-free fallback re-times every instrumented dispatch with
    # a block_until_ready sync plus AOT micro-reruns at window close —
    # the same attribution pipeline, usable on CPU CI. "bench" keeps
    # the window open for the whole run (bench.py arms this itself
    # around its measured loop). Capture only adds syncs — trained
    # model bytes are bit-identical profiling on vs off. Results:
    # obs.profile.global_profile.summary()/roofline(), the
    # lgbmtpu_profile_* OpenMetrics families, bench JSON
    # device_seconds_by_tag/roofline, and a device lane in the Chrome
    # trace export. With LGBM_TPU_PROFILE_DIR set the window's profiler
    # trace also gives device_seconds_by_layer (the lgbm/<layer> scopes).
    tpu_profile: str = "off"
    tpu_profile_window: int = 5
    # persistent XLA compile cache (compile_cache.py; ROADMAP item 2 —
    # kill cold start). Where the environment sets
    # JAX_COMPILATION_CACHE_DIR that directory is the cache and neither
    # knob places it elsewhere. Otherwise "auto" (default) arms
    # jax.config.jax_compilation_cache_dir at the train/serve entry
    # UNLESS jax.config already names one; "on" forces it to
    # tpu_compile_cache_dir (default the repo-local .jax_cache); "off"
    # opts this entry point out without disarming anything. A
    # cache-warm second process pays ~zero compile seconds for the same
    # programs (bench.py --coldstart measures it; perf-gate check 10
    # caps it). Framework-owned cache dirs are LRU-pruned once per
    # process to LGBM_TPU_COMPILE_CACHE_MAX_BYTES (default 4 GiB).
    tpu_compile_cache: str = "auto"
    tpu_compile_cache_dir: str = ""
    # fault-tolerant training (resilience/checkpoint.py). With
    # tpu_checkpoint_path set, engine.train snapshots FULL boosting
    # state (trees + scores + sampling masks + RNG streams + DART drop
    # bookkeeping + best-iteration) atomically every
    # tpu_checkpoint_every iterations, installs a SIGTERM handler that
    # finishes the in-flight iteration, snapshots, and exits with code
    # 75 (EXIT_PREEMPTED), and RESUMES from an existing checkpoint at
    # the same path — train-N-straight == train-k/kill/resume/train-
    # (N-k) bit-identically (tests/test_resilience.py). Checkpoints
    # carry a SHA-256 digest footer; a corrupt/truncated file raises
    # CorruptCheckpointError instead of resuming on torn state.
    # tpu_checkpoint_every=0 still snapshots on SIGTERM, just never
    # periodically.
    tpu_checkpoint_every: int = 0
    tpu_checkpoint_path: str = ""
    # elastic resume (resilience/elastic.py): a checkpoint whose
    # fingerprint differs from the rebuilt run in MESH SHAPE ONLY
    # (tpu_num_shards drift — W-shard snapshot restored on a W'-shard
    # mesh) is re-sharded through the rebuilt booster's sharding and
    # admitted after a cross-shard drift-digest gate on the restored
    # state (ElasticResumeError names any diverged shard before it
    # votes). false = any fingerprint drift, mesh included, refuses
    # with ResumeMismatchError. Structural drift (objective, dataset
    # shape, tree counts) ALWAYS refuses.
    tpu_elastic_resume: bool = True
    # distributed-training watchdog (resilience/watchdog.py). With
    # tpu_watchdog_deadline_s > 0, engine.train runs a per-iteration
    # heartbeat allgather (reusing the obs/health straggler machinery)
    # bounded by this deadline: a peer that hangs mid-collective turns
    # the infinite stall into a structured PeerLostError within the
    # deadline, the flight recorder dumps a postmortem, a checkpoint is
    # written (tpu_checkpoint_path set), and the process exits with
    # code 75 (EXIT_PREEMPTED) so a supervisor restarts the survivors
    # on a shrunk mesh through the elastic-resume path. 0 = watchdog
    # off (single-host default — collectives can't be peer-hung).
    tpu_watchdog_deadline_s: float = 0.0
    # continual training (resilience/continual.py; lgb.continual_train).
    # Each ingested chunk trains one GENERATION of tpu_continual_rounds
    # extra iterations onto the long-lived model ("extend" mode;
    # "refit" refreshes leaf values on the fresh chunk instead, decay
    # refit_decay_rate). A held-out tpu_continual_eval_fraction slice
    # of every chunk feeds the obs/health eval NaN/spike/plateau
    # anomaly detector — the automatic accept-vs-rollback trigger; a
    # rejected generation restores the last-good snapshot (bounded at
    # tpu_continual_retain retained generations). Accepted generations
    # hot-swap into the serve registry through the transactional
    # validate-predict path with a bit-identical-on-reload assertion,
    # so a rolled-back generation is never observable from the serve
    # side. Exported as lgbmtpu_continual_* (obs/export.py).
    tpu_continual_rounds: int = 10
    tpu_continual_retain: int = 3
    tpu_continual_eval_fraction: float = 0.2
    tpu_continual_mode: str = "extend"
    # serving (serve/ async model server; task=serve and the in-process
    # API). Micro-batching: requests coalesce until serve_max_batch_rows
    # rows are pending or the OLDEST pending request has waited
    # serve_max_wait_ms; requests of <= serve_lowlat_max_rows rows skip
    # the batcher entirely and dispatch through the AOT-compiled
    # low-latency path. serve_cache_bytes bounds the total packed-
    # ensemble bytes the multi-tenant registry keeps resident (LRU pack
    # eviction; 0 = unbounded). serve_request_rows is the CLI replay's
    # rows-per-request (0 = a mixed small/large size cycle).
    # serve_metrics_port exposes /metrics + /healthz + /readyz on
    # task=serve (obs/export.py): -1 = off, 0 = ephemeral port (logged
    # in the stats line), >0 = that port.
    serve_max_batch_rows: int = 8192
    serve_max_wait_ms: float = 2.0
    serve_lowlat_max_rows: int = 64
    serve_cache_bytes: int = 1 << 30
    serve_request_rows: int = 0
    serve_metrics_port: int = -1
    # serving graceful degradation (resilience/degrade.py). Per-request
    # deadline: a request older than serve_deadline_ms fails fast with
    # a structured DeadlineExceeded instead of occupying the batcher
    # (0 = no deadline). Bounded admission: when more than
    # serve_max_queue_rows rows are already queued/in flight, new
    # arrivals are shed with ServerOverloaded carrying retry-after
    # semantics (0 = unbounded). Transient registry pack/compile
    # failures retry with exponential backoff (serve_retry_max
    # attempts, base serve_retry_backoff_ms). A model whose dispatches
    # keep faulting trips a per-model circuit breaker after
    # serve_breaker_threshold consecutive failures (0 = breaker off);
    # the breaker fails fast for serve_breaker_reset_s seconds, then
    # half-opens one probe. All events are counted in obs.metrics and
    # exported as lgbmtpu_resilience_* OpenMetrics families.
    serve_deadline_ms: float = 0.0
    serve_max_queue_rows: int = 0
    serve_retry_max: int = 2
    serve_retry_backoff_ms: float = 10.0
    serve_breaker_threshold: int = 5
    serve_breaker_reset_s: float = 30.0
    # serialized AOT serving artifacts (serve/artifacts.py): when set,
    # every low-latency executable a model compiles is exported to this
    # directory (jax.experimental.serialize_executable), keyed by an
    # artifact fingerprint (format version + jax/jaxlib + backend +
    # packed-ensemble digest + bucket/width), and ModelServer.warm() /
    # LRU re-admission re-import instead of recompiling — a replica
    # restart warms from disk in milliseconds with ZERO
    # serve/lowlat compiles (obs-counter-asserted by
    # tools/check_coldstart.py). Any fingerprint mismatch falls back to
    # a fresh compile with bit-identical predictions either way.
    # Empty = off.
    serve_artifact_dir: str = ""
    # serving fleet (serve/fleet.py FleetRouter): N ModelServer
    # replicas behind health-gated routing. serve_fleet_replicas sizes
    # the fleet (task-level drivers and bench.py --fleet build this
    # many in-process replicas; tools/check_fleet.py spawns them as
    # subprocesses). serve_probe_interval_ms paces the /readyz +
    # /healthz probe loop that drives the quarantine/reinstate state
    # machine. serve_hedge_ms > 0 arms hedged dispatch: a request
    # still unanswered after this many ms fires a duplicate on another
    # healthy replica and the first answer wins (bit-identical by the
    # pack contract, asserted) — a p99 tail cutter that costs duplicate
    # work, so off (0) by default.
    serve_fleet_replicas: int = 3
    serve_probe_interval_ms: float = 50.0
    serve_hedge_ms: float = 0.0

    # stash for unknown params (kept for forward-compat, like reference ignores)
    extra_params: Dict[str, Any] = dataclasses.field(default_factory=dict)

    # ------------------------------------------------------------------
    @staticmethod
    def canonical_key(key: str) -> str:
        return _ALIAS_TO_CANONICAL.get(key, key)

    @classmethod
    def from_params(cls, params: Optional[Dict[str, Any]]) -> "Config":
        cfg = cls()
        cfg.update(params or {})
        return cfg

    def update(self, params: Dict[str, Any]) -> None:
        field_types = {f.name: f.type for f in dataclasses.fields(self)}
        canon_params: Dict[str, Any] = {}
        for key, value in params.items():
            canon = self.canonical_key(str(key))
            # first alias wins for conflicting duplicates, matching reference
            # KeyAliasTransform behavior of preferring the canonical key
            if canon in canon_params and key != canon:
                continue
            canon_params[canon] = value
        for key, value in canon_params.items():
            if key in field_types and key != "extra_params":
                setattr(self, key, _coerce(value, getattr(self, key)))
            else:
                self.extra_params[key] = value
        if not hasattr(self, "explicit_keys"):
            self.explicit_keys = set()
        new_keys = set(canon_params) - self.explicit_keys
        self.explicit_keys.update(canon_params)
        self._post_process()
        # warn only for keys newly set by THIS update: Booster and
        # Dataset each build a Config from overlapping param dicts and
        # the warning should fire once per distinct user setting
        self._warn_unsupported(new_keys)

    # params that are accepted (for config compatibility) but have no
    # effect in this build; explicitly setting one warns instead of
    # silently no-oping. Audited by tests/test_param_honesty.py.
    _UNSUPPORTED_EXPLICIT = {
        "two_round": "two-round loading is not needed (single in-memory "
                     "binning pass)",
        "pre_partition": "pre-partitioned loading is not implemented",
        "gpu_platform_id": "OpenCL params are ignored on TPU",
        "gpu_device_id": "OpenCL params are ignored on TPU",
        "gpu_use_dp": "OpenCL params are ignored on TPU",
        "num_gpu": "multi-device training uses the TPU mesh "
                   "(tpu_num_shards), not num_gpu",
    }

    def _warn_unsupported(self, new_keys) -> None:
        from . import log
        # self.verbosity is already set by this update(); honor it even
        # before the Booster installs the global log level (verbosity=-1
        # in the same params dict must silence these, like the reference)
        if self.verbosity < 0:
            return
        for key, msg in self._UNSUPPORTED_EXPLICIT.items():
            if key in new_keys and key not in _WARNED_UNSUPPORTED:
                _WARNED_UNSUPPORTED.add(key)
                log.warning(f"{key} has no effect: {msg}")

    def _post_process(self) -> None:
        self.objective = _OBJECTIVE_ALIASES.get(str(self.objective).lower(), self.objective)
        boosting = _BOOSTING_ALIASES.get(str(self.boosting).lower(), self.boosting)
        if boosting == "goss":
            boosting = "gbdt"
            self.data_sample_strategy = "goss"
        self.boosting = boosting
        if self.objective in ("multiclass", "multiclassova") and self.num_class <= 1:
            raise ValueError("num_class must be >1 for multiclass objectives")
        if self.metric is None:
            metrics = []
        elif isinstance(self.metric, str):
            metrics = [m for m in self.metric.split(",") if m]
        else:
            metrics = list(self.metric)
        self.metric = [_METRIC_ALIASES.get(str(m).lower(), str(m)) for m in metrics]
        if self.eval_at is None:
            self.eval_at = [1, 2, 3, 4, 5]
        elif isinstance(self.eval_at, str):
            self.eval_at = [int(x) for x in self.eval_at.split(",") if x]
        else:
            self.eval_at = [int(x) for x in self.eval_at]
        if self.valid is None:
            self.valid = []
        elif isinstance(self.valid, str):
            self.valid = [v for v in self.valid.split(",") if v]

    def default_metric(self) -> List[str]:
        """Metric implied by the objective when none requested (ref: config.cpp)."""
        obj_to_metric = {
            "regression": "l2", "regression_l1": "l1", "huber": "huber", "fair": "fair",
            "poisson": "poisson", "quantile": "quantile", "mape": "mape",
            "gamma": "gamma", "tweedie": "tweedie",
            "binary": "binary_logloss",
            "multiclass": "multi_logloss", "multiclassova": "multi_logloss",
            "cross_entropy": "cross_entropy", "cross_entropy_lambda": "cross_entropy_lambda",
            "lambdarank": "ndcg", "rank_xendcg": "ndcg",
        }
        m = obj_to_metric.get(self.objective)
        return [m] if m else []

    def to_params(self) -> Dict[str, Any]:
        out = dataclasses.asdict(self)
        out.pop("extra_params", None)
        return out

    # ------------------------------------------------------------------
    @staticmethod
    def kv2map(args: List[str]) -> Dict[str, str]:
        """Parse `key=value` CLI tokens (ref: Config::KV2Map config.h:101)."""
        out: Dict[str, str] = {}
        for arg in args:
            arg = arg.strip()
            if not arg or arg.startswith("#"):
                continue
            if "=" not in arg:
                continue
            key, value = arg.split("=", 1)
            key = key.strip()
            value = value.split("#", 1)[0].strip()
            if key:
                out[key] = value
        return out


def _coerce(value: Any, default: Any) -> Any:
    if default is None or value is None:
        return value
    if isinstance(default, bool):
        if isinstance(value, str):
            return value.strip().lower() in ("true", "1", "yes", "+", "on")
        return bool(value)
    if isinstance(default, int) and not isinstance(default, bool):
        return int(float(value)) if not isinstance(value, int) else value
    if isinstance(default, float):
        return float(value)
    if isinstance(default, str):
        return str(value)
    if isinstance(default, list) and isinstance(value, str):
        return [v for v in value.split(",") if v]
    return value
