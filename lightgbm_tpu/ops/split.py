"""Best-split search over histograms (device).

TPU-native replacement for the reference split kernels
(ref: src/treelearner/feature_histogram.hpp:166 FindBestThreshold,
src/treelearner/cuda/cuda_best_split_finder.cu:776). The per-feature
sequential threshold scan becomes a fully vectorized prefix-sum + gain
evaluation over ``[F, B]`` with a global argmax, evaluated for both
missing-value directions (the reference's two-direction scan).

Split semantics (numerical): rows with ``bin <= threshold`` go left; the
NaN bin (when missing_type == NAN) is the feature's last bin and goes to
the side indicated by ``default_left``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from .histogram import GRAD, HESS, COUNT
from ..obs.metrics import global_metrics

MISSING_NONE, MISSING_ZERO, MISSING_NAN = 0, 1, 2
K_MIN_SCORE = -1e30
# Tie-rejection band for the net-gain acceptance, relative to the
# parent-gain shift. L1-family gradients are lattice-valued (e.g.
# quantile: every grad is 1-alpha or -alpha), so candidate splits with
# EXACTLY zero net improvement are structural, not rare — and f32
# accumulation noise between two compilations of the same math (the
# fused one-program iteration vs the standalone grower; XLA contracts
# them differently) lands on either side of a strict `> 0` cut,
# flipping whether a worthless split is made. Requiring the net gain to
# clear a noise-sized band keeps both programs' verdicts identical on
# structural ties while rejecting nothing a f32 pipeline could
# meaningfully resolve (tests/test_engine.py::TestFusedRenewal).
K_GAIN_TIE_RTOL = 1e-5
K_EPSILON = 1e-15


class SplitHyperParams(NamedTuple):
    """Dynamic (traced) regularization scalars (ref: config.h)."""
    lambda_l1: jax.Array
    lambda_l2: jax.Array
    min_data_in_leaf: jax.Array
    min_sum_hessian_in_leaf: jax.Array
    min_gain_to_split: jax.Array
    max_delta_step: jax.Array
    path_smooth: jax.Array     # (ref: config.h path_smooth)
    cegb_split_pen: jax.Array  # cegb_tradeoff * cegb_penalty_split
    cat_l2: jax.Array          # extra L2 for categorical subset splits
    cat_smooth: jax.Array      # grad/hess ratio smoothing + count filter
    max_cat_threshold: jax.Array   # max categories sent left
    max_cat_to_onehot: jax.Array   # one-hot below this many bins
    min_data_per_group: jax.Array  # min data per categorical group
    monotone_penalty: jax.Array    # gain penalty on monotone splits

    @classmethod
    def from_config(cls, cfg) -> "SplitHyperParams":
        f = jnp.float32
        return cls(
            lambda_l1=jnp.asarray(cfg.lambda_l1, f),
            lambda_l2=jnp.asarray(cfg.lambda_l2, f),
            min_data_in_leaf=jnp.asarray(cfg.min_data_in_leaf, f),
            min_sum_hessian_in_leaf=jnp.asarray(
                max(cfg.min_sum_hessian_in_leaf, K_EPSILON), f),
            min_gain_to_split=jnp.asarray(cfg.min_gain_to_split, f),
            max_delta_step=jnp.asarray(cfg.max_delta_step, f),
            path_smooth=jnp.asarray(cfg.path_smooth, f),
            cegb_split_pen=jnp.asarray(
                cfg.cegb_tradeoff * cfg.cegb_penalty_split, f),
            cat_l2=jnp.asarray(cfg.cat_l2, f),
            cat_smooth=jnp.asarray(cfg.cat_smooth, f),
            max_cat_threshold=jnp.asarray(cfg.max_cat_threshold, jnp.int32),
            max_cat_to_onehot=jnp.asarray(cfg.max_cat_to_onehot, jnp.int32),
            min_data_per_group=jnp.asarray(cfg.min_data_per_group, f),
            monotone_penalty=jnp.asarray(cfg.monotone_penalty, f),
        )


class FeatureMeta(NamedTuple):
    """Static per-feature binning metadata, as device arrays.

    num_bins: [F] actual bin count per feature (<= B).
    missing_type: [F] MISSING_* code.
    default_bin: [F] bin that value 0.0 maps to.
    is_categorical: [F] bool.
    monotone: [F] int8 in {-1, 0, +1}.
    penalty: [F] multiplicative gain penalty (feature_contri; 1.0 = none).
    """
    num_bins: jax.Array
    missing_type: jax.Array
    default_bin: jax.Array
    is_categorical: jax.Array
    monotone: jax.Array
    penalty: jax.Array
    cegb_feat: jax.Array  # [F] additive gain penalty (CEGB coupled, pre-scaled)
    cegb_lazy: jax.Array  # [F] per-row additive penalty (CEGB lazy, pre-scaled)


class SplitInfo(NamedTuple):
    """Best split for one leaf — scalar fields (ref: split_info.hpp:22).

    cat_mask: [B] bool — for categorical splits, the set of bins sent left
    (the device analog of the reference's cat_threshold bitset,
    split_info.hpp cat_threshold / tree.h:375). All-False for numerical.
    """
    gain: jax.Array          # gain above (parent_gain + min_gain_to_split); <=0 => no split
    feature: jax.Array       # int32 feature index
    threshold: jax.Array     # int32 bin threshold (bin <= threshold -> left)
    default_left: jax.Array  # bool
    left_sum_grad: jax.Array
    left_sum_hess: jax.Array
    left_count: jax.Array
    right_sum_grad: jax.Array
    right_sum_hess: jax.Array
    right_count: jax.Array
    left_output: jax.Array
    right_output: jax.Array
    cat_mask: jax.Array      # [B] bool, bins going left (categorical)


def split_info_nbytes(max_bins: int) -> int:
    """Wire size of ONE SplitInfo record: 11 four-byte scalar fields
    (gain, feature, threshold, 6 child sums, 2 outputs) + the
    default_left bool + the [max_bins] bool cat_mask. This is the
    all_gather payload unit of the reduce-scatter learner's winner
    sync (ref: SyncUpGlobalBestSplit ships sizeof(SplitInfo) per
    machine, data_parallel_tree_learner.cpp:297) — O(bytes) per split,
    vs O(F * B) for a full histogram row."""
    return 11 * 4 + 1 + max_bins


def threshold_l1(s: jax.Array, l1: jax.Array) -> jax.Array:
    """Soft-threshold by lambda_l1 (ref: feature_histogram.hpp ThresholdL1)."""
    return jnp.sign(s) * jnp.maximum(jnp.abs(s) - l1, 0.0)


def leaf_output(sum_grad, sum_hess, hp: SplitHyperParams):
    """Optimal leaf value -TL1(G)/(H+l2), clipped by max_delta_step
    (ref: feature_histogram.hpp CalculateSplittedLeafOutput)."""
    raw = -threshold_l1(sum_grad, hp.lambda_l1) / (sum_hess + hp.lambda_l2)
    return jnp.where(hp.max_delta_step > 0,
                     jnp.clip(raw, -hp.max_delta_step, hp.max_delta_step), raw)


def leaf_gain_given_output(sum_grad, sum_hess, output, hp: SplitHyperParams):
    """-(2*TL1(G)*w + (H+l2)*w^2) — equals TL1(G)^2/(H+l2) at the optimum
    (ref: feature_histogram.hpp GetLeafGainGivenOutput)."""
    g = threshold_l1(sum_grad, hp.lambda_l1)
    return -(2.0 * g * output + (sum_hess + hp.lambda_l2) * output * output)


def leaf_gain(sum_grad, sum_hess, hp: SplitHyperParams):
    return leaf_gain_given_output(sum_grad, sum_hess,
                                  leaf_output(sum_grad, sum_hess, hp), hp)


def smooth_output(raw, count, parent_output, hp: SplitHyperParams):
    """Path smoothing: pull a leaf's output toward its parent's,
    weighted by leaf size (ref: feature_histogram.hpp
    CalculateSplittedLeafOutput USE_SMOOTHING branch:
    w' = w * (n/a)/(n/a+1) + parent/(n/a+1), a = path_smooth)."""
    ratio = count / jnp.maximum(hp.path_smooth, K_EPSILON)
    smoothed = (raw * ratio + parent_output) / (ratio + 1.0)
    return jnp.where(hp.path_smooth > 0, smoothed, raw)


def leaf_output_smooth(sum_grad, sum_hess, count, parent_output,
                       hp: SplitHyperParams):
    return smooth_output(leaf_output(sum_grad, sum_hess, hp), count,
                         parent_output, hp)


def propagate_monotone_bounds(out_l, out_r, mono_t, is_cat_split,
                              p_minb, p_maxb):
    """Children's output bounds after a split (basic method,
    ref: monotone_constraints.hpp:466 Update): a numerical split on a
    monotone feature pins the children's shared boundary at the midpoint
    of their outputs. Returns (l_min, l_max, r_min, r_max)."""
    upd = ~is_cat_split & (mono_t != 0)
    mid = (out_l + out_r) * 0.5
    l_max = jnp.where(upd & (mono_t > 0), jnp.minimum(p_maxb, mid), p_maxb)
    l_min = jnp.where(upd & (mono_t < 0), jnp.maximum(p_minb, mid), p_minb)
    r_min = jnp.where(upd & (mono_t > 0), jnp.maximum(p_minb, mid), p_minb)
    r_max = jnp.where(upd & (mono_t < 0), jnp.minimum(p_maxb, mid), p_maxb)
    return l_min, l_max, r_min, r_max


def compute_box_bounds(box_lo, box_hi, outputs, leaf_valid, monotone):
    """Exact pairwise leaf-output bounds for the `intermediate` and
    `advanced` monotone methods — the TPU-native re-architecture of
    IntermediateLeafConstraints / AdvancedLeafConstraints (ref:
    monotone_constraints.hpp:517,859).

    The reference refines its basic midpoint constraints by recursively
    walking the tree (GoUpToFindLeavesToUpdate / GoDown…, hpp:625,707)
    to find leaves whose feature ranges are contiguous to a changed
    leaf, with per-threshold cumulative extremum arrays in the advanced
    mode. Here the same information lives in flat per-leaf FEATURE-RANGE
    BOXES, and the true constraint set is computed exactly in one
    vectorized pass: monotonicity along feature f relates leaves a, b
    iff their boxes overlap in every other feature and a's f-range lies
    strictly below b's (leaf boxes partition the space, so overlapping
    everywhere else forces disjoint f-ranges). `out_a <= out_b` over
    exactly those pairs is the minimal sound constraint set — it
    subsumes both reference methods (their ancestor-based sets are
    supersets of these pairs), so one mechanism serves both modes.

    box_lo/box_hi: [L, F] int32 inclusive bin ranges; outputs: [L];
    leaf_valid: [L] bool (slots in use); monotone: [F] in {-1, 0, +1}.
    Returns (min_bound, max_bound): [L] f32.
    """
    f32 = outputs.dtype
    num_l, num_f = box_lo.shape

    # Never materialize [L, L, F]: at F=10k (the wide-sparse regime)
    # that is ~650M elements per scan step. Everything stays [L, L] via
    # a rolled loop over features.
    def _ov(f):
        return ((box_lo[:, None, f] <= box_hi[None, :, f])
                & (box_lo[None, :, f] <= box_hi[:, None, f]))

    ov_cnt = lax.fori_loop(
        0, num_f,
        lambda f, acc: acc + _ov(f).astype(jnp.int32),
        jnp.zeros((num_l, num_l), jnp.int32))

    def _accum(f, p_rel):
        # overlap in all features except f <=> ov_cnt - ov_f == F-1
        rel = ((box_hi[:, None, f] < box_lo[None, :, f])
               & ((ov_cnt - _ov(f).astype(jnp.int32)) == (num_f - 1)))
        m = monotone[f]
        return p_rel | (rel & (m > 0)) | (rel.T & (m < 0))

    # P[a, b] = "out_a <= out_b required"
    p_rel = lax.fori_loop(0, num_f, _accum,
                          jnp.zeros((num_l, num_l), jnp.bool_))
    p_rel = p_rel & leaf_valid[:, None] & leaf_valid[None, :]
    inf = jnp.asarray(jnp.inf, f32)
    max_bound = jnp.min(jnp.where(p_rel, outputs[None, :], inf), axis=1)
    min_bound = jnp.max(jnp.where(p_rel, outputs[:, None], -inf), axis=0)
    return min_bound, max_bound


def split_child_boxes(box_lo, box_hi, leaf, new_leaf, feat, thr,
                      is_cat_split, valid):
    """Update leaf boxes after applying a split: left keeps `leaf`'s id
    with f-range capped at thr, right (`new_leaf`) starts at thr+1.
    Categorical splits leave both ranges untouched (no order semantics;
    the reference likewise descends categorical children conservatively,
    monotone_constraints.hpp:598-601)."""
    p_lo, p_hi = box_lo[leaf], box_hi[leaf]
    l_hi = jnp.where(is_cat_split, p_hi, p_hi.at[feat].set(
        jnp.minimum(p_hi[feat], thr)))
    r_lo = jnp.where(is_cat_split, p_lo, p_lo.at[feat].set(
        jnp.maximum(p_lo[feat], thr + 1)))
    box_lo = box_lo.at[new_leaf].set(jnp.where(valid, r_lo,
                                               box_lo[new_leaf]))
    box_hi = box_hi.at[leaf].set(jnp.where(valid, l_hi, box_hi[leaf]))
    box_hi = box_hi.at[new_leaf].set(jnp.where(valid, p_hi,
                                               box_hi[new_leaf]))
    return box_lo, box_hi


def _monotone_penalty_factor(depth, hp: SplitHyperParams):
    """Multiplicative gain penalty for splits on monotone-constrained
    features (ref: monotone_constraints.hpp:358
    ComputeMonotoneSplitGainPenalty)."""
    pen = hp.monotone_penalty
    dep = jnp.maximum(depth, 0).astype(jnp.float32)
    factor = jnp.where(
        pen >= dep + 1.0, K_EPSILON,
        jnp.where(pen <= 1.0, 1.0 - pen / (2.0 ** dep) + K_EPSILON,
                  1.0 - 2.0 ** (pen - 1.0 - dep) + K_EPSILON))
    return jnp.where(pen > 0, factor, 1.0)


def _gain_tensors(hist: jax.Array,
                  parent_sum_grad: jax.Array,
                  parent_sum_hess: jax.Array,
                  parent_count: jax.Array,
                  meta: FeatureMeta,
                  hp: SplitHyperParams,
                  feature_mask: jax.Array,
                  parent_output,
                  min_bound,
                  max_bound,
                  depth,
                  has_categorical: bool,
                  rand_bins=None):
    """NET candidate gains for every (feature, threshold, variant).

    Variants: A numerical/missing-right, B numerical/missing-left,
    C categorical one-hot, and (when has_categorical) D/E categorical
    sorted-subset scans in ascending/descending grad-ratio order
    (ref: feature_histogram.cpp:243-344 categorical branch).

    rand_bins: optional [F] int32 — extra-trees mode: only this bin is a
    numerical split candidate per feature (ref: feature_histogram.hpp:205
    rand_threshold in BeforeNumerical, checked at :897,:995).

    Gains are net of (parent_gain + min_gain_to_split) with the monotone
    split penalty applied, so a positive entry is a strictly improving
    split. Returns (gains [F, B, V], aux dict).
    """
    num_features, num_bin_slots, _ = hist.shape
    t_idx = jnp.arange(num_bin_slots, dtype=jnp.int32)[None, :]  # [1, B]
    nb = meta.num_bins[:, None]  # [F, 1]
    has_nan = meta.missing_type[:, None] == MISSING_NAN

    # Both sides of every candidate come from THIS histogram's bins: the
    # left as a sum of bins, the right as the feature's own total (the
    # sum of all its bins) less the left, in every variant. The parent's stored totals enter
    # only the gain shift below. A float histogram's bins hold rounded
    # operands (bf16 on the MXU, float32 sums in the kernel's own order),
    # so `stored total - prefix` would hand the right side the whole
    # difference between the two roundings, and a split at a feature's
    # last bins made a small child out of mostly that (PERF.md section
    # 7.1). What is left is float32's own rounding of the feature's
    # total, 6e-8 of the node. (A suffix sum by a second cumsum would be
    # exact for a small right side and cost 6 ms a tree on the chip,
    # PERF.md section 6.)
    prefix = jnp.cumsum(hist, axis=1)  # [F, B, 3]
    total = prefix[:, -1:, :]          # [F, 1, 3] each feature's own sum
    # the NaN bin is a feature's last
    nan_at = ((t_idx == nb - 1) & has_nan)[:, :, None]
    nan_bin = jnp.sum(jnp.where(nan_at, hist, 0.0), axis=1,
                      keepdims=True)   # [F, 1, 3]

    # --- variant A: missing (NaN bin = last) goes RIGHT; left = prefix[t]
    left_a = prefix
    # --- variant B: missing goes LEFT with the bins up to t
    left_b = prefix + nan_bin

    # net-gain shift (ref: FindBestThresholdFromHistogram min_gain_shift;
    # with smoothing the parent's gain is evaluated at its actual output)
    parent_gain = jnp.where(
        hp.path_smooth > 0,
        leaf_gain_given_output(parent_sum_grad, parent_sum_hess,
                               parent_output, hp),
        leaf_gain(parent_sum_grad, parent_sum_hess, hp))
    shift = parent_gain + hp.min_gain_to_split

    # monotone split penalty (multiplies the net gain of candidates on
    # monotone features; ref: serial_tree_learner.cpp:1001-1005)
    mono_factor = _monotone_penalty_factor(depth, hp)
    mono_feat = (meta.monotone != 0)[:, None]

    # CEGB delta per feature (ref: cost_effective_gradient_boosting.hpp
    # DeltaGain: tradeoff*penalty_split*n_leaf + coupled-first-use +
    # lazy per-row costs; coupled/lazy are pre-scaled by tradeoff on host)
    cegb_delta = (meta.cegb_feat
                  + (hp.cegb_split_pen + meta.cegb_lazy) * parent_count)

    def eval_variant(left, valid_extra, hp_eff):
        right = total - left
        gl, hl, cl = left[..., GRAD], left[..., HESS], left[..., COUNT]
        gr, hr, cr = right[..., GRAD], right[..., HESS], right[..., COUNT]
        out_l = smooth_output(leaf_output(gl, hl, hp_eff), cl, parent_output,
                              hp_eff)
        out_r = smooth_output(leaf_output(gr, hr, hp_eff), cr, parent_output,
                              hp_eff)
        # per-leaf output bounds from ancestors' monotone splits
        # (ref: monotone_constraints.hpp:466 BasicLeafConstraints)
        out_l = jnp.clip(out_l, min_bound, max_bound)
        out_r = jnp.clip(out_r, min_bound, max_bound)
        gain = (leaf_gain_given_output(gl, hl, out_l, hp_eff)
                + leaf_gain_given_output(gr, hr, out_r, hp_eff))
        # monotone split check: increasing (+1) needs out_l <= out_r
        mono = meta.monotone[:, None]
        mono_ok = jnp.where(
            mono == 0, True,
            jnp.where(mono > 0, out_l <= out_r, out_l >= out_r))
        valid = (
            valid_extra
            & mono_ok
            & (cl >= jnp.maximum(hp.min_data_in_leaf, 1.0))
            & (cr >= jnp.maximum(hp.min_data_in_leaf, 1.0))
            & (hl >= hp.min_sum_hessian_in_leaf)
            & (hr >= hp.min_sum_hessian_in_leaf)
            & feature_mask[:, None]
        )
        net = (gain * meta.penalty[:, None] - cegb_delta[:, None] - shift)
        net = jnp.where(mono_feat, net * mono_factor, net)
        # structural-tie rejection (see K_GAIN_TIE_RTOL): a candidate
        # must clear the f32 noise band of the gain arithmetic to count
        # as an improvement at all
        tie = K_GAIN_TIE_RTOL * jnp.maximum(jnp.abs(shift), 1.0)
        return jnp.where(valid & (net > tie), net, K_MIN_SCORE)

    is_cat = meta.is_categorical[:, None]
    base_valid_a = (t_idx < nb - 1) & ~is_cat
    base_valid_b = has_nan & (t_idx < nb - 2) & ~is_cat
    if rand_bins is not None:
        rand_ok = t_idx == rand_bins[:, None]
        base_valid_a = base_valid_a & rand_ok
        base_valid_b = base_valid_b & rand_ok
    gains_a = eval_variant(left_a, base_valid_a, hp)
    gains_b = eval_variant(left_b, base_valid_b, hp)

    # --- variant C: categorical one-hot split, bin == t goes LEFT
    # (ref: feature_histogram.cpp:188-242 one-hot branch when
    # num_bins <= max_cat_to_onehot; bin 0 = "other/unseen" never splits
    # left so binned and raw-value prediction stay consistent)
    left_c = hist
    onehot_ok = nb <= hp.max_cat_to_onehot
    base_valid_c = is_cat & onehot_ok & (t_idx >= 1) & (t_idx < nb)
    gains_c = eval_variant(left_c, base_valid_c, hp)

    aux = dict(left_a=left_a, left_b=left_b, left_c=left_c, total=total,
               parent_gain=parent_gain)

    if not has_categorical:
        gains = jnp.stack([gains_a, gains_b, gains_c], axis=-1)  # [F, B, 3]
        return gains, aux

    # --- variants D/E: categorical sorted-subset scan
    # (ref: feature_histogram.cpp:243-344): bins with enough estimated
    # count enter, sorted ascending by g/(h + cat_smooth); prefixes of the
    # sorted order (D) and of the reversed order (E) go left, with
    # l2 += cat_l2 and a min_data_per_group thinning of candidates.
    hp_cat = hp._replace(lambda_l2=hp.lambda_l2 + hp.cat_l2)
    g_b, h_b, c_b = hist[..., GRAD], hist[..., HESS], hist[..., COUNT]
    eligible = (t_idx >= 1) & (t_idx < nb) & (c_b >= hp.cat_smooth) & is_cat
    ratio = g_b / (h_b + hp.cat_smooth)
    sort_key = jnp.where(eligible, ratio, jnp.inf)
    order = jnp.argsort(sort_key, axis=1)                    # [F, B]
    rank = jnp.argsort(order, axis=1).astype(jnp.int32)       # [F, B]
    used = jnp.sum(eligible, axis=1).astype(jnp.int32)        # [F]
    sorted_hist = jnp.take_along_axis(hist, order[:, :, None], axis=1)
    pos_ok = t_idx < used[:, None]
    sorted_hist = jnp.where(pos_ok[:, :, None], sorted_hist, 0.0)
    sortP = jnp.cumsum(sorted_hist, axis=1)                   # [F, B, 3]
    totalP = jnp.take_along_axis(
        sortP, jnp.maximum(used - 1, 0)[:, None, None], axis=1)  # [F,1,3]
    totalP = jnp.where((used > 0)[:, None, None], totalP, 0.0)

    # descending-direction prefix: last i+1 eligible bins
    idx_rev = used[:, None] - 2 - t_idx                       # [F, B]
    take_rev = jnp.take_along_axis(
        sortP, jnp.clip(idx_rev, 0, num_bin_slots - 1)[:, :, None], axis=1)
    left_e = totalP - jnp.where((idx_rev >= 0)[:, :, None], take_rev, 0.0)

    # candidate validity: position in range, bounded subset size
    # (max_num_cat = min(max_cat_threshold, (used+1)/2),
    #  feature_histogram.cpp:267-269)
    max_num_cat = jnp.minimum(hp.max_cat_threshold, (used[:, None] + 1) // 2)
    cat_pos_ok = pos_ok & (t_idx < max_num_cat) & is_cat & ~onehot_ok
    # min_data_per_group thinning: emit a candidate only when the data
    # accumulated since the previous candidate reaches the group minimum.
    # The reference resets a running counter at each emission
    # (feature_histogram.cpp:280-317); the crossing-of-multiples form
    # below is its vectorized equivalent up to overshoot at boundaries.
    G = jnp.maximum(hp.min_data_per_group, 1.0)

    def group_ok(P):
        cum_c = P[..., COUNT]
        prev_c = jnp.concatenate(
            [jnp.zeros_like(cum_c[:, :1]), cum_c[:, :-1]], axis=1)
        return jnp.floor(cum_c / G) > jnp.floor(prev_c / G)

    # right side must also keep min_data_per_group
    # (feature_histogram.cpp:302-305)
    right_big_d = (total - sortP)[..., COUNT] >= G
    right_big_e = (total - left_e)[..., COUNT] >= G
    gains_d = eval_variant(sortP,
                           cat_pos_ok & group_ok(sortP) & right_big_d, hp_cat)
    gains_e = eval_variant(left_e,
                           cat_pos_ok & group_ok(left_e) & right_big_e,
                           hp_cat)

    gains = jnp.stack([gains_a, gains_b, gains_c, gains_d, gains_e],
                      axis=-1)  # [F, B, 5]
    aux.update(sortP=sortP, left_e=left_e, rank=rank, used=used,
               eligible=eligible)
    return gains, aux


def per_feature_best_gain(hist, parent_sum_grad, parent_sum_hess,
                          parent_count, meta: FeatureMeta,
                          hp: SplitHyperParams, feature_mask,
                          parent_output=None, min_bound=None, max_bound=None,
                          depth=None, has_categorical: bool = True
                          ) -> jax.Array:
    """Best candidate net gain per feature ([F]) — the voting statistic
    each worker computes from its local histograms (ref:
    voting_parallel_tree_learner.cpp:353 local FindBestThreshold + MaxK)."""
    if parent_output is None:
        parent_output = jnp.float32(0.0)
    if min_bound is None:
        min_bound = jnp.float32(-jnp.inf)
    if max_bound is None:
        max_bound = jnp.float32(jnp.inf)
    if depth is None:
        depth = jnp.int32(1)
    gains, _ = _gain_tensors(hist, parent_sum_grad, parent_sum_hess,
                             parent_count, meta, hp, feature_mask,
                             parent_output, min_bound, max_bound, depth,
                             has_categorical)
    return jnp.max(gains, axis=(1, 2))


def find_best_split(hist: jax.Array,
                    parent_sum_grad: jax.Array,
                    parent_sum_hess: jax.Array,
                    parent_count: jax.Array,
                    meta: FeatureMeta,
                    hp: SplitHyperParams,
                    feature_mask: jax.Array,
                    parent_output=None,
                    min_bound=None,
                    max_bound=None,
                    depth=None,
                    has_categorical: bool = True,
                    rand_bins=None) -> SplitInfo:
    """Find the best split across all features for one leaf.

    hist: [F, B, 3]; parent_*: scalars; feature_mask: [F] bool (feature
    fraction / interaction constraints); parent_output: scalar output of
    the leaf being split (path smoothing); min_bound/max_bound: the
    leaf's output bounds from ancestor monotone splits; depth: the
    leaf's depth (monotone penalty); rand_bins: optional [F] extra-trees
    random thresholds. Returns scalar SplitInfo.
    """
    # trace-time only: counts split-search (re)compilations
    global_metrics.note_trace("ops/split_search")
    if parent_output is None:
        parent_output = jnp.float32(0.0)
    if min_bound is None:
        min_bound = jnp.float32(-jnp.inf)
    if max_bound is None:
        max_bound = jnp.float32(jnp.inf)
    if depth is None:
        depth = jnp.int32(1)
    num_bin_slots = hist.shape[1]
    gains, aux = _gain_tensors(
        hist, parent_sum_grad, parent_sum_hess, parent_count, meta, hp,
        feature_mask, parent_output, min_bound, max_bound, depth,
        has_categorical, rand_bins)
    num_variants = gains.shape[-1]
    flat = gains.reshape(-1)
    best = jnp.argmax(flat)
    gain = flat[best]  # already net of parent gain + min_gain_to_split

    feature = (best // (num_bin_slots * num_variants)).astype(jnp.int32)
    threshold = ((best // num_variants) % num_bin_slots).astype(jnp.int32)
    variant = (best % num_variants).astype(jnp.int32)
    variant_b = variant == 1
    variant_c = variant == 2

    def at_best(name):
        return aux[name][feature, threshold]

    left = jnp.where(variant_b, at_best("left_b"),
                     jnp.where(variant_c, at_best("left_c"),
                               at_best("left_a")))
    bidx = jnp.arange(num_bin_slots, dtype=jnp.int32)
    cat_mask = variant_c & (bidx == threshold)

    if num_variants == 5:
        variant_d = variant == 3
        variant_e = variant == 4
        left = jnp.where(variant_d, at_best("sortP"),
                         jnp.where(variant_e, at_best("left_e"), left))
        rank_f = aux["rank"][feature]
        used_f = aux["used"][feature]
        elig_f = aux["eligible"][feature]
        mask_d = (rank_f <= threshold) & elig_f
        mask_e = (rank_f >= used_f - 1 - threshold) & elig_f
        cat_mask = jnp.where(variant_d, mask_d,
                             jnp.where(variant_e, mask_e, cat_mask))
    # the winner's two sides as the scan saw them, and nothing else: the
    # children are stored, valued and bounded with these
    right = aux["total"][feature, 0] - left

    is_cat_split = variant >= 2
    l2_eff = hp.lambda_l2 + jnp.where(variant >= 3, hp.cat_l2, 0.0)
    hp_out = hp._replace(lambda_l2=l2_eff)

    mt = meta.missing_type[feature]
    default_left = jnp.where(
        is_cat_split, False,
        jnp.where(mt == MISSING_NAN, variant_b,
                  jnp.where(mt == MISSING_ZERO,
                            meta.default_bin[feature] <= threshold, False)))

    out_l = jnp.clip(
        leaf_output_smooth(left[GRAD], left[HESS], left[COUNT],
                           parent_output, hp_out), min_bound, max_bound)
    out_r = jnp.clip(
        leaf_output_smooth(right[GRAD], right[HESS], right[COUNT],
                           parent_output, hp_out), min_bound, max_bound)

    return SplitInfo(
        gain=gain,
        feature=feature,
        threshold=threshold,
        default_left=default_left,
        left_sum_grad=left[GRAD], left_sum_hess=left[HESS], left_count=left[COUNT],
        right_sum_grad=right[GRAD], right_sum_hess=right[HESS], right_count=right[COUNT],
        left_output=out_l,
        right_output=out_r,
        cat_mask=cat_mask,
    )
