"""Feature-parallel tree learner — explicit shard_map collectives.

TPU-native FeatureParallelTreeLearner (ref: parallel_tree_learner.h:27,
src/treelearner/feature_parallel_tree_learner.cpp:63-80): every shard
holds the FULL row set (data replicated, like every machine loading the
full dataset), but histogram construction and split search are sharded
over the feature axis. Each shard finds the best split among its feature
slice, then the per-shard winners are all-gathered and the global best
chosen (SyncUpGlobalBestSplit's Allgather + reduce). Row partitioning
needs no sync — every shard applies the same split to its full row copy.

Compute per shard drops to O(F/W * B); comm per split is one SplitInfo
all_gather (O(W) scalars) — the cheapest of the three strategies, at the
price of replicated data (exactly the reference's trade-off).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..learner import TreeArrays, _LeafSplits, _store_split
from ..obs import health as obs_health
from ..obs import xla as obs_xla
from ..ops import histogram as hist_ops
from ..ops import partition as part_ops
from ..ops import split as split_ops
from ..ops.split import (FeatureMeta, K_MIN_SCORE, SplitHyperParams,
                         SplitInfo, find_best_split, leaf_output,
                         propagate_monotone_bounds)
from . import mesh as mesh_lib
from .scatter import allgather_argmax_best


def _sync_best_split(info: SplitInfo, feat_offset, axis_name,
                     loop_factor: int = 1) -> SplitInfo:
    """All-gather per-shard winners, keep the globally best
    (ref: feature_parallel_tree_learner.cpp:63 SyncUpGlobalBestSplit).
    Shared combiner with the reduce-scatter learner (parallel/scatter.py);
    this learner's feature indices are slice-local, so they shift to
    global before the gather. loop_factor: static trip count of the
    enclosing scan, for the health wrappers' byte/call attribution."""
    info = info._replace(feature=info.feature + feat_offset)
    return allgather_argmax_best(info, axis_name, tag="split/all_gather",
                                 loop_factor=loop_factor)


def grow_tree_feature_parallel(bins_fm, grad, hess, sample_mask,
                               feature_mask, meta: FeatureMeta,
                               hp: SplitHyperParams, max_depth,
                               *, num_leaves: int, max_bins: int,
                               num_shards: int,
                               axis_name: str = mesh_lib.DATA_AXIS,
                               hist_dtype=jnp.float32,
                               hist_impl: str = "xla",
                               hist_deterministic: bool = False,
                               has_categorical: bool = True,
                               mono_pairwise: bool = False):
    """Runs INSIDE shard_map with fully-replicated inputs; each shard
    works on its feature slice. Outputs are replicated.

    mono_pairwise: exact pairwise leaf-box bounds (intermediate/advanced
    monotone methods); the [L, F] box state is over GLOBAL feature
    indices and fully replicated — identical deterministic updates on
    every shard, no extra collective."""
    num_features = bins_fm.shape[0]
    L = num_leaves
    f32 = hist_dtype
    # overlapping slices when W doesn't divide F: the last shards re-scan
    # a few features — duplicate candidates only tie in the argmax
    fp = -(-num_features // num_shards)
    start = jnp.minimum(lax.axis_index(axis_name) * fp,
                        jnp.maximum(num_features - fp, 0))
    fp = min(fp, num_features)

    bins_loc = lax.dynamic_slice_in_dim(bins_fm, start, fp, axis=0)
    meta_loc = jax.tree_util.tree_map(
        lambda a: lax.dynamic_slice_in_dim(a, start, fp, axis=0), meta)
    fmask_loc = lax.dynamic_slice_in_dim(feature_mask, start, fp, axis=0)

    build = functools.partial(hist_ops.build_histogram, max_bins=max_bins,
                              dtype=f32, row_chunk=0, impl=hist_impl,
                              deterministic=hist_deterministic)
    sync = functools.partial(_sync_best_split, feat_offset=start,
                             axis_name=axis_name)

    root_hist = build(bins_loc, grad, hess, sample_mask)
    # every shard holds all rows, so its own features' bins sum to the
    # same totals (to float32 summation order)
    root_g, root_h, root_c = hist_ops.node_totals(root_hist)
    root_out = leaf_output(root_g, root_h, hp)
    neg_inf, pos_inf = jnp.float32(-jnp.inf), jnp.float32(jnp.inf)
    root_split = sync(find_best_split(root_hist, root_g, root_h, root_c,
                                      meta_loc, hp, fmask_loc, root_out,
                                      neg_inf, pos_inf, jnp.int32(0),
                                      has_categorical))

    leaves = _LeafSplits.empty(L, max_bins, f32)
    leaves = _store_split(leaves, 0, root_split, jnp.int32(1), root_out,
                          root_g, root_h, root_c, neg_inf, pos_inf, True)

    pool = jnp.zeros((L, fp, max_bins, hist_ops.NUM_HIST_CHANNELS), f32)
    pool = pool.at[0].set(root_hist)
    row_leaf0 = jnp.zeros((bins_fm.shape[1],), jnp.int32)
    box_lo0 = (jnp.zeros((L, num_features), jnp.int32)
               if mono_pairwise else None)
    box_hi0 = (jnp.full((L, num_features), max_bins - 1, jnp.int32)
               if mono_pairwise else None)

    def step(carry, step_idx):
        row_leaf, pool, leaves, box_lo, box_hi = carry
        best_leaf = jnp.argmax(leaves.gain).astype(jnp.int32)
        valid = leaves.gain[best_leaf] > 0.0
        new_leaf = (step_idx + 1).astype(jnp.int32)

        feat = leaves.feature[best_leaf]  # GLOBAL feature index
        thr = leaves.threshold[best_leaf]
        dleft = leaves.default_left[best_leaf]
        cmask = leaves.cat_mask[best_leaf]

        # full data on every shard: apply the split locally, no row sync
        # (ref: feature-parallel "no row sync" property)
        row_leaf = part_ops.apply_split(
            row_leaf, bins_fm, best_leaf, new_leaf, feat, thr, dleft, cmask,
            meta.num_bins, meta.missing_type, meta.is_categorical, valid)

        ph, pc = leaves.sum_hess[best_leaf], leaves.count[best_leaf]
        (lg, lh, lc), (rg, rh, rc) = leaves.candidate_sides(best_leaf)

        left_smaller = lc <= rc
        small_id = jnp.where(left_smaller, best_leaf, new_leaf)
        small_mask = sample_mask * (row_leaf == small_id) * valid
        small_hist = build(bins_loc, grad, hess, small_mask)
        parent_hist = pool[best_leaf]
        large_hist = hist_ops.subtract_histogram(parent_hist, small_hist)
        left_hist = jnp.where(left_smaller, small_hist, large_hist)
        right_hist = jnp.where(left_smaller, large_hist, small_hist)
        pool = pool.at[best_leaf].set(
            jnp.where(valid, left_hist, parent_hist))
        pool = pool.at[new_leaf].set(
            jnp.where(valid, right_hist, pool[new_leaf]))

        parent_out = leaves.output[best_leaf]
        p_minb = leaves.min_bound[best_leaf]
        p_maxb = leaves.max_bound[best_leaf]
        out_l = leaves.left_output[best_leaf]
        out_r = leaves.right_output[best_leaf]

        if mono_pairwise:
            # see voting.py: re-clip stored candidate outputs to the
            # CURRENT bounds, then refresh pairwise leaf-box bounds
            out_l = jnp.clip(out_l, p_minb, p_maxb)
            out_r = jnp.clip(out_r, p_minb, p_maxb)
            box_lo, box_hi = split_ops.split_child_boxes(
                box_lo, box_hi, best_leaf, new_leaf, feat, thr,
                meta.is_categorical[feat], valid)
            out_now = leaves.output.at[best_leaf].set(
                jnp.where(valid, out_l, parent_out))
            out_now = out_now.at[new_leaf].set(
                jnp.where(valid, out_r,
                          out_now[jnp.minimum(new_leaf, L - 1)]))
            leaf_in_use = jnp.arange(L, dtype=jnp.int32) <= \
                jnp.where(valid, new_leaf, step_idx)
            minb_all, maxb_all = split_ops.compute_box_bounds(
                box_lo, box_hi, out_now, leaf_in_use, meta.monotone)
            leaves = leaves._replace(
                min_bound=jnp.where(valid, minb_all, leaves.min_bound),
                max_bound=jnp.where(valid, maxb_all, leaves.max_bound))
            l_min, l_max = minb_all[best_leaf], maxb_all[best_leaf]
            r_min, r_max = minb_all[new_leaf], maxb_all[new_leaf]
        else:
            l_min, l_max, r_min, r_max = propagate_monotone_bounds(
                out_l, out_r, meta.monotone[feat].astype(jnp.int32),
                meta.is_categorical[feat], p_minb, p_maxb)

        child_depth = leaves.depth[best_leaf] + 1
        pen_depth = child_depth - 1
        split_l = sync(find_best_split(left_hist, lg, lh, lc, meta_loc,
                                       hp, fmask_loc, out_l, l_min, l_max,
                                       pen_depth, has_categorical),
                       loop_factor=L - 1)
        split_r = sync(find_best_split(right_hist, rg, rh, rc, meta_loc,
                                       hp, fmask_loc, out_r, r_min, r_max,
                                       pen_depth, has_categorical),
                       loop_factor=L - 1)
        depth_ok = (max_depth <= 0) | (child_depth < max_depth)
        split_l = split_l._replace(
            gain=jnp.where(depth_ok, split_l.gain, K_MIN_SCORE))
        split_r = split_r._replace(
            gain=jnp.where(depth_ok, split_r.gain, K_MIN_SCORE))

        chosen_gain = leaves.gain[best_leaf]
        leaves = _store_split(leaves, best_leaf, split_l, child_depth,
                              out_l, lg, lh, lc, l_min, l_max, valid)
        leaves = _store_split(leaves, new_leaf, split_r, child_depth,
                              out_r, rg, rh, rc, r_min, r_max, valid)

        record = dict(
            split_leaf=jnp.where(valid, best_leaf, -1),
            split_feature=feat,
            split_bin_threshold=thr,
            split_default_left=dleft,
            split_gain=jnp.where(valid, chosen_gain, 0.0),
            split_cat_mask=cmask,
            internal_value=parent_out,
            internal_weight=ph,
            internal_count=pc,
        )
        return (row_leaf, pool, leaves, box_lo, box_hi), record

    (row_leaf, pool, leaves, _, _), records = lax.scan(
        step, (row_leaf0, pool, leaves, box_lo0, box_hi0),
        jnp.arange(L - 1, dtype=jnp.int32), unroll=2 if L > 2 else 1)

    num_leaves_out = 1 + jnp.sum(records["split_leaf"] >= 0).astype(
        jnp.int32)
    tree = TreeArrays(
        split_leaf=records["split_leaf"],
        split_feature=records["split_feature"],
        split_bin_threshold=records["split_bin_threshold"],
        split_default_left=records["split_default_left"],
        split_gain=records["split_gain"],
        split_cat_mask=records["split_cat_mask"],
        internal_value=records["internal_value"],
        internal_weight=records["internal_weight"],
        internal_count=records["internal_count"],
        leaf_value=leaves.output,
        leaf_weight=leaves.sum_hess,
        leaf_count=leaves.count,
        num_leaves=num_leaves_out,
    )
    return tree, row_leaf


def make_sharded_feature_grow(mesh, *, num_leaves: int, max_bins: int,
                              hist_impl: str = "xla",
                              has_categorical: bool = True,
                              mono_pairwise: bool = False,
                              hist_deterministic: bool = False):
    """jit(shard_map(grow_tree_feature_parallel)): everything replicated
    in and out; sharding is purely over the computation."""
    grow = functools.partial(grow_tree_feature_parallel,
                             num_leaves=num_leaves, max_bins=max_bins,
                             num_shards=mesh.size, hist_impl=hist_impl,
                             has_categorical=has_categorical,
                             mono_pairwise=mono_pairwise,
                             hist_deterministic=hist_deterministic)
    rep = P()
    meta_spec = FeatureMeta(*([rep] * len(FeatureMeta._fields)))
    hp_spec = SplitHyperParams(*([rep] * len(SplitHyperParams._fields)))
    # no histogram pass of this learner is counted (hist_counts None)
    tree_spec = TreeArrays(*([rep] * len(TreeArrays._fields)))._replace(
        hist_counts=None)
    from .mesh import shard_map as _shard_map
    sharded = _shard_map(
        grow, mesh=mesh,
        in_specs=(rep, rep, rep, rep, rep, meta_spec, hp_spec, rep),
        out_specs=(tree_spec, rep))
    # instrumented boundary: health manifests attribute the per-split
    # SplitInfo all_gathers per runtime call (see parallel/voting.py)
    return obs_xla.instrumented_jit("parallel/feature_grow", sharded,
                                    phase="grow")
