"""Voting-parallel tree learner — explicit shard_map collectives.

TPU-native PV-tree (ref: src/treelearner/voting_parallel_tree_learner.cpp,
parallel_tree_learner.h:127). Rows are sharded over the mesh "data" axis;
histograms stay LOCAL to each shard. Per leaf, every shard proposes its
top-k features by local gain (the "vote",
voting_parallel_tree_learner.cpp:353-373 MaxK + Allgather), a global vote
picks 2k candidate features (GlobalVoting, :152), and ONLY those
candidates' histograms are summed across shards (:396) — ICI traffic per
split drops from O(F * B) to O(W * k + 2k * B), the same bandwidth
reduction PV-tree buys over plain data-parallel.

Collectives used (all over ICI via shard_map):
  psum        — root/candidate histogram reduction (HistogramSumReducer)
  all_gather  — top-k vote exchange (SyncUpGlobalBestSplit's Allgather)
  psum_scatter— hist_reduce="scatter": each shard reduces only its owned
                slice of the candidate axis (ReduceScatter,
                data_parallel_tree_learner.cpp:287) and searches it; one
                SplitInfo all_gather + argmax picks the winner. Another
                W-fold cut on the already-voted candidate traffic,
                bit-identical to the psum path (see parallel/scatter.py).
"""

from __future__ import annotations

import functools
from typing import Optional

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
                         find_best_split, leaf_output, per_feature_best_gain,
                         propagate_monotone_bounds)
from . import mesh as mesh_lib
from .scatter import allgather_argmax_best


def _vote_and_reduce(local_hist, pg, ph, pc, parent_out, min_b, max_b,
                     depth, meta, hp, feature_mask, *,
                     num_candidates: int, top_k: int, axis_name: str,
                     has_categorical: bool = True, loop_factor: int = 1,
                     hist_reduce: str = "psum", num_shards: int = 1):
    """One voting round for one leaf: local top-k proposal -> global vote
    -> candidate-only histogram psum -> global best split.

    local_hist: [F, B, 3] this shard's histogram for the leaf.
    pg/ph/pc: GLOBAL leaf sums (replicated). Returns a SplitInfo whose
    `feature` is a real feature index.

    loop_factor: static trip count of the enclosing ``lax.scan`` (the
    per-split step body) — the health wrappers attribute this many
    issued collectives per program run, so the runtime byte/call
    counters match what the ICI actually carries.
    """
    # this shard's sums for the leaf, from its local histogram
    lg, lh, lc = hist_ops.node_totals(local_hist)
    local_gain = per_feature_best_gain(local_hist, lg, lh, lc, meta, hp,
                                       feature_mask, parent_out,
                                       min_b, max_b, depth,
                                       has_categorical)  # [F]
    num_features = local_gain.shape[0]

    # --- vote: each shard proposes its top-k features
    _, prop = lax.top_k(local_gain, top_k)                    # [k]
    all_props = obs_health.all_gather(
        prop, axis_name, tag="vote/all_gather",
        loop_factor=loop_factor).reshape(-1)                   # [W*k]
    votes = jnp.zeros((num_features,), jnp.float32).at[all_props].add(1.0)
    # tie-break votes by the summed local gains (deterministic; the
    # reference breaks ties arbitrarily by machine order)
    gain_sum = obs_health.psum(jnp.maximum(local_gain, K_MIN_SCORE * 1e-3),
                               axis_name, tag="vote/psum_gain",
                               loop_factor=loop_factor)
    norm = jnp.max(jnp.abs(gain_sum)) + 1.0
    _, cand = lax.top_k(votes + gain_sum / (norm * 4.0), num_candidates)
    cand = cand.astype(jnp.int32)                              # [C]

    # --- reduce only the candidates' histograms (ref: :396)
    cand_meta = jax.tree_util.tree_map(lambda a: a[cand], meta)
    if hist_reduce == "scatter" and num_shards > 1:
        # ReduceScatter over the candidate axis: each shard owns a
        # contiguous slice of C, embeds it back at its global offset in
        # an all-zero [C, B, 3] (the ORACLE's shape, so XLA emits the
        # same split-search arithmetic bit for bit), searches with
        # non-owned candidates masked off, and one SplitInfo-sized
        # all_gather + first-max argmax recovers exactly the psum
        # winner (see parallel/scatter.py for the parity argument).
        w = num_shards
        c_pad = -(-num_candidates // w) * w
        cand_padded = jnp.pad(cand, (0, c_pad - num_candidates),
                              mode="edge")
        part = obs_health.psum_scatter(
            local_hist[cand_padded], axis_name, tag="hist/psum_scatter",
            loop_factor=loop_factor, scatter_dimension=0)
        c_loc = c_pad // w
        idx = lax.axis_index(axis_name)
        full = lax.dynamic_update_slice(
            jnp.zeros((c_pad,) + part.shape[1:], part.dtype), part,
            (idx * c_loc, jnp.int32(0), jnp.int32(0)))[:num_candidates]
        slot = jnp.arange(num_candidates, dtype=jnp.int32)
        owned = (slot >= idx * c_loc) & (slot < (idx + 1) * c_loc)
        info = find_best_split(full, pg, ph, pc, cand_meta, hp,
                               feature_mask[cand] & owned, parent_out,
                               min_b, max_b, depth, has_categorical)
        info = allgather_argmax_best(info, axis_name,
                                     tag="split/allgather_best",
                                     loop_factor=loop_factor)
    else:
        cand_hist = obs_health.psum(local_hist[cand], axis_name,
                                    tag="vote/psum_hist",
                                    loop_factor=loop_factor)  # [C, B, 3]
        info = find_best_split(cand_hist, pg, ph, pc, cand_meta, hp,
                               feature_mask[cand], parent_out, min_b,
                               max_b, depth, has_categorical)
    return info._replace(feature=cand[info.feature])


def grow_tree_voting(bins_fm, grad, hess, sample_mask, feature_mask,
                     meta: FeatureMeta, hp: SplitHyperParams, max_depth,
                     *, num_leaves: int, max_bins: int, top_k: int,
                     axis_name: str = mesh_lib.DATA_AXIS,
                     hist_dtype=jnp.float32, hist_impl: str = "xla",
                     has_categorical: bool = True,
                     mono_pairwise: bool = False,
                     hist_deterministic: bool = False,
                     hist_reduce: str = "psum", num_shards: int = 1):
    """Grow one tree with voting-parallel split search. Runs INSIDE
    shard_map: all row-indexed inputs are this shard's slice; returned
    TreeArrays are replicated, row_leaf is the local slice.

    mono_pairwise: exact pairwise leaf-box monotone bounds
    (monotone_constraints_method intermediate/advanced). The [L, F] box
    state is replicated across shards — every shard runs the identical
    deterministic update, so no extra collective is needed (the
    reference's constraint factory is likewise learner-agnostic,
    monotone_constraints.hpp:330)."""
    num_data = bins_fm.shape[1]
    num_features = bins_fm.shape[0]
    L = num_leaves
    f32 = hist_dtype
    C = min(2 * top_k, num_features)
    k_eff = min(top_k, num_features)

    build = functools.partial(hist_ops.build_histogram, max_bins=max_bins,
                              dtype=f32, row_chunk=0, impl=hist_impl,
                              deterministic=hist_deterministic)
    vote = functools.partial(_vote_and_reduce, meta=meta, hp=hp,
                             feature_mask=feature_mask, num_candidates=C,
                             top_k=k_eff, axis_name=axis_name,
                             has_categorical=has_categorical,
                             hist_reduce=hist_reduce, num_shards=num_shards)

    # --- root: local histogram; global sums by psum (ref: data_parallel
    # root Allreduce, data_parallel_tree_learner.cpp:170)
    root_hist = build(bins_fm, grad, hess, sample_mask)
    root_g, root_h, root_c = obs_health.psum(
        hist_ops.node_totals(root_hist), axis_name, tag="root/psum")
    root_out = leaf_output(root_g, root_h, hp)
    neg_inf, pos_inf = jnp.float32(-jnp.inf), jnp.float32(jnp.inf)
    root_split = vote(root_hist, root_g, root_h, root_c, root_out,
                      neg_inf, pos_inf, jnp.int32(0))

    leaves = _LeafSplits.empty(L, max_bins, f32)
    leaves = _store_split(leaves, 0, root_split, jnp.int32(1), root_out,
                          root_g, root_h, root_c, neg_inf, pos_inf, True)

    pool = jnp.zeros((L, num_features, max_bins,
                      hist_ops.NUM_HIST_CHANNELS), f32)
    pool = pool.at[0].set(root_hist)
    row_leaf0 = jnp.zeros((num_data,), jnp.int32)
    box_lo0 = (jnp.zeros((L, num_features), jnp.int32)
               if mono_pairwise else None)
    box_hi0 = (jnp.full((L, num_features), max_bins - 1, jnp.int32)
               if mono_pairwise else None)

    def step(carry, step_idx):
        row_leaf, pool, leaves, box_lo, box_hi = carry
        best_leaf = jnp.argmax(leaves.gain).astype(jnp.int32)
        valid = leaves.gain[best_leaf] > 0.0
        new_leaf = (step_idx + 1).astype(jnp.int32)

        feat = leaves.feature[best_leaf]
        thr = leaves.threshold[best_leaf]
        dleft = leaves.default_left[best_leaf]
        cmask = leaves.cat_mask[best_leaf]

        row_leaf = part_ops.apply_split(
            row_leaf, bins_fm, best_leaf, new_leaf, feat, thr, dleft, cmask,
            meta.num_bins, meta.missing_type, meta.is_categorical, valid)

        # global child sums come from the stored (globally-reduced) split
        ph, pc = leaves.sum_hess[best_leaf], leaves.count[best_leaf]
        (lg, lh, lc), (rg, rh, rc) = leaves.candidate_sides(best_leaf)

        # local histograms: build smaller child locally, subtract
        left_smaller = lc <= rc
        small_id = jnp.where(left_smaller, best_leaf, new_leaf)
        small_mask = sample_mask * (row_leaf == small_id) * valid
        small_hist = build(bins_fm, grad, hess, small_mask)
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
            # bounds may have tightened after OTHER leaves split since
            # this candidate was stored (ref: RecomputeConstraintsIfNeeded
            # monotone_constraints.hpp:52) — re-clip, then refresh all
            # leaves' pairwise box bounds
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
            # validity is monotone here (no forced-split revival): after
            # a valid step leaves 0..new_leaf are in use
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
        # inside the L-1-trip split scan: traced once, issued L-1 times
        split_l = vote(left_hist, lg, lh, lc, out_l, l_min, l_max,
                       pen_depth, loop_factor=L - 1)
        split_r = vote(right_hist, rg, rh, rc, out_r, r_min, r_max,
                       pen_depth, loop_factor=L - 1)
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


def make_sharded_voting_grow(mesh, *, num_leaves: int, max_bins: int,
                             top_k: int, hist_impl: str = "xla",
                             has_categorical: bool = True,
                             mono_pairwise: bool = False,
                             hist_deterministic: bool = False,
                             hist_reduce: str = "psum"):
    """jit(shard_map(grow_tree_voting)): rows sharded over "data",
    everything else replicated; tree replicated out, row_leaf sharded."""
    grow = functools.partial(grow_tree_voting, num_leaves=num_leaves,
                             max_bins=max_bins, top_k=top_k,
                             hist_impl=hist_impl,
                             has_categorical=has_categorical,
                             mono_pairwise=mono_pairwise,
                             hist_deterministic=hist_deterministic,
                             hist_reduce=hist_reduce,
                             num_shards=int(mesh.shape[mesh_lib.DATA_AXIS]))
    data = P(None, mesh_lib.DATA_AXIS)   # bins [F, N]
    rows = P(mesh_lib.DATA_AXIS)         # [N]
    rep = P()
    meta_spec = FeatureMeta(*([rep] * len(FeatureMeta._fields)))
    hp_spec = SplitHyperParams(*([rep] * len(SplitHyperParams._fields)))
    tree_spec = TreeArrays(*([rep] * len(TreeArrays._fields)))
    sharded = mesh_lib.shard_map(
        grow, mesh=mesh,
        in_specs=(data, rows, rows, rows, rep, meta_spec, hp_spec, rep),
        out_specs=(tree_spec, rows))
    # instrumented program boundary: recompile attribution + the health
    # manifest that attributes this program's collectives per call
    return obs_xla.instrumented_jit("parallel/voting_grow", sharded,
                                    phase="grow")
