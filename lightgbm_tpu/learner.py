"""Serial TPU tree learner — the jitted leaf-wise tree grower.

TPU-native re-architecture of the reference learners
(ref: src/treelearner/serial_tree_learner.cpp:183 Train,
src/treelearner/cuda/cuda_single_gpu_tree_learner.cpp:170). The
``num_leaves - 1`` best-first splits become a single ``lax.scan`` with
fixed trip count; all state (row->leaf map, histogram pool, per-leaf best
splits) has static shapes, so the whole tree grows inside one XLA program
with no host round-trips (the CUDA learner pays one readback per split).

Key correspondences:
  - histogram pool  ~ HistogramPool (serial_tree_learner.cpp:40)
  - smaller-child build + sibling subtraction ~ serial_tree_learner.cpp:373,582
  - per-leaf best-split arrays ~ best_split_per_leaf_
  - row_leaf vector ~ CUDADataPartition's cuda_data_index_to_leaf_index_

Memory stance on the pool: the reference bounds host RAM with an LRU
cache (histogram_pool_size) and recomputes evicted histograms. Static
XLA shapes preclude an LRU; the full [L, F, B, 3] pool is kept in HBM
(5.5 MB at Higgs shape, ~784 MB worst-case at 255 leaves x 1k features
x 256 bins — well inside a 16 GB chip, and EFB bundling shrinks F for
exactly the wide datasets that would push it). Only one grower's pool
is live at a time; the buffer is freed when its program ends.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from .obs import health as obs_health
from .obs.metrics import global_metrics  # noqa: F401  (re-export compat)
from .ops import histogram as hist_ops
from .ops import partition as part_ops
from .ops import split as split_ops
from .ops.histogram import COUNT, GRAD, HESS
from .ops.split import (FeatureMeta, K_MIN_SCORE, SplitHyperParams, SplitInfo,
                        find_best_split, leaf_gain_given_output, leaf_output,
                        leaf_output_smooth)


class TreeArrays(NamedTuple):
    """One grown tree, flat arrays (device). L = num_leaves slots.

    Splits are recorded in creation order: split s creates internal node s;
    its left child keeps leaf id `split_leaf[s]`, its right child is the new
    leaf id ``s + 1`` (the reference uses the same numbering,
    ref: src/io/tree.cpp Tree::Split).
    """
    split_leaf: jax.Array          # [L-1] int32, -1 when unused
    split_feature: jax.Array       # [L-1] int32
    split_bin_threshold: jax.Array  # [L-1] int32
    split_default_left: jax.Array  # [L-1] bool
    split_gain: jax.Array          # [L-1] f32
    split_cat_mask: jax.Array      # [L-1, B] bool (bins going left, cat)
    internal_value: jax.Array      # [L-1] f32 (unshrunk output of split node)
    internal_weight: jax.Array     # [L-1] f32 (sum_hess)
    internal_count: jax.Array      # [L-1] f32
    leaf_value: jax.Array          # [L] f32 (unshrunk)
    leaf_weight: jax.Array         # [L] f32
    leaf_count: jax.Array          # [L] f32
    num_leaves: jax.Array          # scalar int32
    # [passes, len(PASS_COUNTS)] int32: what the multi-leaf kernels
    # counted in each histogram pass of grow_tree_waved (the root's, then
    # a wave's; ops/pallas_histogram.PASS_COUNTS); None where the passes
    # ran elsewhere (the XLA twins, the sparse builder, the per-shard
    # kernels of a mesh, the streamed grower's slabs) or no grower
    # counted (grow_tree, the feature- and voting-parallel learners)
    hist_counts: Optional[jax.Array] = None


class _LeafSplits(NamedTuple):
    """Per-leaf stats + stored best split (ref: leaf_splits.hpp:23 +
    best_split_per_leaf_ in serial_tree_learner.h). min/max_bound are the
    leaf's output bounds inherited from ancestor monotone splits
    (ref: monotone_constraints.hpp:466 BasicLeafConstraints entries)."""
    sum_grad: jax.Array   # [L]
    sum_hess: jax.Array   # [L]
    count: jax.Array      # [L]
    depth: jax.Array      # [L] int32
    output: jax.Array     # [L] (path-smoothed) leaf output
    gain: jax.Array       # [L]
    feature: jax.Array    # [L] int32
    threshold: jax.Array  # [L] int32
    default_left: jax.Array  # [L] bool
    # [L, 2, 3] the candidate's (left, right) x (grad, hess, count) as
    # the split scan summed them from the leaf's own bins: a child's
    # sum_grad/sum_hess/count are copied from here, never formed as the
    # leaf's totals less the other side. One array, so that a scan step
    # reads and writes it once (a step costs by its count of small ops)
    sides: jax.Array
    left_output: jax.Array   # [L] candidate left-child output
    right_output: jax.Array  # [L] candidate right-child output
    cat_mask: jax.Array      # [L, B] bool candidate categorical mask
    min_bound: jax.Array     # [L] monotone lower output bound
    max_bound: jax.Array     # [L] monotone upper output bound

    @classmethod
    def empty(cls, L: int, max_bins: int, f32) -> "_LeafSplits":
        """L unused slots: no stats, no candidate, unbounded output."""
        zero_l = jnp.zeros((L,), f32)
        return cls(
            sum_grad=zero_l, sum_hess=zero_l, count=zero_l,
            depth=jnp.zeros((L,), jnp.int32),
            output=zero_l,
            gain=jnp.full((L,), K_MIN_SCORE, f32),
            feature=jnp.zeros((L,), jnp.int32),
            threshold=jnp.zeros((L,), jnp.int32),
            default_left=jnp.zeros((L,), jnp.bool_),
            sides=jnp.zeros((L, 2, 3), f32),
            left_output=zero_l, right_output=zero_l,
            cat_mask=jnp.zeros((L, max_bins), jnp.bool_),
            min_bound=jnp.full((L,), -jnp.inf, f32),
            max_bound=jnp.full((L,), jnp.inf, f32),
        )

    def candidate_sides(self, leaf):
        """((lg, lh, lc), (rg, rh, rc)) of `leaf`'s stored candidate."""
        left, right = self.sides[leaf]
        return tuple(left), tuple(right)


class _GrowState(NamedTuple):
    row_leaf: jax.Array   # [N] int32
    pool: jax.Array       # [L, F, B, 3] histogram pool
    leaves: _LeafSplits
    used_features: Optional[jax.Array]  # [L, F] bool (interaction constraints)
    n_applied: jax.Array  # scalar int32: applied-split counter (leaf ids)
    # leaf feature-range boxes [L, F] int32 (pairwise monotone modes only)
    box_lo: Optional[jax.Array] = None
    box_hi: Optional[jax.Array] = None


def _split_sides(info: SplitInfo) -> jax.Array:
    """[..., 2, 3] (left, right) x (grad, hess, count) of a SplitInfo
    (scalar fields, or [S]-leading from a vmapped search)."""
    return jnp.stack(
        [jnp.stack([info.left_sum_grad, info.left_sum_hess,
                    info.left_count], axis=-1),
         jnp.stack([info.right_sum_grad, info.right_sum_hess,
                    info.right_count], axis=-1)], axis=-2)


def _store_split(leaves: _LeafSplits, idx, info: SplitInfo, depth, output,
                 sum_grad, sum_hess, count, min_bound, max_bound,
                 valid) -> _LeafSplits:
    """Write one leaf's stats + its best candidate split at slot `idx`."""
    def upd(arr, val):
        return arr.at[idx].set(jnp.where(valid, val, arr[idx]))
    return _LeafSplits(
        sum_grad=upd(leaves.sum_grad, sum_grad),
        sum_hess=upd(leaves.sum_hess, sum_hess),
        count=upd(leaves.count, count),
        depth=upd(leaves.depth, depth),
        output=upd(leaves.output, output),
        gain=upd(leaves.gain, info.gain),
        feature=upd(leaves.feature, info.feature),
        threshold=upd(leaves.threshold, info.threshold),
        default_left=upd(leaves.default_left, info.default_left),
        sides=upd(leaves.sides, _split_sides(info)),
        left_output=upd(leaves.left_output, info.left_output),
        right_output=upd(leaves.right_output, info.right_output),
        cat_mask=upd(leaves.cat_mask, info.cat_mask),
        min_bound=upd(leaves.min_bound, min_bound),
        max_bound=upd(leaves.max_bound, max_bound),
    )


def _allowed_features(used_row: jax.Array, groups: jax.Array) -> jax.Array:
    """Features usable below a node given the features already used on its
    path (ref: col_sampler.hpp interaction-constraint filtering): the
    union of constraint groups that contain every used feature."""
    # group g qualifies iff used_row is a subset of groups[g]
    qualifies = ~jnp.any(used_row[None, :] & ~groups, axis=1)  # [G]
    return jnp.any(groups & qualifies[:, None], axis=0)  # [F]


def _rand_bins(key, meta: FeatureMeta):
    """Extra-trees: one uniform random threshold bin per feature in
    [0, num_bins-2] (ref: feature_histogram.hpp:205 rand.NextInt)."""
    u = jax.random.uniform(key, meta.num_bins.shape)
    return jnp.floor(u * jnp.maximum(meta.num_bins - 1, 1)).astype(jnp.int32)


def _bynode_mask(key, feature_mask, ff_bynode: float):
    """Per-node feature subsample FROM the node's allowed set
    (ref: col_sampler.hpp GetByNode samples ceil(fraction * valid_count)
    of the currently-valid features, so a constrained node always keeps
    at least one usable feature)."""
    f = feature_mask.shape[0]
    u = jax.random.uniform(key, (f,))
    u_masked = jnp.where(feature_mask, u, jnp.inf)  # disallowed sort last
    cnt = jnp.sum(feature_mask).astype(jnp.float32)
    k = jnp.maximum(jnp.ceil(ff_bynode * cnt), 1.0).astype(jnp.int32)
    thr = jnp.sort(u_masked)[jnp.clip(k - 1, 0, f - 1)]
    return feature_mask & (u_masked <= thr)


def _node_randomness(node_key, salt, meta, feature_mask,
                     extra_trees: bool, ff_bynode: float):
    """(rand_bins, node feature mask) for one candidate evaluation."""
    if node_key is None:
        return None, feature_mask
    key = jax.random.fold_in(node_key, salt)
    rb = _rand_bins(jax.random.fold_in(key, 0), meta) if extra_trees else None
    fm = _bynode_mask(jax.random.fold_in(key, 1), feature_mask,
                      ff_bynode) if ff_bynode < 1.0 else feature_mask
    return rb, fm


def _pad_rows(arrays, axes, n: int, mult: int, pad_values):
    """Pad each array's row axis (given per-array in `axes`) so the row
    count divides `mult` — shard_map needs equal per-device slices."""
    pad = (-n) % mult
    if pad == 0:
        return arrays
    out = []
    for a, ax, v in zip(arrays, axes, pad_values):
        cfg = [(0, 0)] * a.ndim
        cfg[ax] = (0, pad)
        out.append(jnp.pad(a, cfg, constant_values=v))
    return out


def _sharded_pallas_build(shard_mesh, *, max_bins: int, dtype,
                          row_chunk: int, precision: str,
                          impl: str = "pallas",
                          hist_reduce: str = "psum",
                          deterministic: bool = False):
    """Single-leaf histogram build distributed over the mesh row axis:
    each shard runs the histogram kernel on its rows, results reduce —
    the shard_map analog of HistogramSumReducer + Allreduce
    (ref: data_parallel_tree_learner.cpp:287-297).

    hist_reduce="scatter" replaces the full-histogram psum with a
    ``psum_scatter`` over the (zero-padded) feature axis: each shard
    receives only its owned 1/W feature slice — the reference's
    ReduceScatter — and the result stays feature-sharded for the
    scatter split stage (parallel/scatter.py). Bitwise: psum_scatter
    slices equal the matching psum rows, so models are unchanged.

    On a hierarchical ("dcn", "ici") mesh, rows shard over BOTH axes,
    the scatter runs over the fast in-process ICI axis and the owned
    slice then psums over the slow DCN link — only 1/W_ici of the
    histogram ever crosses DCN (int32 on the quantized path, so the
    compressed partial sums stay exact)."""
    from jax.sharding import PartitionSpec as P
    axes = tuple(shard_mesh.axis_names)
    row_axes = axes if len(axes) > 1 else axes[0]
    scat_axis = axes[-1]
    width = int(shard_mesh.shape[scat_axis])
    scatter = hist_reduce == "scatter"

    def local(b_l, g_l, h_l, m_l):
        hl = hist_ops.build_histogram(
            b_l, g_l, h_l, m_l, max_bins=max_bins, dtype=dtype,
            row_chunk=row_chunk, impl=impl, precision=precision,
            deterministic=deterministic)
        # tagged health wrapper: trace-time counters + runtime per-call
        # attribution through the enclosing program's manifest
        if not scatter:
            return obs_health.psum(hl, row_axes, tag="hist/psum")
        fpad = (-hl.shape[0]) % width
        if fpad:
            hl = jnp.pad(hl, ((0, fpad), (0, 0), (0, 0)))
        hl = obs_health.psum_scatter(hl, scat_axis,
                                     tag="hist/psum_scatter",
                                     scatter_dimension=0)
        if len(axes) > 1:
            hl = obs_health.psum(hl, axes[:-1], tag="hist/psum_dcn")
        return hl

    from .parallel.mesh import shard_map as _shard_map
    fn = _shard_map(local, mesh=shard_mesh,
                    in_specs=(P(None, row_axes), P(row_axes), P(row_axes),
                              P(row_axes)),
                    out_specs=(P(scat_axis, None, None) if scatter
                               else P()))

    def build(bins, g, h, m):
        # padded rows carry mask 0 -> no histogram contribution
        bins, g, h, m = _pad_rows((bins, g, h, m), (1, 0, 0, 0),
                                  bins.shape[1], shard_mesh.size,
                                  (0, 0.0, 0.0, 0.0))
        return fn(bins, g, h, m)
    return build


def _sharded_pallas_multi(shard_mesh, *, max_bins: int,
                          precision: str, int8: bool,
                          impl: str = "pallas",
                          hist_reduce: str = "psum",
                          deterministic: bool = False):
    """Multi-leaf wave histogram pass distributed over the mesh row axis.

    int8=True: the int8 x int8 -> int32 kernel (MXU pallas where Mosaic
    runs, its exact-integer XLA twin for impl="xla") runs per shard and
    the reduce moves INT32 histograms — exact integer accumulation
    across the mesh, the collective analog of the reference's quantized
    histogram reduction (ref: data_parallel_tree_learner.cpp:290-297,
    which reduces packed integer bins instead of floats). Callers
    dequantize AFTER the reduce, so cross-shard sums are exact
    multiples of the grad/hess scales.

    hist_reduce="scatter": ``psum_scatter`` over the (zero-padded)
    feature axis instead of the full psum — each shard receives only
    its owned feature slice (ReduceScatter,
    data_parallel_tree_learner.cpp:287) and the result stays
    feature-sharded for the scatter split stage. Hierarchical
    ("dcn", "ici") meshes scatter over ICI and psum the owned slice
    over DCN (see _sharded_pallas_build).
    """
    from jax.sharding import PartitionSpec as P
    from .ops.pallas_histogram import (hist_pallas_multi,
                                       hist_pallas_multi_int8,
                                       hist_multi, hist_multi_int8)
    axes = tuple(shard_mesh.axis_names)
    row_axes = axes if len(axes) > 1 else axes[0]
    scat_axis = axes[-1]
    width = int(shard_mesh.shape[scat_axis])
    scatter = hist_reduce == "scatter"

    def local(b_l, ghT_l, rl_l, ids):
        # a shard's pass counts are left where they were made: the
        # sharded learners keep no hist_counts
        if impl == "pallas":
            if int8:
                h, _ = hist_pallas_multi_int8(b_l, ghT_l, rl_l, ids,
                                              max_bins=max_bins,
                                              num_slots=ids.shape[0])
            else:
                h, _ = hist_pallas_multi(b_l, ghT_l, rl_l, ids,
                                         max_bins=max_bins,
                                         num_slots=ids.shape[0],
                                         precise=precision)
        elif int8:
            # per-shard exact-integer XLA twin of the MXU kernel
            h, _ = hist_multi_int8(b_l, ghT_l, rl_l, ids, max_bins=max_bins,
                                   num_slots=ids.shape[0], impl=impl)
        else:
            h, _ = hist_multi(b_l, ghT_l, rl_l, ids, max_bins=max_bins,
                              num_slots=ids.shape[0], impl=impl,
                              precision=precision,
                              deterministic=deterministic)
        if not scatter:
            return obs_health.psum(h, row_axes, tag="hist/psum_wave")
        fpad = (-h.shape[1]) % width
        if fpad:
            h = jnp.pad(h, ((0, 0), (0, fpad), (0, 0), (0, 0)))
        # ReduceScatter over the feature axis: INT32 payloads on the
        # int8 path stay exact under any reduction grouping
        h = obs_health.psum_scatter(h, scat_axis,
                                    tag="hist/psum_scatter",
                                    scatter_dimension=1)
        if len(axes) > 1:
            h = obs_health.psum(h, axes[:-1], tag="hist/psum_dcn")
        return h

    from .parallel.mesh import shard_map as _shard_map
    fn = _shard_map(local, mesh=shard_mesh,
                    in_specs=(P(None, row_axes), P(row_axes, None),
                              P(row_axes), P()),
                    out_specs=(P(None, scat_axis, None, None) if scatter
                               else P()))

    def multi(bins, ghT, row_leaf, ids):
        # padded rows: leaf id -1 matches no slot (slots are >= 0 or the
        # invalid sentinel -2), gh rows are zero
        bins, ghT, row_leaf = _pad_rows((bins, ghT, row_leaf), (1, 0, 0),
                                        bins.shape[1], shard_mesh.size,
                                        (0, 0, -1))
        return fn(bins, ghT, row_leaf, ids)
    return multi


def _wave_partition(shard_mesh, rows_gspmd: bool, num_leaves: int, bundle,
                    has_categorical: bool):
    """`part_ops.apply_wave_splits` as the waved grower calls it, on the
    wave's arrays alone, in the form `part_ops.wave_form` gives the
    storage (the Pallas kernel on accelerator backends). Under a shard
    mesh the kernel runs once a shard: a pallas_call does not partition
    under GSPMD, and a row's move needs only its own leaf and bins and
    the wave's facts, which every shard holds. Where GSPMD partitions the
    rows with no shard mesh to run it under (`rows_gspmd`: data-parallel
    XLA histograms, a sharded stream), the XLA form, which GSPMD
    partitions."""
    def part(row_leaf, bins_fm, *wave):
        return part_ops.apply_wave_splits(
            row_leaf, bins_fm, *wave, num_leaves, bundle, has_categorical,
            form="xla" if rows_gspmd else None)
    if rows_gspmd or shard_mesh is None or shard_mesh.size == 1:
        return part
    from jax.sharding import PartitionSpec as P

    from .parallel.mesh import shard_map as _shard_map
    axes = tuple(shard_mesh.axis_names)
    row_axes = axes if len(axes) > 1 else axes[0]

    def sharded(row_leaf, bins_fm, *wave):
        max_bins = wave[5].shape[1]                 # the cat masks, [W, B]
        if part_ops.wave_form(bins_fm, max_bins, num_leaves, bundle,
                              has_categorical) != "kernel":
            return part(row_leaf, bins_fm, *wave)
        n = row_leaf.shape[0]
        row_leaf, bins_fm = _pad_rows((row_leaf, bins_fm), (0, 1), n,
                                      shard_mesh.size, (0, 0))
        fn = _shard_map(part, mesh=shard_mesh,
                        in_specs=(P(row_axes), P(None, row_axes))
                        + (P(),) * len(wave),
                        out_specs=P(row_axes))
        return fn(row_leaf, bins_fm, *wave)[:n]
    return sharded


def grow_tree(bins_fm: jax.Array,
              grad: jax.Array,
              hess: jax.Array,
              sample_mask: jax.Array,
              feature_mask: jax.Array,
              meta: FeatureMeta,
              hp: SplitHyperParams,
              max_depth: jax.Array,
              forced: Optional[tuple] = None,
              node_key: Optional[jax.Array] = None,
              *,
              num_leaves: int,
              max_bins: int,
              hist_dtype=jnp.float32,
              row_chunk: int = 0,
              hist_impl: str = "xla",
              hist_precision: str = "highest",
              interaction_groups=None,
              has_categorical: bool = True,
              extra_trees: bool = False,
              ff_bynode: float = 1.0,
              bundle=None,
              num_bundle_bins: int = 0,
              mono_pairwise: bool = False,
              shard_mesh=None,
              hist_reduce: str = "psum",
              sparse_shape=None,
              hist_deterministic: bool = False):
    """Grow one leaf-wise tree. Returns (TreeArrays, row_leaf [N] int32).

    sparse_shape: static (num_features, num_data) when bins_fm is a
    SparseBins COO pytree (ultra-sparse storage — see
    partition.SparseBins); histogram builds then run O(nnz)
    segment-sums instead of dense one-hot contractions.

    shard_mesh: a 1-D jax.sharding.Mesh with rows sharded over its axis.
    With hist_impl="pallas", histogram builds run per-shard inside
    shard_map (pallas_call does not auto-partition under GSPMD) and are
    psum-reduced — the device analog of HistogramSumReducer
    (ref: data_parallel_tree_learner.cpp:287-297).

    hist_reduce: "psum" all-reduces full histograms (the A/B oracle);
    "scatter" reduce-scatters them over a static feature partition —
    each shard owns 1/W of the (zero-padded) feature axis, best-split
    search runs feature-sharded (parallel/scatter.py keeps it at the
    oracle's tensor shape for bit-parity) and per-shard winners combine
    through one tiny SplitInfo all_gather + argmax
    (ref: data_parallel_tree_learner.cpp:287-297 ReduceScatter +
    FindBestSplitsFromHistograms + SyncUpGlobalBestSplit). Demoted to
    psum when there is no multi-device mesh or the storage is
    EFB-bundled / COO-sparse (those builds don't run under shard_map).

    mono_pairwise: use the exact pairwise leaf-box monotone bounds
    (monotone_constraints_method intermediate/advanced — see
    split_ops.compute_box_bounds) instead of basic midpoint propagation.

    sample_mask: [N] float {0,1} bagging/GOSS selection (excluded rows still
    get a leaf assignment for score updates, but contribute no statistics —
    ref: bagging keeps full score updates, gbdt.cpp:502).
    forced: optional (leaf [L-1], feature [L-1], threshold_bin [L-1],
    is_categorical [L-1] bool) arrays; leaf entries >= 0 force that split
    at that scan step — numerical splits on bin <= threshold, categorical
    as the one-vs-rest bitset on the threshold's bin
    (ref: serial_tree_learner.cpp:628 ForceSplits).
    interaction_groups: optional [G, F] bool array of allowed feature
    combinations (ref: config.h interaction_constraints).
    """
    if sparse_shape is not None:
        num_features, num_data = sparse_shape
    else:
        num_data = bins_fm.shape[1]
        num_features = (bins_fm.shape[0] if bundle is None
                        else bundle[0].shape[0])
    L = num_leaves
    f32 = hist_dtype

    use_mesh = shard_mesh is not None and shard_mesh.size > 1
    if (not use_mesh or bundle is not None or sparse_shape is not None):
        hist_reduce = "psum"

    build_bins = max_bins if bundle is None else num_bundle_bins
    if sparse_shape is not None:
        assert bundle is None, "sparse COO storage is not bundled"
        build = functools.partial(
            hist_ops.build_histogram_sparse,
            num_features=num_features, max_bins=max_bins, dtype=f32)
    elif use_mesh and (hist_impl == "pallas" or hist_reduce == "scatter"):
        raw_build = _sharded_pallas_build(
            shard_mesh, max_bins=build_bins, dtype=f32,
            row_chunk=row_chunk, precision=hist_precision,
            impl=hist_impl, hist_reduce=hist_reduce,
            deterministic=hist_deterministic)
    else:
        raw_build = functools.partial(
            hist_ops.build_histogram, max_bins=build_bins, dtype=f32,
            row_chunk=row_chunk, impl=hist_impl, precision=hist_precision,
            deterministic=hist_deterministic)
    if sparse_shape is not None:
        pass  # build already set
    elif bundle is None:
        build = raw_build
    else:
        # EFB: build on the bundled [G, N] columns, expand to the logical
        # per-feature layout (ref: dataset.cpp:251 FastFeatureBundling)
        from .bundling import expand_bundle_hist
        group_of, offset_of, nb_arr = bundle

        def build(bins, grad_, hess_, mask_):
            hg = raw_build(bins, grad_, hess_, mask_)  # [G, B_tot, 3]
            totals = jnp.sum(hg[0], axis=0)  # every row hits group 0 once
            return expand_bundle_hist(hg, group_of, offset_of, nb_arr,
                                      max_bins, totals)
    # every build (root and per-split smaller child) is the hist layer,
    # pads and bundle expansion included; innermost scope wins, so the
    # enclosing lgbm/split of the scan does not claim it
    build = jax.named_scope("lgbm/hist")(build)

    if interaction_groups is not None:
        interaction_groups = jnp.asarray(interaction_groups, bool)
        root_allowed = jnp.any(interaction_groups, axis=0)
    else:
        root_allowed = None

    # --- root (ref: serial_tree_learner.cpp BeforeTrain root LeafSplits init)
    root_hist = build(bins_fm, grad, hess, sample_mask)
    with jax.named_scope("lgbm/split/totals"):
        root_g, root_h, root_c = hist_ops.node_totals(root_hist)
        root_out = leaf_output(root_g, root_h, hp)
    root_fmask = feature_mask if root_allowed is None else \
        feature_mask & root_allowed
    neg_inf, pos_inf = jnp.float32(-jnp.inf), jnp.float32(jnp.inf)

    if hist_reduce == "scatter":
        # feature-sharded split search + SplitInfo winner all_gather —
        # root gathers once, the scan-body sites gather L-1 times each
        from .parallel.scatter import make_scatter_split
        _scat_kw = dict(num_features=num_features,
                        hist_features=root_hist.shape[0],
                        has_categorical=has_categorical, batched=False)
        split_root_fn = make_scatter_split(shard_mesh, loop_factor=1,
                                           **_scat_kw)
        split_step_fn = make_scatter_split(shard_mesh,
                                           loop_factor=max(L - 1, 1),
                                           **_scat_kw)
    else:
        def _split_plain(hist, pg, ph, pc, meta_, hp_, fm, parent_out,
                         min_b, max_b, depth, rand_bins=None):
            return find_best_split(hist, pg, ph, pc, meta_, hp_, fm,
                                   parent_out, min_b, max_b, depth,
                                   has_categorical, rand_bins)
        split_root_fn = split_step_fn = _split_plain

    with jax.named_scope("lgbm/split"):
        rb_root, fm_root = _node_randomness(node_key, 0, meta, root_fmask,
                                            extra_trees, ff_bynode)
        root_split = split_root_fn(root_hist, root_g, root_h, root_c,
                                   meta, hp, fm_root, root_out,
                                   neg_inf, pos_inf, jnp.int32(0), rb_root)

    leaves = _LeafSplits.empty(L, max_bins, f32)
    leaves = _store_split(leaves, 0, root_split, jnp.int32(1), root_out,
                          root_g, root_h, root_c, neg_inf, pos_inf, True)

    # pool shape follows the built histogram: [F, B, 3] replicated, or
    # the zero-padded [Fp, B, 3] feature-sharded slab in scatter mode
    # (GSPMD propagates the feature sharding through the pool updates)
    pool = jnp.zeros((L,) + tuple(root_hist.shape), f32)
    pool = pool.at[0].set(root_hist)

    state = _GrowState(
        row_leaf=jnp.zeros((num_data,), jnp.int32),
        pool=pool,
        leaves=leaves,
        used_features=(jnp.zeros((L, num_features), bool)
                       if interaction_groups is not None else None),
        n_applied=jnp.int32(0),
        box_lo=(jnp.zeros((L, num_features), jnp.int32)
                if mono_pairwise else None),
        box_hi=(jnp.full((L, num_features), max_bins - 1, jnp.int32)
                if mono_pairwise else None),
    )

    if forced is None:
        neg1 = jnp.full((L - 1,), -1, jnp.int32)
        forced = (neg1, neg1, neg1, jnp.zeros((L - 1,), jnp.bool_))
    forced_leaf_arr, forced_feat_arr, forced_thr_arr, forced_cat_arr = forced

    def step(state: _GrowState, step_idx):
        leaves = state.leaves

        # --- forced candidate (ref: serial_tree_learner.cpp:628
        # ForceSplits): stats gathered from the target leaf's histogram;
        # aborted (falling back to the best split) when degenerate or
        # loss-increasing, like the reference's abort_last_forced_split
        f_leaf = jnp.maximum(forced_leaf_arr[step_idx], 0)
        f_feat = jnp.maximum(forced_feat_arr[step_idx], 0)
        f_thr = forced_thr_arr[step_idx]
        f_is_cat = forced_cat_arr[step_idx]
        f_hist = state.pool[f_leaf]
        # numerical: cumulative bins <= threshold go left; categorical:
        # one-vs-rest on the forced category's bin (ref:
        # feature_histogram.hpp GatherInfoForThreshold{Numerical,
        # Categorical} — the reference's forced categorical split is the
        # single-category bitset, tree.h:375)
        bin_eq = (jnp.arange(f_hist.shape[1]) == f_thr)
        bin_sel = jnp.where(f_is_cat, bin_eq,
                            jnp.arange(f_hist.shape[1]) <= f_thr)
        f_left = jnp.sum(f_hist[f_feat] * bin_sel[:, None], axis=0)
        f_right = jnp.sum(f_hist[f_feat] * ~bin_sel[:, None], axis=0)
        f_pg, f_ph = leaves.sum_grad[f_leaf], leaves.sum_hess[f_leaf]
        f_lg, f_lh, f_lc = f_left[GRAD], f_left[HESS], f_left[COUNT]
        f_rg, f_rh, f_rc = f_right[GRAD], f_right[HESS], f_right[COUNT]
        f_parent_out = leaves.output[f_leaf]
        f_out_l = leaf_output_smooth(f_lg, f_lh, f_lc, f_parent_out, hp)
        f_out_r = leaf_output_smooth(f_rg, f_rh, f_rc, f_parent_out, hp)
        f_gain = (leaf_gain_given_output(f_lg, f_lh, f_out_l, hp)
                  + leaf_gain_given_output(f_rg, f_rh, f_out_r, hp)
                  - leaf_gain_given_output(f_pg, f_ph, f_parent_out, hp))
        use_forced = (forced_leaf_arr[step_idx] >= 0) & (f_lc > 0) & \
            (f_rc > 0) & (f_gain > 0)

        best_leaf = jnp.where(use_forced, f_leaf,
                              jnp.argmax(leaves.gain).astype(jnp.int32))
        feat = jnp.where(use_forced, f_feat, leaves.feature[best_leaf])
        thr = jnp.where(use_forced, f_thr, leaves.threshold[best_leaf])
        # forced splits route missing by the zero-bin rule (categorical
        # partitioning ignores default_left: membership in cat_mask decides)
        forced_dleft = (~f_is_cat) & \
            (meta.missing_type[feat] == split_ops.MISSING_ZERO) & \
            (meta.default_bin[feat] <= thr)
        dleft = jnp.where(use_forced, forced_dleft,
                          leaves.default_left[best_leaf])
        forced_cat_mask = bin_eq[:leaves.cat_mask.shape[1]] & f_is_cat
        cat_mask = jnp.where(use_forced, forced_cat_mask,
                             leaves.cat_mask[best_leaf])

        # --- children stats: stored candidate, or the forced gather
        ph, pc = leaves.sum_hess[best_leaf], leaves.count[best_leaf]
        (lg, lh, lc), (rg, rh, rc) = jax.tree_util.tree_map(
            lambda forced_, stored: jnp.where(use_forced, forced_, stored),
            ((f_lg, f_lh, f_lc), (f_rg, f_rh, f_rc)),
            leaves.candidate_sides(best_leaf))

        valid = use_forced | (leaves.gain[best_leaf] > 0.0)
        # applied-split counter ids: a forced split can revive growth
        # after an invalid step, so step_idx+1 would leave id gaps that
        # Tree.from_arrays/replay can't index. Invalid steps write to the
        # out-of-bounds dummy L (scatter-dropped under jit).
        new_leaf = jnp.where(valid, state.n_applied + 1, L).astype(jnp.int32)
        n_applied = state.n_applied + valid.astype(jnp.int32)

        # --- partition rows (left keeps best_leaf id, right -> new_leaf)
        with jax.named_scope("lgbm/partition"):
            row_leaf = part_ops.apply_split(
                state.row_leaf, bins_fm, best_leaf, new_leaf, feat, thr,
                dleft, cat_mask, meta.num_bins, meta.missing_type,
                meta.is_categorical, valid, bundle)

        # --- histograms: build smaller child, subtract for the sibling
        # (ref: serial_tree_learner.cpp:373-386,582)
        left_smaller = lc <= rc
        small_id = jnp.where(left_smaller, best_leaf, new_leaf)
        small_mask = sample_mask * (row_leaf == small_id) * valid
        small_hist = build(bins_fm, grad, hess, small_mask)
        parent_hist = state.pool[best_leaf]
        large_hist = hist_ops.subtract_histogram(parent_hist, small_hist)
        left_hist = jnp.where(left_smaller, small_hist, large_hist)
        right_hist = jnp.where(left_smaller, large_hist, small_hist)

        pool = state.pool
        pool = pool.at[best_leaf].set(jnp.where(valid, left_hist, parent_hist))
        pool = pool.at[new_leaf].set(
            jnp.where(valid, right_hist, pool[new_leaf]))

        # --- child outputs: the stored candidate's (clamped, with the
        # categorical l2 where applicable), or recomputed for forced splits
        parent_out = leaves.output[best_leaf]
        p_minb = leaves.min_bound[best_leaf]
        p_maxb = leaves.max_bound[best_leaf]
        f_out_l_c = jnp.clip(f_out_l, p_minb, p_maxb)
        f_out_r_c = jnp.clip(f_out_r, p_minb, p_maxb)
        out_l = jnp.where(use_forced, f_out_l_c,
                          leaves.left_output[best_leaf])
        out_r = jnp.where(use_forced, f_out_r_c,
                          leaves.right_output[best_leaf])
        if mono_pairwise:
            # pairwise modes tighten bounds after OTHER leaves split, so
            # stored candidate outputs must be re-clipped to the leaf's
            # CURRENT bounds (the reference instead recomputes affected
            # leaves' best splits, hpp:52 RecomputeConstraintsIfNeeded)
            out_l = jnp.clip(out_l, p_minb, p_maxb)
            out_r = jnp.clip(out_r, p_minb, p_maxb)
            box_lo, box_hi = split_ops.split_child_boxes(
                state.box_lo, state.box_hi, best_leaf, new_leaf, feat, thr,
                meta.is_categorical[feat], valid)
            out_now = leaves.output.at[best_leaf].set(
                jnp.where(valid, out_l, parent_out))
            out_now = out_now.at[new_leaf].set(
                jnp.where(valid, out_r, out_now[jnp.minimum(new_leaf, L - 1)]))
            leaf_in_use = jnp.arange(L, dtype=jnp.int32) <= n_applied
            minb_all, maxb_all = split_ops.compute_box_bounds(
                box_lo, box_hi, out_now, leaf_in_use, meta.monotone)
            leaves = leaves._replace(
                min_bound=jnp.where(valid, minb_all, leaves.min_bound),
                max_bound=jnp.where(valid, maxb_all, leaves.max_bound))
            l_min, l_max = minb_all[best_leaf], maxb_all[best_leaf]
            ni = jnp.minimum(new_leaf, L - 1)
            r_min, r_max = minb_all[ni], maxb_all[ni]
        else:
            box_lo, box_hi = state.box_lo, state.box_hi
            l_min, l_max, r_min, r_max = split_ops.propagate_monotone_bounds(
                out_l, out_r, meta.monotone[feat].astype(jnp.int32),
                meta.is_categorical[feat], p_minb, p_maxb)

        # --- per-child allowed features (interaction constraints)
        used_features = state.used_features
        if used_features is not None:
            child_used = used_features[best_leaf].at[feat].set(True)
            used_features = used_features.at[best_leaf].set(
                jnp.where(valid, child_used, used_features[best_leaf]))
            used_features = used_features.at[new_leaf].set(
                jnp.where(valid, child_used, used_features[new_leaf]))
            child_fmask = feature_mask & _allowed_features(
                child_used, interaction_groups)
        else:
            child_fmask = feature_mask

        # --- find child best splits
        child_depth = leaves.depth[best_leaf] + 1
        pen_depth = child_depth - 1  # reference depth of the child leaf
        rb_l, fm_l = _node_randomness(node_key, 2 * step_idx + 2, meta,
                                      child_fmask, extra_trees, ff_bynode)
        rb_r, fm_r = _node_randomness(node_key, 2 * step_idx + 3, meta,
                                      child_fmask, extra_trees, ff_bynode)
        split_l = split_step_fn(left_hist, lg, lh, lc, meta, hp,
                                fm_l, out_l, l_min, l_max,
                                pen_depth, rb_l)
        split_r = split_step_fn(right_hist, rg, rh, rc, meta, hp,
                                fm_r, out_r, r_min, r_max,
                                pen_depth, rb_r)
        # depth cap (ref: serial_tree_learner.cpp max_depth check)
        depth_ok = (max_depth <= 0) | (child_depth < max_depth)
        split_l = split_l._replace(
            gain=jnp.where(depth_ok, split_l.gain, K_MIN_SCORE))
        split_r = split_r._replace(
            gain=jnp.where(depth_ok, split_r.gain, K_MIN_SCORE))

        # the parent's chosen gain, before leaves is overwritten (for a
        # forced split: the actual gain of the forced threshold)
        chosen_gain = jnp.where(use_forced, f_gain, leaves.gain[best_leaf])

        leaves = _store_split(leaves, best_leaf, split_l, child_depth, out_l,
                              lg, lh, lc, l_min, l_max, valid)
        leaves = _store_split(leaves, new_leaf, split_r, child_depth, out_r,
                              rg, rh, rc, r_min, r_max, valid)

        record = dict(
            split_leaf=jnp.where(valid, best_leaf, -1),
            split_feature=feat,
            split_bin_threshold=thr,
            split_default_left=dleft,
            split_gain=jnp.where(valid, chosen_gain, 0.0),
            split_cat_mask=cat_mask,
            internal_value=parent_out,
            internal_weight=ph,
            internal_count=pc,
        )
        return (_GrowState(row_leaf, pool, leaves, used_features, n_applied,
                           box_lo, box_hi),
                dict(record=record, valid=valid))

    # unroll=2: a single-step scan body wrapping pallas_call lowers to a
    # pathologically slow while-loop on TPU (~1000x); any unrolling avoids it.
    # The step is split search and bookkeeping except where an inner
    # scope (hist, partition, collective) says otherwise.
    with jax.named_scope("lgbm/split"):
        state, ys = lax.scan(step, state,
                             jnp.arange(L - 1, dtype=jnp.int32),
                             unroll=2 if L > 2 else 1)
    with jax.named_scope("lgbm/records"):
        records = ys["record"]
        # compact valid records first (a forced split can revive growth
        # after an invalid step; split s must create leaf s+1 gap-free)
        steps = jnp.arange(L - 1, dtype=jnp.int32)
        order = jnp.argsort(jnp.where(ys["valid"], steps, steps + L))
        records = jax.tree_util.tree_map(lambda a: a[order], records)

    leaves = state.leaves
    leaf_values = leaves.output
    num_leaves_out = 1 + state.n_applied

    tree_arrays = TreeArrays(
        split_leaf=records["split_leaf"],
        split_feature=records["split_feature"],
        split_bin_threshold=records["split_bin_threshold"],
        split_default_left=records["split_default_left"],
        split_gain=records["split_gain"],
        split_cat_mask=records["split_cat_mask"],
        internal_value=records["internal_value"],
        internal_weight=records["internal_weight"],
        internal_count=records["internal_count"],
        leaf_value=leaf_values,
        leaf_weight=leaves.sum_hess,
        leaf_count=leaves.count,
        num_leaves=num_leaves_out,
    )
    return tree_arrays, state.row_leaf


# multi-leaf histogram kernel slot count: 128 MXU lanes // 3 channels.
# Shared by the wave scheduler, the traffic model, and the peak-memory
# model (obs/memory.py) — the wave slab is [HIST_SLOTS, F, B, 3].
HIST_SLOTS = 42


def _wave_schedule(num_leaves: int, wave_max: int, slots: int,
                   slots_per_split: int = 1):
    """Static split-batch sizes: 1, 2, 4, ... doubling, capped at
    min(max(8, splits_done // 2), wave_max, slots // slots_per_split),
    summing to num_leaves - 1.

    The frontier-proportional cap (a wave never splits more than ~half
    the leaves the tree currently has) keeps the split ORDER close to
    exact leaf-wise where it matters: early high-impact splits are
    near-exact, late waves batch up to the slot cap per histogram
    pass. Measured on held-out data this matches the exact grower's
    quality (AUC +-0.002 at 63 and 255 leaves) while cutting full-data
    histogram passes from num_leaves-1 to ~13 at 255 leaves; fixed caps
    either lose quality (32: -0.01 AUC) or passes (8: 34).

    slots_per_split makes the schedule SUBTRACTION-AWARE: with sibling
    subtraction each split consumes ONE of the multi-kernel's 42 slots
    (build the smaller child, derive the larger from the parent), so a
    wave packs up to 42 splits per full-data pass; without it (the
    oracle mode `tpu_wave_subtract=False`) every split needs TWO slots
    and late waves halve — 17 passes instead of 13 at 255 leaves, and
    every wave scans the rows of both children instead of only the
    smaller one (<= half a skewed split's rows). The A/B is what the
    obs `hist_traffic` counters and bench.py's JSON line report."""
    sizes, total, w = [], num_leaves - 1, 1
    done = 0
    while total > 0:
        cap = min(max(8, done // 2), max(wave_max, 1),
                  max(slots // slots_per_split, 1))
        s = min(w, total, cap)
        sizes.append(s)
        total -= s
        done += s
        w *= 2
    return sizes


def hist_live_rows(counts, *, num_data: int, num_leaves: int,
                   wave_max: int, subtract: bool = True,
                   slots: int = HIST_SLOTS):
    """What the histogram passes of ONE grown tree multiplied, as the
    multi-leaf kernels counted it in their steps.

    `counts` is the tree's `TreeArrays.hist_counts` as NumPy: one row a
    pass, the root's and then a wave's (the last wave's pass is
    skipped), in ops/pallas_histogram.PASS_COUNTS' order. The wave
    schedule names the passes and gives their slots (a wave's smaller
    children; both children without sibling subtraction). A wave pass's
    `rows_live` are the rows whose leaf is one of its slot ids, bagged
    or not; the root's pass looks for none, and multiplies every row.
    `k_tiles` are the turns its steps' loops ran over every row chunk,
    bit-section and feature block, `k_tiles_full` the turns with nothing
    squeezed, `chunks_squeezed` the chunks (a bit-section's, a feature
    block's) whose live lanes were squeezed: fewer than `k_tiles_full`
    turns needed.

    Returns one dict a pass: pass ("root", "w00", ...), slots,
    rows_passed and PASS_COUNTS' fields."""
    from .ops.pallas_histogram import PASS_COUNTS
    per_split = 1 if subtract else 2
    sizes = _wave_schedule(num_leaves, wave_max, slots, per_split)
    names = [("root", 1)] + [(f"w{wi:02d}", w * per_split)
                             for wi, w in enumerate(sizes[:-1])]
    assert len(names) == len(counts), (len(names), len(counts))
    out = []
    for (name, w), row in zip(names, counts):
        one = {"pass": name, "slots": int(w), "rows_passed": int(num_data),
               **{k: int(v) for k, v in zip(PASS_COUNTS, row)}}
        if name == "root":
            one["rows_live"] = int(num_data)
        out.append(one)
    return out


def hist_traffic_model(*, num_data: int, storage_features: int,
                       max_bins: int, num_leaves: int, wave_max: int,
                       slots: int = HIST_SLOTS, pack_vpb=None,
                       gh_read_bytes: int = 12, row_leaf_bytes: int = 4,
                       subtract: bool = True, fused_grad: bool = False,
                       waved: bool = True):
    """Static per-iteration HBM traffic model of the histogram passes —
    the driver-visible counter behind ROADMAP item 3 (the shapes, wave
    schedule, packing factor and gh encoding are all trace-time
    constants, so the model is exact for what the compiled program
    streams; only gather inefficiency is outside it).

    Per pass: the bin tensor read (``storage_features x ceil(N/vpb)``
    bytes — halved by 4-bit packing), the gh operand read
    (12 B/row f32 ghT, 3 B/row int8 quantized, 12 B/row
    score+label+mask when the gradient pass is fused in-kernel) and the
    row->leaf read. ``fused_grad`` additionally drops the standalone
    gradient/bagging element-wise pass (read score/label/mask + write
    ghT ~= 24 B/row once per iteration).

    Returns a dict with per-wave and per-iteration byte/row counters;
    obs.metrics carries it as the ``hist_traffic`` meta entry and
    bench.py folds it into its JSON line."""
    import math as _math

    if pack_vpb is None:
        # default: the packing factor tpu_bin_pack=auto would pick for
        # this bin width (callers pass the ACTUAL vpb when they know it)
        from .ops.bin_pack import pack_vpb as _pack_vpb
        pack_vpb = _pack_vpb(max_bins)
    bin_bytes = storage_features * _math.ceil(num_data / pack_vpb)
    if waved:
        sizes = _wave_schedule(num_leaves, wave_max, slots,
                               1 if subtract else 2)
        passes = len(sizes)  # root + per-wave boundaries (last skipped)
    else:
        sizes = [1] * (num_leaves - 1)
        passes = num_leaves  # root + one masked full-data build per split
    per_pass = bin_bytes + num_data * (gh_read_bytes + row_leaf_bytes)
    grad_pass_bytes = 0 if fused_grad else num_data * 24
    return {
        "passes": passes,
        "wave_sizes": sizes,
        "rows_scanned_per_iter": passes * num_data,
        "wave_rows_scanned": [num_data] * passes,
        "bytes_per_pass": per_pass,
        "bin_bytes_per_pass": bin_bytes,
        "grad_pass_bytes": grad_pass_bytes,
        "hist_bytes_per_iter": passes * per_pass + grad_pass_bytes,
        "pack_vpb": pack_vpb,
        "gh_read_bytes": gh_read_bytes,
        "subtract": subtract,
        "fused_grad": fused_grad,
    }


def collective_traffic_model(*, num_features: int, max_bins: int,
                             num_leaves: int, wave_max: int, width: int,
                             reduction: str = "psum", dcn: int = 1,
                             slots: int = HIST_SLOTS,
                             subtract: bool = True, waved: bool = True):
    """Static per-iteration COLLECTIVE traffic model of the mesh grower
    — the byte counterpart of ``hist_traffic_model`` for what crosses
    the interconnect rather than HBM. Exact for the compiled program:
    wave schedule, feature padding and payload record sizes are all
    trace-time constants, and the runtime ``collectives`` counters use
    the same per-shard-result byte convention (obs/health.py), so model
    and counters agree by construction.

    reduction="psum": every histogram pass all-reduces the full
    [S, F, B, 3] slab (per-shard result bytes = the full slab).
    reduction="scatter": each pass reduce-scatters the zero-padded
    [S, Fp, B, 3] slab over ``width`` shards (per-shard result = 1/W of
    it) and every split-search batch all_gathers ``width`` SplitInfo
    records per tree position — O(W * sizeof(SplitInfo)), not
    O(F * B). With ``dcn`` > 1 (hierarchical mesh) the owned 1/W slice
    additionally psums over the slow inter-host link: ``dcn_bytes``
    prices that leg separately since DCN bandwidth, not ICI, is the
    multi-host ceiling.

    width: shards on the scatter (last, ICI) mesh axis; dcn: process
    groups on the outer axis (1 = flat single-host mesh)."""
    from .ops.split import split_info_nbytes

    f_pad = -(-num_features // max(width, 1)) * max(width, 1)
    if waved:
        sizes = _wave_schedule(num_leaves, wave_max, slots,
                               1 if subtract else 2)
        # root pass + one boundary per wave (the last is skipped);
        # boundary passes build S (or 2S) slots and search 2S children
        hist_slots = [1] + [(s if subtract else 2 * s)
                            for s in sizes[:-1]]
        search_records = 1 + 2 * sum(sizes[:-1])
    else:
        hist_slots = [1] * num_leaves  # root + smaller child per split
        search_records = 1 + 2 * (num_leaves - 1)
    slab = max_bins * 3 * 4  # one feature row: [B, 3] x 4-byte elems
    if reduction == "psum":
        hist_bytes = sum(hist_slots) * num_features * slab
        split_bytes = 0
        dcn_bytes = 0
    else:
        hist_bytes = sum(hist_slots) * (f_pad // max(width, 1)) * slab
        split_bytes = search_records * width * split_info_nbytes(max_bins)
        dcn_bytes = (hist_bytes if dcn > 1 else 0)
    return {
        "reduction": reduction,
        "width": width,
        "dcn": dcn,
        "padded_features": f_pad,
        "hist_collective_bytes_per_iter": hist_bytes,
        "split_collective_bytes_per_iter": split_bytes,
        "dcn_bytes_per_iter": dcn_bytes,
        "collective_bytes_per_iter": hist_bytes + split_bytes + dcn_bytes,
        "split_records_per_iter": search_records,
        "split_info_nbytes": split_info_nbytes(max_bins),
    }


def _wave_step_stored(carry, step_idx, *, L, meta, hp, unknown,
                      mono_pairwise, partition_fn=None):
    """One stored-candidate split application (no histogram builds) —
    the scan body shared by the resident waved grower and the streamed
    grower's wave-apply program (the streamed twin must run the SAME
    traced ops so models stay bit-identical across the modes).

    ``partition_fn(row_leaf, best, new, feat, thr, dleft, cmask, valid)``
    applies the split to row_leaf immediately (the per-split partition
    path); None leaves row_leaf untouched (batched wave partition, or
    the streamed grower where partition runs per slab).

    Invalid steps use the out-of-bounds id L: every .at[] write to it
    is dropped (jit scatter semantics), so a dummy can never clobber a
    real leaf's slot."""
    row_leaf, leaves, used, n_applied, box_lo, box_hi = carry
    best_leaf = jnp.argmax(leaves.gain).astype(jnp.int32)
    valid = leaves.gain[best_leaf] > 0.0
    new_leaf = jnp.where(valid, n_applied + 1, L).astype(jnp.int32)
    n_applied = n_applied + valid.astype(jnp.int32)
    feat = leaves.feature[best_leaf]
    thr = leaves.threshold[best_leaf]
    dleft = leaves.default_left[best_leaf]
    cmask = leaves.cat_mask[best_leaf]

    if partition_fn is not None:
        row_leaf = partition_fn(row_leaf, best_leaf, new_leaf, feat, thr,
                                dleft, cmask, valid)

    ph, pc = leaves.sum_hess[best_leaf], leaves.count[best_leaf]
    (lg, lh, lc), (rg, rh, rc) = leaves.candidate_sides(best_leaf)
    parent_out = leaves.output[best_leaf]
    p_minb = leaves.min_bound[best_leaf]
    p_maxb = leaves.max_bound[best_leaf]
    out_l = leaves.left_output[best_leaf]
    out_r = leaves.right_output[best_leaf]
    chosen_gain = leaves.gain[best_leaf]

    if mono_pairwise:
        # bounds may have tightened since this candidate was stored
        out_l = jnp.clip(out_l, p_minb, p_maxb)
        out_r = jnp.clip(out_r, p_minb, p_maxb)
        box_lo, box_hi = split_ops.split_child_boxes(
            box_lo, box_hi, best_leaf, new_leaf, feat, thr,
            meta.is_categorical[feat], valid)
        out_now = leaves.output.at[best_leaf].set(
            jnp.where(valid, out_l, parent_out))
        ni = jnp.minimum(new_leaf, L - 1)
        out_now = out_now.at[new_leaf].set(
            jnp.where(valid, out_r, out_now[ni]))
        leaf_in_use = jnp.arange(L, dtype=jnp.int32) <= n_applied
        minb_all, maxb_all = split_ops.compute_box_bounds(
            box_lo, box_hi, out_now, leaf_in_use, meta.monotone)
        leaves = leaves._replace(
            min_bound=jnp.where(valid, minb_all, leaves.min_bound),
            max_bound=jnp.where(valid, maxb_all, leaves.max_bound))
        l_min, l_max = minb_all[best_leaf], maxb_all[best_leaf]
        r_min, r_max = minb_all[ni], maxb_all[ni]
    else:
        l_min, l_max, r_min, r_max = split_ops.propagate_monotone_bounds(
            out_l, out_r, meta.monotone[feat].astype(jnp.int32),
            meta.is_categorical[feat], p_minb, p_maxb)

    if used is not None:
        child_used = used[best_leaf].at[feat].set(True)
        used = used.at[best_leaf].set(
            jnp.where(valid, child_used, used[best_leaf]))
        used = used.at[new_leaf].set(
            jnp.where(valid, child_used, used[new_leaf]))

    child_depth = leaves.depth[best_leaf] + 1
    # children have no candidates until the wave-boundary build
    leaves = _store_split(leaves, best_leaf, unknown, child_depth,
                          out_l, lg, lh, lc, l_min, l_max, valid)
    leaves = _store_split(leaves, new_leaf, unknown, child_depth,
                          out_r, rg, rh, rc, r_min, r_max, valid)

    left_smaller = lc <= rc
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
    ys = dict(record=record, valid=valid,
              left_id=best_leaf, right_id=new_leaf,
              small_id=jnp.where(left_smaller, best_leaf, new_leaf),
              left_smaller=left_smaller)
    return (row_leaf, leaves, used, n_applied, box_lo, box_hi), ys


def _unknown_split(max_bins: int) -> SplitInfo:
    """The no-candidate sentinel stored for freshly-created children
    until the wave boundary builds their histograms."""
    return SplitInfo(
        gain=jnp.float32(K_MIN_SCORE), feature=jnp.int32(0),
        threshold=jnp.int32(0), default_left=jnp.bool_(False),
        left_sum_grad=jnp.float32(0), left_sum_hess=jnp.float32(0),
        left_count=jnp.float32(0), right_sum_grad=jnp.float32(0),
        right_sum_hess=jnp.float32(0), right_count=jnp.float32(0),
        left_output=jnp.float32(0), right_output=jnp.float32(0),
        cat_mask=jnp.zeros((max_bins,), jnp.bool_))


def _init_wave_state(root_hist, meta, hp,
                     root_fmask, node_key, *, L, max_bins, num_features,
                     f32, has_categorical, extra_trees, ff_bynode,
                     interaction_groups, split_fn=None):
    """Root leaf state + histogram pool from a built root histogram —
    shared by the resident waved grower and the streamed grower (the
    streamed root histogram arrives accumulated over slabs). The root's
    totals are the histogram's own (hist_ops.node_totals).

    split_fn: optional find_best_split replacement (signature minus
    has_categorical) — the feature-sharded scatter search
    (parallel/scatter.py). The pool then inherits the (possibly
    feature-padded) built histogram's shape."""
    neg_inf, pos_inf = jnp.float32(-jnp.inf), jnp.float32(jnp.inf)
    with jax.named_scope("lgbm/split/totals"):
        root_g, root_h, root_c = hist_ops.node_totals(root_hist)
    root_out = leaf_output(root_g, root_h, hp)
    rb_root, fm_root = _node_randomness(node_key, 0, meta, root_fmask,
                                        extra_trees, ff_bynode)
    if split_fn is None:
        root_split = find_best_split(root_hist, root_g, root_h, root_c,
                                     meta, hp, fm_root, root_out,
                                     neg_inf, pos_inf, jnp.int32(0),
                                     has_categorical, rb_root)
    else:
        root_split = split_fn(root_hist, root_g, root_h, root_c,
                              meta, hp, fm_root, root_out,
                              neg_inf, pos_inf, jnp.int32(0), rb_root)

    leaves = _LeafSplits.empty(L, max_bins, f32)
    leaves = _store_split(leaves, 0, root_split, jnp.int32(1), root_out,
                          root_g, root_h, root_c, neg_inf, pos_inf, True)
    pool = jnp.zeros((L,) + tuple(root_hist.shape), f32)
    pool = pool.at[0].set(root_hist)
    used = (jnp.zeros((L, num_features), bool)
            if interaction_groups is not None else None)
    return leaves, pool, used


def _wave_boundary_core(pool, leaves, used_features, ys, wave_hists,
                        feature_mask, max_depth, node_key, s0, *,
                        subtract_siblings, L, num_features, f32, meta, hp,
                        interaction_groups, has_categorical, extra_trees,
                        ff_bynode, split_fn=None):
    """Wave-boundary histogram bookkeeping + child candidate search,
    given the wave's built histograms (`wave_hists`: the W smaller
    children under subtraction, or both-children [2W] in oracle mode).
    Shared by the resident waved grower (which builds wave_hists with
    one resident multi-leaf pass) and the streamed grower (which
    accumulates them over host-fed slabs).

    split_fn: optional BATCHED find_best_split replacement taking the
    [2W]-leading child histograms/stats (the feature-sharded scatter
    search); None runs the stock replicated vmap."""
    W = ys["valid"].shape[0]
    if subtract_siblings:
        parents = pool[ys["left_id"]]                      # [W, F, B, 3]
        small_h = wave_hists.astype(f32)
        large_h = hist_ops.subtract_histogram(parents, small_h)
        ls = ys["left_smaller"][:, None, None, None]
        left_h = jnp.where(ls, small_h, large_h)
        right_h = jnp.where(ls, large_h, small_h)
    else:
        left_h = wave_hists[:W].astype(f32)
        right_h = wave_hists[W:].astype(f32)
    left_w = jnp.where(ys["valid"], ys["left_id"], L)
    right_w = jnp.where(ys["valid"], ys["right_id"], L)
    pool = pool.at[left_w].set(left_h)
    pool = pool.at[right_w].set(right_h)

    def child_candidates(hist, cid, fmask_c, salt, leaves):
        """find_best_split for one child from its stored stats."""
        rb, fm = _node_randomness(node_key, salt, meta, fmask_c,
                                  extra_trees, ff_bynode)
        return find_best_split(
            hist, leaves.sum_grad[cid], leaves.sum_hess[cid],
            leaves.count[cid], meta, hp, fm, leaves.output[cid],
            leaves.min_bound[cid], leaves.max_bound[cid],
            leaves.depth[cid] - 1, has_categorical, rb)

    # --- candidates for the 2W children, batched
    child_ids = jnp.concatenate([ys["left_id"], ys["right_id"]])
    child_valid = jnp.concatenate([ys["valid"], ys["valid"]])
    hists = pool[child_ids]
    if used_features is not None:
        fmask_c = feature_mask[None, :] & jax.vmap(
            _allowed_features, in_axes=(0, None))(
                used_features[child_ids], interaction_groups)
    else:
        fmask_c = jnp.broadcast_to(feature_mask, (2 * W, num_features))
    salts = 2 * s0 + jnp.arange(2 * W, dtype=jnp.int32)
    if split_fn is None:
        infos = jax.vmap(child_candidates, in_axes=(0, 0, 0, 0, None))(
            hists, child_ids, fmask_c, salts, leaves)
    else:
        # same per-node randomness as the vmapped oracle, then ONE
        # batched feature-sharded search over the 2W children
        if node_key is None:
            rbs, fms = None, fmask_c
        else:
            rbs, fms = jax.vmap(
                lambda s, f: _node_randomness(node_key, s, meta, f,
                                              extra_trees, ff_bynode))(
                salts, fmask_c)
        infos = split_fn(hists, leaves.sum_grad[child_ids],
                         leaves.sum_hess[child_ids],
                         leaves.count[child_ids], meta, hp, fms,
                         leaves.output[child_ids],
                         leaves.min_bound[child_ids],
                         leaves.max_bound[child_ids],
                         leaves.depth[child_ids] - 1, rbs)
    depth_ok = (max_depth <= 0) | (leaves.depth[child_ids] < max_depth)
    gains = jnp.where(child_valid & depth_ok, infos.gain, K_MIN_SCORE)

    def upd(arr, val):
        keep = arr[child_ids]
        return arr.at[child_ids].set(
            jnp.where(child_valid.reshape(
                (-1,) + (1,) * (val.ndim - 1)), val, keep))
    leaves = leaves._replace(
        gain=leaves.gain.at[child_ids].set(
            jnp.where(child_valid, gains, leaves.gain[child_ids])),
        feature=upd(leaves.feature, infos.feature),
        threshold=upd(leaves.threshold, infos.threshold),
        default_left=upd(leaves.default_left, infos.default_left),
        sides=upd(leaves.sides, _split_sides(infos)),
        left_output=upd(leaves.left_output, infos.left_output),
        right_output=upd(leaves.right_output, infos.right_output),
        cat_mask=upd(leaves.cat_mask, infos.cat_mask),
    )
    return pool, leaves


def grow_tree_waved(bins_fm: jax.Array,
                    grad: jax.Array,
                    hess: jax.Array,
                    sample_mask: jax.Array,
                    feature_mask: jax.Array,
                    meta: FeatureMeta,
                    hp: SplitHyperParams,
                    max_depth: jax.Array,
                    forced: Optional[tuple] = None,
                    node_key: Optional[jax.Array] = None,
                    *,
                    num_leaves: int,
                    max_bins: int,
                    hist_dtype=jnp.float32,
                    hist_impl: str = "xla",
                    hist_precision: str = "highest",
                    interaction_groups=None,
                    has_categorical: bool = True,
                    wave_max: int = 32,
                    extra_trees: bool = False,
                    ff_bynode: float = 1.0,
                    quant: Optional[tuple] = None,
                    bundle=None,
                    num_bundle_bins: int = 0,
                    mono_pairwise: bool = False,
                    shard_mesh=None,
                    hist_reduce: str = "psum",
                    sparse_shape=None,
                    batched_partition=None,
                    fused_grad=None,
                    subtract_siblings: bool = True,
                    hist_deterministic: bool = False,
                    rows_gspmd: bool = False):
    """Leaf-wise growth with waved (batched) histogram construction.

    fused_grad: optional (pointwise_fn, label, weight_or_None, score)
    from the objective (objectives.pointwise_grad_fn): grad/hess are
    then DERIVED inside the grower — bitwise-identical formulas to
    objective.get_gradients — instead of arriving as materialized [N]
    buffers, and on the pallas path the multi-leaf kernel computes them
    IN-KERNEL from (score, label[, weight], mask), so the standalone
    gradient/bagging element-wise pass and the [N, 3] ghT round-trip
    through HBM disappear (~0.5 GB/iter of the cost model). The
    `grad`/`hess` arguments may be None in this mode.

    subtract_siblings: True (default) builds each split's SMALLER child
    and derives the larger by subtraction from the pooled parent
    (ref: serial_tree_learner.cpp:582); the wave schedule packs one
    slot per split. False is the no-subtraction ORACLE: both children
    are built directly (two slots per split, more waves) — retained for
    A/B parity checks and the traffic counters' baseline.

    hist_deterministic: Kahan-compensated fixed-chunk accumulation in
    the XLA histogram paths (`deterministic_hist` knob).

    batched_partition: apply each wave's splits in one pass over the
    rows (partition.apply_wave_splits: on accelerator backends one Pallas
    kernel for uint8 and packed storage, compare-and-select in XLA for EFB
    and categorical splits; no per-row gather either way) instead of one
    apply_split pass per split. None = auto: on for accelerator backends,
    off on the CPU backend; always off for COO sparse storage, which has
    no [F, N] matrix to select from. PERF.md sections 5 and 6 hold what
    each costs on the chip.

    rows_gspmd: the rows are partitioned by GSPMD over a mesh that is
    not `shard_mesh` (data-parallel XLA histograms): the wave partition
    then keeps its XLA form, which GSPMD partitions (`_wave_partition`).

    Identical split mathematics to `grow_tree`, but histogram builds are
    batched: splits are applied in waves; at each wave boundary ONE
    multi-leaf pass (ops/pallas_histogram.hist_multi) builds the smaller
    children of all the wave's splits simultaneously, and siblings come
    from subtraction. This turns the reference's per-leaf histogram
    kernels (cuda_histogram_constructor.cu:21 — one launch per leaf,
    touching that leaf's rows) into ~log2(num_leaves)+L/slots full-data
    passes — the shape the TPU MXU wants.

    Semantics vs exact leaf-wise: within a wave, freshly-created children
    are not yet split candidates (their histograms arrive at the wave
    boundary). Wave sizes grow geometrically from 1, so the early,
    high-impact splits are chosen exactly as in `grow_tree`.

    Forced splits are not supported (the caller falls back to
    `grow_tree`).

    Each pass's counts, as the multi-leaf Mosaic kernel counted them in
    its steps (live rows, sub-tile turns, chunks squeezed:
    ops/pallas_histogram.PASS_COUNTS), ride with the tree as
    `TreeArrays.hist_counts`, [passes, len(PASS_COUNTS)] int32: no
    fetch and no host work while training; they reach the host with the
    records. None where any pass ran elsewhere (XLA twins, sparse,
    per-shard).

    quant: optional (g_int [N] int-valued f32, h_int [N] int-valued f32,
    g_scale, h_scale) from the gradient discretizer. The histogram
    passes then run the int8 x int8 -> int32 kernel — the MXU pallas
    kernel on device backends (exact integer accumulation at twice the
    bf16 rate, the TPU shape of the reference's quantized histograms,
    gradient_discretizer.hpp:23), its exact-integer XLA twin elsewhere
    — and the int32 results are scaled back to the f32 statistics. The
    `grad`/`hess` arguments must already be the dequantized values
    (g_int * g_scale) so all non-histogram math is unchanged.
    """
    assert forced is None, "waved growth does not support forced splits"
    from .ops.pallas_histogram import (hist_multi, hist_multi_int8,
                                       hist_pallas_multi_fused)

    if sparse_shape is not None:
        assert bundle is None and quant is None, \
            "sparse COO storage composes with neither EFB nor int8 hist"
        num_features, num_data = sparse_shape
    else:
        num_data = bins_fm.shape[1]
        num_features = (bins_fm.shape[0] if bundle is None
                        else bundle[0].shape[0])
    L = num_leaves
    f32 = hist_dtype
    SLOTS = HIST_SLOTS  # 128 MXU columns // 3 channels
    build_bins = max_bins if bundle is None else num_bundle_bins

    use_mesh = shard_mesh is not None and shard_mesh.size > 1
    if (not use_mesh or bundle is not None or sparse_shape is not None):
        # scatter needs shard_map histogram builds over the raw bins;
        # EFB/COO storage builds don't run there — psum oracle instead
        hist_reduce = "psum"
    use_shard_hist = use_mesh and (hist_impl == "pallas"
                                   or hist_reduce == "scatter")
    use_kernel_fused = False
    if fused_grad is not None:
        assert quant is None and sparse_shape is None, \
            "fused gradients compose with neither int8 hist nor COO"
        fg_fn, fg_label, fg_weight, fg_score = fused_grad
        # derive grad/hess from the pointwise objective — bitwise the
        # same values get_gradients would have produced, but XLA can now
        # fuse the element-wise math straight into its consumers instead
        # of round-tripping materialized [N] buffers through HBM
        with jax.named_scope("lgbm/gradient"):
            grad, hess = fg_fn(fg_score, fg_label, fg_weight)
        # uint16 storage (max_bin > 256) stays on the materialized-ghT
        # path, where it has always run: the kernels' shared step takes
        # uint16 ids on either path, but no test trains through this one
        use_kernel_fused = (hist_impl == "pallas" and bundle is None
                            and shard_mesh is None and build_bins <= 256)
    # every multi_raw(bins, ghT, row_leaf, ids, **step) returns (the
    # histograms, the pass's counts: ops/pallas_histogram.PASS_COUNTS
    # where a Mosaic kernel ran the pass, else None). `step` is what the
    # caller knows of the pass (all_live: the root's), handed to the
    # Mosaic kernels; the sparse and the per-shard builders have no use
    # for it
    if sparse_shape is not None:
        def multi_raw(bins, ghT_, row_leaf, ids, **step):
            # O(nnz) segment-sum wave pass (the sparse row-wise
            # MultiValBin analog, multi_val_sparse_bin.hpp:70)
            return hist_ops.hist_multi_sparse(
                bins, ghT_, row_leaf, ids, num_features=num_features,
                max_bins=max_bins, num_slots=ids.shape[0]), None
    elif quant is not None:
        g_int, h_int, g_scale, h_scale = quant
        with jax.named_scope("lgbm/gradient"):
            m8 = sample_mask.astype(jnp.int8)
            ghT_i8 = jnp.stack([g_int.astype(jnp.int8) * m8,
                                h_int.astype(jnp.int8) * m8, m8], axis=1)
            hscale_vec = jnp.stack([g_scale, h_scale,
                                    jnp.float32(1.0)]).astype(f32)
        if use_shard_hist:
            # per-shard int8 kernel + INT32 psum: the cross-mesh reduce
            # moves exact integer histograms and dequantizes after —
            # the collective analog of the reference's quantized
            # histogram reduction (data_parallel_tree_learner.cpp:290)
            _multi_i32 = _sharded_pallas_multi(
                shard_mesh, max_bins=build_bins,
                precision=hist_precision, int8=True, impl=hist_impl,
                hist_reduce=hist_reduce,
                deterministic=hist_deterministic)

            def multi_raw(bins, ghT_unused, row_leaf, ids, **step):
                return _multi_i32(bins, ghT_i8, row_leaf,
                                  ids).astype(f32) * hscale_vec, None
        else:
            # default-capable on every backend: the pallas MXU kernel
            # where Mosaic runs, the exact-integer XLA contraction
            # elsewhere — identical int32 histograms either way
            def multi_raw(bins, ghT_unused, row_leaf, ids, **step):
                hist_i, counts = hist_multi_int8(bins, ghT_i8, row_leaf,
                                                 ids, max_bins=build_bins,
                                                 num_slots=ids.shape[0],
                                                 impl=hist_impl, **step)
                return hist_i.astype(f32) * hscale_vec, counts
    elif use_kernel_fused:
        def multi_raw(bins, ghT_unused, row_leaf, ids, **step):
            # gradient pass fused INTO the histogram kernel: reads
            # (score, label[, weight], mask) and computes gh in VMEM —
            # ghT never exists in HBM (see hist_pallas_multi_fused)
            return hist_pallas_multi_fused(
                bins, fg_score, fg_label, fg_weight, sample_mask,
                row_leaf, ids, grad_fn=fg_fn, max_bins=build_bins,
                num_slots=ids.shape[0], precise=hist_precision, **step)
    elif use_shard_hist:
        _multi_f32 = _sharded_pallas_multi(
            shard_mesh, max_bins=build_bins, precision=hist_precision,
            int8=False, impl=hist_impl, hist_reduce=hist_reduce,
            deterministic=hist_deterministic)

        def multi_raw(bins, ghT_, row_leaf, ids, **step):
            return _multi_f32(bins, ghT_, row_leaf, ids), None
    else:
        def multi_raw(bins, ghT_, row_leaf, ids, **step):
            # num_slots = the wave's LIVE count: the pallas kernel's cost
            # is fixed (128 lanes) either way, but the XLA fallback loops
            # one build per slot, so early 1-8 split waves must not pay
            # for 42
            return hist_multi(bins, ghT_, row_leaf, ids,
                              max_bins=build_bins, num_slots=ids.shape[0],
                              impl=hist_impl, precision=hist_precision,
                              deterministic=hist_deterministic, **step)
    if bundle is None:
        multi = multi_raw
    else:
        from .bundling import expand_bundle_hist
        group_of, offset_of, nb_arr = bundle

        def multi(bins, ghT_, row_leaf, ids, **step):
            hg, counts = multi_raw(bins, ghT_, row_leaf, ids,
                                   **step)             # [S, G, B_tot, 3]
            totals = jnp.sum(hg[:, 0], axis=1)  # [S, 3]
            return expand_bundle_hist(hg, group_of, offset_of, nb_arr,
                                      max_bins, totals), counts
    # the gradient/bagging element-wise product: skipped entirely when
    # the kernel computes gh in-place (fused_grad on the pallas path)
    with jax.named_scope("lgbm/gradient"):
        ghT = None if use_kernel_fused else jnp.stack(
            [grad * sample_mask, hess * sample_mask, sample_mask],
            axis=1).astype(jnp.float32)

    if interaction_groups is not None:
        interaction_groups = jnp.asarray(interaction_groups, bool)
        root_allowed = jnp.any(interaction_groups, axis=0)
    else:
        root_allowed = None

    # --- root: one slot of the multi-leaf kernel (every row is in leaf 0).
    # The single-leaf kernel's [3, C] x [C, B] dots leave the MXU 97% idle
    # (M=3 rows); the multi kernel's [f_blk*B, C] x [C, 128] shape is the
    # efficient one, so the root rides it too.
    with jax.named_scope("lgbm/hist/root"):
        root_ids = jnp.zeros((1,), jnp.int32)
        root_hist, root_counts = multi(
            bins_fm, ghT, jnp.zeros((num_data,), jnp.int32), root_ids,
            all_live=True)
        root_hist = root_hist[0].astype(f32)
    pass_counts = [root_counts]
    root_fmask = feature_mask if root_allowed is None else \
        feature_mask & root_allowed
    if hist_reduce == "scatter":
        from .parallel.scatter import make_scatter_split
        _scat_kw = dict(num_features=num_features,
                        hist_features=root_hist.shape[0],
                        has_categorical=has_categorical)
        split_root_fn = make_scatter_split(shard_mesh, batched=False,
                                           **_scat_kw)
        # one batched search per wave boundary: [2W] children gather as
        # ONE all_gather of 2W SplitInfo records per shard
        split_wave_fn = make_scatter_split(shard_mesh, batched=True,
                                           **_scat_kw)
    else:
        split_root_fn = split_wave_fn = None
    with jax.named_scope("lgbm/split"):
        leaves, pool, used_features = _init_wave_state(
            root_hist, meta, hp, root_fmask, node_key, L=L,
            max_bins=max_bins, num_features=num_features,
            f32=f32, has_categorical=has_categorical,
            extra_trees=extra_trees, ff_bynode=ff_bynode,
            interaction_groups=interaction_groups, split_fn=split_root_fn)
    row_leaf = jnp.zeros((num_data,), jnp.int32)

    unknown = _unknown_split(max_bins)

    def wave_step(carry, step_idx):
        """Apply one split using STORED candidates only (no histograms).

        New-leaf ids come from the APPLIED-split counter, not the scan
        step: a step can be invalid (stale candidates all <= 0) while a
        later wave revives growth with fresh candidates, and gap-free
        ids are what Tree.from_arrays and the score updater index by.
        """
        if use_batched_partition:
            partition_fn = None
        else:
            # per-split partition (apply_split once a step): COO storage
            # has no [F, N] matrix for the wave pass to select from, and
            # the CPU backend keeps it by batched_partition's resolution
            # below (PERF.md section 7 says what is not measured there)
            @jax.named_scope("lgbm/partition")
            def partition_fn(row_leaf, best_leaf, new_leaf, feat, thr,
                             dleft, cmask, valid):
                return part_ops.apply_split(
                    row_leaf, bins_fm, best_leaf, new_leaf, feat, thr,
                    dleft, cmask, meta.num_bins, meta.missing_type,
                    meta.is_categorical, valid, bundle)
        return _wave_step_stored(carry, step_idx, L=L, meta=meta, hp=hp,
                                 unknown=unknown,
                                 mono_pairwise=mono_pairwise,
                                 partition_fn=partition_fn)

    if batched_partition is None:
        batched_partition = not hist_ops.cpu_backend()
    use_batched_partition = sparse_shape is None and batched_partition
    partition_wave = _wave_partition(shard_mesh if use_mesh else None,
                                     rows_gspmd, L, bundle, has_categorical)

    all_records = []
    all_valid = []
    s0 = 0
    n_applied = jnp.int32(0)
    wbox_lo = (jnp.zeros((L, num_features), jnp.int32)
               if mono_pairwise else None)
    wbox_hi = (jnp.full((L, num_features), max_bins - 1, jnp.int32)
               if mono_pairwise else None)
    schedule = _wave_schedule(L, wave_max, SLOTS,
                              1 if subtract_siblings else 2)
    for wi, W in enumerate(schedule):
        with jax.named_scope(f"lgbm/split/apply/w{wi:02d}"):
            (row_leaf, leaves, used_features, n_applied, wbox_lo,
             wbox_hi), ys = lax.scan(
                wave_step,
                (row_leaf, leaves, used_features, n_applied,
                 wbox_lo, wbox_hi),
                jnp.arange(s0, s0 + W, dtype=jnp.int32))
        all_records.append(ys["record"])
        all_valid.append(ys["valid"])
        s0 += W

        if use_batched_partition:
            # ONE partition pass for the whole wave (dense, EFB and
            # packed layouts on accelerator backends, in the form
            # _wave_partition picks; each row moves at most once per
            # wave — see partition.apply_wave_splits). The COO and CPU
            # paths partitioned inside wave_step instead.
            with jax.named_scope(f"lgbm/partition/w{wi:02d}"):
                row_leaf = partition_wave(
                    row_leaf, bins_fm, ys["left_id"], ys["right_id"],
                    ys["record"]["split_feature"],
                    ys["record"]["split_bin_threshold"],
                    ys["record"]["split_default_left"],
                    ys["record"]["split_cat_mask"], ys["valid"],
                    meta.num_bins, meta.missing_type,
                    meta.is_categorical)

        if wi == len(schedule) - 1:
            # the tree is full: the children of the final wave can never
            # be split, so their histograms/candidates are dead weight —
            # skip the boundary pass entirely (saves 1 of ~13 full-data
            # passes at 255 leaves)
            break

        # --- wave boundary: ONE multi-leaf pass builds all the wave's
        # smaller children; siblings come from subtraction
        # (ref: serial_tree_learner.cpp:582 histogram subtraction).
        # One batched gather + two batched scatters instead of a W-long
        # unrolled chain: a wave's split leaves are pairwise distinct
        # (a split leaf's candidate becomes `unknown` within the wave),
        # and invalid steps write to the out-of-bounds row L, which jit
        # scatters drop — so the batch has no index collisions.
        with jax.named_scope(f"lgbm/hist/w{wi:02d}"):
            if subtract_siblings:
                small_ids = jnp.where(ys["valid"], ys["small_id"], -2)
                wave_hists, counts = multi(bins_fm, ghT, row_leaf,
                                           small_ids)  # [W, F, B, 3]
            else:
                # no-subtraction ORACLE (tpu_wave_subtract=False): build
                # BOTH children directly. Two slots per split — the
                # schedule above already halved the wave width — and the
                # pass accumulates the rows of the full frontier instead
                # of only the smaller siblings. Kept as the
                # parity/traffic baseline.
                lids = jnp.where(ys["valid"], ys["left_id"], -2)
                rids = jnp.where(ys["valid"], ys["right_id"], -2)
                wave_hists, counts = multi(bins_fm, ghT, row_leaf,
                                           jnp.concatenate([lids, rids]))
            pass_counts.append(counts)
        with jax.named_scope(f"lgbm/split/w{wi:02d}"):
            pool, leaves = _wave_boundary_core(
                pool, leaves, used_features, ys, wave_hists,
                feature_mask, max_depth, node_key, s0,
                subtract_siblings=subtract_siblings, L=L,
                num_features=num_features, f32=f32, meta=meta, hp=hp,
                interaction_groups=interaction_groups,
                has_categorical=has_categorical, extra_trees=extra_trees,
                ff_bynode=ff_bynode, split_fn=split_wave_fn)

    with jax.named_scope("lgbm/records"):
        records = jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(xs, axis=0), *all_records)
        # compact: valid splits first, in application order. A
        # stale-candidate step can be invalid while later waves keep
        # splitting, so raw scan order may interleave -1 records among
        # real ones; Tree.from_arrays and replay_tree index split s ->
        # new leaf s+1, which requires the gap-free prefix this
        # permutation restores.
        valid_all = jnp.concatenate(all_valid)
        steps = jnp.arange(L - 1, dtype=jnp.int32)
        order = jnp.argsort(jnp.where(valid_all, steps, steps + L))
        records = jax.tree_util.tree_map(lambda a: a[order], records)
        hist_counts = (None if any(c is None for c in pass_counts)
                       else jnp.stack(pass_counts))
    num_leaves_out = 1 + n_applied

    tree_arrays = TreeArrays(
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
        hist_counts=hist_counts,
    )
    return tree_arrays, row_leaf


class StreamTreeGrower:
    """Host-orchestrated ``grow_tree_waved`` twin for host-resident bins
    (out-of-core streaming training, ``tpu_stream``).

    Same split mathematics, wave schedule and traced step/boundary ops
    as the resident waved grower (the scan body and boundary math are
    literally shared: ``_wave_step_stored`` / ``_wave_boundary_core`` /
    ``_init_wave_state``); the difference is WHERE the dominant ``[F,
    N]`` bin operand lives. Every full-data pass — the root build and
    each wave's batched partition + boundary histogram build — becomes
    a loop over ``io.streaming.HostSlabBins`` slabs, with slab k+1's
    host->device upload double-buffered behind the program consuming
    slab k (the predict engine's pipeline, factored into
    ``io/streaming.py``).

    Numerics contract: per-slab partial histograms accumulate in slab
    order (slab 0 assigns, later slabs add). With a single slab the
    program consumes the same arrays through the same ops as the
    resident grower => bit-identical models (asserted in
    tests/test_stream.py across the sampling matrix). With int32
    (quantized) histograms the slab partials are exact integer sums
    that are scaled AFTER accumulation, so ANY slab count is
    bit-identical to resident. f32 multi-slab accumulation differs
    from the resident single contraction only by float-add
    associativity (~1 ulp per boundary add).

    Unsupported (callers gate to the resident grower): EFB bundles,
    COO sparse storage, forced splits, interaction constraints,
    pairwise monotone modes, exact (non-waved) growth.
    """

    def __init__(self, plan, *, num_leaves: int, max_bins: int,
                 num_features: int, hist_impl: str, hist_precision: str,
                 has_categorical: bool, extra_trees: bool,
                 ff_bynode: float, wave_max: int, subtract_siblings: bool,
                 hist_deterministic: bool):
        self.plan = plan
        self.L = int(num_leaves)
        self.max_bins = int(max_bins)
        self.num_features = int(num_features)
        self._impl = hist_impl
        self._precision = hist_precision
        self._has_cat = bool(has_categorical)
        self._extra_trees = bool(extra_trees)
        self._ff_bynode = float(ff_bynode)
        self._wave_max = int(wave_max)
        self._subtract = bool(subtract_siblings)
        self._deterministic = bool(hist_deterministic)
        self._progs = {}

    # -- jitted program builders (one callable per kind; jax's jit
    # caches per input shape, so full slabs and the tail slab simply
    # specialize the same callable) ------------------------------------
    def _prog(self, kind: str, builder):
        prog = self._progs.get(kind)
        if prog is None:
            from .obs import xla as obs_xla
            prog = self._progs[kind] = obs_xla.instrumented_jit(
                f"stream/{kind}", builder, phase="train")
        return prog

    def _slab_rows(self, slab) -> int:
        from .ops.bin_pack import PackedBins
        return slab.num_data if isinstance(slab, PackedBins) \
            else int(slab.shape[1])

    @jax.named_scope("lgbm/hist")
    def _multi(self, slab, gh_slab, rl_slab, ids):
        """One slab's histograms; the kernel's counts of a slab are
        left out (the streamed grower keeps no hist_counts)."""
        from .ops.pallas_histogram import hist_multi, hist_multi_int8
        if gh_slab.dtype == jnp.int8:
            return hist_multi_int8(slab, gh_slab, rl_slab, ids,
                                   max_bins=self.max_bins,
                                   num_slots=ids.shape[0],
                                   impl=self._impl)[0]
        return hist_multi(slab, gh_slab, rl_slab, ids,
                          max_bins=self.max_bins,
                          num_slots=ids.shape[0], impl=self._impl,
                          precision=self._precision,
                          deterministic=self._deterministic)[0]

    @staticmethod
    def _scaled(acc, hscale):
        """int32 (quantized) accumulators dequantize AFTER the cross-
        slab sum — exact integer totals, the property that makes the
        quantized streamed path bit-identical at any slab count."""
        if acc.dtype == jnp.int32:
            return acc.astype(jnp.float32) * hscale
        return acc

    def _gh_slice(self, ghT, lo, n):
        return lax.dynamic_slice_in_dim(ghT, lo, n, axis=0)

    def _run_hist(self, slab, ghT, rl_slab, lo, ids, acc):
        """One slab's histogram contribution (root or wave boundary)."""
        def first(slab_, ghT_, lo_, ids_, rl_):
            gh = self._gh_slice(ghT_, lo_, self._slab_rows(slab_))
            return self._multi(slab_, gh, rl_, ids_)

        def nxt(slab_, ghT_, lo_, ids_, rl_, acc_):
            gh = self._gh_slice(ghT_, lo_, self._slab_rows(slab_))
            return acc_ + self._multi(slab_, gh, rl_, ids_)

        if acc is None:
            return self._prog("hist_first", first)(slab, ghT, lo, ids,
                                                   rl_slab)
        return self._prog("hist_next", nxt)(slab, ghT, lo, ids, rl_slab,
                                            acc)

    def _run_wave_slab(self, slab, ghT, rl_slab, lo, wave, ids, acc,
                       meta, with_hist: bool):
        """One slab's wave work: batched partition, then (except for
        the final wave, whose children can never split) the boundary
        histogram contribution — one upload serves both."""
        mesh = getattr(self.plan, "mesh", None)
        partition_wave = _wave_partition(
            None, mesh is not None and mesh.size > 1, self.L, None,
            self._has_cat)

        @jax.named_scope("lgbm/partition")
        def part(slab_, rl_, wave_, meta_):
            return partition_wave(
                rl_, slab_, wave_["left_id"], wave_["right_id"],
                wave_["feat"], wave_["thr"], wave_["dleft"],
                wave_["cmask"], wave_["valid"], meta_.num_bins,
                meta_.missing_type, meta_.is_categorical)

        if not with_hist:
            return self._prog("wave_last", part)(slab, rl_slab, wave,
                                                 meta), None

        def part_hist_first(slab_, ghT_, rl_, lo_, wave_, ids_, meta_):
            new_rl = part(slab_, rl_, wave_, meta_)
            gh = self._gh_slice(ghT_, lo_, self._slab_rows(slab_))
            return new_rl, self._multi(slab_, gh, new_rl, ids_)

        def part_hist_next(slab_, ghT_, rl_, lo_, wave_, ids_, meta_,
                           acc_):
            new_rl = part(slab_, rl_, wave_, meta_)
            gh = self._gh_slice(ghT_, lo_, self._slab_rows(slab_))
            return new_rl, acc_ + self._multi(slab_, gh, new_rl, ids_)

        if acc is None:
            return self._prog("wave_first", part_hist_first)(
                slab, ghT, rl_slab, lo, wave, ids, meta)
        return self._prog("wave_next", part_hist_next)(
            slab, ghT, rl_slab, lo, wave, ids, meta, acc)

    def _run_wave_apply(self, leaves, n_applied, steps, meta, hp):
        unknown = _unknown_split(self.max_bins)

        @jax.named_scope("lgbm/split/apply")
        def wave_apply(leaves_, n_applied_, steps_, meta_, hp_):
            def step(carry, s):
                return _wave_step_stored(carry, s, L=self.L, meta=meta_,
                                         hp=hp_, unknown=unknown,
                                         mono_pairwise=False,
                                         partition_fn=None)
            carry, ys = lax.scan(
                step, (jnp.int32(0), leaves_, None, n_applied_, None,
                       None), steps_)
            return carry[1], carry[3], ys

        return self._prog("wave_apply", wave_apply)(leaves, n_applied,
                                                    steps, meta, hp)

    def _run_root_finish(self, acc, hscale, fmask, node_key, meta, hp):
        @jax.named_scope("lgbm/split")
        def root_finish(acc_, hscale_, fmask_, node_key_, meta_, hp_):
            root_hist = self._scaled(acc_, hscale_)[0].astype(jnp.float32)
            leaves, pool, _ = _init_wave_state(
                root_hist, meta_, hp_, fmask_, node_key_,
                L=self.L, max_bins=self.max_bins,
                num_features=self.num_features, f32=jnp.float32,
                has_categorical=self._has_cat,
                extra_trees=self._extra_trees, ff_bynode=self._ff_bynode,
                interaction_groups=None)
            return leaves, pool

        return self._prog("root_finish", root_finish)(
            acc, hscale, fmask, node_key, meta, hp)

    def _run_boundary(self, acc, hscale, pool, leaves, ys, fmask,
                      max_depth, node_key, s0, meta, hp):
        @jax.named_scope("lgbm/split")
        def boundary(acc_, hscale_, pool_, leaves_, ys_, fmask_,
                     max_depth_, node_key_, s0_, meta_, hp_):
            wave_hists = self._scaled(acc_, hscale_)
            return _wave_boundary_core(
                pool_, leaves_, None, ys_, wave_hists, fmask_,
                max_depth_, node_key_, s0_,
                subtract_siblings=self._subtract,
                L=self.L, num_features=self.num_features,
                f32=jnp.float32, meta=meta_, hp=hp_,
                interaction_groups=None, has_categorical=self._has_cat,
                extra_trees=self._extra_trees, ff_bynode=self._ff_bynode)

        return self._prog("boundary", boundary)(
            acc, hscale, pool, leaves, ys, fmask, max_depth, node_key,
            s0, meta, hp)

    # -- the grower -----------------------------------------------------
    def grow(self, ghT, hscale, feature_mask, meta, hp, max_depth,
             node_key=None):
        """Grow one tree over the host-resident slab plan.

        ghT: device ``[N, 3]`` pre-masked (g, h, m) operand — f32, or
        int8 with ``hscale`` the [3] dequantization vector (f32 passes
        ``hscale=ones``, applied only on int32 accumulators).
        Returns (TreeArrays, row_leaf [N]) like the resident growers.
        """
        plan = self.plan
        stats = plan.stats
        root_ids = jnp.zeros((1,), jnp.int32)

        # --- root histogram: one pass over the slabs
        acc = None
        for i, slab in plan.feed():
            lo = jnp.int32(plan.bounds[i][0])
            rl0 = jnp.zeros((self._slab_rows(slab),), jnp.int32)
            acc = self._run_hist(slab, ghT, rl0, lo, root_ids, acc)
            stats.note_dispatch()
        leaves, pool = self._run_root_finish(
            acc, hscale, feature_mask, node_key, meta, hp)

        rl_slabs = None  # per-slab row->leaf pieces (lazily zeros)
        n_applied = jnp.int32(0)
        all_records, all_valid = [], []
        s0 = 0
        schedule = _wave_schedule(self.L, self._wave_max, HIST_SLOTS,
                                  1 if self._subtract else 2)
        for wi, W in enumerate(schedule):
            steps = jnp.arange(s0, s0 + W, dtype=jnp.int32)
            leaves, n_applied, ys = self._run_wave_apply(
                leaves, n_applied, steps, meta, hp)
            all_records.append(ys["record"])
            all_valid.append(ys["valid"])
            s0 += W
            last = wi == len(schedule) - 1
            if self._subtract:
                ids = jnp.where(ys["valid"], ys["small_id"], -2)
            else:
                ids = jnp.concatenate(
                    [jnp.where(ys["valid"], ys["left_id"], -2),
                     jnp.where(ys["valid"], ys["right_id"], -2)])
            wave = {"left_id": ys["left_id"], "right_id": ys["right_id"],
                    "feat": ys["record"]["split_feature"],
                    "thr": ys["record"]["split_bin_threshold"],
                    "dleft": ys["record"]["split_default_left"],
                    "cmask": ys["record"]["split_cat_mask"],
                    "valid": ys["valid"]}
            acc = None
            new_rls = []
            for i, slab in plan.feed():
                lo_i, hi_i = plan.bounds[i]
                rl = (rl_slabs[i] if rl_slabs is not None else
                      jnp.zeros((hi_i - lo_i,), jnp.int32))
                rl2, acc = self._run_wave_slab(
                    slab, ghT, rl, jnp.int32(lo_i), wave, ids, acc,
                    meta, with_hist=not last)
                new_rls.append(rl2)
                stats.note_dispatch()
            rl_slabs = new_rls
            stats.waves_total += 1
            if last:
                # the tree is full: the final wave's children can never
                # split, so the boundary pass is skipped — same as the
                # resident grower
                break
            pool, leaves = self._run_boundary(
                acc, hscale, pool, leaves, ys, feature_mask, max_depth,
                node_key, jnp.int32(s0), meta, hp)

        # --- assemble (same compaction as the resident grower)
        records = jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(xs, axis=0), *all_records)
        valid_all = jnp.concatenate(all_valid)
        steps_all = jnp.arange(self.L - 1, dtype=jnp.int32)
        order = jnp.argsort(jnp.where(valid_all, steps_all,
                                      steps_all + self.L))
        records = jax.tree_util.tree_map(lambda a: a[order], records)
        row_leaf = (rl_slabs[0] if len(rl_slabs) == 1
                    else jnp.concatenate(rl_slabs))
        tree_arrays = TreeArrays(
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
            num_leaves=1 + n_applied,
        )
        return tree_arrays, row_leaf


def replay_tree(tree: TreeArrays, bins_fm, meta: FeatureMeta, bundle=None,
                num_data: Optional[int] = None) -> jax.Array:
    """Re-derive the row -> leaf map of a grown tree on another binned
    dataset (device). Replays the recorded splits in creation order — the
    device analog of updating a validation ScoreUpdater
    (ref: score_updater.hpp:22, gbdt.cpp UpdateScore valid path).
    num_data is required when bins_fm is a SparseBins COO pytree."""
    if num_data is None:
        num_data = bins_fm.shape[1]
    num_splits = tree.split_leaf.shape[0]

    def step(row_leaf, inputs):
        step_idx, leaf, feat, thr, dleft, cmask = inputs
        row_leaf = part_ops.apply_split(
            row_leaf, bins_fm, leaf, step_idx + 1, feat, thr, dleft, cmask,
            meta.num_bins, meta.missing_type, meta.is_categorical, leaf >= 0,
            bundle)
        return row_leaf, None

    row_leaf, _ = lax.scan(
        step, jnp.zeros(num_data, jnp.int32),
        (jnp.arange(num_splits, dtype=jnp.int32), tree.split_leaf,
         tree.split_feature, tree.split_bin_threshold,
         tree.split_default_left, tree.split_cat_mask),
        unroll=2 if num_splits > 1 else 1)
    return row_leaf
