"""Row partition op (device).

TPU-native replacement for the reference DataPartition
(ref: src/treelearner/data_partition.hpp:22, cuda_data_partition.cu:291).
Rather than physically permuting row indices per leaf, we keep a full-length
``row_leaf: [N] int32`` map (row -> leaf id) and update it with masked
`where` — the mask-over-permutation idiom that XLA/TPU prefers.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import histogram as hist_ops, pallas_partition
from .bin_pack import PackedBins, unpack_feature, unpack_rows
from .split import MISSING_NAN
from ..obs.metrics import global_metrics


class SparseBins(NamedTuple):
    """COO binned storage for ultra-sparse, non-bundleable data — the
    TPU-native analog of the reference's sparse row-wise MultiValBin
    (ref: include/LightGBM/bin.h:482, multi_val_sparse_bin.hpp:21).

    Only entries whose bin differs from the feature's implicit-zero bin
    are stored; histogram builds run one O(nnz) segment-sum instead of
    the O(N*F*B) dense one-hot contraction, and the implicit-zero bin
    mass is recovered per feature as (leaf totals - explicit bins) —
    the same residual trick the reference's sparse bins use. Flows
    through the growers in the `bins_fm` argument slot; every consumer
    dispatches on isinstance.

    coo_row/coo_feat/coo_bin: [nnz] int32; zero_bins: [F] int32
    (the bin an implicit zero maps to, per feature).
    """
    coo_row: jax.Array
    coo_feat: jax.Array
    coo_bin: jax.Array
    zero_bins: jax.Array


def sparse_feature_bins(sb: SparseBins, feature: jax.Array,
                        num_data: int) -> jax.Array:
    """Materialize one logical [N] bin column from the COO storage:
    rows absent from the column's explicit entries carry its
    implicit-zero bin."""
    sel = sb.coo_feat == feature
    rows = jnp.where(sel, sb.coo_row, num_data)  # OOB rows are dropped
    out = jnp.full((num_data,), sb.zero_bins[feature], jnp.int32)
    return out.at[rows].set(jnp.where(sel, sb.coo_bin, 0).astype(jnp.int32),
                            mode="drop")


def feature_bins(bins_fm, feature: jax.Array, bundle=None,
                 num_data: int = 0) -> jax.Array:
    """Logical [N] bin column of `feature` — a plain row slice for a
    dense matrix, an on-the-fly decode of the EFB-bundled matrix
    (bundle = (group_of, offset_of, num_bins) device arrays; ref:
    feature_group.h bin_offsets_ decoding), a shift/mask unpack for
    PackedBins, or a COO materialization for SparseBins storage."""
    if isinstance(bins_fm, SparseBins):
        return sparse_feature_bins(bins_fm, feature, num_data)
    if isinstance(bins_fm, PackedBins):
        return unpack_feature(bins_fm, feature)
    if bundle is None:
        return jnp.take(bins_fm, feature, axis=0).astype(jnp.int32)
    group_of, offset_of, nb = bundle
    col = jnp.take(bins_fm, group_of[feature], axis=0).astype(jnp.int32)
    return _decode_bundled(col, offset_of[feature], nb[feature])


def _decode_bundled(col: jax.Array, off: jax.Array,
                    nbf: jax.Array) -> jax.Array:
    """EFB stored-column -> logical bin (ref: feature_group.h
    bin_offsets_): values inside [off, off + nbf - 1) map to logical
    bins 1.., everything else is the feature's implicit bin 0. Single
    source of the decode rule for every device bin consumer."""
    in_range = (col >= off) & (col < off + nbf - 1)
    return jnp.where(in_range, col - off + 1, 0)


def _bits(n: int) -> int:
    """Bits that hold every value of [0, n]."""
    return max(int(n), 1).bit_length()


def _per_row(m: jax.Array, rec: jax.Array) -> jax.Array:
    """Row i's value of a per-split record (rec: [W]) under the match
    matrix m[w, i] (at most one w true per row; 0 where none is)."""
    return jnp.sum(jnp.where(m, rec[:, None], 0), axis=0, dtype=rec.dtype)


# Largest table `per_row_lookup` reads by selects; a longer one is
# gathered. The selects cost L - 1 vector operations a row and as many
# instructions to compile, the gather a constant 5.6-8.6 ns a row: PERF.md
# section 6 (PR 31) holds the chip readings of both at 63M rows (a 74th of
# the gather's time at 255 entries, a 14th at 1,023 after a compile of
# 11 s; at 4,095 still a 5th, after a compile of 53 s).
LOOKUP_SELECT_MAX = 1023


def per_row_lookup(table: jax.Array, idx: jax.Array) -> jax.Array:
    """``table[idx]`` for a small table: row i's value of `table` ([L])
    at idx[i] (idx: [N] int32), [N] of table.dtype.

    At L <= LOOKUP_SELECT_MAX (a static shape) nothing row-sized is
    gathered: bit b of a row's index selects between the two halves of
    every 2^(b+1) entries, level by level, L - 1 selects a row in one
    fused pass over idx; above it the table is gathered, which costs the
    same whatever L is. For 0 <= idx < L the two forms agree bit for
    bit (a select copies its value; NaN, infinities and -0.0 reach the
    rows that name them and no other). Outside that range they differ:
    the selects return 0, the gather clamps an index >= L to L - 1 and
    wraps a negative one. Every grower's row -> leaf map and
    `replay_tree`'s stay inside [0, num_leaves) (padded rows of sharded
    storage walk the tree like any row; the -1 leaf of `_pad_rows` lives
    inside a histogram call only), so no caller sees the difference.
    """
    L = table.shape[0]
    if L > LOOKUP_SELECT_MAX:
        global_metrics.note_trace("ops/row_lookup_gather")
        return table[idx]
    global_metrics.note_trace("ops/row_lookup_select")
    vals = [table[j] for j in range(L)]
    bit = 0
    while len(vals) > 1:
        odd = ((idx >> bit) & 1) == 1
        # an entry with no sibling (ragged L) goes up as it is: the
        # indices that would reach the missing one are >= L
        vals = [jnp.where(odd, vals[j + 1], vals[j]) if j + 1 < len(vals)
                else vals[j] for j in range(0, len(vals), 2)]
        bit += 1
    out = jnp.where((idx >= 0) & (idx < L), vals[0], 0)
    # the result is written as the [N] vector it is: fused into a
    # consumer shaped [1, N] (the scores of one class) the selects ran
    # on one sublane of eight, 0.035 s for 0.007 (PERF.md section 6)
    return jax.lax.optimization_barrier(out)


def _per_row_fields(m: jax.Array, fields: dict) -> dict:
    """Per-row values of the per-split `fields` ({name: (values [W],
    bits)}, every value in [0, 2^bits) where it matters): the fields
    are packed, first fit, into as few 31-bit int32 records as hold
    them, each record is brought to the rows once (`_per_row`) and cut
    up again there. The widths come from static shapes only."""
    words, used, place = [], [], {}
    for name, (val, bits) in fields.items():
        val = val.astype(jnp.int32) & ((1 << bits) - 1)
        k = next((i for i, u in enumerate(used) if u + bits <= 31), None)
        if k is None:
            k = len(words)
            words.append(jnp.zeros_like(val))
            used.append(0)
        words[k] = words[k] | (val << used[k])
        place[name] = (k, used[k], bits)
        used[k] += bits
    rows = [_per_row(m, w) for w in words]
    return {name: (rows[k] >> shift) & ((1 << bits) - 1)
            for name, (k, shift, bits) in place.items()}


def _per_row_feature_bins(bins_fm, feat: jax.Array) -> jax.Array:
    """Stored bin of row i on the stored row feat[i] of `bins_fm`
    (feat: [N] int32), as a select over the F rows and not as a
    gather: every bin is read once, in the layout it is stored in."""
    if isinstance(bins_fm, PackedBins):
        return unpack_rows(bins_fm, feat)
    ids = jnp.arange(bins_fm.shape[0], dtype=jnp.int32)[:, None]
    return jnp.sum(jnp.where(feat[None, :] == ids, bins_fm, 0), axis=0,
                   dtype=jnp.int32)


def _per_row_cat_bit(row_leaf: jax.Array, lids: jax.Array,
                     cat_masks: jax.Array, fbins: jax.Array) -> jax.Array:
    """cat_masks[w, fbins[i]] for the step w whose leaf lids[w] row i is
    in (False where it is in none): each step's [B] mask is packed into
    K = ceil(B / 32) words, and a row finds its word by comparing the
    key (leaf, word index) with the W * K keys of the wave."""
    w_count, b = cat_masks.shape
    k = -(-b // 32)
    bit = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    words = jnp.sum(
        jnp.where(jnp.pad(cat_masks, ((0, 0), (0, k * 32 - b)))
                  .reshape(w_count, k, 32), bit, jnp.uint32(0)),
        axis=2, dtype=jnp.uint32)                       # [W, K]
    keys = lids[:, None] * k + jnp.arange(k, dtype=jnp.int32)
    mine = (row_leaf * k + (fbins >> 5))[None, :] == keys.reshape(-1, 1)
    word = _per_row(mine, words.reshape(-1))
    return ((word >> (fbins & 31).astype(jnp.uint32)) & 1) == 1


def apply_wave_splits(row_leaf: jax.Array, bins_fm: jax.Array,
                      leaf_ids: jax.Array, right_ids: jax.Array,
                      features: jax.Array, thresholds: jax.Array,
                      default_lefts: jax.Array, cat_masks: jax.Array,
                      valid: jax.Array, num_bins: jax.Array,
                      missing_type: jax.Array, is_categorical: jax.Array,
                      num_leaves: int, bundle=None,
                      has_categorical: bool = True,
                      form: str | None = None) -> jax.Array:
    """Apply a whole wave's W splits in ONE pass over the rows.

    A wave's split leaves are pairwise distinct and a leaf created
    within the wave is never split in the same wave (its candidates are
    unknown until the boundary), so each row moves AT MOST once per
    wave and the W sequential apply_split passes collapse into one
    decision per row, bit-equal to that chain.

    Nothing row-sized is gathered. Each step's facts (feature,
    threshold, NaN bin, default side, right child; for EFB storage the
    group, offset and width) are looked up at [W] and packed into one
    or two int32 records; a row finds its step by comparing its leaf
    with the W split leaves, and its bin on that step's feature by
    comparing that feature with the F stored rows, so the pass reads
    row_leaf and every bin once. has_categorical (static) False traces
    nothing categorical.

    `form` (static; None: `wave_form`'s choice) is "kernel", the same
    decision as one Pallas pass that reads row_leaf and the bins once and
    writes row_leaf once (`pallas_partition.wave_partition`; interpret
    mode on the CPU backend), or "xla", the fusions below. Both count
    their traced call sites in `global_metrics.trace_counts` ("ops/partition_wave_kernel",
    "ops/partition_wave") and publish the pass in
    `global_metrics.meta["partition_wave"]`. PERF.md sections 5 and 6
    hold what each costs on the chip.
    """
    L = num_leaves
    B = cat_masks.shape[1]
    if form is None:
        form = wave_form(bins_fm, B, L, bundle, has_categorical)
    pallas_partition.note_pass(form, row_leaf.shape[0], bins_fm)
    if form == "kernel":
        assert bundle is None and not has_categorical, form
        global_metrics.note_trace("ops/partition_wave_kernel")
        return pallas_partition.wave_partition(
            row_leaf, bins_fm, leaf_ids, right_ids, features, thresholds,
            default_lefts, valid, num_bins, missing_type, num_leaves=L,
            max_bins=B, interpret=hist_ops.cpu_backend())
    global_metrics.note_trace("ops/partition_wave")
    lids = jnp.where(valid, leaf_ids, L)
    m = row_leaf[None, :] == lids[:, None]              # [W, N], fused
    nan_code = jnp.where(missing_type[features] == MISSING_NAN,
                         num_bins[features], 0)         # NaN bin + 1
    fields = {
        "hit": (jnp.ones_like(features), 1),
        "row": (features if bundle is None else bundle[0][features],
                _bits(bins_fm.shape[0] - 1)),      # stored row of bins_fm
        "thr": (thresholds, _bits(B - 1)),
        "nan_code": (nan_code, _bits(B)),
        "dleft": (default_lefts, 1),
        "right": (right_ids, _bits(L - 1))}
    if has_categorical:
        fields["is_cat"] = (is_categorical[features], 1)
    if bundle is not None:
        _, offset_of, nb = bundle
        stored = bins_fm.data if isinstance(bins_fm, PackedBins) else bins_fm
        fields["off"] = (offset_of[features], 8 * stored.dtype.itemsize + 1)
        fields["nb"] = (nb[features], _bits(B))
    r = _per_row_fields(m, fields)
    fbins = _per_row_feature_bins(bins_fm, r["row"])
    if bundle is not None:
        fbins = _decode_bundled(fbins, r["off"], r["nb"])
    go_left = jnp.where(fbins + 1 == r["nan_code"], r["dleft"] == 1,
                        fbins <= r["thr"])
    if has_categorical:
        cat_left = _per_row_cat_bit(row_leaf, lids, cat_masks, fbins)
        go_left = jnp.where(r["is_cat"] == 1, cat_left, go_left)
    move = (r["hit"] == 1) & ~go_left
    return jnp.where(move, r["right"], row_leaf)


def wave_form(bins_fm, max_bins: int, num_leaves: int, bundle=None,
              has_categorical: bool = True) -> str:
    """The form `apply_wave_splits` takes, by static facts alone:
    "kernel" (`pallas_partition`) on accelerator backends for uint8 or
    PackedBins storage with no EFB bundle and no categorical split, whose
    split records fit 32 bits and whose grid step fits VMEM
    (`pallas_partition.admits`); "xla" elsewhere (EFB, categorical,
    uint16 bins, tables of more than 15,328 stored rows, the CPU
    backend)."""
    if hist_ops.cpu_backend() or bundle is not None or has_categorical:
        return "xla"
    return ("kernel" if pallas_partition.admits(bins_fm, max_bins, num_leaves)
            else "xla")


def apply_split(row_leaf: jax.Array, bins_fm: jax.Array,
                leaf_id: jax.Array, new_leaf_id: jax.Array,
                feature: jax.Array, threshold: jax.Array,
                default_left: jax.Array, cat_mask: jax.Array,
                num_bins: jax.Array, missing_type: jax.Array,
                is_categorical: jax.Array, valid: jax.Array,
                bundle=None) -> jax.Array:
    """Send rows of `leaf_id` that fail the decision to `new_leaf_id`.

    Numerical: bin <= threshold -> left; the NaN bin (last bin when
    missing_type == NAN) follows `default_left`. Categorical: bins set in
    `cat_mask` ([B] bool — the device analog of the reference's category
    bitset, tree.h:375) go left. No-op when `valid` is False.
    """
    global_metrics.note_trace("ops/partition")
    fbins = feature_bins(bins_fm, feature, bundle,
                         num_data=row_leaf.shape[0])  # [N]
    nan_bin = num_bins[feature] - 1
    is_nan = (missing_type[feature] == MISSING_NAN) & (fbins == nan_bin)
    numerical = jnp.where(is_nan, default_left, fbins <= threshold)
    go_left = jnp.where(is_categorical[feature], cat_mask[fbins], numerical)
    move = valid & (row_leaf == leaf_id) & ~go_left
    return jnp.where(move, new_leaf_id, row_leaf)
