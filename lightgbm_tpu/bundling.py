"""Exclusive Feature Bundling (EFB) — the wide-sparse data path.

TPU-native re-think of the reference's FeatureGroup/EFB machinery
(ref: src/io/dataset.cpp:112 FindGroups, :251 FastFeatureBundling,
include/LightGBM/feature_group.h:27). The reference bundles mutually
exclusive features so one Bin column stores many features. On TPU the
dense ``[F, N]`` bin tensor is the memory ceiling for wide one-hot data
(10k features x 10M rows = 100 GB unbundled), so bundling compresses
STORAGE to ``[G, N]`` with G = #bundles; histograms are built on the
bundled columns and expanded back to the logical per-feature layout with
a static gather, so the split finder and all tree semantics are
unchanged.

Encoding inside a bundle (ref: feature_group.h bin_offsets_): bundle bin
0 = every member feature at its default bin; member f's non-default bins
``1..nb_f-1`` occupy the half-open range ``[offset_f, offset_f+nb_f-1)``.
The logical bin-0 row of each member's histogram is recovered as
``leaf_total - sum(non-default bins)`` — exact for conflict-free
bundles (and the bundler only merges conflict-free features unless
`max_conflict_rate` allows otherwise, like the reference).
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Tuple

import numpy as np


class BundleInfo(NamedTuple):
    """Static bundle structure (host). F = logical used features,
    G = stored columns."""
    bundles: Tuple[Tuple[int, ...], ...]  # member feature idxs per bundle
    group_of: np.ndarray   # [F] int32: stored column of feature f
    offset_of: np.ndarray  # [F] int32: bundle bin of f's logical bin 1
    num_bundle_bins: int   # max bins over stored columns (B_tot)

    @classmethod
    def from_bundles(cls, bundles, num_bins) -> "BundleInfo":
        """Derive the offset layout from bundle membership — the single
        source of truth for the encoding (build + binary reload both
        call this)."""
        f = len(num_bins)
        group_of = np.zeros(f, np.int32)
        offset_of = np.zeros(f, np.int32)
        widths = []
        for g, members in enumerate(bundles):
            off = 1
            for feat in members:
                group_of[feat] = g
                offset_of[feat] = off
                off += int(num_bins[feat]) - 1
            widths.append(off)
        return cls(bundles=tuple(tuple(m) for m in bundles),
                   group_of=group_of, offset_of=offset_of,
                   num_bundle_bins=max(widths) if widths else 1)


def find_bundles(nonzero_masks: np.ndarray, num_bins: np.ndarray,
                 *, max_conflict_rate: float = 0.0,
                 max_bundle_bins: int = 256,
                 bundleable: Optional[np.ndarray] = None) -> List[List[int]]:
    """Greedy conflict-bounded grouping (ref: dataset.cpp:112 FindGroups).

    nonzero_masks: [F, S] bool over the binning SAMPLE rows — True where
    the feature is at a non-default bin. Features are scanned in
    decreasing nonzero count (the reference's ordering) and placed into
    the first bundle whose accumulated conflict count and total bin width
    allow it. Features with `bundleable[f] == False` (e.g. default bin
    != 0, which the offset encoding can't represent) are forced into
    singleton bundles — stored verbatim.
    """
    f, s = nonzero_masks.shape
    nz_rows = [np.flatnonzero(nonzero_masks[i]) for i in range(f)]
    return find_bundles_sparse(nz_rows, s, num_bins,
                               max_conflict_rate=max_conflict_rate,
                               max_bundle_bins=max_bundle_bins,
                               bundleable=bundleable)


def find_bundles_sparse(nz_rows: List[np.ndarray], sample_cnt: int,
                        num_bins: np.ndarray,
                        *, max_conflict_rate: float = 0.0,
                        max_bundle_bins: int = 256,
                        bundleable: Optional[np.ndarray] = None
                        ) -> List[List[int]]:
    """Greedy grouping from per-feature non-default sample row INDICES —
    the core shared with the dense path and the entry point for sparse
    (CSC) ingestion, where a dense [F, S] mask would defeat the point.
    Bundle masks stay dense [S] bool (few bundles); each feature costs
    O(nnz_f) to test and place."""
    max_conflicts = int(max_conflict_rate * sample_cnt)
    order = np.argsort(-np.array([len(r) for r in nz_rows], np.int64))
    # cap the per-feature candidate search like the reference's
    # max_search_group (ref: dataset.cpp:118 FindGroups) — without it,
    # wide data where most features conflict degrades quadratically
    max_search = 100
    search_rng = np.random.RandomState(3)

    bundle_members: List[List[int]] = []
    bundle_masks: List[Optional[np.ndarray]] = []
    bundle_conflicts: List[int] = []
    bundle_bins: List[int] = []
    for feat in order:
        feat = int(feat)
        width = int(num_bins[feat]) - 1  # non-default bins it adds
        rows = nz_rows[feat]
        placed = False
        if bundleable is None or bundleable[feat]:
            n_groups = len(bundle_members)
            if n_groups > max_search:
                candidates = search_rng.choice(n_groups, max_search,
                                               replace=False)
            else:
                candidates = range(n_groups)
            for g in candidates:
                if bundle_masks[g] is None:  # singleton-only bundle
                    continue
                if bundle_bins[g] + width + 1 > max_bundle_bins:
                    continue
                conflicts = int(bundle_masks[g][rows].sum())
                if bundle_conflicts[g] + conflicts <= max_conflicts:
                    bundle_members[g].append(feat)
                    bundle_masks[g][rows] = True
                    bundle_conflicts[g] += conflicts
                    bundle_bins[g] += width
                    placed = True
                    break
        if not placed:
            bundle_members.append([feat])
            if bundleable is None or bundleable[feat]:
                mask = np.zeros(sample_cnt, bool)
                mask[rows] = True
                bundle_masks.append(mask)
            else:
                bundle_masks.append(None)
            bundle_conflicts.append(0)
            bundle_bins.append(width + 1)
    return bundle_members


def build_bundled_matrix(bins_fm: np.ndarray, num_bins: np.ndarray,
                         bundles: List[List[int]]
                         ) -> Tuple[np.ndarray, BundleInfo]:
    """Merge a logical [F, N] bin matrix into stored [G, N] columns.

    Rows with several non-default members in one bundle (conflicts, when
    max_conflict_rate > 0) keep the LAST member's code, like the
    reference's push order.
    """
    f, n = bins_fm.shape
    info = BundleInfo.from_bundles(bundles, num_bins)
    dtype = np.uint8 if info.num_bundle_bins <= 256 else np.uint16
    out = np.zeros((len(bundles), n), dtype)
    for g, members in enumerate(bundles):
        col = np.zeros(n, np.int64)
        for feat in members:
            fb = bins_fm[feat].astype(np.int64)
            nz = fb > 0
            col[nz] = info.offset_of[feat] + fb[nz] - 1
        out[g] = col.astype(dtype)
    return out, info


def build_bundled_from_csc(csc, mappers, used: List[int],
                           bundles: List[List[int]],
                           num_bins: np.ndarray
                           ) -> Tuple[np.ndarray, BundleInfo]:
    """Build the stored [G, N] bundle matrix DIRECTLY from a scipy CSC
    matrix — no dense [N, F] or [F, N] intermediate ever exists (the
    point of the sparse ingestion path; ref: sparse_bin.hpp:74 and
    LGBM_DatasetCreateFromCSC c_api.cpp:1330).

    `used[j]` is the raw CSC column of logical feature j; `bundles`
    holds logical feature indices. Encoding identical to
    build_bundled_matrix: member f's non-default bins live at
    [offset_f, offset_f + nb_f - 1); non-bundleable singletons are
    stored verbatim (their implicit zeros at their default bin).
    """
    n = csc.shape[0]
    info = BundleInfo.from_bundles(bundles, num_bins)
    dtype = np.uint8 if info.num_bundle_bins <= 256 else np.uint16
    out = np.zeros((len(bundles), n), dtype)
    col = np.empty(n, np.int64)
    for g, members in enumerate(bundles):
        col[:] = 0
        for feat in members:
            m = mappers[feat]
            # the bin an IMPLICIT zero lands in — transform(0.0), NOT
            # m.default_bin: for categorical mappers category 0's bin is
            # >= 1 while default_bin is always 0
            zb = int(m.transform(np.zeros(1))[0])
            sl = slice(csc.indptr[used[feat]], csc.indptr[used[feat] + 1])
            rows = csc.indices[sl]
            fb = m.transform(csc.data[sl]).astype(np.int64)
            if len(members) == 1 and zb != 0:
                # verbatim singleton: implicit zeros sit at zero's bin
                col[:] = zb
                col[rows] = fb
            elif zb != 0:
                # shared-bundle member whose implicit zeros are a REAL
                # bin (a categorical with category 0 — dense-made
                # bundles can contain these): zeros must be encoded,
                # exactly like the dense builder encodes every fb > 0
                # row. Write the complement first so explicit rows (and
                # later members, last-wins like the reference's push
                # order) overwrite it.
                mask = np.ones(n, bool)
                mask[rows] = False
                col[mask] = info.offset_of[feat] + zb - 1
                nz = fb > 0
                col[rows[nz]] = info.offset_of[feat] + fb[nz] - 1
                col[rows[~nz]] = 0
            else:
                # sparse-made bundles guarantee zb == 0 for shared
                # members, so implicit zeros stay at stored 0
                nz = fb > 0
                col[rows[nz]] = info.offset_of[feat] + fb[nz] - 1
        out[g] = col.astype(dtype)
    return out, info


def should_bundle(bundles: List[List[int]], num_features: int) -> bool:
    """Bundling pays when it actually shrinks the matrix (ref:
    dataset.cpp FastFeatureBundling only groups when beneficial)."""
    return len(bundles) < num_features


# ----------------------------------------------------------------------
# logical views. Device-side decode lives in ops/partition.feature_bins
# (the jit-traced twin of this helper); keep the two in sync.


def decode_stored_host(col_stored: np.ndarray, offset: np.ndarray,
                       width: np.ndarray) -> np.ndarray:
    """Host decode of stored bundle codes to logical bins (vectorized
    over rows with per-row offsets/widths): stored in
    [off, off+width) -> stored - off + 1; else default 0."""
    in_range = (col_stored >= offset) & (col_stored < offset + width)
    return np.where(in_range, col_stored - offset + 1, 0)


def expand_bundle_hist(bundle_hist, group_of, offset_of, nb,
                       max_bins: int, totals):
    """[..., G, B_tot, C] bundled histogram -> [..., F, B, C] logical.

    nb: [F] logical bin counts; totals: [..., C] per-leaf channel totals
    (each feature's default-bin row = total - sum of its own non-default
    bins). Rows b >= nb[f] would hold neighboring features' bins: they
    are zeroed, so that every feature's bins sum to the totals as in an
    unbundled histogram (the split scan and hist_ops.node_totals sum a
    feature's whole row).
    """
    import jax.numpy as jnp
    b_tot = bundle_hist.shape[-2]
    # gather non-default bins: logical (f, b >= 1) <- bundled
    # (group_of[f], offset_of[f] + b - 1)
    bidx = jnp.arange(max_bins)  # [B]
    src_bin = jnp.clip(offset_of[:, None] + bidx[None, :] - 1, 0, b_tot - 1)
    gathered = bundle_hist[..., group_of, :, :]  # [..., F, B_tot, C]
    idx = jnp.broadcast_to(
        src_bin[..., None],
        gathered.shape[:-2] + (max_bins, gathered.shape[-1]))
    hist = jnp.take_along_axis(gathered, idx, axis=-2)  # [..., F, B, C]
    own = (bidx[None, :] >= 1) & (bidx[None, :] < nb[:, None])  # [F, B]
    hist = jnp.where(own[..., None], hist, 0.0)
    default_row = totals[..., None, :] - jnp.sum(hist, axis=-2)
    return hist.at[..., 0, :].set(default_row)
