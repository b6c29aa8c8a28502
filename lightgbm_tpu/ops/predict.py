"""Device (XLA) batch prediction — the tree-parallel inference engine.

TPU-native analog of the reference prediction kernels
(ref: src/boosting/gbdt_prediction.cpp:16, CUDATree prediction kernels in
src/io/cuda/cuda_tree.cu). Trees are packed into dense [T, ...] tensors
and traversed tree-parallel: node state is a row-major [B, T] tensor and
EVERY tree advances one level per step for the whole row block (leaves
self-loop), so a handful of fused [B, T] flat gathers per depth step
replace the reference's per-tree kernels — the batched device-side
traversal shape of arXiv:1806.11248 §4. (The naive `vmap`-over-trees
formulation broadcasts the row block per tree and measured SLOWER than
the per-tree scan; the row-major layout with raveled-table gathers is
what wins.)

Multiclass is a [T] -> [T/K, K] reshape of the per-tree leaf values
inside the same program (trees are stored class-interleaved: tree
t = iteration*K + class), not K separately compiled subset programs.
Per-class sums accumulate sequentially over the iteration axis, so the
f32 addition order — and therefore the bits — match the old per-tree
scan exactly.

Serving path (`predict_raw_cached`) is a streaming pipeline:

- **Incremental packing** (`EnsemblePacker`): per-iteration eval during
  training appends only the NEW trees into capacity-doubled host
  tensors instead of repacking all T (O(T) amortized over a run, not
  O(T^2)); capacity padding also keeps the traversal program's [T]
  shape stable so recompiles happen O(log T) times, not per iteration.
- **Shape-bucketed chunking**: an uneven final chunk is padded up to a
  power-of-two row bucket, so prediction over any N compiles a small
  fixed set of programs and an N not divisible by the chunk size never
  triggers a fresh JIT (assertable via obs.metrics recompile counters).
- **Double-buffered feed**: chunk i+1's host->device transfer is
  enqueued before chunk i's result is awaited, and all device->host
  gathers happen after the last dispatch, so transfer overlaps
  traversal.
- **Mesh sharding**: with `num_shards`, the row block is `shard_map`ped
  over the "data" axis of a `parallel.mesh` device mesh — a pod serves
  one batch cooperatively.

Categorical splits carry their category-value bitsets in a packed
[T, W] word tensor (the device mirror of tree.h:375 cat_threshold_ +
cat_boundaries_), checked with a dynamic word gather per row.
"""

from __future__ import annotations

import functools
import time
from typing import List, NamedTuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..obs.metrics import global_metrics
from ..obs.trace import global_tracer

_DEFAULT_LEFT_MASK = 2

# traversal program recompile tag (tests assert chunk-shape stability
# through global_metrics.recompiles(PREDICT_TRACE_TAG))
PREDICT_TRACE_TAG = "predict/traversal"


class PackedEnsemble(NamedTuple):
    """Dense ensemble tensors. T trees, I = max internal nodes, L = max
    leaves, D = max depth. Child convention: >=0 internal, <0 = ~leaf.
    T may include zero-tree capacity padding (num_internal=0, leaf
    value 0 — contributes nothing); `num_trees` is the real count."""
    split_feature: jax.Array   # [T, I] int32
    threshold: jax.Array       # [T, I] f32 (real-valued)
    decision_type: jax.Array   # [T, I] int32
    left_child: jax.Array      # [T, I] int32
    right_child: jax.Array     # [T, I] int32
    leaf_value: jax.Array      # [T, L] f32
    num_internal: jax.Array    # [T] int32
    cat_start: jax.Array       # [T, I] int32 word offset into cat_words
    cat_nwords: jax.Array      # [T, I] int32 word count (0 = not cat)
    cat_words: jax.Array       # [T, W] uint32 bitset words
    max_depth: int             # static
    num_trees_per_class: int   # static (for multiclass reshape)
    num_trees: int = -1        # static real tree count (-1 = all of T)
    has_categorical: bool = True  # static: False elides the bitset ops


_ARRAY_FIELDS = PackedEnsemble._fields[:10]


def _tree_depth(tr) -> int:
    if tr.num_internal == 0:
        return 1
    depth = np.zeros(tr.num_internal, np.int32)
    out = 1
    for nd in range(tr.num_internal):  # parents precede children
        for child in (tr.left_child[nd], tr.right_child[nd]):
            if child >= 0:
                depth[child] = depth[nd] + 1
                out = max(out, int(depth[child]) + 1)
    return out + 1


def _next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length() if n > 1 else 1


class EnsemblePacker:
    """Incremental host-side ensemble packer.

    Keeps the packed [T, ...] numpy tensors across calls and appends
    only trees it has not seen, identified by a (tree, mutation-version)
    token per tree — the token holds the Tree object itself, so identity
    can't be spoofed by CPython id() recycling after a tree is freed,
    and Tree bumps its version on apply_shrinkage/add_bias, so DART
    renormalization invalidates exactly the rebuilt prefix.
    Capacities grow by doubling — both the tree axis and the per-tree
    dims — so a training run that predicts every iteration packs O(T)
    trees total instead of O(T^2), and the device tensors keep a stable
    shape between capacity doublings (stable shapes = no per-iteration
    traversal recompiles).

    `trees_packed` counts every tree ever written (including rewrites
    during a capacity regrow); tests assert it stays linear in T.
    """

    def __init__(self) -> None:
        self._tokens: List[tuple] = []
        self._depths: List[int] = []
        self._arrs = None          # dict of host numpy arrays at capacity
        self._cap_t = 0            # tree-axis capacity
        self._dims = (0, 0, 0)     # (max_i, max_l, max_w) capacities
        self.num_tree_per_class = 1
        self.trees_packed = 0      # cumulative (monotonic; test hook)
        self.full_repacks = 0
        self._cached = None        # device PackedEnsemble of _tokens
        # TreeSHAP path-table pack (shap_update); cached independently
        # of the traversal pack but under the same identity tokens
        self._shap_tokens: List[tuple] = []
        self._shap_key = None
        self._shap_pack = None
        self.shap_repacks = 0      # full path-table rebuilds (test hook)

    # -- internals -----------------------------------------------------
    def _alloc(self, cap_t: int, max_i: int, max_l: int, max_w: int):
        self._cap_t = cap_t
        self._dims = (max_i, max_l, max_w)
        self._arrs = dict(
            split_feature=np.zeros((cap_t, max_i), np.int32),
            threshold=np.zeros((cap_t, max_i), np.float64),
            decision_type=np.zeros((cap_t, max_i), np.int32),
            left_child=np.full((cap_t, max_i), -1, np.int32),
            right_child=np.full((cap_t, max_i), -1, np.int32),
            leaf_value=np.zeros((cap_t, max_l), np.float32),
            num_internal=np.zeros(cap_t, np.int32),
            cat_start=np.zeros((cap_t, max_i), np.int32),
            cat_nwords=np.zeros((cap_t, max_i), np.int32),
            cat_words=np.zeros((cap_t, max_w), np.uint32),
        )

    def _clear_slot(self, i: int) -> None:
        a = self._arrs
        for f in ("split_feature", "threshold", "decision_type",
                  "cat_start", "cat_nwords"):
            a[f][i] = 0
        a["left_child"][i] = -1
        a["right_child"][i] = -1
        a["leaf_value"][i] = 0
        a["num_internal"][i] = 0
        a["cat_words"][i] = 0

    def _pack_one(self, i: int, tr) -> None:
        a = self._arrs
        n = tr.num_internal
        a["num_internal"][i] = n
        if n:
            a["split_feature"][i, :n] = tr.split_feature[:n]
            a["decision_type"][i, :n] = tr.decision_type[:n]
            a["left_child"][i, :n] = tr.left_child[:n]
            a["right_child"][i, :n] = tr.right_child[:n]
            a["threshold"][i, :n] = tr.threshold[:n]
            if tr.num_cat:
                w = len(tr.cat_threshold)
                a["cat_words"][i, :w] = np.asarray(tr.cat_threshold,
                                                   np.uint32)
                for nd in range(n):
                    if tr.decision_type[nd] & 1:
                        cat_idx = int(tr.threshold[nd])
                        a["cat_start"][i, nd] = tr.cat_boundaries[cat_idx]
                        a["cat_nwords"][i, nd] = (
                            tr.cat_boundaries[cat_idx + 1]
                            - tr.cat_boundaries[cat_idx])
        a["leaf_value"][i, :tr.num_leaves] = tr.leaf_value[:tr.num_leaves]
        self.trees_packed += 1

    @staticmethod
    def _token(tr) -> tuple:
        # tuple equality on (tr, version): Tree has no __eq__, so the
        # first element compares by IDENTITY, and the strong reference
        # pins the object so its id can't be recycled while tracked
        return (tr, getattr(tr, "pack_version", 0))

    # -- TreeSHAP path decomposition -----------------------------------
    def shap_update(self, trees: List, num_tree_per_iteration: int = 1,
                    num_features: int = 1,
                    chunk_rows: int = 4096) -> "ShapPack":
        """Pack-time TreeSHAP path decomposition (GPUTreeShap-style):
        enumerate every root->leaf path of every tree ONCE on the host,
        merge repeated features along each path into unique elements
        (interval-merged numeric thresholds, AND-merged categorical
        bitsets, product zero-fractions), and ravel the result into
        depth-padded [n_chunks, Pc, D] device tables the ops/shap.py
        kernel consumes. Cached under the same (tree, pack_version)
        identity tokens as the traversal pack, so DART renorm / refit /
        rollback invalidate the path tables exactly like traversal."""
        k = max(int(num_tree_per_iteration), 1)
        f = max(int(num_features), 1)
        tokens = [self._token(tr) for tr in trees]
        key = (k, f, int(chunk_rows))
        if (self._shap_pack is not None and key == self._shap_key
                and tokens == self._shap_tokens):
            return self._shap_pack
        self._shap_pack = None
        pack = _build_shap_pack(trees, k, f, int(chunk_rows))
        self._shap_tokens = tokens
        self._shap_key = key
        self._shap_pack = pack
        self.shap_repacks += 1
        return pack

    @property
    def shap_nbytes(self) -> int:
        """Host-side estimate of the path-table pack bytes (the device
        tables mirror the same shapes; see nbytes for the 2x story)."""
        return 0 if self._shap_pack is None else self._shap_pack.nbytes

    @property
    def nbytes(self) -> int:
        """Host bytes held by the packed tensors. The cached device
        ensemble mirrors the same shapes, so total resident cost is
        ~2x this — serve/registry.py budgets with that factor."""
        if self._arrs is None:
            return 0
        return sum(a.nbytes for a in self._arrs.values())

    # -- public --------------------------------------------------------
    def update(self, trees: List, num_tree_per_iteration: int = 1,
               pad: bool = True) -> PackedEnsemble:
        """Pack `trees` (the FULL list), reusing previously packed
        prefixes. pad=False packs to exact dims with no capacity
        headroom (the one-shot `pack_ensemble` path)."""
        k = max(int(num_tree_per_iteration), 1)
        t = len(trees)
        tokens = [self._token(tr) for tr in trees]
        if (self._cached is not None and k == self.num_tree_per_class
                and tokens == self._tokens):
            # identical tree set at identical versions: serve the cached
            # device ensemble — this token compare (not any caller-side
            # key) is the correctness gate, so rollback+retrain key
            # collisions can never resurrect stale packs
            return self._cached
        self._cached = None
        prefix = min(len(self._tokens), t)
        if (self._arrs is None or k != self.num_tree_per_class
                or tokens[:prefix] != self._tokens[:prefix]):
            prefix = 0
        self.num_tree_per_class = k

        new = trees[prefix:]
        need_i = max([tr.num_internal for tr in new] + [1])
        need_l = max([tr.num_leaves for tr in new] + [1])
        need_w = max([len(tr.cat_threshold) for tr in new] + [1])
        max_i, max_l, max_w = self._dims
        grow = (need_i > max_i or need_l > max_l or need_w > max_w
                or t > self._cap_t)
        if prefix == 0 or grow:
            if pad and self._arrs is not None:
                # an append outgrew capacity: double so appends during
                # training touch O(T) trees total and keep stable [T]
                # shapes between regrows
                cap_t = k * _next_pow2(-(-max(t, 1) // k))
                dims = (_next_pow2(max(need_i, max_i)),
                        _next_pow2(max(need_l, max_l)),
                        _next_pow2(max(need_w, max_w)))
            else:
                # first pack (the one-shot serving case): exact shapes —
                # a static ensemble must not pay capacity headroom
                cap_t = max(t, 1)
                dims = (max([tr.num_internal for tr in trees] + [1]),
                        max([tr.num_leaves for tr in trees] + [1]),
                        max([len(tr.cat_threshold) for tr in trees] + [1]))
            self._alloc(cap_t, *dims)
            if prefix > 0:
                self.full_repacks += 1
            prefix = 0
            new = trees
            self._depths = []
        elif t < len(self._tokens):
            # rollback / shorter subset: retire the stale tail slots
            # (prefix == t here, so `new` is already empty)
            for i in range(t, len(self._tokens)):
                self._clear_slot(i)
            self._depths = self._depths[:t]

        for j, tr in enumerate(new):
            self._pack_one(prefix + j, tr)
            self._depths.append(_tree_depth(tr))
        self._tokens = tokens

        depth = max(self._depths, default=1)
        if pad:
            depth = -(-depth // 4) * 4  # bucket: recompile every 4 levels,
            # not every level (extra steps self-loop at leaves — no-ops)
        has_cat = bool(np.any(self._arrs["cat_nwords"]))
        self._cached = PackedEnsemble(
            **{f: jnp.asarray(self._arrs[f]) if f != "threshold"
               else jnp.asarray(_f32_floor(self._arrs[f]))
               for f in _ARRAY_FIELDS},
            max_depth=int(depth), num_trees_per_class=k, num_trees=t,
            has_categorical=has_cat)
        return self._cached


def _f32_floor(threshold) -> np.ndarray:
    """Largest float32 <= threshold, elementwise. The model defines a
    numeric split as ``value <= threshold`` in float64; for a value that
    is float32-representable that holds exactly when ``value <=
    _f32_floor(threshold)``, so the device's f32 compare routes such
    rows as the host tree walk does. Round-to-nearest flips the rows
    that sit on a threshold which rounds UP (a midpoint of two adjacent
    float32 values — found by chip_smoke.py: 1 training row in 200k)."""
    t = np.asarray(threshold, np.float64)
    with np.errstate(over="ignore"):  # beyond float32: inf, then stepped down
        f = t.astype(np.float32)
    return np.where(f.astype(np.float64) > t,
                    np.nextafter(f, np.float32(-np.inf)), f)


def pack_ensemble(trees: List, num_tree_per_iteration: int = 1
                  ) -> PackedEnsemble:
    """Pack host Tree objects (tree.py) into exact-shape device tensors
    (one-shot; the serving path uses an owner-cached EnsemblePacker)."""
    return EnsemblePacker().update(trees, num_tree_per_iteration, pad=False)


# ----------------------------------------------------------------------
# TreeSHAP path decomposition (pack time, host side)
#
# The ops/shap.py kernel evaluates rows x paths: each root->leaf path
# becomes one row of depth-padded element tables, where an "element" is
# one UNIQUE feature on the path (the reference recursion's dedup/unwind
# merges repeated features on the fly; we merge them once at pack time):
#
# - zero_fraction = product of taken-child cover ratios over the
#   feature's occurrences (exactly the incoming_zero_fraction product
#   the recursion accumulates through _unwind_path);
# - one_fraction is 0/1 (a row either follows the whole path at this
#   feature or not), so the per-row decision merges too: numeric
#   occurrences collapse to an (lo, hi] interval in f32 (matching the
#   device traversal's f32 threshold compare), categorical occurrences
#   AND their direction-oriented bitset images into one merged bitset;
# - missing routing merges as AND over "does the default direction
#   follow this path here" (default_follows / oor_follows).
#
# Every path is padded to a uniform D slots with NEUTRAL elements
# (one_fraction = zero_fraction = 1): extending a path by a (1,1)
# element never changes any real element's unwound weight — the dummy
# root element the reference recursion starts from is exactly such an
# element — so padded paths stay bit-for-bit consistent with the
# variable-depth recursion while giving the kernel static shapes.

_SHAP_TABLE_FIELDS = (
    "feature", "z", "z_inv", "lo", "hi", "no_lo", "default_follows",
    "is_cat", "oor_follows", "mt", "cat_start", "cat_nwords", "segid")

# working-set budget for the [B, Pc, D] kernel temporaries (pweights,
# one-fractions, unwound totals, ...): Pc (the path-chunk width) is
# sized so ~6 such tensors at the row-chunk cap fit in this budget
_SHAP_BUDGET_BYTES = 128 << 20


class ShapPack(NamedTuple):
    """Depth-padded TreeSHAP path tables. P paths pad to n_chunks * Pc
    rows; every path pads to D element slots (slot 0 is the dummy root
    element). Neutral slots carry z = 1 and decide to one_fraction = 1,
    so they contribute (1 - 1) * w = 0; their segid points at the trash
    column num_class * (F + 1), which the kernel slices off."""
    tables: tuple          # 13 [n_chunks, Pc, D] arrays (_SHAP_TABLE_FIELDS)
    leaf_value: jax.Array  # [n_chunks, Pc] f32
    cat_words: jax.Array   # [W] uint32 merged bitset words (>= 1 word)
    bias: np.ndarray       # [K] f64 per-class expected values (host)
    num_paths: int
    depth: int             # D (element slots incl. dummy root)
    path_chunk: int        # Pc
    num_chunks: int
    num_features: int
    num_class: int
    has_categorical: bool  # static: False elides the bitset ops
    nbytes: int


def _shap_child_count(tr, child: int) -> float:
    return float(tr.leaf_count[~child]) if child < 0 else \
        float(tr.internal_count[child])


def _shap_paths_of_tree(tr):
    """[(occurrences, leaf_value)] per root->leaf path, where an
    occurrence is (node, went_left) in root->leaf order. Iterative so
    deep trees can't blow the recursion limit."""
    out = []
    if tr.num_internal == 0:
        return out
    stack = [(0, [])]
    while stack:
        node, occs = stack.pop()
        for went_left in (True, False):
            child = int(tr.left_child[node] if went_left
                        else tr.right_child[node])
            occ2 = occs + [(node, went_left)]
            if child < 0:
                out.append((occ2, float(tr.leaf_value[~child])))
            else:
                stack.append((child, occ2))
    return out


def _shap_merge_elements(tr, occs):
    """Merge one path's occurrences into unique per-feature elements
    (first-occurrence order; order is irrelevant to the math)."""
    elements = {}
    order = []
    for node, went_left in occs:
        taken = int(tr.left_child[node] if went_left
                    else tr.right_child[node])
        count = int(tr.internal_count[node])
        denom = float(count) if count > 0 else 1.0
        feat = int(tr.split_feature[node])
        dt = int(tr.decision_type[node])
        el = elements.get(feat)
        if el is None:
            el = elements[feat] = dict(
                feature=feat, z=1.0, lo=-np.inf, no_lo=True, hi=np.inf,
                default_follows=True, is_cat=bool(dt & 1),
                mt=(dt >> 2) & 3, oor_follows=True, cat_occ=[])
            order.append(feat)
        el["z"] *= _shap_child_count(tr, taken) / denom
        default_left = bool(dt & _DEFAULT_LEFT_MASK)
        el["default_follows"] &= (default_left == went_left)
        if el["is_cat"]:
            cat_idx = int(tr.threshold[node])
            w_lo = tr.cat_boundaries[cat_idx]
            w_hi = tr.cat_boundaries[cat_idx + 1]
            words = np.asarray(tr.cat_threshold[w_lo:w_hi], np.uint32)
            el["cat_occ"].append((words, went_left))
            # values outside every occurrence's bitset range go right
            el["oor_follows"] &= (not went_left)
        else:
            # f32 threshold compare, matching the device traversal pack
            thr = float(_f32_floor(tr.threshold[node]))
            if went_left:
                el["hi"] = min(el["hi"], thr)
            else:
                el["lo"] = max(el["lo"], thr)
                el["no_lo"] = False
    return [elements[feat] for feat in order]


def _shap_merge_cat_words(el) -> np.ndarray:
    """AND the direction-oriented images of each occurrence's bitset:
    a category follows the path iff it takes the recorded direction at
    EVERY occurrence. Left-taken occurrences contribute their words
    (in-set bit = goes left = follows), right-taken contribute the
    complement; words beyond an occurrence's own range image to 0 (left
    expects in-set, out-of-range is not) or all-ones (right)."""
    width = max(len(words) for words, _ in el["cat_occ"])
    merged = np.full(width, 0xFFFFFFFF, np.uint32)
    for words, went_left in el["cat_occ"]:
        if went_left:
            img = np.zeros(width, np.uint32)
            img[:len(words)] = words
        else:
            img = np.full(width, 0xFFFFFFFF, np.uint32)
            img[:len(words)] = ~words
        merged &= img
    return merged


def _pow2_floor(n: int) -> int:
    return 1 << (max(int(n), 1).bit_length() - 1)


def _shap_path_chunk(num_paths: int, depth: int, chunk_rows: int) -> int:
    """Pc: paths per kernel invocation, sized so the [B, Pc, D] f32
    working set (~6 tensors) at the row-chunk cap stays inside the
    budget. Power of two so path counts bucket like row counts do."""
    per_path = max(int(chunk_rows) * int(depth) * 4 * 6, 1)
    pc = max(_pow2_floor(_SHAP_BUDGET_BYTES // per_path), 32)
    return min(pc, _next_pow2(max(num_paths, 1)))


def _build_shap_pack(trees: List, k: int, num_features: int,
                     chunk_rows: int) -> ShapPack:
    from ..shap import _expected_value
    f = num_features
    num_out = k * (f + 1)
    bias = np.zeros(k, np.float64)
    paths = []  # (class, elements, leaf_value)
    for j, tr in enumerate(trees):
        ki = j % k
        bias[ki] += _expected_value(tr)
        for occs, leaf_value in _shap_paths_of_tree(tr):
            paths.append((ki, _shap_merge_elements(tr, occs), leaf_value))

    num_paths = len(paths)
    # D: dummy root slot + max unique elements, bucketed to a multiple
    # of 4 (same recompile-bucketing story as the traversal depth)
    depth = 1 + max((len(els) for _, els, _ in paths), default=0)
    depth = max(-(-depth // 4) * 4, 4)
    pc = _shap_path_chunk(num_paths, depth, chunk_rows)
    p_pad = -(-max(num_paths, 1) // pc) * pc
    n_chunks = p_pad // pc

    arrs = dict(
        feature=np.full((p_pad, depth), -1, np.int32),
        z=np.ones((p_pad, depth), np.float32),
        z_inv=np.ones((p_pad, depth), np.float32),
        lo=np.zeros((p_pad, depth), np.float32),
        hi=np.full((p_pad, depth), np.inf, np.float32),
        no_lo=np.ones((p_pad, depth), np.bool_),
        default_follows=np.zeros((p_pad, depth), np.bool_),
        is_cat=np.zeros((p_pad, depth), np.bool_),
        oor_follows=np.zeros((p_pad, depth), np.bool_),
        mt=np.zeros((p_pad, depth), np.int32),
        cat_start=np.zeros((p_pad, depth), np.int32),
        cat_nwords=np.zeros((p_pad, depth), np.int32),
        segid=np.full((p_pad, depth), num_out, np.int32),
    )
    leaf_value = np.zeros(p_pad, np.float32)
    cat_words: List[np.ndarray] = []
    cat_offset = 0
    for p, (ki, els, lv) in enumerate(paths):
        leaf_value[p] = lv
        for d, el in enumerate(els, start=1):  # slot 0 = dummy root
            z = el["z"]
            arrs["feature"][p, d] = el["feature"]
            arrs["z"][p, d] = z
            arrs["z_inv"][p, d] = 1.0 / z if z > 0 else 0.0
            arrs["segid"][p, d] = ki * (f + 1) + el["feature"]
            arrs["mt"][p, d] = el["mt"]
            arrs["default_follows"][p, d] = el["default_follows"]
            if el["is_cat"]:
                words = _shap_merge_cat_words(el)
                arrs["is_cat"][p, d] = True
                arrs["oor_follows"][p, d] = el["oor_follows"]
                arrs["cat_start"][p, d] = cat_offset
                arrs["cat_nwords"][p, d] = len(words)
                cat_words.append(words)
                cat_offset += len(words)
            else:
                arrs["lo"][p, d] = el["lo"] if not el["no_lo"] else 0.0
                arrs["no_lo"][p, d] = el["no_lo"]
                arrs["hi"][p, d] = el["hi"]

    words_flat = (np.concatenate(cat_words) if cat_words
                  else np.zeros(1, np.uint32))
    nbytes = (sum(a.nbytes for a in arrs.values()) + leaf_value.nbytes
              + words_flat.nbytes)
    tables = tuple(
        jnp.asarray(arrs[name].reshape(n_chunks, pc, depth))
        for name in _SHAP_TABLE_FIELDS)
    return ShapPack(
        tables=tables,
        leaf_value=jnp.asarray(leaf_value.reshape(n_chunks, pc)),
        cat_words=jnp.asarray(words_flat), bias=bias,
        num_paths=num_paths, depth=depth, path_chunk=pc,
        num_chunks=n_chunks, num_features=f, num_class=k,
        has_categorical=bool(cat_words), nbytes=int(nbytes))


def _predict_leaf_one_tree(tree, x, max_depth: int):
    """Leaf index per row for one packed tree (tuple of arrays).
    Traceable; `vmap` over the tree axis advances all trees at once."""
    sf, th, dt, lc, rc, ni, cs, cn, cw = tree
    num_rows = x.shape[0]

    def body(_, node):
        nd = jnp.maximum(node, 0)
        feat = sf[nd]
        val = jnp.take_along_axis(x, feat[:, None], axis=1)[:, 0]
        thr = th[nd]
        d = dt[nd]
        default_left = (d & _DEFAULT_LEFT_MASK) > 0
        missing_type = (d >> 2) & 3
        is_cat = (d & 1) > 0
        isnan = jnp.isnan(val)
        v0 = jnp.where(isnan, 0.0, val)
        # categorical bitset decision (ref: tree.h:375 CategoricalDecision)
        v_int = v0.astype(jnp.int32)
        widx = jnp.clip(cs[nd] + v_int // 32, 0, cw.shape[0] - 1)
        word = cw[widx]
        in_range = (~isnan) & (v0 >= 0) & (v_int // 32 < cn[nd])
        cat_left = in_range & (
            (word >> (v_int % 32).astype(jnp.uint32)) & 1 > 0)
        go_left = jnp.where(is_cat, cat_left, v0 <= thr)
        use_default = (isnan & (missing_type == 2)) | \
            ((missing_type == 1) & (isnan | (jnp.abs(v0) <= 1e-35)))
        go_left = jnp.where(use_default & ~is_cat, default_left, go_left)
        nxt = jnp.where(go_left, lc[nd], rc[nd])
        # leaves (node < 0) self-loop
        return jnp.where(node < 0, node, nxt)

    node0 = jnp.where(ni > 0, jnp.zeros(num_rows, jnp.int32),
                      jnp.full(num_rows, -1, jnp.int32))
    node = lax.fori_loop(0, max_depth, body, node0)
    return jnp.where(node < 0, ~node, 0)


def _tree_operands(ens: PackedEnsemble):
    return (ens.split_feature, ens.threshold, ens.decision_type,
            ens.left_child, ens.right_child, ens.num_internal,
            ens.cat_start, ens.cat_nwords, ens.cat_words)


def predict_leaves_all(ens: PackedEnsemble, x: jax.Array) -> jax.Array:
    """x: [B, F] -> [B, T] leaf index per (row, tree): the tree-parallel
    traversal. Node state is [B, T] row-major — every tree advances one
    level per step for the whole row block — and all table lookups are
    flat gathers into the raveled [T*I] node tables, so the per-step
    working set per row is one x row plus the (cache-resident) tree
    tables. Measured on the serving bench shape (CPU, T=100, 255
    leaves): ~4x the per-tree `lax.scan` path this replaced; the
    vmapped [T, B] formulation broadcast the row block per tree and
    came out slower than the scan. Flat indices are int32: callers
    must keep B*F (and T*I) below 2^31 — predict_raw_cached clamps its
    chunk size to guarantee this."""
    sf, th, dt, lc, rc, ni, cs, cn, cw = _tree_operands(ens)
    t, i = sf.shape
    b, f = x.shape
    w = cw.shape[1]
    sf_f, th_f, dt_f, lc_f, rc_f, cs_f, cn_f = (
        jnp.ravel(a) for a in (sf, th, dt, lc, rc, cs, cn))
    cw_f = jnp.ravel(cw)
    toff = (jnp.arange(t, dtype=jnp.int32) * i)[None, :]   # [1, T]
    woff = (jnp.arange(t, dtype=jnp.int32) * w)[None, :]
    x_f = jnp.ravel(x)
    brow = (jnp.arange(b, dtype=jnp.int32) * f)[:, None]   # [B, 1]

    def body(_, node):
        nd = jnp.maximum(node, 0)
        fi = nd + toff                       # flat [B, T] node-table index
        val = x_f[sf_f[fi] + brow]
        d = dt_f[fi]
        default_left = (d & _DEFAULT_LEFT_MASK) > 0
        missing_type = (d >> 2) & 3
        isnan = jnp.isnan(val)
        v0 = jnp.where(isnan, 0.0, val)
        numeric_left = v0 <= th_f[fi]
        if ens.has_categorical:
            # categorical bitset decision (ref: tree.h:375
            # CategoricalDecision); statically elided for ensembles
            # without categorical splits — the common serving case
            is_cat = (d & 1) > 0
            v_int = v0.astype(jnp.int32)
            widx = jnp.clip(cs_f[fi] + v_int // 32, 0, w - 1)
            word = cw_f[widx + woff]
            in_range = (~isnan) & (v0 >= 0) & (v_int // 32 < cn_f[fi])
            cat_left = in_range & (
                (word >> (v_int % 32).astype(jnp.uint32)) & 1 > 0)
            go_left = jnp.where(is_cat, cat_left, numeric_left)
            not_cat = ~is_cat
        else:
            go_left = numeric_left
            not_cat = True
        use_default = (isnan & (missing_type == 2)) | \
            ((missing_type == 1) & (isnan | (jnp.abs(v0) <= 1e-35)))
        go_left = jnp.where(use_default & not_cat, default_left, go_left)
        nxt = jnp.where(go_left, lc_f[fi], rc_f[fi])
        # leaves (node < 0) self-loop
        return jnp.where(node < 0, node, nxt)

    node0 = jnp.where((ni > 0)[None, :], jnp.zeros((b, t), jnp.int32), -1)
    node = lax.fori_loop(0, ens.max_depth, body, node0)
    return jnp.where(node < 0, ~node, 0)


def _class_sums(ens: PackedEnsemble, leaves: jax.Array) -> jax.Array:
    """[B, T] leaves -> [B, K] raw scores. Trees are class-interleaved
    (tree t = iteration*K + class), so a [T] -> [T/K, K] reshape of the
    per-tree leaf values replaces the old K-subset-programs loop; the
    per-class accumulation runs sequentially over the iteration axis so
    f32 addition order (and bits) match the old per-tree scan."""
    k = max(ens.num_trees_per_class, 1)
    t = leaves.shape[1]
    lv = ens.leaf_value
    lv_f = jnp.ravel(lv)
    loff = (jnp.arange(t, dtype=jnp.int32) * lv.shape[1])[None, :]
    vals = lv_f[leaves + loff]                  # [B, T]
    vals = vals.reshape(-1, t // k, k)

    def body(i, acc):
        return acc + vals[:, i, :]

    return lax.fori_loop(0, t // k, body,
                         jnp.zeros((vals.shape[0], k), jnp.float32))


def predict_raw(ens: PackedEnsemble, x: jax.Array) -> jax.Array:
    """x: [B, F] raw features (NaN = missing) -> raw scores [B] (all
    trees summed into one stream). Traceable inside an outer jit."""
    one = ens._replace(num_trees_per_class=1)
    return _class_sums(one, predict_leaves_all(ens, x))[:, 0]


def predict_raw_multiclass(ens: PackedEnsemble, x: jax.Array) -> jax.Array:
    """-> [B, K] for K = num_trees_per_class class streams, in ONE
    program (no per-class subset ensembles, host- or device-side)."""
    return _class_sums(ens, predict_leaves_all(ens, x))


def predict_leaf_index(ens: PackedEnsemble, x: jax.Array) -> jax.Array:
    """x: [B, F] -> leaf indices [B, T] (ref: PredictLeafIndex)."""
    leaves = predict_leaves_all(ens, x)
    t = ens.num_trees
    return leaves if t < 0 else leaves[:, :t]


def predict_raw_scan(ens: PackedEnsemble, x: jax.Array) -> jax.Array:
    """The pre-engine per-tree `lax.scan` traversal, kept as the bench
    baseline and parity oracle for the tree-parallel path: same math,
    trees advance one AT A TIME. -> [B, K]."""
    num_rows = x.shape[0]
    k = max(ens.num_trees_per_class, 1)

    def one_class(ki):
        idx = jnp.arange(ki, ens.split_feature.shape[0], k)
        ops = tuple(jnp.take(a, idx, axis=0) for a in _tree_operands(ens))
        lv = jnp.take(ens.leaf_value, idx, axis=0)

        def one_tree(carry, tree):
            *nav, tlv = tree
            leaf = _predict_leaf_one_tree(tuple(nav), x, ens.max_depth)
            return carry + tlv[leaf], None

        total, _ = lax.scan(one_tree, jnp.zeros(num_rows, jnp.float32),
                            ops + (lv,))
        return total

    return jnp.stack([one_class(ki) for ki in range(k)], axis=1)


# ----------------------------------------------------------------------
# streaming serving pipeline
def _resolve_mesh(num_shards: int):
    if not num_shards or num_shards == 1:
        return None
    if len(jax.devices()) <= 1:
        # single device: sharding degrades to serial — expected, silent
        return None
    try:
        from ..parallel.mesh import get_mesh
        mesh = get_mesh(num_shards)
        return mesh if mesh.size > 1 else None
    except Exception as exc:
        # an explicit tpu_num_shards>1 request must not misroute quietly
        from .. import log
        log.warning(f"sharded predict unavailable "
                    f"(num_shards={num_shards}): {exc!r}; "
                    "falling back to single-device traversal")
        return None


@functools.lru_cache(maxsize=64)
def _traversal_program(mesh, k: int, max_depth: int, has_cat: bool = True):
    """jit(program) over (10 packed arrays, x) -> [B, K]; optionally
    shard_mapped over the data axis of `mesh`. Cached per (mesh, K,
    depth, cat) — array shapes key the underlying jit cache, and the
    wrap_traced tag feeds obs.metrics recompile counters."""
    def run(*args):
        ens = PackedEnsemble(*args[:-1], max_depth=max_depth,
                             num_trees_per_class=k,
                             has_categorical=has_cat)
        return predict_raw_multiclass(ens, args[-1])

    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        from ..parallel import mesh as mesh_lib
        rep = P()
        run = mesh_lib.shard_map(
            run, mesh=mesh,
            in_specs=tuple([rep] * len(_ARRAY_FIELDS))
            + (P(mesh_lib.DATA_AXIS, None),),
            out_specs=P(mesh_lib.DATA_AXIS, None))
    from ..obs import xla as obs_xla
    return obs_xla.instrumented_jit(PREDICT_TRACE_TAG, run, phase="predict")


def _row_bucket(rows: int, chunk: int, mesh) -> int:
    """Pad target for a chunk of `rows`: full chunks stay `chunk`; an
    uneven tail rounds up to a power of two while small (so tiny
    predicts waste at most 2x compute) and to a chunk/16 multiple once
    large (so big tails waste at most ~6%). Either way any N compiles
    only a small bounded set of row shapes — never a per-N program."""
    if rows >= chunk:
        b = chunk
    else:
        grain = max(chunk // 16, 16)
        b = (_next_pow2(max(rows, 16)) if rows < grain
             else min(-(-rows // grain) * grain, chunk))
    if mesh is not None:
        from ..parallel.mesh import pad_rows_to_shards
        b = pad_rows_to_shards(b, mesh)
    return b


def _get_packer(owner, cache_key):
    """Owner-cached EnsemblePacker, keyed by the prediction window start
    so alternating sub-range predicts don't thrash one packer's prefix.
    `owner._packed_key = None` (capi's post-surgery invalidation) drops
    every packer: in-place tree edits don't change identity tokens."""
    if getattr(owner, "_packed_key", "unset") is None:
        owner._packers = {}
    packers = getattr(owner, "_packers", None)
    if packers is None:
        packers = owner._packers = {}
    pk = cache_key[0] if isinstance(cache_key, tuple) and cache_key else None
    packer = packers.get(pk)
    if packer is None:
        while len(packers) >= 8:  # bound host memory across odd sub-ranges
            packers.pop(next(iter(packers)))
        packer = packers[pk] = EnsemblePacker()
    return packer


def predict_raw_cached(owner, trees: List, num_tree_per_iteration: int,
                       data: np.ndarray, cache_key,
                       chunk: int = 1 << 20,
                       num_shards: int = 0) -> np.ndarray:
    """Raw [N, K] prediction through the packed device ensemble — the
    streaming inference engine. Packed tensors are cached on `owner`
    (incrementally appended, see EnsemblePacker) under `cache_key`.
    GBDT and LoadedModel (model_io.py) both predict through this
    helper, so a save/load round trip runs the identical XLA program
    and returns bit-equal outputs (the reference gets the same property
    by sharing GBDT::PredictRaw between live and loaded boosters,
    gbdt_prediction.cpp:16)."""
    k = max(int(num_tree_per_iteration), 1)
    # ALWAYS revalidate through the packer's identity tokens: the
    # caller's cache_key only selects a packer (and carries capi's
    # None-invalidation); correctness never rides on key uniqueness
    # (a rollback + retrain can reproduce an old (start, end, iter) key
    # with different trees — the token compare catches that, and it is
    # O(T) cheap when nothing changed)
    packer = _get_packer(owner, cache_key)
    with global_tracer.span("predict/pack"):
        ens = owner._packed = packer.update(trees, num_tree_per_iteration)
    owner._packed_key = cache_key
    n = data.shape[0]
    if n == 0:
        return np.zeros((0, k))
    mesh = _resolve_mesh(num_shards)
    ms = mesh.size if mesh is not None else 1
    # flat row*F+feature gathers index in int32: keep every chunk's
    # B*F below 2^31 (wide-feature data just streams smaller chunks).
    # The cap is floored to a mesh multiple so _row_bucket's round-UP
    # to the shard count can never push a bucket back over the bound.
    cap = ((1 << 31) - 1) // max(int(data.shape[1]), 1)
    cap = max(cap // ms * ms, ms)
    chunk = max(1, min(int(chunk), cap))
    prog = _traversal_program(mesh, k, ens.max_depth, ens.has_categorical)
    arrs = tuple(getattr(ens, f) for f in _ARRAY_FIELDS)
    sharding = None
    if mesh is not None:
        from ..parallel.mesh import data_sharding
        sharding = data_sharding(mesh, ndim=2)

    bounds = [(lo, min(lo + chunk, n)) for lo in range(0, n, chunk)]

    def stage(lo, hi):
        """Enqueue one (padded) chunk's host->device transfer."""
        rows = hi - lo
        b = _row_bucket(rows, chunk, mesh)
        xb = np.zeros((b, data.shape[1]), np.float32)
        xb[:rows] = data[lo:hi]
        dev = (jax.device_put(xb, sharding) if sharding is not None
               else jax.device_put(xb))
        return dev, rows

    t0 = time.perf_counter()
    with global_tracer.span("predict/traversal"):
        parts = []
        # double-buffer: chunk i+1's transfer overlaps chunk i's
        # traversal (device_put and the jitted call are both async) —
        # the shared pipeline implementation in io/streaming.py, also
        # used by out-of-core training's slab feed
        from ..io.streaming import double_buffered
        for dev, rows in double_buffered(bounds,
                                         lambda b: stage(*b)):
            parts.append((prog(*arrs, dev), rows))
        out = np.concatenate(
            [np.asarray(y, np.float64)[:rows] for y, rows in parts], axis=0)
    global_metrics.note_predict(n, time.perf_counter() - t0)
    return out
