"""The plain reference that decides ``correct``: NumPy, float64, no kernels.

It imports nothing of ``lightgbm_tpu`` and takes nothing that the program
made but its answer: the trees (split feature, real-valued threshold,
children, leaf values, leaf counts) and the scores they left. From the
raw rows and labels (the benchmark's own, from the seed) and the
configuration's published parameters it recomputes, tree by tree,

- the objective's gradient and hessian at the scores before the tree
  (binary log loss; the first tree starts from the label average),
- every row's leaf, by walking the tree on the raw values (partition),
- the leaf sums and from them each leaf's value (histogram build, leaf
  values) and each split's gain,
- on a quantile grid of its own (``max_bin`` bins from the data's first
  rows; the program's bin boundaries are not read), the best gain any
  split could have had at nodes picked from every depth of the tree: the
  root, the most lopsided split and others drawn from the seed (split
  search),
- the smallest hessian sum any leaf was left with, against the stated
  ``min_sum_hessian_in_leaf`` (the guarantee the split search gives),
- the scores after the tree (score update).

``stand_ins`` (at the end) is this reference put in the program's place:
one precision below the configuration's (the controls), or with a fault
planted. Its answer goes through ``compare`` like the program's.

``compare`` (below) turns these into the numbers ``correct`` is decided
by; ``parse_model`` reads the program's answer from the text model
format (``Booster.model_to_string``), the public LightGBM format.

Row blocks run on a few threads; ``np.take`` and the ufuncs release the
interpreter lock, ``np.bincount`` does not.
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

_BLOCK_VALUES = 1 << 23
KNOWN_PARAMS = {"objective", "num_leaves", "max_bin", "learning_rate",
                "min_data_in_leaf", "min_sum_hessian_in_leaf",
                "use_quantized_grad", "num_grad_quant_bins"}


def check_params(params: dict) -> None:
    """The reference follows binary log loss with LightGBM's defaults for
    everything the configuration does not name (lambda_l1 = lambda_l2 = 0,
    sigmoid = 1, boost_from_average). ``use_quantized_grad`` and
    ``num_grad_quant_bins`` say in what precision the program sums its
    gradients; the reference's own sums are float64 whatever they say, and
    the limits hold the program's rounding. Anything else it refuses."""
    unknown = sorted(set(params) - KNOWN_PARAMS)
    if unknown:
        raise ValueError(f"the reference does not follow parameter(s) "
                         f"{unknown}")
    if params.get("objective") != "binary":
        raise ValueError("the reference follows objective=binary only")


def _blocks(n: int, width: int):
    step = max(1024, _BLOCK_VALUES // max(1, width))
    return [(a, min(n, a + step)) for a in range(0, n, step)]


def _pmap(fn, items, threads: int):
    if threads <= 1:
        return [fn(item) for item in items]
    with ThreadPoolExecutor(threads) as pool:
        return list(pool.map(fn, items))


def init_score(y: np.ndarray) -> float:
    """boost_from_average of binary log loss: the log odds of the labels."""
    p = float(np.mean(y > 0, dtype=np.float64))
    p = min(max(p, 1e-15), 1.0 - 1e-15)
    return float(np.log(p / (1.0 - p)))


def grad_hess(score: np.ndarray, y: np.ndarray, threads: int):
    """g = p - y, h = p (1 - p), p = sigmoid(score); float64."""
    n = score.shape[0]
    g = np.empty(n, np.float64)
    h = np.empty(n, np.float64)

    def one(ab):
        a, b = ab
        p = 1.0 / (1.0 + np.exp(-score[a:b]))
        g[a:b] = p - (y[a:b] > 0)
        h[a:b] = p * (1.0 - p)

    _pmap(one, _blocks(n, 8), threads)
    return g, h


def leaf_index(x: np.ndarray, tree: dict, threads: int) -> np.ndarray:
    """The leaf every row falls in: from the root, a row goes left where
    its raw value is <= the node's threshold. Children >= 0 are nodes,
    < 0 are ~leaf (the reference model format)."""
    n, f = x.shape
    leaf = np.zeros(n, np.int32)
    if tree["num_leaves"] <= 1:
        return leaf
    feat = tree["split_feature"].astype(np.int64)
    thr = tree["threshold"].astype(np.float64)
    left = tree["left_child"].astype(np.int32)
    right = tree["right_child"].astype(np.int32)

    def one(ab):
        a, b = ab
        flat = x[a:b].reshape(-1)
        rows = np.arange(b - a, dtype=np.int64)
        node = np.zeros(b - a, np.int32)
        out = leaf[a:b]
        while rows.size:
            fn = np.take(feat, node)
            val = np.take(flat, rows * f + fn)
            nxt = np.where(val <= np.take(thr, node),
                           np.take(left, node), np.take(right, node))
            done = nxt < 0
            out[rows[done]] = ~nxt[done]
            rows, node = rows[~done], nxt[~done]

    _pmap(one, _blocks(n, f), threads)
    return leaf


def leaf_sums(leaf: np.ndarray, g: np.ndarray, h: np.ndarray, leaves: int):
    """(sum g, sum h, rows) of every leaf."""
    return (np.bincount(leaf, weights=g, minlength=leaves),
            np.bincount(leaf, weights=h, minlength=leaves),
            np.bincount(leaf, minlength=leaves))


def leaf_values(gsum, hsum, learning_rate: float) -> np.ndarray:
    """-G / H, shrunk (lambda_l1 = lambda_l2 = 0, no output clamp)."""
    return -gsum / np.maximum(hsum, 1e-300) * learning_rate


_GRID_ROWS = 20_000   # rows the grid's quantiles are taken from


def grid_rows(chunks) -> np.ndarray:
    """The data's first ``_GRID_ROWS`` rows or so (whole chunks): the rows
    are drawn independently, so the first are as good a sample as any."""
    blocks, have = [], 0
    for c in range(len(chunks)):
        blocks.append(chunks[c][2])
        have += blocks[-1].shape[0]
        if have >= _GRID_ROWS:
            break
    return blocks[0] if len(blocks) == 1 else np.concatenate(blocks)


def quantile_grid(xs: np.ndarray, max_bin: int, threads: int) -> np.ndarray:
    """[F, max_bin - 1] thresholds: the k/max_bin quantiles of (at most
    ``_GRID_ROWS`` of) the given rows, an evenly spaced pick of them. The
    reference's own candidate splits."""
    xt = np.ascontiguousarray(xs[::max(1, xs.shape[0] // _GRID_ROWS)].T)
    f, n = xt.shape

    def one(ab):
        xt[ab[0]:ab[1]].sort(axis=1)

    step = -(-f // max(1, threads))
    _pmap(one, [(a, min(f, a + step)) for a in range(0, f, step)], threads)
    return xt[:, (np.arange(1, max_bin) * n) // max_bin].copy()


def bin_rows(xs: np.ndarray, grid: np.ndarray, threads: int) -> np.ndarray:
    """[F, rows] uint8: a row's bin on feature f is the number of grid
    thresholds below its value, so value <= grid[f, k] iff bin <= k."""
    n, f = xs.shape
    out = np.empty((f, n), np.uint8)

    def one(ab):
        a, b = ab
        xt = np.ascontiguousarray(xs[a:b].T)
        for j in range(f):
            out[j, a:b] = np.searchsorted(grid[j], xt[j], side="left")

    _pmap(one, _blocks(n, f), threads)
    return out


def _gain(gl, hl, gr, hr):
    g, h = gl + gr, hl + hr
    tiny = 1e-300
    return (gl * gl / np.maximum(hl, tiny) + gr * gr / np.maximum(hr, tiny)
            - g * g / np.maximum(h, tiny))


def leaves_under(tree: dict) -> list:
    """For every internal node, the leaves below its left and its right
    child. Node s is made by split s, so a child node has a larger index
    than its parent and one backward sweep fills the table."""
    k = int(tree["num_leaves"]) - 1
    under = [None] * k
    for s in range(k - 1, -1, -1):
        sides = []
        for child in (int(tree["left_child"][s]), int(tree["right_child"][s])):
            sides.append([~child] if child < 0
                         else under[child][0] + under[child][1])
        under[s] = sides
    return under


def node_histograms(bins: np.ndarray, g: np.ndarray, h: np.ndarray,
                    nbins: int) -> np.ndarray:
    """[F, nbins, 2]: the sum of (g, h) in every bin of every feature over
    the given rows (``bins`` is [F, rows])."""
    out = np.empty((bins.shape[0], nbins, 2), np.float64)
    for j, b in enumerate(bins):
        out[j, :, 0] = np.bincount(b, weights=g, minlength=nbins)
        out[j, :, 1] = np.bincount(b, weights=h, minlength=nbins)
    return out


def pick_nodes(tree: dict, under: list, k: int, seed: int, tag: int) -> list:
    """The ``k`` splits of the tree whose search the reference repeats, from
    every depth: the root; the most lopsided split, whose smaller child
    holds the smallest share of its node's rows (a split at a feature's
    last bin, where a child's sums are small differences of large ones);
    the rest drawn from the seed over all the tree's splits, most of which
    are deep. Rows are the model's own leaf counts (``leaf_count_gap``
    checks those)."""
    m = int(tree["num_leaves"]) - 1
    k = min(int(k), m)
    if k <= 0:
        return []
    count = tree["leaf_count"]
    small = [min(count[lo].sum(), count[hi].sum())
             / max(count[lo].sum() + count[hi].sum(), 1) for lo, hi in under]
    picked = [0, int(np.argmin(small))]
    rng = np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([int(seed), 0x5B117, int(tag)])))
    picked += [int(s) for s in rng.permutation(m)]
    return sorted(list(dict.fromkeys(picked))[:k])


_NOISE_GAINS = 8.0


def gain_floor(features: int, max_bin: int) -> float:
    """The gain under which a split's shortfall is measured against this
    floor and not against the best gain: ``_NOISE_GAINS`` times the largest
    gain that labels of pure noise reach over features x (max_bin - 1)
    candidate splits, 2 ln(candidates) (a gain is a chi-square of one
    degree for each; sum g^2 = sum h for log loss). Deep in a tree most
    splits fit noise, and the reference's grid is not the program's: the
    two best gains then differ by about the root of the gain, the whole of
    a gain near nought (PERF.md section 2 has the readings)."""
    return _NOISE_GAINS * 2.0 * float(np.log(features * (max_bin - 1)))


def split_shortfall(side: np.ndarray, bins: np.ndarray, g: np.ndarray,
                    h: np.ndarray, nbins: int, min_hessian: float,
                    floor: float) -> tuple:
    """One split against the best the reference finds on its own grid, on
    rows of the split's node: (shortfall, best gain, gain of the split
    taken), shortfall = (best - taken) / the larger of best and ``floor``,
    0 where the grid has no allowed split. ``side`` says which child each
    row went to (1 left, 2 right), ``bins`` [F, rows] is their bins on the
    grid, ``g`` and ``h`` their gradient and hessian."""
    left = side == 1
    taken = float(_gain(g[left].sum(), h[left].sum(),
                        g[~left].sum(), h[~left].sum()))
    hist = np.cumsum(node_histograms(bins, g, h, nbins), axis=1)
    lo, total = hist[:, :-1], hist[:, -1:]
    hi = total - lo
    ok = (lo[..., 1] >= min_hessian) & (hi[..., 1] >= min_hessian)
    gain = np.where(ok, _gain(lo[..., 0], lo[..., 1], hi[..., 0], hi[..., 1]),
                    -np.inf)
    best = float(gain.max())
    if not (best > 0 and np.isfinite(best)):
        return 0.0, best, taken
    return max(0.0, (best - taken) / max(best, floor)), best, taken


# ---------------------------------------------------------------------------
# the program's answer, read from the text model format

_INT_KEYS = ("split_feature", "left_child", "right_child", "leaf_count",
             "decision_type")
_FLOAT_KEYS = ("threshold", "leaf_value")


def parse_model(text: str) -> list:
    """The trees of a LightGBM text model as dicts of arrays
    (``num_leaves``, ``split_feature``, ``threshold``, ``left_child``,
    ``right_child``, ``leaf_value``, ``leaf_count``)."""
    trees = []
    for block in text.split("\nTree=")[1:]:
        fields = {}
        for line in block.split("\n\n")[0].splitlines()[1:]:
            key, _, val = line.partition("=")
            fields[key] = val
        tree = {"num_leaves": int(fields["num_leaves"])}
        if int(fields.get("num_cat", 0)):
            raise ValueError("the reference follows numerical splits only")
        for key in _INT_KEYS:
            tree[key] = np.array(fields.get(key, "").split(), np.int64)
        for key in _FLOAT_KEYS:
            tree[key] = np.array(fields.get(key, "").split(), np.float64)
        if tree["num_leaves"] > 1 and (tree["decision_type"] & 1).any():
            raise ValueError("the reference follows numerical splits only")
        trees.append(tree)
    return trees


# ---------------------------------------------------------------------------
# the comparison

def _sample(n: int, k: int, seed: int, tag: int) -> np.ndarray:
    """About ``k`` of the rows 0..n-1, sorted, drawn from the seed (with
    replacement and made unique: a draw without replacement shuffles all
    ``n``, seconds at 63M rows)."""
    if k >= n:
        return np.arange(n)
    rng = np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([int(seed), tag])))
    return np.unique(rng.integers(0, n, size=int(k)))


def leaf_gaps(got_value, got_count, ref_value, ref_count) -> tuple:
    """Every leaf's (value gap, count gap): the distance from the
    reference over the larger of the reference's own reading of that leaf
    and of the median leaf."""
    vgap = np.abs(got_value - ref_value) / np.maximum(
        np.maximum(np.abs(ref_value), np.median(np.abs(ref_value))), 1e-300)
    cgap = np.abs(got_count - ref_count) / np.maximum(
        ref_count, np.median(ref_count))
    return vgap, cgap


class Whole:
    """Arrays that are there already, as the one chunk ``compare`` reads."""

    def __init__(self, x: np.ndarray, y: np.ndarray):
        self.x, self.y = x, y
        self.rows, self.features = x.shape

    def __len__(self) -> int:
        return 1

    def __getitem__(self, c: int):
        return 0, self.rows, self.x, self.y


def _node_tables(tree: dict, k: int, split_rows: int, seed: int,
                 tag: int) -> dict:
    """{split: (side of every leaf: 1 under its left child, 2 under its
    right, 0 outside; stride)} for the splits ``pick_nodes`` picks: every
    ``stride``-th row of the node is read, at most ``split_rows`` in all,
    every row where the node has no more."""
    under = leaves_under(tree)
    out = {}
    for s in pick_nodes(tree, under, k, seed, tag):
        side = np.zeros(int(tree["num_leaves"]), np.int8)
        side[under[s][0]] = 1
        side[under[s][1]] = 2
        rows = int(tree["leaf_count"][under[s][0] + under[s][1]].sum())
        out[s] = (side, max(1, -(-rows // max(1, int(split_rows)))))
    return out


def _walk_chunks(chunks, trees, follow, nodes, score_rows, seed, threads,
                 split_rows: int = 0):
    """One pass over the data: the labels, every row's leaf in each of the
    first ``follow`` trees, for every picked split ``nodes[t][s]`` the
    (row numbers, raw values) of every stride-th row of its node (of one
    chunk at most twice its share of ``split_rows``: the strides come from
    the model's counts, and a model that counts wrongly must not fill the
    host), and for the rows ``score_rows`` the summed leaf values of the
    later trees."""
    cap = 2 * int(split_rows) // max(1, len(chunks)) + 64
    n = chunks.rows
    y = np.empty(n, np.float32)
    leaf = [np.empty(n, np.int32) for _ in range(follow)]
    tail = np.zeros(score_rows.shape[0], np.float64)
    taken = [{s: [] for s in nodes[t]} for t in range(follow)]

    def one(c):
        a, b, xb, yb = chunks[c]
        y[a:b] = yb
        for t in range(follow):
            at = leaf[t][a:b] = leaf_index(xb, trees[t], 1)
            for s, (side, stride) in nodes[t].items():
                rows = np.flatnonzero(np.take(side, at))[
                    (int(seed) + c) % stride::stride][:cap]
                if rows.size:
                    taken[t][s].append((c, a + rows, xb[rows]))
        lo, hi = np.searchsorted(score_rows, [a, b])
        if hi > lo:
            sub = xb[score_rows[lo:hi] - a]
            for tree in trees[follow:]:
                tail[lo:hi] += np.take(tree["leaf_value"],
                                       leaf_index(sub, tree, 1))

    _pmap(one, range(len(chunks)), threads)
    for table in taken:       # in the data's order, whatever the threads did
        for s, blocks in table.items():
            blocks.sort(key=lambda blk: blk[0])
            table[s] = ((np.concatenate([blk[1] for blk in blocks]),
                         np.concatenate([blk[2] for blk in blocks]))
                        if blocks else None)
    return y, leaf, taken, tail


NUMBERS = ("trees_missing", "score_gap", "leaf_value_gap",
           "median_leaf_value_gap", "leaf_count_gap", "split_gain_shortfall",
           "min_hessian_shortfall")


def compare(chunks, params: dict, trees: list, scores: np.ndarray,
            iterations: int, check: dict, seed: int, threads: int,
            log=lambda msg: None) -> dict:
    """The numbers ``correct`` is decided by, each 0 for a perfect answer.

    ``chunks`` is the data, never whole: ``len(chunks)`` pieces,
    ``chunks[c]`` = (first row, one past the last, x block, y block),
    ``chunks.rows`` in all (``data.Chunks``, or ``Whole`` around arrays).
    ``trees`` and ``scores`` are what the timed path produced: every tree
    of the run and the training scores they left on the device.
    ``check`` says how much the reference follows: the first
    ``check["trees"]`` trees on every row, the rest of the trees on
    ``check["score_rows"]`` rows drawn from the seed, and
    ``check["split_nodes"]`` splits of each followed tree
    (``pick_nodes``), each on about ``check["split_rows"]`` rows of its
    node.

    - ``trees_missing``: iterations the run counted less trees in the model.
    - ``leaf_count_gap``: the worst leaf's |model count - rows the
      reference's walk puts there| over the larger of those rows and the
      median leaf's (partition; the program counts in float32, so at tens
      of millions of rows a count is a few rows off).
    - ``leaf_value_gap``: the worst leaf's |model value - reference value|
      over the larger of that leaf's |reference value| and the median
      leaf's (gradient, histogram sums, leaf values; the first tree
      carries the label average, so that too).
    - ``median_leaf_value_gap``: the median leaf's same gap, the larger of
      the followed trees'. The worst leaf's gap is the noise of one small
      leaf and swings with the smallest leaf a seed draws; the median
      leaf's is steady from seed to seed and reads the precision the
      gradients were summed in (a lower precision moves every leaf).
    - ``split_gain_shortfall``: the worst picked split's
      ``split_shortfall``: the share of the best gain on the reference's
      grid that the split taken falls short by, measured against
      ``gain_floor`` where the best gain is under it (split search,
      histogram).
    - ``min_hessian_shortfall``: how far the smallest hessian sum of any
      leaf lies under ``min_sum_hessian_in_leaf``, as a share of it; 0
      where every leaf holds the stated minimum (the guarantee the split
      search gives; a tree of one leaf made no split and is left out).
    - ``score_gap``: worst row's |device score - reference score| over the
      largest |reference score - initial score| (partition, score update).
    """
    check_params(params)
    n = chunks.rows
    lr = float(params.get("learning_rate", 0.1))
    min_h = float(params.get("min_sum_hessian_in_leaf", 1e-3))
    max_bin = int(params["max_bin"])
    out = dict.fromkeys(NUMBERS, 0.0)
    out["trees_missing"] = float(abs(int(iterations) - len(trees)))
    follow = min(int(check["trees"]), len(trees))
    nodes = [_node_tables(trees[t], check["split_nodes"], check["split_rows"],
                          seed, t) for t in range(follow)]
    score_rows = (_sample(n, check["score_rows"], seed, 0x5C02E)
                  if follow < len(trees) else np.empty(0, np.int64))
    clock = time.perf_counter()
    y, leaves_of, taken, tail = _walk_chunks(
        chunks, trees, follow, nodes, score_rows, seed, threads,
        check["split_rows"])
    spent = {"walk": time.perf_counter() - clock, "sums": 0.0, "splits": 0.0}
    init = init_score(y)
    score = np.full(n, init, np.float64)
    grid, floor = None, gain_floor(chunks.features, max_bin)
    for t in range(follow):
        tree, leaf = trees[t], leaves_of[t]
        clock = time.perf_counter()
        g, h = grad_hess(score, y, threads)
        leaves = int(tree["num_leaves"])
        gsum, hsum, count = leaf_sums(leaf, g, h, leaves)
        ref = leaf_values(gsum, hsum, lr)
        got = tree["leaf_value"] - (init if t == 0 else 0.0)
        vgap, cgap = leaf_gaps(got, tree["leaf_count"], ref, count)
        out["leaf_count_gap"] = max(out["leaf_count_gap"], float(cgap.max()))
        out["leaf_value_gap"] = max(out["leaf_value_gap"], float(vgap.max()))
        out["median_leaf_value_gap"] = max(out["median_leaf_value_gap"],
                                           float(np.median(vgap)))
        lv, lc, lh = int(vgap.argmax()), int(cgap.argmax()), int(hsum.argmin())
        if leaves > 1:
            out["min_hessian_shortfall"] = max(
                out["min_hessian_shortfall"], 1.0 - float(hsum[lh]) / min_h)
        log(f"reference followed tree {t}: {leaves} leaves; median leaf's "
            f"value gap {np.median(vgap):.3e}; leaf_value_gap "
            f"{vgap.max():.3e} at leaf {lv} (model {got[lv]:.6e}, reference "
            f"{ref[lv]:.6e}, rows {count[lv]}, hessian {hsum[lv]:.4e}; median "
            f"|reference| {np.median(np.abs(ref)):.3e}); leaf_count_gap "
            f"{cgap.max():.3e} at leaf {lc} (model "
            f"{int(tree['leaf_count'][lc])}, reference {count[lc]}); smallest "
            f"leaf hessian {hsum[lh]:.4e} at leaf {lh} ({count[lh]} rows), "
            f"stated minimum {min_h:g}")
        spent["sums"] += time.perf_counter() - clock
        clock = time.perf_counter()
        for s, (side, stride) in nodes[t].items():
            if taken[t][s] is None:
                continue
            rows, xs = taken[t][s]
            taken[t][s] = None
            if grid is None:
                grid = quantile_grid(grid_rows(chunks), max_bin, threads)
            short, best, took = split_shortfall(
                np.take(side, leaf[rows]), bin_rows(xs, grid, threads),
                g[rows], h[rows], max_bin, min_h / stride, floor)
            out["split_gain_shortfall"] = max(out["split_gain_shortfall"],
                                              short)
            log(f"reference searched tree {t} split {s} (feature "
                f"{int(tree['split_feature'][s])}, threshold "
                f"{float(tree['threshold'][s]):.4f}) on {rows.size} rows, "
                f"every {stride}: best gain {best:.5e}, taken {took:.5e}, "
                f"shortfall {short:.3e}")
        spent["splits"] += time.perf_counter() - clock
        score += np.take(got, leaf)
    log("reference seconds: " + ", ".join(
        f"{k} {v:.1f}" for k, v in spent.items()))
    if follow < len(trees):     # the later trees: on the sampled rows
        ref_score = score[score_rows] + tail
        got_score = np.asarray(scores)[score_rows].astype(np.float64)
    else:
        ref_score, got_score = score, np.asarray(scores, np.float64)
    moved = float(np.abs(ref_score - init).max())
    out["score_gap"] = float(np.abs(got_score - ref_score).max()
                             / max(moved, 1e-300))
    return out


# ---------------------------------------------------------------------------
# the reference put in the program's place

CONTROLS = ("float8", "int4")
STAND_INS = CONTROLS + ("half_batch", "altered_leaf")
_INT4_LEVELS = 16


def _float8(values: np.ndarray) -> np.ndarray:
    import ml_dtypes
    return values.astype(ml_dtypes.float8_e4m3fn).astype(np.float64)


def _int4(g: np.ndarray, h: np.ndarray, tree: int):
    """(g, h) on the 16 levels four bits hold, rounded stochastically as
    LightGBM's quantized training rounds (``gradient_discretizer.cpp``:
    one gradient level is max|g| / (levels / 2), one hessian level max h /
    levels, a value goes to a neighbouring level with the probability of
    its distance from the other). The draws are from a fixed stream."""
    rng = np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([0x1274, int(tree)])))
    out = []
    for values, levels in ((g, _INT4_LEVELS // 2), (h, _INT4_LEVELS)):
        scale = float(np.abs(values).max()) / levels
        q = values / scale
        q += np.sign(values) * rng.random(values.shape[0])
        out.append(np.trunc(q, out=q) * scale)
    return out


def stand_ins(chunks, params: dict, trees: list, kinds, threads: int) -> dict:
    """{kind: (trees, scores)}: the answer this reference gives when it is
    put in the program's place, growing the given trees' splits again from
    the data and keeping its own scores, as a program does.

    - ``float8`` and ``int4`` are the controls, the reference one
      precision below the one a configuration states: every row's gradient
      and hessian rounded before the leaf sums, to float8 (e4m3) for a
      configuration that states bfloat16, to the 16 levels of four bits
      (``_int4``) for one that states int8. A cell's file names its own.
    - ``half_batch``: leaf sums, values and counts taken over every other
      row only, the mean taken over the rest.
    - ``altered_leaf``: an answer altered where it is produced: the
      largest leaf value of every tree is 5% up in the model, after the
      scores were updated with the true one.

    ``compare`` has to find each of them not correct. (A step that returns
    its state unchanged reads ``score_gap`` 1 by that number's definition.)
    """
    check_params(params)
    unknown = sorted(set(kinds) - set(STAND_INS))
    if unknown:
        raise ValueError(f"unknown stand-in(s) {unknown}")
    n = chunks.rows
    lr = float(params.get("learning_rate", 0.1))
    y, leaves_of, _, _ = _walk_chunks(
        chunks, trees, len(trees), [{} for _ in trees],
        np.empty(0, np.int64), 0, threads)
    init = init_score(y)
    out = {}
    for kind in kinds:
        score = np.full(n, init, np.float64)
        made = []
        for t, (tree, leaf) in enumerate(zip(trees, leaves_of)):
            g, h = grad_hess(score, y, threads)
            if kind == "float8":
                g, h = _float8(g), _float8(h)
            elif kind == "int4":
                g, h = _int4(g, h, t)
            rows = slice(0, None, 2) if kind == "half_batch" else slice(None)
            gsum, hsum, count = leaf_sums(leaf[rows], g[rows], h[rows],
                                          int(tree["num_leaves"]))
            value = leaf_values(gsum, hsum, lr)
            score += np.take(value, leaf)
            if kind == "altered_leaf":
                value[np.abs(value).argmax()] *= 1.05
            made.append(dict(tree, leaf_count=count, leaf_value=value + (
                init if t == 0 else 0.0)))
        out[kind] = (made, score.astype(np.float32))
    return out
