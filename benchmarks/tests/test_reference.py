"""The plain reference on answers made by hand: a perfect answer reads 0
on every number, and each kind of wrong answer moves the number that is
there to catch it."""

import numpy as np
import pytest

import reference as R

PARAMS = {"objective": "binary", "num_leaves": 4, "max_bin": 15,
          "learning_rate": 0.1, "min_data_in_leaf": 1,
          "min_sum_hessian_in_leaf": 1.0}
CHECK = {"trees": 2, "score_rows": 500, "split_nodes": 2, "split_rows": 4000}


def _data(n=4000, f=3, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, f))
    y = (x[:, 0] + 0.3 * rng.standard_normal(n) > 0).astype(np.float32)
    return x, y


def _grow(x, y, score, lr, first):
    """A 3-leaf tree grown by exhaustive search on the reference's own
    grid, in plain loops: split the root, then the left child."""
    g, h = R.grad_hess(score, y, 1)
    grid = R.quantile_grid(x, PARAMS["max_bin"], 1)

    def best(rows):
        top = (-np.inf, 0, 0.0)
        for j in range(x.shape[1]):
            for thr in grid[j]:
                left = x[rows, j] <= thr
                if left.all() or not left.any():
                    continue
                gain = R._gain(g[rows][left].sum(), h[rows][left].sum(),
                               g[rows][~left].sum(), h[rows][~left].sum())
                top = max(top, (float(gain), j, float(thr)))
        return top

    rows = np.arange(x.shape[0])
    _, f0, t0 = best(rows)
    left = rows[x[rows, f0] <= t0]
    _, f1, t1 = best(left)
    tree = {"num_leaves": 3,
            "split_feature": np.array([f0, f1]),
            "threshold": np.array([t0, t1]),
            "left_child": np.array([1, -1]), "right_child": np.array([-2, -3]),
            "decision_type": np.array([0, 0])}
    leaf = R.leaf_index(x, tree, 1)
    gs, hs, count = R.leaf_sums(leaf, g, h, 3)
    tree["leaf_count"] = count
    tree["leaf_value"] = R.leaf_values(gs, hs, lr) + (
        R.init_score(y) if first else 0.0)
    return tree, score + R.leaf_values(gs, hs, lr)[leaf]


@pytest.fixture(scope="module")
def answer():
    x, y = _data()
    score = np.full(x.shape[0], R.init_score(y))
    trees = []
    for t in range(3):
        tree, score = _grow(x, y, score, 0.1, t == 0)
        trees.append(tree)
    return x, y, trees, score.astype(np.float32)


def _compare(answer, trees=None, scores=None, iterations=3):
    x, y, good, s = answer
    return R.compare(R.Whole(x, y), PARAMS,
                     good if trees is None else trees,
                     s if scores is None else scores, iterations, CHECK, 5, 2)


def test_a_perfect_answer_reads_nought(answer):
    out = _compare(answer)
    assert out["trees_missing"] == 0 and out["leaf_count_gap"] == 0
    assert out["leaf_value_gap"] < 1e-12
    assert out["split_gain_shortfall"] < 1e-12
    assert out["score_gap"] < 1e-6          # the scores are float32


def test_a_tree_short_is_trees_missing(answer):
    assert _compare(answer, iterations=4)["trees_missing"] == 1


def test_scores_left_unchanged_read_one(answer):
    x, y, trees, s = answer
    out = _compare(answer, scores=np.full_like(s, R.init_score(y)))
    assert 0.9 < out["score_gap"] <= 1.0


def test_an_altered_leaf_value_shows(answer):
    x, y, trees, s = answer
    bad = [dict(t) for t in trees]
    bad[1]["leaf_value"] = bad[1]["leaf_value"] * np.array([1.0, 1.05, 1.0])
    out = _compare(answer, trees=bad)
    assert 0.01 < out["leaf_value_gap"] < 0.06
    assert out["score_gap"] > 1e-4          # the scores got the true value


def test_an_altered_threshold_moves_rows(answer):
    x, y, trees, s = answer
    bad = [dict(t) for t in trees]
    bad[0]["threshold"] = bad[0]["threshold"] + np.array([0.0, 0.3])
    out = _compare(answer, trees=bad)
    assert out["leaf_count_gap"] > 0.01


def test_a_poor_split_falls_short(answer):
    """The root split on a noise feature: the reference finds better."""
    x, y, trees, s = answer
    tree = dict(trees[0])
    tree["split_feature"] = np.array([2, tree["split_feature"][1]])
    tree["threshold"] = np.array([0.0, tree["threshold"][1]])
    leaf = R.leaf_index(x, tree, 1)
    g, h = R.grad_hess(np.full(x.shape[0], R.init_score(y)), y, 1)
    gs, hs, count = R.leaf_sums(leaf, g, h, 3)
    tree["leaf_count"] = count
    tree["leaf_value"] = R.leaf_values(gs, hs, 0.1) + R.init_score(y)
    out = R.compare(R.Whole(x, y), PARAMS, [tree], s, 1, CHECK, 5, 2)
    assert out["split_gain_shortfall"] > 0.9
    assert out["leaf_count_gap"] == 0 and out["leaf_value_gap"] < 1e-12


def test_half_the_rows_left_out_shows_in_the_counts(answer):
    x, y, trees, s = answer
    bad = [dict(t) for t in trees]
    bad[0]["leaf_count"] = bad[0]["leaf_count"] // 2
    assert 0.4 < _compare(answer, trees=bad)["leaf_count_gap"] < 0.6


def test_chunks_of_the_data_read_as_the_whole(answer):
    """The reference never holds the rows whole: the same answer through
    ``data.Chunks``-like pieces, with later trees on sampled rows."""
    x, y, trees, s = answer

    class Pieces(R.Whole):
        def __len__(self):
            return 8

        def __getitem__(self, c):
            a, b = c * 500, (c + 1) * 500
            return a, b, self.x[a:b], self.y[a:b]

    check = dict(CHECK, trees=2, score_rows=1500)
    whole = R.compare(R.Whole(x, y), PARAMS, trees, s, 3, check, 5, 2)
    pieces = R.compare(Pieces(x, y), PARAMS, trees, s, 3, check, 5, 3)
    assert whole == pieces
    assert whole["score_gap"] < 1e-6 and whole["leaf_value_gap"] < 1e-12


def test_parse_model_reads_the_text_format():
    text = ("tree\nversion=v4\n\nTree=0\nnum_leaves=3\nnum_cat=0\n"
            "split_feature=0 1\nsplit_gain=1 1\nthreshold=0.5 -0.25\n"
            "decision_type=0 0\nleft_child=1 -1\nright_child=-2 -3\n"
            "leaf_value=0.1 -0.2 0.3\nleaf_count=5 6 7\nshrinkage=1\n\n\n"
            "Tree=1\nnum_leaves=1\nnum_cat=0\nleaf_value=0\n\n"
            "end of trees\n")
    trees = R.parse_model(text)
    assert [t["num_leaves"] for t in trees] == [3, 1]
    assert trees[0]["threshold"].tolist() == [0.5, -0.25]
    assert trees[0]["right_child"].tolist() == [-2, -3]
    assert trees[0]["leaf_count"].tolist() == [5, 6, 7]


def test_parameters_the_reference_does_not_follow_are_refused():
    with pytest.raises(ValueError):
        R.check_params(dict(PARAMS, lambda_l2=1.0))
    with pytest.raises(ValueError):
        R.check_params(dict(PARAMS, objective="regression"))


def test_a_poor_deep_split_falls_short_too(answer):
    """Not only the root's search is repeated: the second split put on a
    noise feature, the first left as it was."""
    x, y, trees, s = answer
    tree = dict(trees[0])
    tree["split_feature"] = np.array([tree["split_feature"][0], 2])
    tree["threshold"] = np.array([tree["threshold"][0], 0.0])
    leaf = R.leaf_index(x, tree, 1)
    g, h = R.grad_hess(np.full(x.shape[0], R.init_score(y)), y, 1)
    gs, hs, count = R.leaf_sums(leaf, g, h, 3)
    tree["leaf_count"] = count
    tree["leaf_value"] = R.leaf_values(gs, hs, 0.1) + R.init_score(y)
    out = R.compare(R.Whole(x, y), PARAMS, [tree], s, 1, CHECK, 5, 2)
    assert out["split_gain_shortfall"] > 0.5
    assert out["leaf_count_gap"] == 0 and out["leaf_value_gap"] < 1e-12


def test_a_gain_near_nought_is_measured_against_the_floor():
    """Labels of pure noise: the split taken is as good as any, and what
    the reference's grid finds better is noise too."""
    rng = np.random.default_rng(3)
    x = rng.standard_normal((3000, 3))
    g = rng.choice([-0.5, 0.5], 3000)
    h = np.full(3000, 0.25)
    grid = R.quantile_grid(x, 15, 1)
    bins = R.bin_rows(x, grid, 1)
    side = np.where(x[:, 1] <= 0.123, 1, 2)
    floor = R.gain_floor(3, 15)
    assert floor == pytest.approx(8 * 2 * np.log(42))
    short, best, taken = R.split_shortfall(side, bins, g, h, 15, 1.0, floor)
    assert 0 < best < floor / 4 and short == pytest.approx(
        max(0.0, best - taken) / floor)
    bare, _, _ = R.split_shortfall(side, bins, g, h, 15, 1.0, 0.0)
    assert bare > 3 * short


def _chain(counts):
    """A tree that splits its right child again and again: leaf i hangs
    left of node i, the last leaf right of the last node."""
    k = len(counts) - 1
    return {"num_leaves": k + 1, "leaf_count": np.array(counts),
            "left_child": np.array([~i for i in range(k)]),
            "right_child": np.array(list(range(1, k)) + [~k])}


def test_the_searched_splits_come_from_every_depth():
    tree = _chain([500, 400, 300, 200, 100, 90, 80, 3, 70])
    under = R.leaves_under(tree)
    assert under[6] == [[6], [7, 8]] and under[7] == [[7], [8]]
    picks = [R.pick_nodes(tree, under, 4, seed, 0) for seed in range(20)]
    for picked in picks:
        assert len(picked) == 4 and picked == sorted(set(picked))
        assert 0 in picked          # the root
        assert 7 in picked          # 3 rows against 70: the most lopsided
    assert len({tuple(p) for p in picks}) > 5       # the rest from the seed
    assert {s for p in picks for s in p} == set(range(8))
    assert R.pick_nodes(tree, under, 1, 0, 0) == [0]
    assert R.pick_nodes(tree, under, 99, 0, 0) == list(range(8))
    assert R.pick_nodes(_chain([9]), [], 4, 0, 0) == []


def test_a_leaf_under_the_stated_minimum_shows(answer):
    x, y, trees, s = answer
    assert _compare(answer)["min_hessian_shortfall"] == 0
    leaf = R.leaf_index(x, trees[0], 1)
    g, h = R.grad_hess(np.full(x.shape[0], R.init_score(y)), y, 1)
    smallest = R.leaf_sums(leaf, g, h, 3)[1].min()
    stated = dict(PARAMS, min_sum_hessian_in_leaf=2.0 * smallest)
    out = R.compare(R.Whole(x, y), stated, trees, s, 3, CHECK, 5, 2)
    assert out["min_hessian_shortfall"] == pytest.approx(0.5, abs=0.1)


@pytest.mark.parametrize("kind,number,least,most", [
    ("float8", "leaf_value_gap", 0.02, 0.12),
    ("float8", "median_leaf_value_gap", 0.005, 0.12),
    ("int4", "median_leaf_value_gap", 1e-4, 0.12),
    ("half_batch", "leaf_count_gap", 0.4, 0.6),
    ("altered_leaf", "leaf_value_gap", 0.03, 0.07),
])
def test_the_reference_in_the_programs_place(answer, kind, number, least,
                                             most):
    """Sound, it reads nought; one precision down or with a fault, the
    number that is there to catch it moves."""
    x, y, trees, s = answer
    made = R.stand_ins(R.Whole(x, y), PARAMS, trees[:2], (kind,), 2)
    its_trees, its_scores = made[kind]
    assert its_scores.dtype == np.float32 and len(its_trees) == 2
    out = R.compare(R.Whole(x, y), PARAMS, its_trees, its_scores, 2, CHECK,
                    5, 2)
    assert least < out[number] <= most
    assert out["trees_missing"] == 0
    if kind != "half_batch":
        assert out["leaf_count_gap"] == 0


def test_an_unknown_stand_in_is_refused(answer):
    x, y, trees, s = answer
    with pytest.raises(ValueError):
        R.stand_ins(R.Whole(x, y), PARAMS, trees[:1], ("int2",), 1)
