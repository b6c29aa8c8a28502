"""A node's totals are sums of the numbers its histogram's bins hold.

On the chip a float histogram's bins do not hold the gradients: the MXU
rounds the gradient operand to bfloat16 (``tpu_hist_precision=default``)
and sums in float32 in its own order. The CPU does neither (the XLA twin
and Pallas' interpret mode are exact float32), so these tests round the
operand themselves, ``astype(bfloat16).astype(float32)``, on its way
into the XLA twin, and grow a 255-leaf tree through the growers' normal
calls. The data makes the case that went wrong on the chip (PERF.md
section 7.1): a constant hessian whose bfloat16 neighbour lies 0.19%
below it, and a signal in feature 0's top bins, so that the tree takes a
chain of right turns from the root with a split at the feature's last
bin among them. A grower that takes the root's totals from a
separate, exact reduction of the gradients hands that chain the whole
rounding difference (11 units of hessian here, min_sum_hessian_in_leaf
is 5) and fails every assertion below.

For every node of the tree, internal or leaf:

- the stored hessian sum and count equal the sum of the node's bins
  (of every feature: each row falls in one bin of each, so a feature's
  bins sum to the rounded operands of the node's rows), to float32
  summation tolerance: 2e-5 of the node's sum plus 2e-6 of the root's
  (a histogram made by sibling subtraction carries the root's rounding);
- the stored value is minus the gradient bins' sum over the hessian
  bins' sum, to the same tolerance on the gradient sum;

and for every leaf, from its own rows in float64:

- the hessian meets min_sum_hessian_in_leaf, less the operand's bound
  (2^-8 relative, twice bfloat16's half unit in the last place);
- the value agrees with the plain Newton value -G/H within that bound
  on both sums.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.ops import histogram as hist_ops
from lightgbm_tpu.ops import pallas_histogram
from lightgbm_tpu.ops.split import FeatureMeta, SplitHyperParams

N, F, B, L = 24_000, 4, 16, 255
MIN_HESS = 5.0
HESS = 0.2495            # bfloat16(0.2495) = 0.2490234: 0.19% below
BF16_BOUND = 2.0 ** -8
CASES = ("default", "no_subtract", "int8", "streamed", "exact")


def _bf16(x):
    return np.asarray(jnp.asarray(x, jnp.float32).astype(jnp.bfloat16)
                      .astype(jnp.float32), np.float64)


def _data():
    """bins [F, N] uint8, labels: P(y = 1) rises over feature 0's top
    bins, the other features carry a weak signal each."""
    r = np.random.RandomState(3)
    bins = r.randint(0, B, (F, N)).astype(np.uint8)
    top = np.array([0.30] * 12 + [0.45, 0.60, 0.75, 0.95])
    p = top[bins[0]] + 0.02 * (bins[1] > 7) - 0.02 * (bins[2] > 7)
    y = (r.rand(N) < p).astype(np.float64)
    return bins, y


def _meta():
    return FeatureMeta(
        num_bins=jnp.full((F,), B, jnp.int32),
        missing_type=jnp.zeros((F,), jnp.int32),
        default_bin=jnp.zeros((F,), jnp.int32),
        is_categorical=jnp.zeros((F,), bool),
        monotone=jnp.zeros((F,), jnp.int8),
        penalty=jnp.ones((F,), jnp.float32),
        cegb_feat=jnp.zeros((F,), jnp.float32),
        cegb_lazy=jnp.zeros((F,), jnp.float32))


def _hp():
    cfg = Config()
    cfg.min_data_in_leaf = 1
    cfg.min_sum_hessian_in_leaf = MIN_HESS
    return SplitHyperParams.from_config(cfg)


@pytest.fixture
def chip_rounding(monkeypatch):
    """Every float histogram build rounds its gradient operand as the
    chip's MXU does; the int8 builds sum integers and are left alone."""
    def rounded(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    multi = pallas_histogram.hist_multi
    build = hist_ops.build_histogram

    def hist_multi(bins, ghT, row_leaf, ids, **kw):
        return multi(bins, rounded(ghT), row_leaf, ids, **kw)

    def build_histogram(bins, grad, hess, mask, **kw):
        return build(bins, rounded(grad * mask), rounded(hess * mask),
                     mask, **kw)

    monkeypatch.setattr(pallas_histogram, "hist_multi", hist_multi)
    monkeypatch.setattr(hist_ops, "build_histogram", build_histogram)


def _grow(case, bins, grad, hess, quant):
    """(TreeArrays, row_leaf) through the grower's normal call."""
    from lightgbm_tpu import learner
    meta, hp = _meta(), _hp()
    mask = jnp.ones((N,), jnp.float32)
    fmask = jnp.ones((F,), bool)
    g, h = jnp.asarray(grad, jnp.float32), jnp.asarray(hess, jnp.float32)
    common = dict(num_leaves=L, max_bins=B, hist_impl="xla",
                  hist_precision="default", has_categorical=False)
    if case == "exact":
        grow = jax.jit(functools.partial(learner.grow_tree, **common))
        return grow(jnp.asarray(bins), g, h, mask, fmask, meta, hp,
                    jnp.int32(-1))
    if case == "streamed":
        from lightgbm_tpu.io.streaming import HostSlabBins
        grower = learner.StreamTreeGrower(
            HostSlabBins(bins, B, 8192, pack=False), num_features=F,
            extra_trees=False, ff_bynode=1.0, wave_max=42,
            subtract_siblings=True, hist_deterministic=False, **common)
        ghT = jnp.stack([g, h, mask], axis=1)
        return grower.grow(ghT, jnp.ones((3,), jnp.float32), fmask, meta,
                           hp, jnp.int32(-1))
    grow = jax.jit(functools.partial(
        learner.grow_tree_waved, wave_max=42, quant=quant,
        subtract_siblings=case != "no_subtract", **common))
    return grow(jnp.asarray(bins), g, h, mask, fmask, meta, hp,
                jnp.int32(-1))


def _nodes(rec, bins):
    """Replay the splits on the bins: [(rows, stored hessian, count,
    value)] for every internal node and leaf, and the right-turn chain
    from the root as [(feature, threshold)]."""
    n_leaves = int(rec.num_leaves)
    leaf = np.zeros(N, np.int64)
    nodes, chain, tip = [], [], 0
    for s in range(n_leaves - 1):
        at, f, t = (int(rec.split_leaf[s]), int(rec.split_feature[s]),
                    int(rec.split_bin_threshold[s]))
        rows = leaf == at
        nodes.append((rows, float(rec.internal_weight[s]),
                      float(rec.internal_count[s]),
                      float(rec.internal_value[s])))
        leaf[rows & (bins[f] > t)] = s + 1
        if at == tip:
            chain.append((f, t))
            tip = s + 1
    leaves = [(leaf == k, float(rec.leaf_weight[k]),
               float(rec.leaf_count[k]), float(rec.leaf_value[k]))
              for k in range(n_leaves)]
    return nodes, leaves, chain, leaf


@pytest.mark.parametrize("case", CASES)
def test_stored_totals_are_the_bins_sums(case, chip_rounding):
    bins, y = _data()
    g_true = 0.5224 - y                     # binary log loss at p = 0.5224
    h_true = np.full(N, HESS)
    quant = None
    if case == "int8":
        # LightGBM's quantized gradients, rounded to nearest here: the
        # bins hold integers times a scale, and so do the rows' own values
        g_scale, h_scale = np.abs(g_true).max() / 63, HESS / 126
        g_int, h_int = np.rint(g_true / g_scale), np.rint(h_true / h_scale)
        quant = (jnp.asarray(g_int, jnp.float32),
                 jnp.asarray(h_int, jnp.float32),
                 jnp.float32(g_scale), jnp.float32(h_scale))
        g_true = g_int * np.float64(np.float32(g_scale))
        h_true = h_int * np.float64(np.float32(h_scale))
        g_op, h_op, bound = g_true, h_true, 0.0
    else:
        g_op, h_op, bound = _bf16(g_true), _bf16(h_true), BF16_BOUND
        assert abs(h_op[0] / HESS - 1) > 1.8e-3    # the rounding is there

    rec, row_leaf = _grow(case, bins, g_true, h_true, quant)
    rec = jax.tree_util.tree_map(np.asarray, rec)
    nodes, leaves, chain, leaf = _nodes(rec, bins)
    assert int(rec.num_leaves) == L
    np.testing.assert_array_equal(leaf, np.asarray(row_leaf))
    # the case that went wrong: a chain of right turns from the root,
    # one of them a split at feature 0's last bin
    assert len(chain) >= 3 and chain[0][0] == 0, chain
    assert (0, B - 2) in chain, chain

    root_h, root_g = h_op.sum(), np.abs(g_op).sum()
    for rows, hess, count, value in nodes + leaves:
        bins_g, bins_h = g_op[rows].sum(), h_op[rows].sum()
        assert count == rows.sum()
        assert abs(hess - bins_h) <= 2e-5 * bins_h + 2e-6 * root_h, \
            (hess, bins_h, count)
        tol_g = 2e-5 * np.abs(g_op[rows]).sum() + 2e-6 * root_g
        assert abs(value + bins_g / bins_h) <= \
            (tol_g + abs(value) * (2e-5 * bins_h + 2e-6 * root_h)) / bins_h, \
            (value, -bins_g / bins_h, count)

    for rows, _, count, value in leaves:
        own_g, own_h = g_true[rows].sum(), h_true[rows].sum()
        assert own_h >= MIN_HESS * (1 - bound) - 2e-6 * root_h, \
            (own_h, count)
        newton = -own_g / own_h
        room = bound * (np.abs(g_true[rows]).sum() / own_h + abs(newton))
        assert abs(value - newton) <= room * 1.01 + 1e-4 * abs(newton) \
            + 2e-5 * root_g / root_h / own_h, (value, newton, count)
