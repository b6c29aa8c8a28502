"""Histogram construction ops (device).

TPU-native replacement for the reference histogram kernels
(ref: src/io/dense_bin.hpp ConstructHistogram, src/treelearner/cuda/
cuda_histogram_constructor.cu:21). Instead of scatter-adds (slow on TPU),
histograms are built as one-hot contractions that XLA maps onto the MXU:
for each feature, ``hist[b] = sum_i [bin_i == b] * (g_i, h_i, m_i)``.

Layout: bins are stored feature-major ``[F, N]`` (col-wise access pattern,
ref: Dataset col-wise path dataset.h:727) and histograms are
``[F, B, 3]`` with channels (sum_grad, sum_hess, count).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.metrics import global_metrics
from .bin_pack import PackedBins, unpack_bins

GRAD, HESS, COUNT = 0, 1, 2
NUM_HIST_CHANNELS = 3


def _kahan_scan(fn, init, xs):
    """Kahan-compensated accumulation of ``fn`` over the scanned chunks:
    the running error term keeps the final sum within ~1 ulp of the
    exact chunk-sum regardless of chunk count — the `deterministic_hist`
    accumulation primitive (sharding/regrouping changes which rows land
    in which chunk; compensation makes the result insensitive to it)."""
    def step(carry, inp):
        acc, comp = carry
        y = fn(inp) - comp
        t = acc + y
        comp = (t - acc) - y
        return (t, comp), None

    (acc, _), _ = lax.scan(step, (init, jnp.zeros_like(init)), xs)
    return acc


def _hist_all_features(bins_fm: jax.Array, gh: jax.Array, max_bins: int,
                       dtype) -> jax.Array:
    """``[F, N] x [N, 3] -> [F, B, 3]`` one-hot contraction, scanning features."""
    bidx = jnp.arange(max_bins, dtype=bins_fm.dtype)

    def one_feature(carry, feat_bins):
        onehot = (feat_bins[:, None] == bidx[None, :]).astype(dtype)  # [N, B]
        # HIGHEST precision: the TPU MXU would otherwise truncate the f32
        # grad/hess operand to bf16 (the one-hot side is exact either way)
        h = jax.lax.dot(onehot.T, gh, precision=jax.lax.Precision.HIGHEST)
        return carry, h  # [B, 3]

    _, hist = lax.scan(one_feature, None, bins_fm)
    return hist


def cpu_backend() -> bool:
    """True when the default jax backend is CPU — the shared sniff for
    backend-dependent implementation choices (XLA twin and Pallas
    interpret mode on CPU, Mosaic kernels on TPU). A backend that fails
    to initialise raises: a broken accelerator must never read as
    "we are on CPU" and quietly select the CPU implementations."""
    return jax.default_backend() == "cpu"


def default_impl() -> str:
    """'pallas' on TPU backends, 'xla' elsewhere (CPU tests, interpret)."""
    return "xla" if cpu_backend() else "pallas"


def resolve_impl(cfg_impl: str) -> str:
    """Config tpu_hist_impl -> concrete impl ('auto' = default_impl())."""
    return default_impl() if cfg_impl in (None, "", "auto") else cfg_impl


@functools.partial(jax.jit, static_argnames=("max_bins", "dtype", "row_chunk",
                                             "impl", "precision",
                                             "deterministic"))
def build_histogram(bins_fm: jax.Array, grad: jax.Array, hess: jax.Array,
                    mask: jax.Array, *, max_bins: int,
                    dtype=jnp.float32, row_chunk: int = 0,
                    impl: str = "xla", precision: str = "highest",
                    deterministic: bool = False) -> jax.Array:
    """Build per-feature (grad, hess, count) histograms for one leaf.

    Args:
      bins_fm: ``[F, N]`` integer bin ids, feature-major (or a
        bit-packed ``bin_pack.PackedBins`` — the pallas path unpacks
        nibbles in-kernel, the XLA path unpacks on the fly and lets the
        fusion keep the HBM read at the packed bytes).
      grad, hess: ``[N]`` float gradients / hessians.
      mask: ``[N]`` float weights in {0, 1} (or bagging weights) selecting
        the rows of the leaf; zero rows contribute nothing.
      max_bins: static B (max bins over features).
      row_chunk: if >0, rows are processed in chunks of this size (bounds the
        transient one-hot buffer to ``row_chunk * B`` per feature).
      deterministic: fixed-size chunking + Kahan-compensated cross-chunk
        accumulation (the `deterministic_hist` knob): the result is
        insensitive to how rows are regrouped by sharding or chunking.

    Returns:
      ``[F, B, 3]`` histogram in `dtype`.
    """
    # trace-time only: counts histogram-pass (re)compilations, never
    # executes per iteration (obs.metrics module docstring)
    global_metrics.note_trace("ops/histogram")
    if impl == "pallas" and not deterministic:
        from .pallas_histogram import hist_pallas
        gh3 = jnp.stack([grad * mask, hess * mask, mask]).astype(jnp.float32)
        return hist_pallas(bins_fm, gh3, max_bins=max_bins,
                           precise=precision).astype(dtype)
    if isinstance(bins_fm, PackedBins):
        bins_fm = unpack_bins(bins_fm).astype(jnp.uint8)

    gh = jnp.stack([grad * mask, hess * mask, mask], axis=-1).astype(dtype)  # [N, 3]
    num_features = bins_fm.shape[0]
    n = gh.shape[0]

    if deterministic:
        # 2048 is the measured sweet spot: small enough that the
        # UNcompensated within-chunk dot error stays below the 1e-4
        # parity target, large enough that the Kahan-compensated scan
        # doesn't dominate runtime (N/2048 steps)
        row_chunk = 2048
    if row_chunk and n > row_chunk:
        pad = (-n) % row_chunk
        gh_p = jnp.pad(gh, ((0, pad), (0, 0)))
        bins_p = jnp.pad(bins_fm, ((0, 0), (0, pad)),
                         constant_values=max_bins)  # pad bin id out of range
        nchunk = (n + pad) // row_chunk
        gh_c = gh_p.reshape(nchunk, row_chunk, NUM_HIST_CHANNELS)
        bins_c = bins_p.reshape(num_features, nchunk, row_chunk)
        bins_c = jnp.swapaxes(bins_c, 0, 1)  # [nchunk, F, C]

        init = jnp.zeros((num_features, max_bins, NUM_HIST_CHANNELS), dtype)
        if deterministic:
            return _kahan_scan(
                lambda inp: _hist_all_features(inp[0], inp[1], max_bins,
                                               dtype),
                init, (bins_c, gh_c))

        def one_chunk(acc, inputs):
            bins_chunk, gh_chunk = inputs
            return acc + _hist_all_features(bins_chunk, gh_chunk, max_bins,
                                            dtype), None

        hist, _ = lax.scan(one_chunk, init, (bins_c, gh_c))
        return hist

    return _hist_all_features(bins_fm, gh, max_bins, dtype)


def build_histogram_sparse(sb, grad: jax.Array, hess: jax.Array,
                           mask: jax.Array, *, num_features: int,
                           max_bins: int, dtype=jnp.float32) -> jax.Array:
    """Single-leaf histogram from COO storage (ref: the sparse row-wise
    MultiValBin ConstructHistogram, multi_val_sparse_bin.hpp:70): one
    O(nnz) segment-sum over explicit entries, then the implicit-zero bin
    of every feature receives (leaf totals - explicit sums). Work scales
    with nnz instead of N*F*B — the scaling axis wide-sparse data needs.
    """
    global_metrics.note_trace("ops/histogram_sparse")
    gh = jnp.stack([grad * mask, hess * mask, mask], axis=-1).astype(dtype)
    flat = sb.coo_feat * max_bins + sb.coo_bin
    hist = jax.ops.segment_sum(gh[sb.coo_row], flat,
                               num_segments=num_features * max_bins)
    hist = hist.reshape(num_features, max_bins, NUM_HIST_CHANNELS)
    totals = jnp.sum(gh, axis=0)                     # [3] leaf totals
    resid = totals[None, :] - jnp.sum(hist, axis=1)  # [F, 3]
    return hist.at[jnp.arange(num_features), sb.zero_bins].add(resid)


def hist_multi_sparse(sb, ghT: jax.Array, row_leaf: jax.Array,
                      leaf_ids: jax.Array, *, num_features: int,
                      max_bins: int, num_slots: int) -> jax.Array:
    """Multi-leaf wave histogram from COO storage: rows route to their
    leaf's slot (or a dropped overflow slot), one segment-sum covers all
    slots' explicit entries, and each slot's implicit-zero mass is
    recovered from its own totals. Returns [S, F, B, 3]."""
    global_metrics.note_trace("ops/histogram_multi_sparse")
    eq = row_leaf[:, None] == leaf_ids[None, :]       # [N, S]
    slot = jnp.where(jnp.any(eq, axis=1),
                     jnp.argmax(eq, axis=1), num_slots)
    f, b, s = num_features, max_bins, num_slots
    rs = slot[sb.coo_row]
    flat = (rs * f + sb.coo_feat) * b + sb.coo_bin
    hist = jax.ops.segment_sum(ghT[sb.coo_row], flat,
                               num_segments=(s + 1) * f * b)
    hist = hist[:s * f * b].reshape(s, f, b, NUM_HIST_CHANNELS)
    slot_tot = jax.ops.segment_sum(ghT, slot, num_segments=s + 1)[:s]
    resid = slot_tot[:, None, :] - jnp.sum(hist, axis=2)  # [S, F, 3]
    return hist.at[:, jnp.arange(f), sb.zero_bins].add(resid)


def node_totals(hist: jax.Array) -> jax.Array:
    """``[3]`` (sum_grad, sum_hess, count) of the node a ``[F, B, 3]``
    histogram was built over, read from the histogram itself: every row
    of the node falls in exactly one bin of feature 0 (dense, packed,
    expanded-bundle and COO histograms alike; a feature-sharded one is a
    global value here). A node's totals are taken from here and never
    from a separate reduction of the gradients, whose rounding would not
    be the bins' (PERF.md section 7.1)."""
    return jnp.sum(hist[0], axis=0)


def subtract_histogram(parent: jax.Array, child: jax.Array) -> jax.Array:
    """Sibling histogram via subtraction (ref: serial_tree_learner.cpp:582,
    FeatureHistogram::Subtract). Hessians/counts clamped at 0 to absorb
    floating-point cancellation."""
    sib = parent - child
    return sib.at[..., HESS:].max(0.0)
