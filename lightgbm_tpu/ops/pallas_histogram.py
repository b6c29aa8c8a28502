"""Pallas TPU histogram kernel.

The performance-critical op (ref: the CUDA shared-memory histogram kernels,
src/treelearner/cuda/cuda_histogram_constructor.cu:21). The XLA one-hot
formulation materializes the [N, B] one-hot in HBM (~B x 4 bytes per
element); this kernel builds one-hot tiles in VMEM only, so HBM traffic
drops to one read of the bin matrix (1 byte/element) plus the gh vectors —
the bandwidth floor.

Layout: bins [F, N] (feature-major), gh [3, N] (grad, hess, count rows,
pre-masked), output hist [F, 3, B].

Grid: (feature_blocks, row_chunks); row chunks accumulate into the same
output block (TPU grids execute sequentially, minor-dim fastest).

Every per-row operand (gh channels, row->leaf ids, the fused kernel's
score/label/weight/mask) enters the kernels LANE-DENSE, as ``[k, N]``
with the rows on the minor axis. Mosaic lays a 2-D HBM operand out in
(sublane, 128-lane) tiles, so an ``[N, 1]`` or ``[N, 3]`` column operand
is padded to 128 lanes — 512 bytes per row instead of 4: at N = 10.5M
each such operand took 5 GB of HBM and the v5e compiler refused the
iteration program at 30.5 GB (asked without a chip, PR 21). The
leaf-selected gh operand is therefore built transposed, ``[128, R]``,
and contracted against the one-hot's row axis (A x B^T on the MXU).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .bin_pack import PackedBins

_PRECISIONS = {
    "default": lax.Precision.DEFAULT,   # 1 bf16 MXU pass, f32 accumulation
    "high": lax.Precision.HIGH,         # 3 passes
    "highest": lax.Precision.HIGHEST,   # 6 passes (f32-faithful)
}

# byte-block width of the packed kernels' grid steps; bin_pack.PACK_ALIGN
# guarantees every packed section is a multiple of this
_PACKED_CHUNK_BYTES = 1024

# A [M, R] x B [128, R] -> [M, 128]: contract the row (lane) axis of both
_CONTRACT_ROWS = (((1,), (1,)), ((), ()))


def resolve_precision(precise) -> lax.Precision:
    """bool (legacy) or config string -> lax.Precision."""
    if isinstance(precise, bool):
        return lax.Precision.HIGHEST if precise else lax.Precision.DEFAULT
    return _PRECISIONS[precise]


def _resolve_interpret(interpret) -> bool:
    """None = auto: interpret mode on CPU (tests exercise the kernels and
    their shard_map mesh wrappers without a chip), Mosaic on TPU."""
    if interpret is not None:
        return interpret
    from .histogram import cpu_backend
    return cpu_backend()


def _hist_kernel(bins_ref, gh_ref, out_ref, *, f_blk: int, max_bins: int,
                 precise: bool):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    gh = gh_ref[...]  # [3, C] f32
    chunk = gh.shape[1]
    prec = resolve_precision(precise)

    # static unroll: dynamic sublane indexing into a uint8 tile is not
    # supported by Mosaic; keep f_blk * chunk * B * 4 bytes under VMEM
    for f in range(f_blk):
        b = bins_ref[f, :].astype(jnp.int32)  # [C]
        onehot = (b[:, None] == lax.broadcasted_iota(
            jnp.int32, (chunk, max_bins), 1)).astype(jnp.float32)
        out_ref[f, :, :] += jax.lax.dot(gh, onehot, precision=prec)


def _leaf_bop(g, h, w, rl, leafsel_ref, int8: bool):
    """The MXU's leaf-block-diagonal gh operand, transposed: [128, R]
    with sublane k = (leaf k//3, channel k%3) and the chunk's R rows on
    lanes — shared by every multi-kernel variant. g/h/w/rl: [1, R]
    rows; leafsel_ref: [128, 1] leaf id of each sublane."""
    r = rl.shape[1]
    csel = lax.broadcasted_iota(jnp.int32, (128, r), 0) % 3
    if int8:
        # compare and select in int32, cast once: Mosaic cannot move the
        # i1 mask of a 32-bit compare onto the int8 operand's tiling
        g, h, w = (x.astype(jnp.int32) for x in (g, h, w))
    gsel = jnp.where(csel == 0, g, jnp.where(csel == 1, h, w))
    bop = jnp.where(leafsel_ref[...] == rl, gsel,
                    jnp.zeros((), gsel.dtype))
    return bop.astype(jnp.int8) if int8 else bop


def _gh_rows(gh):
    """A [3, R] (grad, hess, weight) block as its three [1, R] rows."""
    return gh[0:1], gh[1:2], gh[2:3]


def _multi_kernel(bins_ref, gh_ref, rl_ref, leafsel_ref, out_ref, *,
                  f_blk: int, group: int, max_bins: int, precise: bool):
    """One grid step: f_blk features' transposed one-hots ([group*B, R]
    per dot, built in VMEM) x a shared [128, R] leaf-selected gh operand
    -> accumulate [f_blk*B, 128]."""
    ch = pl.program_id(1)

    @pl.when(ch == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    rl = rl_ref[...]       # [1, R] int32 row -> leaf
    r = rl.shape[1]
    # gh block: [3, R] f32 (grad, hess, weight)
    bop = _leaf_bop(*_gh_rows(gh_ref[...]), rl, leafsel_ref, False)
    prec = resolve_precision(precise)

    rows = group * max_bins
    riota = lax.broadcasted_iota(jnp.int32, (rows, r), 0)
    for q in range(f_blk // group):
        b_eff = jnp.zeros((rows, r), jnp.int32)
        for p in range(group):
            b_eff = jnp.where(
                riota // max_bins == p,
                bins_ref[q * group + p, :][None, :].astype(jnp.int32), b_eff)
        onehot_t = (b_eff == riota % max_bins).astype(jnp.float32)
        out_ref[0, q * rows:(q + 1) * rows, :] += lax.dot_general(
            onehot_t, bop, _CONTRACT_ROWS, precision=prec)


def _row_operand_specs(row_chunk: int):
    """BlockSpecs of the unpacked multi kernels' operands after the bin
    block: gh [3, N], row_leaf [1, N], leafsel [128, 1]."""
    return [
        pl.BlockSpec((3, row_chunk), lambda j, i: (0, i),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, row_chunk), lambda j, i: (0, i),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((128, 1), lambda j, i: (0, 0),
                     memory_space=pltpu.VMEM),
    ]


@functools.partial(jax.jit,
                   static_argnames=("max_bins", "num_slots", "row_chunk",
                                    "precise", "interpret"))
def hist_pallas_multi(bins_fm: jax.Array, ghT: jax.Array, row_leaf: jax.Array,
                      leaf_ids: jax.Array, *, max_bins: int, num_slots: int,
                      row_chunk: int = 2048, precise="highest",
                      interpret=None) -> jax.Array:
    """Histograms of up to `num_slots` leaves in ONE pass over the rows.

    The one-hot (bins) operand is leaf-independent, so packing the MXU's
    128 output columns with (leaf, channel) pairs builds J = 42 leaves'
    histograms for the cost of one (the reference instead loops leaves,
    touching each leaf's rows separately — cuda_histogram_constructor.cu:21
    one kernel per leaf). Rows route to their leaf's columns via a
    compare against row_leaf — the device analog of DataPartition.

    bins_fm: [F, N] uint8/16 (or PackedBins); ghT: [N, 3] f32 pre-masked
    (grad, hess, w); row_leaf: [N] int32; leaf_ids: [num_slots] int32
    (pad with -2). Returns hist [num_slots, F, B, 3] f32.
    """
    if isinstance(bins_fm, PackedBins):
        return _hist_multi_packed_f32(bins_fm, ghT, row_leaf, leaf_ids,
                                      max_bins=max_bins,
                                      num_slots=num_slots, precise=precise,
                                      interpret=interpret)
    num_features, n = bins_fm.shape
    assert num_slots * 3 <= 128, "num_slots capped at 42 by MXU columns"
    group = max(1, 128 // max_bins) if max_bins <= 128 else 1
    # bins tile first dim must be a multiple of 8 (Mosaic) AND of group
    # (the kernel consumes features in groups of `group` per dot)
    f_blk = group * 8 // math.gcd(group, 8)
    pad_f = (-num_features) % f_blk
    if pad_f:
        bins_fm = jnp.pad(bins_fm, ((0, pad_f), (0, 0)),
                          constant_values=0)
    fp = bins_fm.shape[0]
    pad_n = (-n) % row_chunk
    if pad_n:
        bins_fm = jnp.pad(bins_fm, ((0, 0), (0, pad_n)),
                          constant_values=0)
        ghT = jnp.pad(ghT, ((0, pad_n), (0, 0)))  # zero gh: no contribution
        row_leaf = jnp.pad(row_leaf, (0, pad_n), constant_values=-1)
    npad = bins_fm.shape[1]

    fblocks = fp // f_blk
    rows = f_blk * max_bins
    grid = (fblocks, npad // row_chunk)
    out = pl.pallas_call(
        functools.partial(_multi_kernel, f_blk=f_blk, group=group,
                          max_bins=max_bins, precise=precise),
        grid=grid,
        in_specs=[pl.BlockSpec((f_blk, row_chunk), lambda j, i: (j, i),
                               memory_space=pltpu.VMEM)]
        + _row_operand_specs(row_chunk),
        out_specs=pl.BlockSpec((1, rows, 128), lambda j, i: (j, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((fblocks, rows, 128), jnp.float32),
        interpret=_resolve_interpret(interpret),
        name="lgbm_hist_multi",
    )(bins_fm, ghT.T, row_leaf[None, :].astype(jnp.int32),
      _leafsel_col(leaf_ids, num_slots))
    # [fblocks, f_blk*B, 128] -> [F, B, J, 3] -> [J, F, B, 3]
    out = out[:, :, :3 * num_slots]
    out = out.reshape(fp, max_bins, num_slots, 3)
    out = jnp.moveaxis(out, 2, 0)
    return out[:, :num_features]


def _multi_kernel_int8(bins_ref, gh_ref, rl_ref, leafsel_ref, out_ref, *,
                       f_blk: int, group: int, max_bins: int):
    """Integer twin of _multi_kernel: int8 one-hot x int8 leaf-selected
    quantized (grad, hess, weight) -> int32 accumulation. This is the MXU
    shape of the reference's quantized histograms (ref:
    gradient_discretizer.hpp:23 int8 packed gradients, bin.h:351-421
    ConstructHistogramInt* variants) — exact integer arithmetic at twice
    the bf16 MXU rate."""
    ch = pl.program_id(1)

    @pl.when(ch == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    rl = rl_ref[...]       # [1, R] int32 row -> leaf
    r = rl.shape[1]
    # gh block: [3, R] int8 (g_int, h_int, weight)
    bop = _leaf_bop(*_gh_rows(gh_ref[...]), rl, leafsel_ref, True)

    rows = group * max_bins
    riota = lax.broadcasted_iota(jnp.int32, (rows, r), 0)
    for q in range(f_blk // group):
        b_eff = jnp.zeros((rows, r), jnp.int32)
        for p in range(group):
            b_eff = jnp.where(
                riota // max_bins == p,
                bins_ref[q * group + p, :][None, :].astype(jnp.int32), b_eff)
        onehot_t = (b_eff == riota % max_bins).astype(jnp.int8)
        out_ref[0, q * rows:(q + 1) * rows, :] += lax.dot_general(
            onehot_t, bop, _CONTRACT_ROWS,
            preferred_element_type=jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("max_bins", "num_slots", "row_chunk",
                                    "interpret"))
def hist_pallas_multi_int8(bins_fm: jax.Array, ghT_i8: jax.Array,
                           row_leaf: jax.Array, leaf_ids: jax.Array, *,
                           max_bins: int, num_slots: int,
                           row_chunk: int = 2048,
                           interpret=None) -> jax.Array:
    """Quantized multi-leaf histograms: one pass, int32 accumulation.

    ghT_i8: [N, 3] int8 (quantized grad, quantized hess, {0,1} weight),
    pre-masked. Returns [num_slots, F, B, 3] int32 — callers scale by
    (g_scale, h_scale, 1) to recover the f32 statistics. Safe for
    N < 2^31 / (num_grad_quant_bins): |g_int| <= bins/2, so per-bin int32
    sums cannot overflow at any realistic scale.
    """
    if isinstance(bins_fm, PackedBins):
        return _hist_multi_packed_int8(bins_fm, ghT_i8, row_leaf, leaf_ids,
                                       max_bins=max_bins,
                                       num_slots=num_slots,
                                       interpret=interpret)
    num_features, n = bins_fm.shape
    assert num_slots * 3 <= 128, "num_slots capped at 42 by MXU columns"
    group = max(1, 128 // max_bins) if max_bins <= 128 else 1
    f_blk = group * 8 // math.gcd(group, 8)
    pad_f = (-num_features) % f_blk
    if pad_f:
        bins_fm = jnp.pad(bins_fm, ((0, pad_f), (0, 0)), constant_values=0)
    fp = bins_fm.shape[0]
    pad_n = (-n) % row_chunk
    if pad_n:
        bins_fm = jnp.pad(bins_fm, ((0, 0), (0, pad_n)), constant_values=0)
        ghT_i8 = jnp.pad(ghT_i8, ((0, pad_n), (0, 0)))
        row_leaf = jnp.pad(row_leaf, (0, pad_n), constant_values=-1)
    npad = bins_fm.shape[1]

    fblocks = fp // f_blk
    rows = f_blk * max_bins
    grid = (fblocks, npad // row_chunk)
    out = pl.pallas_call(
        functools.partial(_multi_kernel_int8, f_blk=f_blk, group=group,
                          max_bins=max_bins),
        grid=grid,
        in_specs=[pl.BlockSpec((f_blk, row_chunk), lambda j, i: (j, i),
                               memory_space=pltpu.VMEM)]
        + _row_operand_specs(row_chunk),
        out_specs=pl.BlockSpec((1, rows, 128), lambda j, i: (j, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((fblocks, rows, 128), jnp.int32),
        interpret=_resolve_interpret(interpret),
        name="lgbm_hist_multi_int8",
    )(bins_fm, ghT_i8.T, row_leaf[None, :].astype(jnp.int32),
      _leafsel_col(leaf_ids, num_slots))
    out = out[:, :, :3 * num_slots]
    out = out.reshape(fp, max_bins, num_slots, 3)
    out = jnp.moveaxis(out, 2, 0)
    return out[:, :num_features]


# ---------------------------------------------------------------------------
# packed-bin kernels: each grid step reads ONE block of packed bytes and
# consumes every bit-section in it, so the dominant bin read shrinks by
# the pack factor (bin_pack.PackedBins split-section layout: byte j of a
# section-aligned block covers rows j, j+section, ...; the v-th section's
# gh/row_leaf operands are the same [k, N] arrays blocked at
# section-strided offsets — no lane interleave anywhere, just vpb dots
# per feature group)
# ---------------------------------------------------------------------------
def _accum_section_dots(bins_ref, out_ref, bops, *, f_blk: int, group: int,
                        max_bins: int, vpb: int, int8: bool, precise):
    """Accumulate all bit-sections of a packed byte block: one one-hot
    build + dot per (feature-group, section). vpb=1 degenerates to the
    unpacked kernels' loop (shift 0, mask 255)."""
    bits = 8 // vpb
    bmask = (1 << bits) - 1
    rows = group * max_bins
    cb = bops[0].shape[1]
    riota = lax.broadcasted_iota(jnp.int32, (rows, cb), 0)
    prec = None if int8 else resolve_precision(precise)
    for q in range(f_blk // group):
        for v in range(vpb):
            b_eff = jnp.zeros((rows, cb), jnp.int32)
            for p in range(group):
                col = (bins_ref[q * group + p, :].astype(jnp.int32)
                       >> (bits * v)) & bmask
                b_eff = jnp.where(riota // max_bins == p,
                                  col[None, :], b_eff)
            if int8:
                onehot_t = (b_eff == riota % max_bins).astype(jnp.int8)
                out_ref[0, q * rows:(q + 1) * rows, :] += lax.dot_general(
                    onehot_t, bops[v], _CONTRACT_ROWS,
                    preferred_element_type=jnp.int32)
            else:
                onehot_t = (b_eff == riota % max_bins).astype(jnp.float32)
                out_ref[0, q * rows:(q + 1) * rows, :] += lax.dot_general(
                    onehot_t, bops[v], _CONTRACT_ROWS, precision=prec)


def _multi_kernel_packed(bins_ref, *refs, f_blk: int, group: int,
                         max_bins: int, vpb: int, int8: bool, precise):
    """Packed twin of _multi_kernel/_multi_kernel_int8: refs =
    (gh_0..gh_{vpb-1}, rl_0..rl_{vpb-1}, leafsel, out)."""
    out_ref = refs[-1]
    leafsel_ref = refs[-2]
    gh_refs, rl_refs = refs[:vpb], refs[vpb:2 * vpb]
    ch = pl.program_id(1)

    @pl.when(ch == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    bops = [_leaf_bop(*_gh_rows(gh_refs[v][...]), rl_refs[v][...],
                      leafsel_ref, int8) for v in range(vpb)]
    _accum_section_dots(bins_ref, out_ref, bops, f_blk=f_blk, group=group,
                        max_bins=max_bins, vpb=vpb, int8=int8,
                        precise=precise)


def _multi_kernel_fused(bins_ref, *refs, f_blk: int, group: int,
                        max_bins: int, vpb: int, precise, grad_fn,
                        has_weight: bool):
    """Gradient-fused multi kernel: instead of reading a pre-built
    [R, 3] ghT operand, read (score, label[, weight], mask) vectors and
    compute grad/hess with the objective's pointwise function INSIDE the
    kernel (VPU math under the MXU's bandwidth shadow). This removes the
    standalone gradient/bagging element-wise pass — ghT is never
    materialized in HBM — which is the ~0.5 GB/iter term of the cost
    model. Works for packed (vpb>1) and raw uint8 (vpb=1) bins alike."""
    out_ref = refs[-1]
    leafsel_ref = refs[-2]
    ch = pl.program_id(1)

    @pl.when(ch == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # row operands are laid out operand-major: operand k's section v
    # lives at refs[k * vpb + v] (operands: score, label, [weight],
    # mask, rl — matching _packed_multi_call's row_vecs order)
    def op(k, v):
        return refs[k * vpb + v][...]

    iw = int(has_weight)
    bops = []
    for v in range(vpb):
        score, label = op(0, v), op(1, v)
        weight = op(2, v) if has_weight else None
        mask, rl = op(2 + iw, v), op(3 + iw, v)
        g, h = grad_fn(score, label, weight)  # [1, cb] rows
        bops.append(_leaf_bop(g * mask, h * mask, mask, rl, leafsel_ref,
                              False))
    _accum_section_dots(bins_ref, out_ref, bops, f_blk=f_blk, group=group,
                        max_bins=max_bins, vpb=vpb, int8=False,
                        precise=precise)


def _fb_geometry(num_features: int, max_bins: int):
    """(group, f_blk) — the multi kernels' feature-block geometry."""
    group = max(1, 128 // max_bins) if max_bins <= 128 else 1
    f_blk = group * 8 // math.gcd(group, 8)
    return group, f_blk


def _leafsel_col(leaf_ids, num_slots: int):
    """[128, 1] leaf id of each MXU output column: sublane k holds
    leaf_ids[k//3]; the ones beyond 3*num_slots get sentinel -2 (never
    equals a row_leaf entry, which is >= 0 or -1 padding)."""
    k = jnp.arange(128)
    return jnp.where(k < 3 * num_slots,
                     leaf_ids[jnp.minimum(k // 3, num_slots - 1)],
                     -2).astype(jnp.int32)[:, None]


def _packed_multi_call(pb: PackedBins, row_vecs, leaf_ids, kernel, *,
                       max_bins: int, num_slots: int, out_dtype,
                       interpret):
    """Shared pallas_call plumbing of the packed multi kernels.

    row_vecs: list of ([N] or [k, N] array, pad_value) pairs; each
    becomes vpb lane-dense operands blocked at section-strided offsets
    so grid step i sees the rows matching byte block i's bit-sections.
    Returns the histograms [num_slots, F, B, 3].
    """
    num_features = pb.data.shape[0]
    vpb, sec = pb.vpb, pb.section
    group, f_blk = _fb_geometry(num_features, max_bins)
    data = pb.data
    pad_f = (-num_features) % f_blk
    if pad_f:
        data = jnp.pad(data, ((0, pad_f), (0, 0)), constant_values=0)
    fp = data.shape[0]
    cb = min(_PACKED_CHUNK_BYTES, sec)
    assert sec % cb == 0, "bin_pack.PACK_ALIGN must tile the byte chunk"
    nsb = sec // cb
    n_rows = vpb * sec

    in_specs = [pl.BlockSpec((f_blk, cb), lambda j, i: (j, i),
                             memory_space=pltpu.VMEM)]
    operands = [data]
    # operand-major layout (all of operand k's sections consecutively) —
    # the kernels index refs[k * vpb + v]
    for vec, pad_val in row_vecs:
        arr = vec[None, :] if vec.ndim == 1 else vec
        arr = jnp.pad(arr, ((0, 0), (0, n_rows - arr.shape[1])),
                      constant_values=pad_val)
        for v in range(vpb):
            in_specs.append(pl.BlockSpec(
                (arr.shape[0], cb), lambda j, i, v=v: (0, i + v * nsb),
                memory_space=pltpu.VMEM))
            operands.append(arr)
    in_specs.append(pl.BlockSpec((128, 1), lambda j, i: (0, 0),
                                 memory_space=pltpu.VMEM))
    operands.append(_leafsel_col(leaf_ids, num_slots))

    fblocks = fp // f_blk
    rows = f_blk * max_bins
    out = pl.pallas_call(
        functools.partial(kernel, f_blk=f_blk, group=group,
                          max_bins=max_bins, vpb=vpb),
        grid=(fblocks, nsb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, rows, 128), lambda j, i: (j, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((fblocks, rows, 128), out_dtype),
        interpret=_resolve_interpret(interpret),
        name="lgbm_hist_multi_packed",
    )(*operands)
    out = out[:, :, :3 * num_slots]
    out = out.reshape(fp, max_bins, num_slots, 3)
    return jnp.moveaxis(out, 2, 0)[:, :num_features]


@functools.partial(jax.jit, static_argnames=("max_bins", "num_slots",
                                             "interpret", "precise"))
def _hist_multi_packed_f32(pb, ghT, row_leaf, leaf_ids, *, max_bins: int,
                           num_slots: int, precise="highest",
                           interpret=None):
    kern = functools.partial(_multi_kernel_packed, int8=False,
                             precise=precise)
    return _packed_multi_call(
        pb, [(ghT.T, 0.0), (row_leaf.astype(jnp.int32), -1)], leaf_ids,
        kern,
        max_bins=max_bins, num_slots=num_slots, out_dtype=jnp.float32,
        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("max_bins", "num_slots",
                                             "interpret"))
def _hist_multi_packed_int8(pb, ghT_i8, row_leaf, leaf_ids, *,
                            max_bins: int, num_slots: int, interpret=None):
    kern = functools.partial(_multi_kernel_packed, int8=True, precise=None)
    return _packed_multi_call(
        pb, [(ghT_i8.T, 0), (row_leaf.astype(jnp.int32), -1)], leaf_ids,
        kern,
        max_bins=max_bins, num_slots=num_slots, out_dtype=jnp.int32,
        interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("grad_fn", "max_bins", "num_slots",
                                    "precise", "interpret"))
def hist_pallas_multi_fused(bins_fm, score, label, weight, mask, row_leaf,
                            leaf_ids, *, grad_fn, max_bins: int,
                            num_slots: int, precise="highest",
                            interpret=None) -> jax.Array:
    """Multi-leaf histograms with the gradient pass fused in: operands
    are (score, label[, weight], mask) instead of a pre-built ghT, and
    grad_fn (the objective's pointwise gradient) runs inside the kernel.
    Accepts PackedBins or raw [F, N] uint8 bins. Returns [S, F, B, 3]."""
    # the kernel reads bins through the byte-sectioned path (vpb=1 masks
    # with & 255): uint16 ids would alias silently — refuse them
    assert max_bins <= 256, \
        "hist_pallas_multi_fused needs byte-representable bin ids"
    has_weight = weight is not None
    kern0 = functools.partial(_multi_kernel_fused, precise=precise,
                              grad_fn=grad_fn, has_weight=has_weight)
    vecs = [(score.astype(jnp.float32), 0.0),
            (label.astype(jnp.float32), 0.0)]
    if has_weight:
        vecs.append((weight.astype(jnp.float32), 0.0))
    vecs.append((mask.astype(jnp.float32), 0.0))
    vecs.append((row_leaf.astype(jnp.int32), -1))
    if not isinstance(bins_fm, PackedBins):
        # unpacked: wrap the raw matrix as a vpb=1 "packed" layout — the
        # kernel's shift-0/mask-255 section loop is then the identity
        n = bins_fm.shape[1]
        sec = -(-n // _PACKED_CHUNK_BYTES) * _PACKED_CHUNK_BYTES
        bins_fm = PackedBins(jnp.pad(bins_fm, ((0, 0), (0, sec - n))), n, 1)
    return _packed_multi_call(
        bins_fm, vecs, leaf_ids, kern0, max_bins=max_bins,
        num_slots=num_slots, out_dtype=jnp.float32, interpret=interpret)


def _hist_kernel_packed(bins_ref, *refs, f_blk: int, max_bins: int,
                        vpb: int, precise):
    """Packed twin of _hist_kernel (single-leaf): refs =
    (gh3_0..gh3_{vpb-1}, out); gh3 blocks are [3, C] at section-strided
    offsets along the row axis."""
    out_ref = refs[-1]
    gh_refs = refs[:-1]
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    bits = 8 // vpb
    bmask = (1 << bits) - 1
    prec = resolve_precision(precise)
    for f in range(f_blk):
        for v in range(vpb):
            b = (bins_ref[f, :].astype(jnp.int32) >> (bits * v)) & bmask
            chunk = b.shape[0]
            onehot = (b[:, None] == lax.broadcasted_iota(
                jnp.int32, (chunk, max_bins), 1)).astype(jnp.float32)
            out_ref[f, :, :] += jax.lax.dot(gh_refs[v][...], onehot,
                                            precision=prec)


@functools.partial(jax.jit, static_argnames=("max_bins", "f_blk",
                                             "precise", "interpret"))
def _hist_pallas_packed(pb, gh3, *, max_bins: int, f_blk: int = 8,
                        precise="highest", interpret=None) -> jax.Array:
    """Single-leaf histogram over PackedBins: [F, section] bytes +
    gh3 [3, N] -> [F, B, 3]."""
    num_features = pb.data.shape[0]
    vpb, sec, n = pb.vpb, pb.section, pb.num_data
    data = pb.data
    pad_f = (-num_features) % f_blk
    if pad_f:
        data = jnp.pad(data, ((0, pad_f), (0, 0)), constant_values=0)
    fp = data.shape[0]
    cb = min(_PACKED_CHUNK_BYTES, sec)
    nsb = sec // cb
    n_rows = vpb * sec
    gh3p = jnp.pad(gh3, ((0, 0), (0, n_rows - gh3.shape[1])))

    in_specs = [pl.BlockSpec((f_blk, cb), lambda j, i: (j, i),
                             memory_space=pltpu.VMEM)]
    operands = [data]
    for v in range(vpb):
        in_specs.append(pl.BlockSpec((3, cb),
                                     lambda j, i, v=v: (0, i + v * nsb),
                                     memory_space=pltpu.VMEM))
        operands.append(gh3p)

    out = pl.pallas_call(
        functools.partial(_hist_kernel_packed, f_blk=f_blk,
                          max_bins=max_bins, vpb=vpb, precise=precise),
        grid=(fp // f_blk, nsb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((f_blk, 3, max_bins), lambda j, i: (j, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((fp, 3, max_bins), jnp.float32),
        interpret=_resolve_interpret(interpret),
        name="lgbm_hist_packed",
    )(*operands)
    return jnp.swapaxes(out[:num_features], 1, 2)


def _chunked_slot_hist(bins_fm, ghT, row_leaf, hist_of, *, max_bins: int,
                       num_slots: int, acc_dtype,
                       deterministic: bool = False) -> jax.Array:
    """Shared pad/chunk/scan scaffold of the XLA multi-slot builders:
    `hist_of(bins_part, gh_part, leaf_part) -> [F, B, S*3]` runs per
    row chunk and the partials accumulate in `acc_dtype`. Padded rows
    contribute nothing (gh channels zero, leaf sentinel -7 matches no
    slot — invalid slots are -2). Returns [S, F, B, 3].

    deterministic=True (f32 only): fixed 2048-row chunking with
    Kahan-compensated cross-chunk accumulation (the `deterministic_hist`
    knob) — the cross-chunk error no longer grows with the chunk count,
    keeping the result within the 1e-4 parity target regardless of N or
    of how sharding regroups rows."""
    from jax import lax

    from .histogram import _kahan_scan

    s = num_slots
    n = ghT.shape[0]
    f = bins_fm.shape[0]
    # 131072 bounds the [c, S*3] packed operand to ~64MB at S=42;
    # deterministic mode fixes 2048 (see histogram.build_histogram)
    chunk = 2048 if deterministic else 131072
    if n > chunk:
        pad = (-n) % chunk
        ghp = jnp.pad(ghT, ((0, pad), (0, 0)))
        binsp = jnp.pad(bins_fm, ((0, 0), (0, pad)))
        leafp = jnp.pad(row_leaf, (0, pad), constant_values=-7)
        nchunk = (n + pad) // chunk
        ghc = ghp.reshape(nchunk, chunk, ghT.shape[1])
        binsc = jnp.swapaxes(binsp.reshape(f, nchunk, chunk), 0, 1)
        leafc = leafp.reshape(nchunk, chunk)

        init = jnp.zeros((f, max_bins, s * 3), acc_dtype)
        if deterministic:
            hist = _kahan_scan(lambda inp: hist_of(*inp), init,
                               (binsc, ghc, leafc))
        else:
            def one_chunk(acc, inputs):
                b, g, lf = inputs
                return acc + hist_of(b, g, lf), None
            hist, _ = lax.scan(one_chunk, init, (binsc, ghc, leafc))
    else:
        hist = hist_of(bins_fm, ghT, row_leaf)
    hist = hist.reshape(f, max_bins, s, 3)
    return jnp.moveaxis(hist, 2, 0)  # [S, F, B, 3]


def hist_multi_xla(bins_fm, ghT, row_leaf, leaf_ids, *, max_bins: int,
                   num_slots: int, deterministic: bool = False) -> jax.Array:
    """XLA fallback (CPU tests + CPU bench): ALL leaf slots in one
    contraction per feature. The bin one-hot is built once and dotted
    against the per-slot masked channels packed side-by-side — the
    former per-slot loop rebuilt the one-hot `num_slots` times, roughly
    doubling the work and unrolling W separate passes into the HLO."""
    from .histogram import _hist_all_features

    s = num_slots

    def hist_of(bins_part, gh_part, leaf_part):
        # [S, c] row->slot selection; ghT channels are pre-masked
        # (g*w, h*w, w) with w in {0,1}, so multiplying by the selector
        # alone reproduces the old per-slot mask exactly
        sel = (leaf_part[None, :] == leaf_ids[:, None]).astype(jnp.float32)
        ghs = (sel[:, :, None] * gh_part[None, :, :])          # [S, c, 3]
        ghs = jnp.moveaxis(ghs, 0, 1).reshape(-1, s * 3)       # [c, S*3]
        # _hist_all_features is generic over the trailing dim
        return _hist_all_features(bins_part, ghs, max_bins, jnp.float32)

    return _chunked_slot_hist(bins_fm, ghT, row_leaf, hist_of,
                              max_bins=max_bins, num_slots=s,
                              acc_dtype=jnp.float32,
                              deterministic=deterministic)


def hist_multi(bins_fm, ghT, row_leaf, leaf_ids, *, max_bins: int,
               num_slots: int, impl: str = "xla",
               precision: str = "highest",
               deterministic: bool = False) -> jax.Array:
    if impl == "pallas" and not deterministic:
        return hist_pallas_multi(bins_fm, ghT, row_leaf, leaf_ids,
                                 max_bins=max_bins, num_slots=num_slots,
                                 precise=precision)
    # XLA path (CPU tests, deterministic_hist): f32 dots are exact
    # regardless of precision
    if isinstance(bins_fm, PackedBins):
        from .bin_pack import unpack_bins
        bins_fm = unpack_bins(bins_fm).astype(jnp.uint8)
    return hist_multi_xla(bins_fm, ghT, row_leaf, leaf_ids,
                          max_bins=max_bins, num_slots=num_slots,
                          deterministic=deterministic)


def hist_multi_int8_xla(bins_fm, ghT_i8, row_leaf, leaf_ids, *,
                        max_bins: int, num_slots: int) -> jax.Array:
    """XLA twin of the int8 pallas kernel: int8 one-hot x int8 packed
    leaf-channel operand with int32 accumulation — EXACT integer sums,
    so this path is interchangeable with the device kernel (and with
    the mesh's int32 psum) bit-for-bit. Makes use_quantized_grad
    default-capable on every backend, not just where Mosaic runs."""
    if isinstance(bins_fm, PackedBins):
        from .bin_pack import unpack_bins
        bins_fm = unpack_bins(bins_fm).astype(jnp.uint8)
    s = num_slots
    bidx = jnp.arange(max_bins, dtype=jnp.int32)

    def hist_of(bins_part, gh_part, leaf_part):
        sel = (leaf_part[None, :] == leaf_ids[:, None]).astype(jnp.int8)
        ghs = sel[:, :, None] * gh_part[None, :, :]            # [S, c, 3]
        ghs = jnp.moveaxis(ghs, 0, 1).reshape(-1, s * 3)       # [c, S*3]

        def one_feature(carry, feat_bins):
            onehot = (feat_bins[:, None].astype(jnp.int32)
                      == bidx[None, :]).astype(jnp.int8)       # [c, B]
            h = lax.dot_general(onehot, ghs, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.int32)
            return carry, h                                    # [B, S*3]

        _, hist = lax.scan(one_feature, None, bins_part)
        return hist                                            # [F, B, S*3]

    return _chunked_slot_hist(bins_fm, ghT_i8, row_leaf, hist_of,
                              max_bins=max_bins, num_slots=s,
                              acc_dtype=jnp.int32)


def hist_multi_int8(bins_fm, ghT_i8, row_leaf, leaf_ids, *, max_bins: int,
                    num_slots: int, impl: str = "xla") -> jax.Array:
    """Quantized multi-leaf histogram dispatch: the pallas MXU kernel on
    device backends, the exact-integer XLA contraction elsewhere. Both
    return identical int32 histograms (asserted in tests/test_waved.py),
    which is what lets the waved grower run quantized training on any
    backend — ROADMAP item 3's "promote int8 to default-capable"."""
    if impl == "pallas":
        return hist_pallas_multi_int8(bins_fm, ghT_i8, row_leaf, leaf_ids,
                                      max_bins=max_bins,
                                      num_slots=num_slots)
    return hist_multi_int8_xla(bins_fm, ghT_i8, row_leaf, leaf_ids,
                               max_bins=max_bins, num_slots=num_slots)


@functools.partial(jax.jit,
                   static_argnames=("max_bins", "f_blk", "row_chunk",
                                    "precise", "interpret"))
def hist_pallas(bins_fm: jax.Array, gh3: jax.Array, *, max_bins: int,
                f_blk: int = 8, row_chunk: int = 0,
                precise="highest", interpret=None) -> jax.Array:
    """bins_fm [F, N] uint8/uint16 (or PackedBins), gh3 [3, N] f32
    (pre-masked) -> hist [F, B, 3] f32."""
    if isinstance(bins_fm, PackedBins):
        return _hist_pallas_packed(bins_fm, gh3, max_bins=max_bins,
                                   f_blk=f_blk, precise=precise,
                                   interpret=interpret)
    num_features, n = bins_fm.shape
    if row_chunk == 0:
        # keep the f_blk unrolled one-hot buffers under ~8 MB of VMEM
        budget = 8 * 1024 * 1024 // (f_blk * max_bins * 4)
        row_chunk = max(512, min(2048, (budget // 512) * 512))
    # pad N to a multiple of row_chunk (pad bins with max_bins -> one-hot
    # of the padded rows is all-zero, and gh pads with zeros anyway)
    pad_n = (-n) % row_chunk
    if pad_n:
        bins_fm = jnp.pad(bins_fm, ((0, 0), (0, pad_n)),
                          constant_values=max_bins)
        gh3 = jnp.pad(gh3, ((0, 0), (0, pad_n)))
    pad_f = (-num_features) % f_blk
    if pad_f:
        bins_fm = jnp.pad(bins_fm, ((0, pad_f), (0, 0)),
                          constant_values=max_bins)
    fp = bins_fm.shape[0]
    npad = bins_fm.shape[1]

    grid = (fp // f_blk, npad // row_chunk)
    out = pl.pallas_call(
        functools.partial(_hist_kernel, f_blk=f_blk, max_bins=max_bins,
                          precise=precise),
        grid=grid,
        in_specs=[
            pl.BlockSpec((f_blk, row_chunk), lambda j, i: (j, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((3, row_chunk), lambda j, i: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((f_blk, 3, max_bins), lambda j, i: (j, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((fp, 3, max_bins), jnp.float32),
        interpret=_resolve_interpret(interpret),
        name="lgbm_hist",
    )(bins_fm, gh3)
    # [F, 3, B] -> [F, B, 3] to match the XLA path's layout
    return jnp.swapaxes(out[:num_features], 1, 2)
