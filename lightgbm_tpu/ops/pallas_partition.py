"""The wave partition as one Pallas pass over the rows (device).

`partition.apply_wave_splits` decides, for every row, whether a wave's
split sends it to its leaf's right child. Written in XLA that pass is
four or five row-sized fusions a wave, each reduction ending one, so a
row-sized vector goes to HBM and back between the steps (PERF.md section
6). Here the same decision is one kernel: it reads each row's
leaf and that row's bins once and writes the row's new leaf once, in
place (`input_output_aliases`).

What a grid step does with its block of R rows, `chunk` rows a turn of
its loop, the per-row values laid out on whole vregs ([chunk / 128,
128] int32, every sublane used; a [1, R] row would fill one sublane of
eight, PERF.md section 7.8):

- **The row's split record.** Each split of the wave is packed, at [W]
  in XLA, into one 32-bit record (`_fields`: the stored row of its
  feature, its threshold, its NaN code and its right child) and the
  records are laid into a table indexed by leaf id ([ceil(L / 128),
  128], zero where a leaf is not split). A row finds its record by a
  lane gather of the table vreg that holds its leaf (one gather a 128
  leaves), whatever the wave's W: a row whose leaf is not split reads 0
  and stays.
- **The row's bin.** The bins block is `[F up to whole 32-row tiles,
  R]` uint8 (read as the program holds them: no padded copy; the rows
  past F are never selected). As 32-bit words, word k holds stored rows
  4k .. 4k + 3, one a byte; a row takes its record's word by a select
  over the block's words, a tile of eight a loop turn, then shifts out
  its byte.
- **The decision.** `bin <= threshold`, flipped where the bin is the
  feature's NaN bin and `default_left` says otherwise (`_records`), and
  the new leaf: the right child where the row leaves, its leaf where it
  stays.

On `PackedBins` (vpb rows a byte, bin_pack's split-section layout) the
grid is (byte blocks, bit-sections): one byte block is read once and
decides vpb blocks of rows, each with its own `row_leaf` block in and
out at section-strided offsets, and the row's bits are shifted out of
its byte in VMEM.

The form a call takes is chosen by static facts only
(`partition.wave_form`): this kernel on accelerator backends for uint8
and packed storage without EFB bundles or categorical splits, whose
records fit 32 bits and whose grid step fits VMEM; the XLA form
elsewhere. The waved grower takes it on every path but rows that GSPMD
partitions, one call a shard under a shard mesh
(`learner._wave_partition`).
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
from .split import MISSING_NAN

# rows a turn of the step's loop decides: 8 [8, 128] vregs of each value
_CHUNK_ROWS = 8192
# row blocks are whole multiples of a vreg's 1,024 rows, at most this
# many: a grid step costs some 0.06 us of its own, so larger blocks gain
# little (PERF.md section 6)
_MAX_BLOCK_ROWS = 65536
# bytes of bins one grid step's block may take (pipelined: twice that)
_BLOCK_BYTES = 4 * 1024 * 1024
# stored rows of a bins tile: uint8 blocks are whole tiles, 4 rows a word
_TILE_ROWS = 32
_VMEM_LIMIT = 32 * 1024 * 1024
# VMEM a grid step's loop may take beside its blocks: some ten [chunk /
# 128, 128] int32 values of 32 KiB, with room to spare
_LOOP_BYTES = 2 * 1024 * 1024


def _bits(n: int) -> int:
    """Bits that hold every value of [0, n]."""
    return max(int(n), 1).bit_length()


def _fields(stored_rows: int, max_bins: int, num_leaves: int):
    """(bits of each field of a split's record, low bit first): the
    stored row of its feature (at least 2 bits, the byte in its word),
    its threshold, its NaN code, its right child (the top field)."""
    return (max(_bits(stored_rows - 1), 2), _bits(max_bins - 1),
            _bits(max_bins), _bits(num_leaves - 1))


def _vmem_bytes(stored_rows: int, r: int) -> int:
    """VMEM a grid step of `r` rows takes: the bins block and the
    row_leaf blocks in and out, each twice (pipelined), and the loop."""
    tile_rows = -(-stored_rows // _TILE_ROWS) * _TILE_ROWS
    return 2 * (tile_rows * r + 2 * 4 * r) + _LOOP_BYTES


def admits(bins_fm, max_bins: int, num_leaves: int) -> bool:
    """Whether this storage's wave can run here: uint8 rows or
    PackedBins, records that fit 32 bits, and a grid step that fits
    `_VMEM_LIMIT` (up to 15,328 stored rows at the least block)."""
    packed = isinstance(bins_fm, PackedBins)
    if packed:
        stored = bins_fm.data
    elif getattr(bins_fm, "dtype", None) == jnp.uint8 and bins_fm.ndim == 2:
        stored = bins_fm
    else:
        return False
    f, cols = stored.shape
    r = _block_rows(f, bins_fm.num_data if packed else cols,
                    cols if packed else None)
    return (sum(_fields(f, max_bins, num_leaves)) <= 32
            and _vmem_bytes(f, r) <= _VMEM_LIMIT)


def _block_rows(stored_rows: int, rows: int, section: int | None) -> int:
    """Rows of a grid step: whole vregs of 1,024, at most
    _MAX_BLOCK_ROWS and `_BLOCK_BYTES` of bins, no more than the rows
    need; on PackedBins a divisor of the section, so that the bit-
    sections' row blocks lie at whole block offsets."""
    tile_rows = -(-stored_rows // _TILE_ROWS) * _TILE_ROWS
    cap = min(_MAX_BLOCK_ROWS, max(_BLOCK_BYTES // tile_rows, 1024))
    if section is None:
        r = 1024
        while r * 2 <= cap and r < rows:
            r *= 2
        return r
    units = section // 1024
    return 1024 * max(d for d in range(1, min(units, cap // 1024) + 1)
                      if units % d == 0)


def _section_block(i, v, nsb: int, last: int):
    """The row_leaf block of grid step (i, v) on PackedBins: block i of
    bit-section v, `i + v * nsb`. Where that lies past the last block,
    the block of the step before it, so that every block's steps run one
    after another (v runs fastest): the pipeline writes an output block
    back whenever the next step's block differs, whether a step wrote it
    or not, and a block seen again later would have been written back
    stale in between. For i past the last block (then nsb > last),
    the last block; else bit-section `top`, the last with rows in i."""
    q, rem = divmod(last, nsb)
    top = jnp.where(i <= rem, q, q - 1)
    return jnp.where(i > last, last, i + jnp.minimum(v, top) * nsb)


def _records(features, thresholds, default_lefts, right_ids, num_bins,
             missing_type, fields):
    """[W] uint32 records of a wave's splits. The XLA form's rule,
    `default_left if bin + 1 == nan_code else bin <= threshold`
    (nan_code: the feature's bin count where its missing type is NaN,
    else 0), is kept as `(bin <= threshold) != (bin + 1 == nan_code')`
    with nan_code' the NaN code only where the NaN bin's side differs
    from what its threshold alone gives, else 0: one compare and one
    exclusive or a row, and no default-left bit."""
    f = features.astype(jnp.int32)
    nb = num_bins[f]
    thr = thresholds.astype(jnp.int32)
    flip = ((missing_type[f] == MISSING_NAN)
            & (default_lefts != (nb - 1 <= thr)))
    vals = (f, thr, jnp.where(flip, nb, 0), right_ids.astype(jnp.int32))
    rec, at = jnp.zeros_like(f, dtype=jnp.uint32), 0
    for val, bits in zip(vals, fields):
        rec = rec | ((val.astype(jnp.uint32) & ((1 << bits) - 1)) << at)
        at += bits
    return rec


def _kernel(table_ref, bins_ref, rl_ref, out_ref, *, fields, vpb: int,
            chunk: int, tables: int, rows: int, nsb: int):
    """One grid step: a block of `row_leaf` in, the same block out, and
    the bins block they lie in (on PackedBins, bit-section `v` of a byte
    block); Pallas pipelines all three. Every `chunk` rows of the block:
    the record, the word, the bin and the new leaf, each as
    [chunk / 128, 128] int32."""
    f_bits, thr_bits, nan_bits, _ = fields
    thr_at, nan_at = f_bits, f_bits + thr_bits
    right_at = nan_at + nan_bits
    bits = 8 // vpb
    v = pl.program_id(1) if vpb > 1 else 0
    r = rl_ref.shape[0]
    shape = (chunk // 128, 128)
    word_ref = bins_ref.bitcast(jnp.int32)       # [tile rows / 4, R]
    tiles = word_ref.shape[0] // 8

    def turn(c, carry):
        at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        leaf = rl_ref[at].reshape(shape)
        lane = leaf & 127

        def lookup(j):
            tab = jnp.broadcast_to(table_ref[pl.ds(j, 1), :], shape)
            return jnp.take_along_axis(tab, lane, axis=1,
                                       mode="promise_in_bounds")

        rec = lax.fori_loop(
            1, tables, lambda j, rec: jnp.where((leaf >> 7) == j,
                                                lookup(j), rec),
            lookup(0), unroll=tables <= 4)
        widx = (rec >> 2) & ((1 << (f_bits - 2)) - 1)

        def pick(g, word):
            # the words of one 32-row tile; the ones past the stored rows
            # are never any row's
            for u in range(8):
                k = 8 * g + u
                word = jnp.where(
                    widx == k, word_ref[pl.ds(k, 1), at].reshape(shape),
                    word)
            return word
        word = lax.fori_loop(0, tiles, pick, jnp.zeros_like(leaf))
        shift = ((rec & 3) << 3) + bits * v
        fb = lax.shift_right_logical(word, shift) & ((1 << bits) - 1)
        thr = (rec >> thr_at) & ((1 << thr_bits) - 1)
        nan = (rec >> nan_at) & ((1 << nan_bits) - 1)
        stay = ((fb <= thr) != (fb + 1 == nan)) | (rec == 0)
        new = jnp.where(stay, leaf, lax.shift_right_logical(rec, right_at))
        out_ref[at] = new.reshape(chunk)
        return carry

    # a block of a bit-section that starts past the last row (small data:
    # vpb sections of a PACK_ALIGN each) has no rows; `_section_block`
    # gave its step the block of the step before, which wrote it
    @pl.when((pl.program_id(0) + v * nsb) * r < rows)
    def _():
        lax.fori_loop(0, r // chunk, turn, 0)


def wave_partition(row_leaf, bins_fm, leaf_ids, right_ids, features,
                   thresholds, default_lefts, valid, num_bins, missing_type,
                   *, num_leaves: int, max_bins: int, interpret: bool):
    """`partition.apply_wave_splits` on uint8 or packed storage without
    EFB or categorical splits, as one Pallas pass: row_leaf [N] int32 in,
    the wave's row_leaf out, bit for bit the XLA form's. The wave's
    records go into a leaf-indexed table at [W] here; the pass itself
    (`_pass`) sees the table's [ceil(L / 128), 128] whatever W, so the
    waves of a tree share one traced kernel."""
    stored = (bins_fm.data if isinstance(bins_fm, PackedBins)
              else bins_fm).shape[0]
    fields = _fields(stored, max_bins, num_leaves)
    tables = -(-num_leaves // 128)
    rec = _records(features, thresholds, default_lefts, right_ids,
                   num_bins, missing_type, fields)
    table = jnp.zeros((tables * 128,), jnp.uint32).at[
        jnp.where(valid, leaf_ids, tables * 128)].set(rec, mode="drop")
    table = lax.bitcast_convert_type(table, jnp.int32).reshape(tables, 128)
    return _pass(row_leaf.astype(jnp.int32), bins_fm, table, fields=fields,
                 interpret=interpret)


@functools.partial(jax.jit, static_argnames=("fields", "interpret"))
def _pass(row_leaf, bins_fm, table, *, fields, interpret: bool):
    """The one pallas_call: the grid, the blocks and the in-place
    output."""
    n = row_leaf.shape[0]
    packed = isinstance(bins_fm, PackedBins)
    data = bins_fm.data if packed else bins_fm
    vpb = bins_fm.vpb if packed else 1
    stored = data.shape[0]
    tile = -(-stored // _TILE_ROWS) * _TILE_ROWS
    r = _block_rows(stored, n, bins_fm.section if packed else None)
    if packed:
        nsb = bins_fm.section // r
        grid = (nsb, vpb)

        def rows_at(i, v):
            return (_section_block(i, v, nsb, pl.cdiv(n, r) - 1),)
    else:
        nsb = 0
        grid = (pl.cdiv(n, r),)

        def rows_at(i):
            return (i,)
    kernel = functools.partial(
        _kernel, fields=fields, vpb=vpb, chunk=math.gcd(_CHUNK_ROWS, r),
        tables=table.shape[0], rows=n, nsb=nsb)
    return pl.pallas_call(
        kernel, grid=grid,
        in_specs=[pl.BlockSpec(table.shape, lambda *g: (0, 0)),
                  pl.BlockSpec((tile, r), lambda i, *v: (0, i)),
                  pl.BlockSpec((r,), rows_at)],
        out_specs=pl.BlockSpec((r,), rows_at),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.int32),
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name="lgbm_partition_wave",
    )(table, data, row_leaf)


def note_pass(form: str, rows: int, bins_fm) -> None:
    """Publish what a traced wave pass took, once a shape, in the list
    ``global_metrics.meta["partition_wave"]`` (always on; trace time
    only): its form, its rows, the stored rows of bins it reads, the
    bytes one pass reads and writes at the least (every stored byte
    once, `row_leaf` read and written), and the kernel's block rows and
    grid steps (None in the XLA form)."""
    from ..obs.metrics import global_metrics
    packed = isinstance(bins_fm, PackedBins)
    stored = (bins_fm.data if packed else bins_fm).shape
    rec = {"form": form, "rows": rows, "stored_rows": stored[0],
           "bytes": stored[0] * stored[1] + 8 * rows,
           "block_rows": None, "grid_steps": None}
    if form == "kernel":
        r = _block_rows(stored[0], rows, bins_fm.section if packed else None)
        rec["block_rows"] = r
        rec["grid_steps"] = (stored[1] // r * bins_fm.vpb if packed
                             else -(-rows // r))
    seen = global_metrics.meta.setdefault("partition_wave", [])
    if rec not in seen:
        seen.append(rec)
