"""Pallas TPU histogram kernels.

The performance-critical op (ref: the CUDA shared-memory histogram kernels,
src/treelearner/cuda/cuda_histogram_constructor.cu:21). The XLA one-hot
formulation materializes the [N, B] one-hot in HBM (~B x 4 bytes per
element); these kernels build one-hot tiles in VMEM only, so HBM traffic
drops to one read of the bin matrix (1 byte/element) plus the row operands.

Layout: bins [F, N] (feature-major, or PackedBins), output hist
[slots, F, B, 3] (multi-leaf) or [F, B, 3] (single-leaf). Grid:
(feature blocks, row chunks); row chunks accumulate into the same output
block (TPU grids execute sequentially, minor-dim fastest).

The multi-leaf kernels (every pass of the waved grower) share ONE step,
`_multi_step`, under three operand readers (float gh, int8 gh, gradient
computed in the kernel), all through one `pallas_call` (`_multi_slabs`).
A dot is issued only over what contributes to the histogram: statically
over the real features, dynamically over the pass's live rows. What a
step does with its row chunk of R rows:

- The chunk's row operands go into a row stack ([8, R] 32-bit rows: gh
  or score / label / weight / mask, and the leaf ids). A row is live if
  its leaf is one of the pass's slot ids (the root's pass, whose rows
  are all live, is told apart at the call site and skips this). The
  live lanes of the bins (as packed 32-bit words, four features a word)
  and of the stack are then squeezed together by a compress network of
  roll-and-select stages (`_squeeze_lanes`), whose first stage k the
  geometry chooses from the step's shape (`_squeeze_stage`): lane i
  belongs to lane column i mod 2^k, and each column's live lanes go to
  its own front. From stage 0 that is the chunk's front, log2(R) stages
  of which the first seven rotate lanes within every vreg; from stage 7
  each of a vreg's 128 lanes is a column, every move is by whole vregs,
  and the columns end at different heights; in between some of each. A
  prefix count of the dead lanes of each column says how far a live
  lane has to go (`_prefix_count`). A chunk whose tallest column needs
  every sub-tile anyway is left as it is. No byte moves in HBM.
- A loop of ceil(tallest column x columns / k_tile) turns then
  multiplies one sub-tile of `k_tile` lanes a turn
  (`_accum_section_dots`); a lane that holds what the squeeze moved on
  is dead:
- The leaf operand ([128, T]: sublane 3 * slot + channel holds the
  row's grad / hess / weight where the row is in that slot's leaf, the
  sub-tile's T rows on lanes) is built once a sub-tile, in the MXU's
  operand type: int8, or bf16 (one pass by default; the float32 values
  split over two or three bf16 passes for tpu_hist_precision=high /
  highest, since the one-hot side is exact).
- Every REAL feature of the block gets a bin-aligned slab of `bp`
  one-hot rows (max_bins rounded up to the operand's sublane tile: 64
  at 63 bins), row b = bin b. A slab is built as packed 32-bit words,
  four int8 (two bf16) one-hot rows a word: `bin_bits - 32 * word_row`
  is the hit's bit offset or out of range, so a word vreg costs one
  subtract, one unsigned compare, one shift and one select, and a
  bitcast gives the operand. No division, no select over features, no
  cast of a compare mask.
- Dots are tall: [dot_feats * bp, T] x [128, T]^T with 512 one-hot
  rows or more, so a latched [128, 128] tile of the leaf operand serves
  hundreds of one-hot rows (A x B^T on the MXU, int32 or float32 sums).
  The features a block is padded with get no dot, and the dot that
  straddles the end of the real ones covers only those (28 features at
  8 a dot: three dots of 8 and one of 4).
- `_fb_geometry` sizes the step from shapes and the scoped-VMEM limit
  alone: at 28-32 features one feature block and 16384 rows a step,
  at 2000 features as many features a block as the accumulator leaves
  room for. `global_metrics.meta["hist_geometry"]` says what each traced
  kernel took, `meta["hist_live_rows"]` (learner.hist_live_rows, while
  a tracer session is live) what the passes of a tree multiplied.

Every per-row operand (gh channels, row->leaf ids, the fused kernel's
score/label/weight/mask) enters the kernels LANE-DENSE, as ``[k, N]``
with the rows on the minor axis. Mosaic lays a 2-D HBM operand out in
(sublane, 128-lane) tiles, so an ``[N, 1]`` or ``[N, 3]`` column operand
is padded to 128 lanes — 512 bytes per row instead of 4: at N = 10.5M
each such operand took 5 GB of HBM and the v5e compiler refused the
iteration program at 30.5 GB (asked without a chip, PR 21).

PERF.md section 6 (PRs 29, 33 and 36) has the step's vector-operation
counts from the compiler's own output and the chip's readings.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .bin_pack import PackedBins

# MXU passes of a float32 dot (single-leaf kernels); the multi-leaf
# kernels' one-hot is exact in bf16 and they take fewer (_PARTS)
_PRECISIONS = {
    "default": lax.Precision.DEFAULT,   # 1 bf16 MXU pass, f32 accumulation
    "high": lax.Precision.HIGH,         # 3 passes
    "highest": lax.Precision.HIGHEST,   # 6 passes (f32-faithful)
}

# byte-block width of the single-leaf packed kernel's grid steps;
# bin_pack.PACK_ALIGN guarantees every packed section is a multiple of it
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


def _leaf_bop(g, h, w, rl, leafsel_ref, int8: bool, parts: int = 1):
    """The MXU's leaf-block-diagonal gh operand, transposed: [128, T]
    with sublane k = (leaf k//3, channel k%3) and T rows of the chunk on
    lanes, built ONCE a sub-tile of rows and latched for every feature
    of the step. g/h/w/rl: [1, T] rows; leafsel_ref: [128, 1] leaf id of
    each sublane. Returns the operand as a tuple of `parts` arrays in
    the MXU's type: one int8, or the bf16 head of the float32 values
    followed by the bf16 heads of what each rounding left over
    (tpu_hist_precision=high / highest: the one-hot is exact in bf16, so
    only this side needs the extra passes)."""
    r = rl.shape[1]
    csel = lax.broadcasted_iota(jnp.int32, (128, r), 0) % 3
    if int8:
        # compare and select in int32, cast once: Mosaic cannot move the
        # i1 mask of a 32-bit compare onto the int8 operand's tiling
        g, h, w = (x.astype(jnp.int32) for x in (g, h, w))
    gsel = jnp.where(csel == 0, g, jnp.where(csel == 1, h, w))
    bop = jnp.where(leafsel_ref[...] == rl, gsel,
                    jnp.zeros((), gsel.dtype))
    if int8:
        return (bop.astype(jnp.int8),)
    out = []
    for _ in range(parts):
        head = bop.astype(jnp.bfloat16)
        out.append(head)
        bop = bop - head.astype(jnp.float32)
    return tuple(out)


# ---------------------------------------------------------------------------
# the multi-leaf kernels: ONE step (_multi_step, whose dots are
# _accum_section_dots) under three operand readers (pre-built float gh,
# pre-built int8 gh, the gradient computed in the kernel). Raw bins go
# through it as a one-value-a-byte "packed" layout; PackedBins bring vpb
# values a byte (bin_pack split-section layout: byte j of a
# section-aligned block covers rows j, j+section, ...; the v-th section's
# row operands are the same [k, N] arrays blocked at section-strided
# offsets, so nothing is interleaved).
# ---------------------------------------------------------------------------
class HistGeometry(NamedTuple):
    """What one grid step of a multi-leaf kernel holds (_fb_geometry)."""
    bp: int          # one-hot rows a feature: max_bins up to the tile
    f_blk: int       # features a step
    row_chunk: int   # rows a step and bit-section
    dot_feats: int   # features a dot: dot_feats * bp one-hot rows
    k_tile: int      # rows a dot contracts: a sub-tile of the live rows
    root_tile: int   # the same for the root's pass (every row live)
    # first stage of the squeeze network (_squeeze_stage), 0 to 7: the
    # live rows go to the front of 2^stage lane columns, each on its own
    # (0: of the chunk; 7: no stage rotates lanes within a vreg)
    squeeze_stage: int = 0


# the scoped VMEM one step's NAMED buffers may take (_step_vmem_bytes);
# the kernels ask the compiler for twice that, the other half for the
# squeeze network's temporaries, which only the compiler counts
_VMEM_LIMIT = 16 * 1024 * 1024
# one-hot rows a dot streams past each latched [128, 128] weight tile
_DOT_ROWS = 512
_ROW_CHUNKS = (16384, 8192, 4096, 2048)
# rows (lanes) of a squeezed chunk that one turn of the step's loop
# multiplies: a turn costs some 350 cycles of its own, a part-filled last
# sub-tile half of this many rows a chunk (PERF.md section 6, PR 33);
# where the squeeze leaves several lane columns the loop runs to the
# tallest in whole sub-tiles (PR 36)
_K_TILE = 1024
# sublanes of the row stack: a chunk's row operands as 32-bit rows, the
# leaf ids and the squeeze's distances to go: one (8, 128) tile deep
_STACK_ROWS = 8


def _step_vmem_bytes(g: HistGeometry, vpb: int, itemsize: int) -> int:
    """Bytes of VMEM one grid step of geometry `g` holds, by the
    buffers it names: the pipelined blocks (two of each), the row stack
    and the squeezed bins, the accumulator, and for one sub-tile of rows
    (the wider of the root's and the other passes') the bins as 32-bit
    words, the leaf operands (three bf16 passes at the most) and one
    dot's one-hot and result. A vector temporary that is consumed as it
    is made lives in registers and is not counted, nor are the squeeze
    network's (see _VMEM_LIMIT); tests/test_chip_compile.py asks the
    compiler."""
    rows = g.dot_feats * g.bp
    wide = 2 if g.bp > 256 else 1                   # uint16 ids
    tile = max(g.k_tile, g.root_tile)
    bins = 2 * g.f_blk * g.row_chunk * wide
    row_ops = vpb * 5 * 2 * 8 * g.row_chunk * 4     # <= 5 [1, R] rows
    scratch = (_STACK_ROWS * 4 + g.f_blk * wide) * g.row_chunk
    bins32 = g.f_blk * tile * 4 * (1 if vpb == 1 else 2)
    bops = 128 * tile * (1 if itemsize == 1 else 6)
    onehot = rows * tile * itemsize
    acc = (2 * g.f_blk * g.bp + rows) * 128 * 4
    return bins + row_ops + scratch + bins32 + bops + onehot + acc


def squeeze_tiles(share: float, row_chunk: int, k_tile: int,
                  stage: int) -> float:
    """Expected sub-tiles of `k_tile` lanes that the step's loop runs
    over a chunk squeezed from network stage `stage`, when each of the
    chunk's rows is live with probability `share`, one apart from
    another (rows in random order): the chunk's 2^stage lane columns
    hold Binomial(row_chunk / 2^stage, share) live rows each and the
    loop runs to the tallest, k_tile / 2^stage rows of a column a turn:
    the sum over j of P(tallest > j turns' worth). One column (stage 0)
    has nothing ragged: that is ceil(live / k_tile) on average."""
    import numpy as np
    cols = 1 << stage
    depth, per = row_chunk // cols, max(k_tile // cols, 1)
    share = min(max(float(share), 0.0), 1.0)
    if share in (0.0, 1.0):
        return share * (row_chunk // k_tile)
    k = np.arange(depth + 1)
    logfact = np.concatenate([[0.0], np.cumsum(np.log(k[1:]))])
    logp = (logfact[depth] - logfact - logfact[::-1]
            + k * np.log(share) + (depth - k) * np.log1p(-share))
    upto = np.minimum(np.cumsum(np.exp(logp)), 1.0)
    return float(np.sum(1.0 - upto[0:depth:per] ** cols))


# live shares of the twelve wave passes of a 255-leaf tree (a wave's
# smaller children over the rows; the benchmark's generator, three trees
# at 400,000 rows): what `_squeeze_stage` weighs the stages by
_WAVE_SHARES = (0.49, 0.34, 0.26, 0.24, 0.22, 0.16, 0.16, 0.15, 0.12, 0.11,
                0.10, 0.09)


@functools.lru_cache(maxsize=None)
def _squeeze_stage(onehot_rows: int, word_rows: int, row_chunk: int,
                   k_tile: int, itemsize: int) -> int:
    """First stage of a geometry's squeeze network (HistGeometry.
    squeeze_stage), from its shapes alone: the stage that makes a pass
    cheapest by a model of two costs, in cycles of the chip's vector
    unit. A stage under 7 rotates the lanes of the network's two arrays
    (8 + `word_rows` sublane rows up to whole vregs, `row_chunk` lanes):
    800 cycles for 256 vregs, bound by the lane-rotate unit. Every stage
    left out makes the lane columns twice as many and the tallest of
    them higher above their mean (`squeeze_tiles`), and a turn of the
    loop costs 770 cycles and 0.8 for each of the step's real one-hot
    rows and operand bytes (`onehot_rows`, `itemsize`). Both from the
    compiler's schedule of the step for the chip and held against the
    chip's readings (PERF.md section 6, PR 36: a pass read within a
    tenth of the model). Narrow steps with cheap turns start late (6 at
    28 features and 63 bins on int8, and at 15 bins packed); dear turns
    start early (4 on the float operand at 28 features, 1 at 2,000; 0,
    the whole network, at 255 bins on the float operand)."""
    vregs = (8 + _round_up(word_rows, 8)) * row_chunk // 1024
    lane_stage = 800.0 * vregs / 256
    turn = 770.0 + 0.8 * onehot_rows * itemsize

    def cost(stage):
        return (7 - stage) * lane_stage + turn * sum(
            squeeze_tiles(p, row_chunk, k_tile, stage)
            for p in _WAVE_SHARES) / len(_WAVE_SHARES)
    return min(range(8), key=cost)


def _fb_geometry(num_features: int, max_bins: int, vpb: int = 1,
                 itemsize: int = 2, vmem_limit: int = _VMEM_LIMIT, *,
                 section: int | None = None,
                 rows: int | None = None) -> HistGeometry:
    """The multi kernels' step, from shapes and VMEM alone.

    `itemsize` is the MXU operand's (1 int8, 2 bf16): its sublane tile
    (32 / 16 rows) is what a feature's slab is rounded up to. A dot
    takes the fewest features that give it _DOT_ROWS one-hot rows. A
    row chunk of _ROW_CHUNKS has to divide `section` (the bytes a
    feature of PackedBins has) or, for raw bins of `rows` rows that the
    caller pads, to add under an eighth to them; the feature block is
    the largest that keeps the step under `vmem_limit`: at 28-32
    features one block, at 2000 as many as the accumulator leaves room
    for; the root's pass multiplies the largest part of the chunk a turn
    that still fits. Of the chunks that fit, the one with the fewest
    grid steps (squeezes of a chunk's live rows) a row wins."""
    bp = _round_up(max_bins, 32 // itemsize)
    k = 1
    while k * bp < _DOT_ROWS:
        k *= 2
    unit = max(k, 8)    # a bins block is a whole number of 8-row tiles
    whole = _round_up(num_features, unit)
    best = None
    for rc in _ROW_CHUNKS:
        if rc != _ROW_CHUNKS[-1] and (
                (section is not None and section % rc)
                or (rows is not None and _round_up(rows, rc) - rows
                    > rows // 8)):
            continue
        # fewest blocks that fit, split as evenly as the unit allows
        fits = (
            g for blocks in range(1, whole // unit + 1)
            for root in (rc, rc // 2, rc // 4, rc // 8)
            for g in [HistGeometry(
                bp, _round_up(-(-num_features // blocks), unit), rc, k,
                min(_K_TILE, rc), max(root, min(_K_TILE, rc)))]
            if _step_vmem_bytes(g, vpb, itemsize) <= vmem_limit)
        g = next(fits, None)
        if g is None:
            continue
        steps_a_row = -(-num_features // g.f_blk) / rc
        if best is None or steps_a_row < best[0]:
            best = (steps_a_row, g)
    assert best is not None, (num_features, max_bins, vpb, itemsize)
    g = best[1]
    wide = 2 if g.bp > 256 else 1                       # uint16 ids
    return g._replace(squeeze_stage=_squeeze_stage(
        min(num_features, g.f_blk) * g.bp, g.f_blk * wide // 4, g.row_chunk,
        g.k_tile, itemsize))


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _slab_words(b_row, wiota, one: int):
    """One feature's one-hot slab as packed 32-bit words: [bp/pack, T]
    where word row s holds the slab's rows pack*s .. pack*s+pack-1, one
    a byte (int8, pack 4) or a half (bf16, pack 2): a row is hit where
    the bin equals its index. b_row: [1, T] int32, the bins times the
    operand's bits; wiota: [bp/pack, T] int32, 32 * the word row's
    index: their difference is the hit's bit offset in the word, or
    outside [0, 32). One subtract, one unsigned compare, one shift and
    one select a word vreg, for pack one-hot vregs; the 8-bit compare
    the int8 operand would want is not one this chip has."""
    d = b_row - wiota
    hit = lax.bitcast_convert_type(d, jnp.uint32) < 32
    return jnp.where(hit, jnp.int32(one) << d, 0)


def _accum_section_dots(btile, out_ref, bop, *, geom: HistGeometry,
                        vpb: int, v: int, int8: bool, tail):
    """The dots every multi-leaf kernel issues, on one sub-tile of a row
    chunk: for each `dot_feats` REAL features of the block, build the
    features' bin-aligned one-hot slabs ([dot_feats * bp, T], in the
    MXU's operand type) from bit-section `v` of `btile` ([f_blk, T]
    int32) and contract them with the section's leaf operand `bop`.
    vpb=1 is the unpacked layout (no shift, no mask: uint16 ids pass
    whole). `tail` = (feature blocks, real features of the last one,
    whether this step's block is the last): no dot is issued past the real features and the one that straddles
    their end covers the real ones only (28 features at 8 a dot: three
    dots of 8 and one of 4), statically with one block, by the block's
    grid index with several; a padded feature's slab keeps its zero
    initialisation."""
    bits = 8 // vpb
    pack, one, optype = ((4, 1, jnp.int8) if int8
                         else (2, 0x3F80, jnp.bfloat16))  # bf16(1.0)
    acc_t = jnp.int32 if int8 else jnp.float32
    wiota = 32 * lax.broadcasted_iota(
        jnp.int32, (geom.bp // pack, btile.shape[1]), 0)
    obits = (32 // pack).bit_length() - 1          # log2 of the operand's bits
    bsec = btile if vpb == 1 else (btile >> (bits * v)) & ((1 << bits) - 1)
    bsec = bsec << obits

    def dot(f0, nf):
        words = jnp.concatenate(
            [_slab_words(bsec[f:f + 1], wiota, one)
             for f in range(f0, f0 + nf)], axis=0)
        onehot_t = pltpu.bitcast(words, optype)       # [nf * bp, T]
        part = None
        for b in reversed(bop):                       # small parts first
            d = lax.dot_general(onehot_t, b, _CONTRACT_ROWS,
                                preferred_element_type=acc_t)
            part = d if part is None else part + d
        out_ref[0, f0 * geom.bp:(f0 + nf) * geom.bp, :] += part

    fblocks, last_feats, in_last = tail
    for f0 in range(0, geom.f_blk, geom.dot_feats):
        nf = min(max(last_feats - f0, 0), geom.dot_feats)
        if nf < geom.dot_feats and fblocks > 1:
            # cut in the last block only
            pl.when(jnp.logical_not(in_last))(
                functools.partial(dot, f0, geom.dot_feats))
            if nf:
                pl.when(in_last)(functools.partial(dot, f0, nf))
        elif nf:
            dot(f0, nf)


def _prefix_count(x, first_stage: int):
    """Inclusive prefix sums of a [1, R] int32 row within its lane
    columns (lane i's column: i mod c, c = 2^first_stage), and the
    columns' totals as a [1, 128] row (lane l: column l mod c's). The
    row is folded to [8, R/8], an eighth of the lanes a sublane, so that
    a roll-and-add stage works on full vregs: stages along the lanes
    from the columns' stride up (a stride of 128 lanes or more moves
    whole vregs), then three along the sublanes for the eighths'
    offsets, on the eighths' totals spread over their columns' lanes."""
    c = 1 << first_stage
    w = x.shape[1] // 8
    x = jnp.concatenate([x[:, j * w:(j + 1) * w] for j in range(8)], axis=0)
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    for k in range(first_stage, w.bit_length() - 1):
        x = x + jnp.where(lane >= 1 << k, pltpu.roll(x, 1 << k, 1), 0)
    # an eighth's column totals are in its last c lanes: to every lane
    # of the column, by rolls that keep a lane's column
    total = jnp.where(lane[:, :128] >= 128 - c, x[:, w - 128:], 0)
    for k in range(first_stage, 7):
        total = total + pltpu.roll(total, 1 << k, 1)
    sub = lax.broadcasted_iota(jnp.int32, total.shape, 0)
    upto = total
    for k in range(3):
        upto = upto + jnp.where(sub >= 1 << k, pltpu.roll(upto, 1 << k, 0), 0)
    x = x + jnp.concatenate([upto - total] * (w // 128), axis=1)
    return (jnp.concatenate([x[j:j + 1] for j in range(8)], axis=1),
            upto[7:8])


def _squeeze_lanes(words_ref, stack_ref, togo_row: int, first_stage: int):
    """Squeeze the live lanes of a row chunk to the front of their lane
    columns (lane i's column: i mod 2^first_stage), in order and in
    place.

    Row `togo_row` of `stack_ref` ([8, R] int32, the chunk's row
    operands) holds how far down each lane has to go: for a live lane
    2^first_stage times the dead lanes before it in its column, for a
    dead lane 0; `words_ref` ([k, R] int32, the chunk's bins as packed
    words) rides along. A compress network of stages from `first_stage`
    to log2(R) - 1, lowest bit first: at stage k lane i takes lane
    i + 2^k's values if that lane has exactly 2^k left to go modulo
    2^(k+1), and what it takes has 2^k less to go (every distance is a
    multiple of 2^first_stage, so no earlier stage would move anything).
    Live lanes never meet (a later one has at least as far to go), and
    what a lane leaves behind when it moves on keeps the bit it moved
    by, a lower bit than any later stage's, so the stale copy is never
    taken again: the distances need no second array and no clearing.

    A stage reads and writes the two scratch arrays in place: a lane's
    source lies behind it, so what a stage has yet to read it has not
    written, and the compiler keeps a few vregs in flight. (As two
    whole-chunk values the 256 vregs were spilled and filled at every
    stage and the one vector-store slot bound the network: PERF.md
    section 6, PR 36.) From stage 7 on a source is whole vregs further
    on: a plain load, and the last 2^k lanes have no source and are left
    alone. Stages 0 to 6 rotate the lanes of every vreg, which is what
    they cost on the chip beside the others; the rotation wraps onto the
    chunk's first 2^k lanes, which have less than 2^k to go. The
    distance is read on all eight sublanes at once, so that the compare
    is the selects' mask as it stands. Afterwards a column's live lanes
    are its first ones and have nothing left to go; behind them lie dead
    lanes and stale copies, which still have: the step's loop takes a
    lane with a distance left for dead."""
    rows, r = stack_ref.shape
    sub = lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    for k in range(first_stage, r.bit_length() - 1):
        s = 1 << k
        n = r - s if s >= 128 else r                  # lanes with a source

        def source(ref, g0=0, g1=None):
            """Lanes s .. s + n of `ref`'s rows g0:g1."""
            if s >= 128:
                return ref[g0:g1, s:]
            return pltpu.roll(ref[g0:g1, :], r - s, 1)

        stack_in = source(stack_ref)
        to_go = jnp.broadcast_to(stack_in[togo_row:togo_row + 1], (rows, n))
        take = (to_go & (2 * s - 1)) == s
        stack_ref[:, :n] = jnp.where(
            take, stack_in - jnp.where(sub == togo_row, s, 0),
            stack_ref[:, :n])
        for g in range(0, words_ref.shape[0], rows):
            g1 = min(g + rows, words_ref.shape[0])
            words_ref[g:g1, :n] = jnp.where(
                take[:g1 - g], source(words_ref, g, g1), words_ref[g:g1, :n])


def _as_words(x):
    """A row operand block as the row stack holds it: 32-bit integers
    (int8 gradients widened, float32 bits kept)."""
    if x.dtype == jnp.float32:
        return pltpu.bitcast(x, jnp.int32)
    return x.astype(jnp.int32)


def _multi_step(bins_ref, refs, *, geom: HistGeometry, vpb: int,
                int8: bool, parts: int, tail, squeeze: bool, gh_of):
    """One grid step of every multi-leaf kernel: a dot is issued only
    over what contributes to the histogram, statically over the real
    features (`tail`, _accum_section_dots) and dynamically over the
    chunk's live rows.

    refs = (row operands, operand-major: operand k's bit-section v at
    refs[k * vpb + v], the leaf ids last; leafsel [128, 1]; slot ids
    [48, 1]; out; scratch: row stack [8, R], squeezed bins words).
    `gh_of(rows)` (_gh_reader) turns the row operands' [1, T] rows (the
    leaf ids left out) into the (grad, hess, weight) rows of the leaf
    operand.

    For each bit-section: the row operands go into the row stack as
    32-bit rows. Where `squeeze` (every pass but the root's, whose rows
    are all live), a row is live if its leaf is one of the pass's slot
    ids, and a lane's column is its index modulo 2^`geom.squeeze_stage`
    (stage 0: the chunk is one column; 7: a vreg's 128 lanes are one
    each). The dead lanes' prefix count in each column says how far
    down its column each live lane has to go and how high the tallest
    column stands (_prefix_count); the loop needs
    ceil(tallest x columns / k_tile) turns, and unless those are all the
    chunk has, the live lanes of the bins (as packed 32-bit words) and
    of the stack are squeezed to the front of their columns, in place
    (_squeeze_lanes). Then the loop: a turn builds the leaf operand of
    one sub-tile of lanes, a lane dead where the squeeze left a stale
    copy, and issues its dots."""
    out_ref, stack_ref, words_ref = refs[-3:]
    leafsel_ref, slots_ref = refs[-5:-3]
    row_refs = refs[:-5]
    n_ops = len(row_refs) // vpb
    cb = geom.row_chunk
    t = geom.k_tile if squeeze else geom.root_tile
    # read here, not in the loop's body, which interpret mode traces
    # apart from the grid
    tail = (*tail, pl.program_id(0) == tail[0] - 1)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    for v in range(vpb):
        ops = [row_refs[k * vpb + v] for k in range(n_ops)]
        heights = [o.shape[0] for o in ops]
        leaf_row = sum(heights) - 1
        assert leaf_row + 1 < _STACK_ROWS, heights
        at = 0
        for o, hgt in zip(ops, heights):
            stack_ref[at:at + hgt, :] = _as_words(o[...])
            at += hgt
        bins_src, n_tiles = bins_ref, cb // t
        if squeeze:
            fs = geom.squeeze_stage
            togo_row = leaf_row + 1
            rl = ops[-1][...]                                  # [1, R]
            hit = slots_ref[0:8, :] == rl                      # [8, R]
            for j in range(8, slots_ref.shape[0], 8):
                hit |= slots_ref[j:j + 8, :] == rl
            live = jnp.max(hit.astype(jnp.int32), axis=0, keepdims=True)
            dead_before, dead = _prefix_count(1 - live, fs)
            stack_ref[togo_row:togo_row + 1, :] = jnp.where(
                live != 0, dead_before << fs, 0)
            # the tallest column's live lanes
            tallest = (cb >> fs) - jnp.min(dead, axis=1, keepdims=True)[0, 0]
            n_tiles = ((tallest << fs) + (t - 1)) // t
            sparse = n_tiles < cb // t
            # the bins as 32-bit words in the scratch the loop reads
            words_ref[...] = bins_ref.bitcast(jnp.int32)[...]
            pl.when(sparse)(functools.partial(
                _squeeze_lanes, words_ref, stack_ref, togo_row, fs))
            bins_src = words_ref.bitcast(bins_ref.dtype)

        def tile(i, carry):
            at = pl.ds(pl.multiple_of(i * t, t), t)
            stack = stack_ref[:, at]
            rows, r0 = [], 0
            for o, hgt in zip(ops, heights):
                x = stack[r0:r0 + hgt]
                rows.append(pltpu.bitcast(x, jnp.float32)
                            if o.dtype == jnp.float32 else x)
                r0 += hgt
            leaf = rows[-1]
            if squeeze:
                # what the squeeze moved on left a copy behind with the
                # bit it moved by; what arrived, and a dead lane, have
                # nothing left to go
                leaf = jnp.where(
                    (stack[togo_row:togo_row + 1] != 0) & sparse, -1, leaf)
            bop = _leaf_bop(*gh_of(rows[:-1]), leaf, leafsel_ref, int8, parts)
            _accum_section_dots(bins_src[:, at].astype(jnp.int32), out_ref,
                                bop, geom=geom, vpb=vpb, v=v, int8=int8,
                                tail=tail)
            return carry

        lax.fori_loop(0, n_tiles, tile, 0)


def _gh_reader(grad_fn, has_weight: bool):
    """The `gh_of` of a kernel's operand reader. Without `grad_fn` the
    row operands are (gh [3, R], leaf ids): a pre-built gh operand,
    float32 or int8 (the MXU shape of the reference's quantized
    histograms, ref: gradient_discretizer.hpp:23 int8 packed gradients,
    bin.h:351-421 ConstructHistogramInt*: exact integer arithmetic at
    twice the bf16 rate). With it they are (score, label, [weight], mask,
    leaf ids) and grad/hess come from the objective's pointwise function
    INSIDE the kernel, once a sub-tile of live rows (VPU math under the
    MXU's shadow): the standalone gradient/bagging element-wise pass
    goes and ghT is never materialized in HBM, for packed (vpb>1) and
    raw uint8 (vpb=1) bins alike."""
    if grad_fn is None:
        return lambda rows: (rows[0][0:1], rows[0][1:2], rows[0][2:3])

    def gh_of(rows):
        score, label = rows[0], rows[1]
        weight = rows[2] if has_weight else None
        mask = rows[2 + int(has_weight)]
        g, h = grad_fn(score, label, weight)          # [1, T] rows
        return g * mask, h * mask, mask
    return gh_of


def _leafsel_col(leaf_ids, num_slots: int):
    """[128, 1] leaf id of each MXU output column: sublane k holds
    leaf_ids[k//3]; the ones beyond 3*num_slots get sentinel -2 (never
    equals a row_leaf entry, which is >= 0 or -1 padding)."""
    k = jnp.arange(128)
    return jnp.where(k < 3 * num_slots,
                     leaf_ids[jnp.minimum(k // 3, num_slots - 1)],
                     -2).astype(jnp.int32)[:, None]


def _slots_col(leaf_ids, num_slots: int):
    """[num_slots up to whole sublane tiles, 1] slot ids, -2 beyond
    them: what a row's leaf is compared with to find the live rows."""
    pad = (-num_slots) % 8
    return jnp.pad(leaf_ids.astype(jnp.int32), (0, pad),
                   constant_values=-2)[:, None]


# bf16 passes over the leaf operand for each tpu_hist_precision: the
# one-hot side is exact, so XLA's 3 and 6 passes come down to 2 and 3
_PARTS = {lax.Precision.DEFAULT: 1, lax.Precision.HIGH: 2,
          lax.Precision.HIGHEST: 3}


# slots of a pass: the MXU's 128 output columns hold 42 x (grad, hess,
# weight). Every pass runs the kernel at all 42, its slot ids padded with
# -2, so that the passes of a tree share one traced and compiled kernel
# (and the root's one more) whatever their slot counts
_MAX_SLOTS = 128 // 3


def _packed_multi_call(bins_fm, row_vecs, leaf_ids, *, max_bins: int,
                       num_slots: int, **kw):
    """Histograms [num_slots, F, B, 3] of the multi-leaf kernels (int32
    for int8 operands, else float32): `_multi_slabs` less the padding.
    row_vecs: list of ([N] or [N, k] array, pad_value) pairs."""
    assert num_slots <= _MAX_SLOTS, "num_slots capped at 42 by MXU columns"
    ids = jnp.pad(leaf_ids.astype(jnp.int32), (0, _MAX_SLOTS - num_slots),
                  constant_values=-2)
    out = _multi_slabs(bins_fm, tuple(v for v, _ in row_vecs), ids,
                       pads=tuple(p for _, p in row_vecs),
                       max_bins=max_bins, **kw)
    num_features = bins_fm.shape[0]
    # [F', bp, 128] -> [F, B, J, 3] -> [J, F, B, 3]: padded features,
    # a slab's rows at max_bins and beyond (never hit) and the columns
    # past the last slot go
    out = out[:num_features, :max_bins, :3 * num_slots]
    out = out.reshape(num_features, max_bins, num_slots, 3)
    return jnp.moveaxis(out, 2, 0)


@functools.partial(jax.jit, static_argnames=(
    "pads", "max_bins", "int8", "precise", "interpret", "all_live", "name",
    "grad_fn", "has_weight"))
def _multi_slabs(bins_fm, vecs, leaf_ids, *, pads, max_bins: int,
                 int8: bool, precise=None, interpret=None,
                 all_live: bool = False, name: str, grad_fn=None,
                 has_weight: bool = False):
    """The one pallas_call of the multi-leaf kernels, jitted on nothing
    that differs between the passes of a tree but `all_live`.

    bins_fm: PackedBins, or raw [F, N] uint8/uint16 bins (padded here
    to whole row chunks: bin 0 under a leaf id of -1). vecs: the row
    operands, [N] or [N, k] arrays, the leaf ids last, and `pads` their
    pad values; each becomes vpb lane-dense operands ([1, N] or [k, N])
    blocked at section-strided offsets, so that grid step i sees the
    rows of byte block i's bit-sections. leaf_ids: [_MAX_SLOTS] slot
    ids, -2 where a slot is unused. `grad_fn` / `has_weight`: the
    gradient-fused reader (_gh_reader). Returns every feature's slab,
    [F padded to whole blocks, bp, 128]: row b of a slab is bin b,
    column 3 * slot + channel.
    """
    itemsize = 1 if int8 else 2
    num_features, n = bins_fm.shape
    if isinstance(bins_fm, PackedBins):
        data, vpb, sec = bins_fm.data, bins_fm.vpb, bins_fm.section
        geom = _fb_geometry(num_features, max_bins, vpb, itemsize,
                            section=sec)
    else:
        vpb = 1
        geom = _fb_geometry(num_features, max_bins, 1, itemsize, rows=n)
        sec = _round_up(n, geom.row_chunk)
        data = jnp.pad(bins_fm, ((0, 0), (0, sec - n)))
    f_blk, cb = geom.f_blk, geom.row_chunk
    pad_f = (-num_features) % f_blk
    if pad_f:
        data = jnp.pad(data, ((0, pad_f), (0, 0)), constant_values=0)
    fp = data.shape[0]
    nsb = sec // cb
    n_rows = vpb * sec

    in_specs = [pl.BlockSpec((f_blk, cb), lambda j, i: (j, i),
                             memory_space=pltpu.VMEM)]
    operands = [data]
    # operand-major layout (all of operand k's sections consecutively) —
    # the step indexes refs[k * vpb + v]
    for vec, pad_val in zip(vecs, pads):
        # padded as the caller holds it ([N] or [N, k]), then turned
        # lane-dense: the pad of a 1-D vector reads as [1, N] for free,
        # where padding its [1, N] view costs a copy of the vector
        pad = ((0, n_rows - vec.shape[0]),) + ((0, 0),) * (vec.ndim - 1)
        arr = jnp.pad(vec, pad, constant_values=pad_val)
        arr = arr[None, :] if vec.ndim == 1 else arr.T
        for v in range(vpb):
            in_specs.append(pl.BlockSpec(
                (arr.shape[0], cb), lambda j, i, v=v: (0, i + v * nsb),
                memory_space=pltpu.VMEM))
            operands.append(arr)
    for col in (_leafsel_col(leaf_ids, _MAX_SLOTS),
                _slots_col(leaf_ids, _MAX_SLOTS)):
        in_specs.append(pl.BlockSpec(col.shape, lambda j, i: (0, 0),
                                     memory_space=pltpu.VMEM))
        operands.append(col)

    fblocks = fp // f_blk
    rows = f_blk * geom.bp
    grid = (fblocks, nsb)
    tail = (fblocks, num_features - (fblocks - 1) * f_blk)
    _note_geometry(name, geom, vpb=vpb, grid=grid, int8=int8,
                   num_features=num_features, max_bins=max_bins,
                   rows=n_rows, tail=tail)
    parts = 1 if int8 else _PARTS[resolve_precision(precise)]

    def kernel(bins_ref, *refs):
        _multi_step(bins_ref, refs, geom=geom, vpb=vpb, parts=parts,
                    int8=int8, tail=tail, squeeze=not all_live,
                    gh_of=_gh_reader(grad_fn, has_weight))

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, rows, 128), lambda j, i: (j, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(
            (fblocks, rows, 128), jnp.int32 if int8 else jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((_STACK_ROWS, cb), jnp.int32),
            pltpu.VMEM((f_blk * data.dtype.itemsize // 4, cb), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=2 * _VMEM_LIMIT),
        interpret=_resolve_interpret(interpret),
        name=name,
    )(*operands)
    return out.reshape(fp, geom.bp, 128)


def _note_geometry(name, geom, *, vpb, grid, int8, num_features, max_bins,
                   rows, tail):
    """Publish a kernel's geometry as it is traced: in the list
    ``global_metrics.meta["hist_geometry"]`` beside ``hist_traffic``,
    and as the args of a ``hist`` program span (``lgbm/hist`` in a
    profiler session). It says whether the large step engaged at a
    shape, or fell back for VMEM or for a section no large chunk
    divides (`rows` are the padded rows a pass covers, `section` those of
    one bit-section: `pack_factor` sections of `section / row_chunk`
    chunks each); `dots_per_step` counts the dots a sub-tile of the last
    feature block issues and `tail_features` the features of its last
    dot (those of a whole dot where nothing is cut)."""
    from ..obs.metrics import global_metrics
    from ..obs.trace import global_tracer
    last_feats = tail[1]
    rec = {"kernel": name, "features": num_features, "max_bins": max_bins,
           "rows": rows, "section": rows // vpb, "bp": geom.bp,
           "features_per_step": geom.f_blk,
           "features_per_dot": geom.dot_feats, "row_chunk": geom.row_chunk,
           "k_tile": geom.k_tile, "root_tile": geom.root_tile,
           "squeeze_stage": geom.squeeze_stage,
           "dots_per_step": -(-last_feats // geom.dot_feats),
           "tail_features": (last_feats - 1) % geom.dot_feats + 1,
           "grid_steps": grid[0] * grid[1], "pack_factor": vpb,
           "operand": "int8" if int8 else "bfloat16"}
    seen = global_metrics.meta.setdefault("hist_geometry", [])
    if rec not in seen:
        seen.append(rec)
    with global_tracer.span("hist", args=rec):
        pass


@functools.partial(jax.jit,
                   static_argnames=("max_bins", "num_slots", "precise",
                                    "interpret", "all_live"))
def hist_pallas_multi(bins_fm: jax.Array, ghT: jax.Array, row_leaf: jax.Array,
                      leaf_ids: jax.Array, *, max_bins: int, num_slots: int,
                      precise="highest", interpret=None,
                      all_live: bool = False) -> jax.Array:
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
    packed = isinstance(bins_fm, PackedBins)
    return _packed_multi_call(
        bins_fm, [(ghT, 0.0), (row_leaf.astype(jnp.int32), -1)], leaf_ids,
        max_bins=max_bins, num_slots=num_slots, int8=False,
        precise=precise, interpret=interpret, all_live=all_live,
        name="lgbm_hist_multi_packed" if packed else "lgbm_hist_multi")


@functools.partial(jax.jit,
                   static_argnames=("max_bins", "num_slots", "interpret",
                                    "all_live"))
def hist_pallas_multi_int8(bins_fm: jax.Array, ghT_i8: jax.Array,
                           row_leaf: jax.Array, leaf_ids: jax.Array, *,
                           max_bins: int, num_slots: int, interpret=None,
                           all_live: bool = False) -> jax.Array:
    """Quantized multi-leaf histograms: one pass, int32 accumulation.

    ghT_i8: [N, 3] int8 (quantized grad, quantized hess, {0,1} weight),
    pre-masked. Returns [num_slots, F, B, 3] int32 — callers scale by
    (g_scale, h_scale, 1) to recover the f32 statistics. Safe for
    N < 2^31 / (num_grad_quant_bins): |g_int| <= bins/2, so per-bin int32
    sums cannot overflow at any realistic scale.
    """
    packed = isinstance(bins_fm, PackedBins)
    return _packed_multi_call(
        bins_fm, [(ghT_i8, 0), (row_leaf.astype(jnp.int32), -1)], leaf_ids,
        max_bins=max_bins, num_slots=num_slots, int8=True,
        interpret=interpret, all_live=all_live,
        name="lgbm_hist_multi_packed" if packed else "lgbm_hist_multi_int8")


@functools.partial(jax.jit,
                   static_argnames=("grad_fn", "max_bins", "num_slots",
                                    "precise", "interpret", "all_live"))
def hist_pallas_multi_fused(bins_fm, score, label, weight, mask, row_leaf,
                            leaf_ids, *, grad_fn, max_bins: int,
                            num_slots: int, precise="highest",
                            interpret=None,
                            all_live: bool = False) -> jax.Array:
    """Multi-leaf histograms with the gradient pass fused in: operands
    are (score, label[, weight], mask) instead of a pre-built ghT, and
    grad_fn (the objective's pointwise gradient) runs inside the kernel.
    Accepts PackedBins or raw [F, N] bins. Returns [S, F, B, 3]."""
    has_weight = weight is not None
    vecs = [(score.astype(jnp.float32), 0.0),
            (label.astype(jnp.float32), 0.0)]
    if has_weight:
        vecs.append((weight.astype(jnp.float32), 0.0))
    vecs.append((mask.astype(jnp.float32), 0.0))
    vecs.append((row_leaf.astype(jnp.int32), -1))
    return _packed_multi_call(
        bins_fm, vecs, leaf_ids, max_bins=max_bins, num_slots=num_slots,
        int8=False, precise=precise, interpret=interpret, all_live=all_live,
        name="lgbm_hist_multi_packed", grad_fn=grad_fn,
        has_weight=has_weight)


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
               deterministic: bool = False,
               all_live: bool = False) -> jax.Array:
    """`all_live`: the caller knows every row to be in a slot's leaf (the
    root's pass); the kernel's step then looks for no live rows."""
    if impl == "pallas" and not deterministic:
        return hist_pallas_multi(bins_fm, ghT, row_leaf, leaf_ids,
                                 max_bins=max_bins, num_slots=num_slots,
                                 precise=precision, all_live=all_live)
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
                    num_slots: int, impl: str = "xla",
                    all_live: bool = False) -> jax.Array:
    """Quantized multi-leaf histogram dispatch: the pallas MXU kernel on
    device backends, the exact-integer XLA contraction elsewhere. Both
    return identical int32 histograms (asserted in tests/test_waved.py),
    which is what lets the waved grower run quantized training on any
    backend — ROADMAP item 3's "promote int8 to default-capable"."""
    if impl == "pallas":
        return hist_pallas_multi_int8(bins_fm, ghT_i8, row_leaf, leaf_ids,
                                      max_bins=max_bins,
                                      num_slots=num_slots,
                                      all_live=all_live)
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
