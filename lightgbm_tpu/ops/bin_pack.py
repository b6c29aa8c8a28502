"""Bit-packed bin storage (device).

The per-iteration training cost model (docs/PERF_PROJECTION.md) is
dominated by re-reading the ``[F, N]`` bin tensor once per histogram
pass (~13 full-data passes per 255-leaf tree). When every feature fits
in few bins the uint8 storage wastes most of each byte: 4-bit nibbles
(``max_bins <= 15``) halve that dominant read, 2-bit pairs
(``max_bins <= 3``) quarter it — the TPU shape of the reference's
packed 4-bit bins (ref: include/LightGBM/bin.h Dense4bitsBin; the same
trick powers arXiv:1706.08359's GPU histogram kernels).

Layout — *split sections*, not interleaved nibbles: the padded row axis
(``n_pad = vpb * section``) is cut into ``vpb`` equal sections of
``section`` rows, and byte ``j`` of a feature's packed row carries rows
``j, j + section, ..., j + (vpb-1) * section`` in ascending bit
position.  Unpacking is therefore a concatenation of shifted/masked
*slices* — no lane interleave — which both XLA and Mosaic handle as
cheap vector ops, and a Pallas grid step that reads one byte block can
consume all of its nibbles by pairing it with ``vpb`` gh/row-leaf
blocks taken at ``section``-strided offsets (see
``pallas_histogram``'s packed kernels).

``PackedBins`` flows through the growers in the ``bins_fm`` argument
slot (like ``partition.SparseBins``); every consumer dispatches on
``isinstance``. The logical ``.shape`` property keeps
``bins_fm.shape[1]``-style call sites working unchanged.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

# the packed row-section length is padded to a multiple of this so the
# Pallas kernels' byte blocks (1024 bytes/step) always tile a section
# exactly; it also keeps gh block offsets section-aligned
PACK_ALIGN = 2048


@jax.tree_util.register_pytree_node_class
class PackedBins:
    """Bit-packed ``[F, N]`` bin matrix.

    data: ``[F, section]`` uint8, ``vpb`` values per byte (2 = 4-bit,
    4 = 2-bit); ``num_data`` is the logical N (static pytree aux, so
    shapes stay trace-time constants).
    """

    def __init__(self, data, num_data: int, vpb: int):
        self.data = data
        self.num_data = int(num_data)
        self.vpb = int(vpb)

    @property
    def bits(self) -> int:
        return 8 // self.vpb

    @property
    def section(self) -> int:
        return self.data.shape[1]

    @property
    def shape(self):
        """Logical (num_features, num_data) — consumers that size row
        buffers by ``bins_fm.shape[1]`` keep working unchanged."""
        return (self.data.shape[0], self.num_data)

    @property
    def nbytes(self) -> int:
        return int(self.data.shape[0]) * int(self.data.shape[1])

    def tree_flatten(self):
        return (self.data,), (self.num_data, self.vpb)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux[0], aux[1])


def pack_vpb(max_bins: int) -> int:
    """Values-per-byte the bin-id range admits: 4 (2-bit) when every id
    AND the out-of-range pad sentinel fit in 2 bits, 2 (4-bit) up to 15
    bins, else 1 (no packing). ``max_bins`` counts bins, so ids span
    [0, max_bins - 1] and the sentinel is ``max_bins`` itself."""
    if max_bins <= 3:
        return 4
    if max_bins <= 15:
        return 2
    return 1


def section_len(num_data: int, vpb: int) -> int:
    """Bytes a feature's packed row takes (= rows of one bit-section):
    ``num_data / vpb`` rounded up to whole PACK_ALIGNs."""
    return -(-(-(-num_data // vpb)) // PACK_ALIGN) * PACK_ALIGN


def pack_bins_host(bins_fm: np.ndarray, max_bins: int):
    """Host-side pack of a ``[F, N]`` uint8 matrix; returns a host
    ``PackedBins`` (numpy data — callers ship with ``to_device``) or
    None when ``max_bins`` does not admit packing."""
    vpb = pack_vpb(max_bins)
    if vpb == 1:
        return None
    f, n = bins_fm.shape
    section = section_len(n, vpb)
    bits = 8 // vpb
    data = np.zeros((f, section), np.uint8)
    for v in range(vpb):
        # section v as the matrix holds it: the last one is short (or
        # empty) and the bytes past it keep bin 0
        part = bins_fm[:, v * section:(v + 1) * section]
        head = data[:, :part.shape[1]]
        if v == 0:
            head[...] = part
        else:
            np.bitwise_or(head, part << (bits * v), out=head)
    return PackedBins(data, n, vpb)


def to_device(pb: PackedBins) -> PackedBins:
    return PackedBins(jnp.asarray(pb.data), pb.num_data, pb.vpb)


# ---------------------------------------------------------------------------
# slab slicing (out-of-core streaming training, io/streaming.HostSlabBins)
def slab_align(max_bins: int) -> int:
    """Row-count alignment of a streaming slab: each slab is packed as
    its OWN section-aligned PackedBins (section a PACK_ALIGN multiple),
    so a slab whose row count is a multiple of ``vpb * PACK_ALIGN``
    packs with zero padding waste and every full slab shares one device
    shape (one compiled slab program, not one per slab)."""
    return pack_vpb(max_bins) * PACK_ALIGN


def slab_bounds(num_data: int, slab_rows: int, max_bins: int):
    """Cut ``num_data`` rows into section-aligned ``[lo, hi)`` slabs.
    ``slab_rows`` is rounded UP to the slab alignment; the tail slab
    keeps its natural (shorter) row count — consumers mask by
    ``num_data`` exactly like the resident packed path does."""
    align = slab_align(max_bins)
    rows = max(int(slab_rows), 1)
    rows = -(-rows // align) * align
    return [(lo, min(lo + rows, int(num_data)))
            for lo in range(0, int(num_data), rows)]


def pack_bins_range(bins_fm: np.ndarray, max_bins: int, lo: int, hi: int,
                    pack: bool = True):
    """Host storage of rows ``[lo, hi)`` as a streaming slab: a
    section-aligned ``PackedBins`` when ``pack`` and the bin width
    admits packing, else the raw uint8/uint16 row slice. The slab is
    self-contained — its section layout is its own, so every device
    consumer (histogram kernels, partition unpack) treats it exactly
    like a full resident matrix of ``hi - lo`` rows."""
    sub = np.ascontiguousarray(bins_fm[:, lo:hi])
    packed = pack_bins_host(sub, max_bins) if pack else None
    return packed if packed is not None else sub


def unpack_bins(pb: PackedBins):
    """``PackedBins -> [F, N]`` logical bins (jnp; XLA fuses the
    shift/mask into consumers, so the HBM read stays the packed
    bytes). The split-section layout makes this a concat of slices."""
    bits = pb.bits
    bmask = (1 << bits) - 1
    parts = [(pb.data >> (bits * v)) & bmask for v in range(pb.vpb)]
    return jnp.concatenate(parts, axis=1)[:, :pb.num_data]


def unpack_feature(pb: PackedBins, feature):
    """One logical [N] bin column (dynamic feature index): slice the
    packed row, then shift/mask per section — a streaming read of
    ``section`` bytes, not a gather."""
    bits = pb.bits
    bmask = (1 << bits) - 1
    row = jnp.take(pb.data, feature, axis=0).astype(jnp.int32)
    parts = [(row >> (bits * v)) & bmask for v in range(pb.vpb)]
    return jnp.concatenate(parts)[:pb.num_data]


def unpack_rows(pb: PackedBins, feat):
    """Per-row unpack: bin of feature ``feat[r]`` at every row ``r``
    (the packed analog of ``bins[feat, arange(N)]``), as a select over
    the F packed rows and not as a gather. Row r lives in byte
    ``r % section`` at bit position ``bits * (r // section)``, so the
    rows of section v compare against the same bytes and shift by
    ``bits * v``. Section by section on [rows of the section] vectors
    (the last one short): a [vpb, section] intermediate would have to be
    padded on the way in and laid out anew on the way out, two more
    passes over the rows a call (PERF.md section 6, PR 35)."""
    bits = pb.bits
    f, sec = pb.data.shape
    ids = jnp.arange(f, dtype=jnp.int32)[:, None]
    parts = []
    for v in range(-(-pb.num_data // sec)):
        mine = feat[v * sec:min((v + 1) * sec, pb.num_data)]
        byte = jnp.sum(jnp.where(mine[None, :] == ids,
                                 pb.data[:, :mine.shape[0]], 0),
                       axis=0, dtype=jnp.int32)
        parts.append((byte >> (bits * v)) & ((1 << bits) - 1))
    return jnp.concatenate(parts)
