"""The v5e compiler's verdict on every Pallas histogram kernel, without
a chip.

Interpret mode (what every other test of ops/pallas_histogram.py runs)
checks the arithmetic; it cannot see what Mosaic refuses: the int8
kernels passed every interpret-mode test and could not compile (an i1
mask layout change), the 2-bit fused kernel overran scoped VMEM. The TPU
compiler is installed here and compiles for a chip that is described
and not attached, so each entry point is AOT-compiled at the flagship
widths (F=28, N=2^20) and must come back as a Mosaic custom call.

The topology is described inside a module-scoped fixture and nowhere
else: describing it loads libtpu, which one process at a time may hold,
so it must not happen at import/collection (every xdist worker imports
every file) and these tests must stay in this one file. A compile that
passes is not a chip run — chip_smoke.py is that.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from lightgbm_tpu.ops import pallas_histogram as ph
from lightgbm_tpu.ops.bin_pack import PackedBins, section_len

F, N, SLOTS = 28, 1 << 20, 42  # Higgs width, 2^20 rows, one full wave
ITER_ROWS = 1 << 14            # rows of the whole iteration program below


@pytest.fixture(scope="module")
def llo_dir(tmp_path_factory):
    """Where Mosaic writes each kernel's stages. libtpu reads the flag
    once, as it is loaded, so it is set before the topology is described
    (`one_chip` asks for this fixture)."""
    path = tmp_path_factory.mktemp("mosaic")
    os.environ["LIBTPU_INIT_ARGS"] = (
        os.environ.get("LIBTPU_INIT_ARGS", "")
        + f" --xla_mosaic_dump_to={path}").strip()
    return path


@pytest.fixture(scope="module")
def one_chip(llo_dir):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A program compiled for a described chip is written to the
    persistent cache but cannot be read back without one: the next run
    would warn and recompile. Keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _binary_grad(score, label, weight):
    """Shape of objectives.Binary's pointwise gradient (sigmoid)."""
    p = jax.nn.sigmoid(score)
    g, h = p - label, p * (1.0 - p)
    if weight is not None:
        g, h = g * weight, h * weight
    return g, h


def _compiles_to_mosaic(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _shapes(one_chip):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return s


def _bins(s, n, max_bins, vpb=1, features=F):
    """[features, n] bin ids as a shape on the described chip: raw
    uint8/uint16 for vpb=1, else PackedBins with vpb values per byte."""
    if vpb == 1:
        return s((features, n),
                 jnp.uint8 if max_bins <= 256 else jnp.uint16)
    return PackedBins(s((features, section_len(n, vpb)), jnp.uint8), n, vpb)


def _kernel(kind, s, bins, n, max_bins, precise="default", **multi):
    """(function, operand shapes) of one Pallas entry point; `multi`:
    further options of the multi-leaf ones (all_live: the root's pass,
    whose step does not look for live rows)."""
    vec, rl = s((n,), jnp.float32), s((n,), jnp.int32)
    ids = s((SLOTS,), jnp.int32)
    if kind == "single":
        return (functools.partial(ph.hist_pallas, max_bins=max_bins,
                                  precise=precise, interpret=False),
                (bins, s((3, n), jnp.float32)))
    if kind == "multi":
        return (functools.partial(ph.hist_pallas_multi, max_bins=max_bins,
                                  num_slots=SLOTS, precise=precise,
                                  interpret=False, **multi),
                (bins, s((n, 3), jnp.float32), rl, ids))
    if kind == "fused":  # the default TPU path for objective=binary
        return (functools.partial(ph.hist_pallas_multi_fused,
                                  grad_fn=_binary_grad, max_bins=max_bins,
                                  num_slots=SLOTS, precise=precise,
                                  interpret=False, **multi),
                (bins, vec, vec, None, vec, rl, ids))
    assert kind == "int8"  # use_quantized_grad's kernel
    return (functools.partial(ph.hist_pallas_multi_int8, max_bins=max_bins,
                              num_slots=SLOTS, interpret=False, **multi),
            (bins, s((n, 3), jnp.int8), rl, ids))


# (static bin count B, values per byte). B is max(num_bins) over the
# features, so max_bin=63/255/15/3 really run B=63/255/15/3 — odd tile
# heights (2*63=126 rows per dot) the compiler must take — next to the
# power-of-two widths above them. 15 and 3 are the widest counts that
# bit-pack (bin_pack.pack_vpb): 4-bit and 2-bit PackedBins.
WIDTHS = [pytest.param(b, vpb, id=f"B{b}" + (f"-{8 // vpb}bit" if vpb > 1
                                               else ""))
          for b, vpb in ((63, 1), (64, 1), (255, 1), (256, 1),
                         (15, 2), (16, 2), (3, 4), (4, 4))]


@pytest.mark.parametrize("max_bins,vpb", WIDTHS)
@pytest.mark.parametrize("kind", ["single", "multi", "fused", "int8"])
def test_kernel_is_accepted(one_chip, kind, max_bins, vpb):
    """At the parent of PR 21 every int8 case was refused ("Non-singleton
    logical dimension is replicated in destination but not in source for
    'vector<2048x128xi1>'", until _leaf_bop compared and selected in
    int32) and fused-B4-2bit overran the 16 MiB of scoped VMEM."""
    s = _shapes(one_chip)
    fn, args = _kernel(kind, s, _bins(s, N, max_bins, vpb), N, max_bins)
    _compiles_to_mosaic(fn, *args)


@pytest.mark.parametrize("kind,max_bins,vpb", [
    ("int8", 63, 1), ("fused", 63, 1), ("multi", 15, 2), ("int8", 3, 4)])
def test_root_pass_is_accepted(one_chip, kind, max_bins, vpb):
    """The root's pass (`all_live`, told at the call site): the same step
    less the search for live rows and the squeeze."""
    s = _shapes(one_chip)
    fn, args = _kernel(kind, s, _bins(s, N, max_bins, vpb), N, max_bins,
                       all_live=True)
    _compiles_to_mosaic(fn, *args)


@pytest.mark.parametrize("kind", ["single", "multi", "int8"])
def test_uint16_bins(one_chip, kind):
    """max_bin > 256 stores uint16 bin ids (the fused kernel refuses
    them by assertion and the learner keeps them off it)."""
    s = _shapes(one_chip)
    fn, args = _kernel(kind, s, _bins(s, N, 300), N, 300)
    _compiles_to_mosaic(fn, *args)


@pytest.mark.parametrize("kind", ["multi", "fused"])
def test_highest_precision(one_chip, kind):
    """tpu_hist_precision=highest (6 MXU passes) at the flagship width:
    the f32-faithful setting chip_smoke.py's XLA reference uses."""
    s = _shapes(one_chip)
    fn, args = _kernel(kind, s, _bins(s, N, 63), N, 63, precise="highest")
    _compiles_to_mosaic(fn, *args)


@pytest.mark.parametrize("kind", ["multi", "fused", "int8"])
def test_row_operands_stay_lane_dense_at_higgs_rows(one_chip, kind):
    """Mosaic tiles a 2-D HBM operand (sublane, 128 lanes): a per-row
    operand shaped [N, 1] or [N, 3] costs 512 bytes a row — 5 GB each
    at the flagship's 10.5M rows, where the compiler refused the whole
    iteration program at 30.5 GB (PR 21). Lane-dense [k, N] operands
    keep one pass's buffers near the algorithm's own bytes: the padded
    bin copy (336 MB) plus 42 MB per f32 row vector."""
    n = 10_500_000
    s = _shapes(one_chip)
    fn, args = _kernel(kind, s, _bins(s, n, 63), n, 63)
    mem = jax.jit(fn).lower(*args).compile().memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 30, mem


# (features, max_bins): the benchmark's shape, the wide shape (PERF.md
# section 6), the one-feature-a-256-row-slab branch at Criteo's width, and
# uint16 ids
GEOMETRIES = [(28, 63), (2000, 63), (67, 255), (28, 300)]


@pytest.mark.parametrize("itemsize", [1, 2], ids=["int8", "bf16"])
@pytest.mark.parametrize("features,max_bins", GEOMETRIES)
def test_geometry_stays_under_scoped_vmem(features, max_bins, itemsize):
    """`_fb_geometry` by its own arithmetic: whole tiles, dots of 512
    one-hot rows or more, and a step under the scoped-VMEM limit."""
    g = ph._fb_geometry(features, max_bins, 1, itemsize)
    assert g.bp >= max_bins and g.bp % (32 // itemsize) == 0
    assert g.f_blk % max(g.dot_feats, 8) == 0
    assert g.dot_feats * g.bp >= 512 and g.row_chunk in ph._ROW_CHUNKS
    assert ph._step_vmem_bytes(g, 1, itemsize) <= ph._VMEM_LIMIT
    # a leaf operand serves 2048 one-hot rows or more (32 features at 63
    # bins: one block at the narrow shape), where it served 504
    assert g.f_blk * g.bp >= 2048


@pytest.mark.parametrize("kind", ["multi", "int8"])
@pytest.mark.parametrize("features,max_bins", GEOMETRIES[1:])
def test_geometry_is_accepted(one_chip, kind, features, max_bins):
    """What the arithmetic allows the compiler takes, at the shapes
    beyond test_kernel_is_accepted's 28 features."""
    n = 1 << 17
    s = _shapes(one_chip)
    fn, args = _kernel(kind, s, _bins(s, n, max_bins, features=features),
                       n, max_bins)
    _compiles_to_mosaic(fn, *args)


# operations of a step's vector program that are not the VPU's
_NOT_ALU = ("vector_load", "vector_store", "vmatmul", "vlatch", "vmatres",
            "vdwg")


def llo_counts(llo_dir, fn, *args):
    """{op: count} of one kernel's last Mosaic stage (``post-finalize-
    llo``: the step's vector program, unrolled), compiled for the
    described chip. The count is per grid step. A rotate is counted by
    its axis: `vrot.lane` moves lanes within a vreg, `vrot.slane`
    sublanes."""
    import collections
    import re
    for old in llo_dir.glob("*"):
        old.unlink()
    jax.jit(fn).lower(*args).compile()
    dump, = llo_dir.glob("*post-finalize-llo*")
    return collections.Counter(
        re.findall(r'"?llo\.(vrot\.s?lane|v[a-z_0-9]+)', dump.read_text()))


def _alu(ops):
    return sum(n for op, n in ops.items() if op not in _NOT_ALU)


def test_int8_step_vector_alu_count(one_chip, llo_dir):
    """The int8 step at the benchmark's shape was bound by the VPU: 41k
    vector-ALU operations per 2048 rows x 32 features (three selects a
    one-hot vreg, the mask's cast to int8, the leaf operand rebuilt per 8
    features), beside 4.1k cycles of matmul. Bin-aligned slabs built as
    packed words took 11.8k (PERF.md section 6, PR 29), of which 4.1k add
    the matmuls' popped results. Since PR 33 the step's program is a loop
    whose body multiplies one sub-tile of rows over the 28 real features
    (`root_tile` rows in the root's pass, which does nothing else:
    10.4k per 2048 rows; `k_tile` rows of the squeezed chunk in the
    others), and before the loop the squeeze of the chunk's live rows
    (live mask, prefix count, compress network: 3.2k per 2048 rows with
    the whole network of PR 33, 1.9k lane rotates a chunk among them).
    At this shape the geometry starts the network at stage 6 (PR 36):
    one stage that rotates lanes, the others move whole vregs, all in
    place: two fifths of the count. Neither count can creep
    back unseen; what binds the squeeze on the chip, the one vector-store
    slot, these counts do not show (PERF.md section 6, PR 36)."""
    s = _shapes(one_chip)
    geom = ph._fb_geometry(F, 63, 1, 1, rows=N)
    fn, args = _kernel("int8", s, _bins(s, N, 63), N, 63, all_live=True)
    root = llo_counts(llo_dir, fn, *args)
    turns = 2048 / geom.root_tile       # of the root's loop per 2048 rows
    # three dots of 8 features and one of 4: 7/8 of the padded block's
    assert root["vmatmul"] * turns == 1024 * 28 / 32
    assert root["vlatch"] * turns <= 256
    assert 4096 < _alu(root) * turns < 12_000, root
    fn, args = _kernel("int8", s, _bins(s, N, 63), N, 63)
    step = llo_counts(llo_dir, fn, *args)
    assert step["vmatmul"] * 2048 / geom.k_tile == 1024 * 28 / 32
    # the loop's body by the root's count a row (its own has one more add
    # a popped result and sub-tile); the rest is the squeeze of one chunk
    body = _alu(root) * geom.k_tile / geom.root_tile
    squeeze = (_alu(step) - body) * 2048 / geom.row_chunk
    assert geom.squeeze_stage == 6
    assert 0 < squeeze < 1_400, step
    # one lane stage of the network (its two arrays' vregs) and the
    # prefix count's: the whole network had 1,904
    assert root["vrot.lane"] == 0 < step["vrot.lane"] < 340
    assert _alu(root) * turns + squeeze < 16_000


# ---------------------------------------------------------------------------
# the whole iteration program, for its layer table (ISSUE 26): what the
# chip's compiler leaves of the lgbm/<layer> scopes is what the benchmark's
# per-layer seconds are read through
# the benchmark's narrow configurations: higgs-gpu63-int8 (quantized
# gradients, the int8 kernel), higgs-gpu63 (every tpu_* parameter at its
# default: float histograms, the gradient computed inside the kernel) and
# higgs-gpu15 (the same at 15 bins, where the default storage is
# PackedBins, two rows a byte: PR 35)
PATHS = {"int8": {"use_quantized_grad": True, "num_grad_quant_bins": 126},
         "float": {}, "packed": {"max_bin": 15}}
KERNEL = {"int8": "%lgbm_hist_multi_int8", "float": "%lgbm_hist_multi_packed",
          "packed": "%lgbm_hist_multi_packed"}


@pytest.fixture(scope="module", params=sorted(PATHS))
def path(request):
    return request.param


_FUSED_ITER_TEXT = {}    # path -> text, compiled once a path


@pytest.fixture(scope="module")
def fused_iter_text(one_chip, path):
    """``boosting/fused_iter`` compiled for the described chip, as text:
    a benchmark cell's path (F=28, 63 bins, 255 leaves) at 16k rows, a
    40 s compile. (At 31 leaves the compiler turns a ``table[idx]`` into
    selects by itself, and the score update's test below would see no
    gather in any program.) pytest sets a parametrised fixture up anew
    whenever the tests' order changes paths, hence the memo."""
    if path not in _FUSED_ITER_TEXT:
        _FUSED_ITER_TEXT[path] = _compile_fused_iter(one_chip,
                                                     path).as_text()
    return _FUSED_ITER_TEXT[path]


def _compile_fused_iter(one_chip, path, features=F, rows=None):
    """``boosting/fused_iter`` compiled for the described chip, from a
    Booster built on ITER_ROWS rows and lowered at ``rows`` of them (at
    ITER_ROWS if None; PackedBins at the section the packer gives that
    many rows)."""
    import numpy as np

    import lightgbm_tpu as lgb
    from lightgbm_tpu.ops import histogram as hist_ops
    mp = pytest.MonkeyPatch()
    # the Booster is built on the CPU; the program takes its TPU branches
    # (Mosaic kernels, batched partition) because the test says so
    mp.setattr(hist_ops, "cpu_backend", lambda: False)
    try:
        r = np.random.RandomState(0)
        x = r.randn(ITER_ROWS, features) + 0.26
        y = (x[:, 0] + x[:, 1] > 0.5).astype(np.float64)
        g = lgb.Booster({"objective": "binary", "num_leaves": 255,
                         "max_bin": 63, "verbosity": -1,
                         "tpu_hist_impl": "pallas", **PATHS[path]},
                        lgb.Dataset(x, label=y))._gbdt
        g._boost_from_average()
        args = (g.bins_fm, tuple(g._valid_bins), g._obj_state(), g.scores,
                g._sample_mask, tuple(g._valid_scores), jnp.int32(0),
                jnp.float32(0.1))
        shapes = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                tuple(rows if rows and d == ITER_ROWS else d
                      for d in a.shape), a.dtype, sharding=one_chip), args)
        if rows and isinstance(g.bins_fm, PackedBins):
            vpb = g.bins_fm.vpb
            section = section_len(rows, vpb)
            shapes = (PackedBins(jax.ShapeDtypeStruct(
                (features, section), jnp.uint8, sharding=one_chip), rows,
                vpb),) + shapes[1:]
        return g._make_fused().lower(*shapes).compile()
    finally:
        mp.undo()


@pytest.fixture(scope="module")
def fused_iter_table(fused_iter_text):
    """{instruction_head: layer} of that program."""
    from lightgbm_tpu.obs.profile import parse_layer_table
    return parse_layer_table(fused_iter_text)


@pytest.mark.parametrize("layer", ["gradient", "hist", "split",
                                   "partition", "score"])
def test_compiled_iteration_keeps_each_layer(fused_iter_table, path, layer):
    """On the float path the gradient is computed inside the histogram
    kernel and the root's totals come from the root histogram, so nothing
    is left under ``lgbm/gradient``: ``layer_gradient_s`` reads 0 there
    and ``layer_hist_s`` holds the gradient's arithmetic."""
    kept = layer in fused_iter_table.values()
    assert kept == (layer != "gradient" or path == "int8")


def test_mosaic_kernel_is_named_and_in_the_hist_layer(fused_iter_table,
                                                      path):
    """``name=`` on the pallas_call names the custom call's instruction,
    and keeps ``hist`` in it: benchmarks/metrics/hist_kernels.py finds the
    kernels by that substring. The float path's kernel computes the
    gradient itself and goes through the byte-sectioned call
    (``lgbm_hist_multi_packed``, one value a byte for raw bins)."""
    kernels = {head: layer for head, layer in fused_iter_table.items()
               if head.startswith("%lgbm_hist_multi")}
    assert kernels and set(kernels.values()) == {"hist"}
    # one kernel name a program: every pass runs the one step
    names = {head.split(" = ")[0].split(".")[0] for head in kernels}
    assert names == {KERNEL[path]}, kernels


def test_each_pass_kernel_keeps_its_own_scope(fused_iter_text, path):
    """The nested jits of the kernel's call leave each of a tree's
    thirteen kernel instructions the scope of the pass that made it
    (``lgbm/hist/root``, ``lgbm/hist/w00`` ... ``w11``), so the
    benchmark's ``hist_turn_us`` pairs a pass's counts with its kernel
    event by scope, not only by order."""
    from lightgbm_tpu.obs.profile import parse_scope_tables
    _, scopes = parse_scope_tables(fused_iter_text)
    kernels = [scope for head, scope in scopes.items()
               if head.startswith(KERNEL[path] + ".")]
    assert sorted(kernels) == ["hist/root"] + [f"hist/w{i:02d}"
                                               for i in range(12)]


def _row_ops(table, shape):
    """{instruction head: layer} of the fusions, and the wave partition's
    kernel instructions, whose result is `shape`."""
    return {head: lay for head, lay in table.items()
            if ("fusion" in head.split(" = ")[0]
                or head.startswith("%lgbm_partition_wave"))
            and head.split(" = ")[1].startswith(shape + "{")}


@pytest.mark.parametrize("path,shape,layer", [
    ("int8", "s32[16384]", "partition"), ("int8", "s8[3,16384]", "hist"),
    ("int8", "f32[16384]", "score"), ("float", "s32[16384]", "partition"),
    ("float", "f32[16384]", "score"), ("packed", "s32[16384]", "partition"),
    ("packed", "s32[8192]", None), ("packed", "f32[16384]", "score")],
    indirect=["path"])
def test_row_sized_fusions_are_one_layers(fused_iter_table, shape, layer):
    """The row-sized operations each fall under one layer: ``s32[N]``
    the wave partition, one Pallas kernel a wave
    (``lgbm_partition_wave``; the compare-and-select fusions of its XLA
    form and the ``u8[N]`` bin gather before them are gone), ``s8[3, N]`` the
    int8 kernel's operand, ``f32[N]`` the score update: its selects,
    written as the vector they make before the add takes them (the
    gather they replaced is gone; PERF.md section 5). On PackedBins
    nothing of a bit-section's rows, ``s32[N / 2]``, is written any
    more: the XLA form's per-section unpack fusions (``unpack_rows``)
    went into the kernel, which shifts a row's bits out of its byte in
    VMEM. The ``layer_*_s`` metrics are defined by the scopes in the
    program: a change that moves a ``named_scope`` moves seconds between
    them, and shows here first."""
    got = set(_row_ops(fused_iter_table, shape).values())
    assert got == ({layer} if layer else set())


def test_no_row_sized_fusion_in_the_split_layer(fused_iter_table):
    """A node's totals are read from its histogram ([B]-sized, scope
    ``lgbm/split/totals``), never by a pass over the rows: every fusion
    with a row-sized result belongs to a layer that works on rows."""
    import re
    row_sized = re.compile(rf"[\[,]{ITER_ROWS}[\],]")
    layers = {lay for head, lay in fused_iter_table.items()
              if "fusion" in head.split(" = ")[0]
              and row_sized.search(head.split(" = ")[1])}
    assert layers and layers <= {"gradient", "hist", "partition", "score"}


def _row_sized_gathers(text, rows, scope):
    """Instructions of a compiled program traced under `scope` that
    gather a result with a `rows`-long dimension: a ``gather``, or a
    fusion whose computation holds one."""
    import re
    row_sized = re.compile(rf"[\[,]{rows}[\],]")
    gathering, comp = set(), None
    for line in text.splitlines():
        if not line.startswith(" "):
            comp = line.removeprefix("ENTRY ").split(" ", 1)[0]
        elif (" gather(" in line
              and row_sized.search(line.split(" gather(")[0])):
            gathering.add(comp)
    bad = []
    for line in text.splitlines():
        head = line.split(", metadata=")[0]
        calls = re.search(r"calls=(%[\w.\-]+)", line)
        if scope in line and (
                (" gather(" in head
                 and row_sized.search(head.split(" gather(")[0]))
                or (calls and calls.group(1) in gathering)):
            bad.append(line.strip()[:200])
    return bad


def test_partition_gathers_nothing_row_sized(fused_iter_text):
    """PR 27: ``apply_wave_splits`` decides every row's move by
    compare-and-select. The gathers it keeps are [W]-sized (a step's
    facts by its feature); a row-sized one read 475 GB a wave at the
    benchmark cell's size (PERF.md section 6)."""
    assert "lgbm/partition" in fused_iter_text
    assert not _row_sized_gathers(fused_iter_text, ITER_ROWS,
                                  "lgbm/partition")
    # the detector sees a row-sized gather where there is one: a plain
    # table[idx] at a width XLA does not rewrite
    gathered = jax.jit(lambda t, i: t[i]).lower(
        jax.ShapeDtypeStruct((1 << 12,), jnp.float32),
        jax.ShapeDtypeStruct((ITER_ROWS,), jnp.int32)).compile().as_text()
    assert _row_sized_gathers(gathered, ITER_ROWS, "")


def test_score_update_gathers_nothing_row_sized(fused_iter_text, one_chip):
    """PR 31: ``_score_rule`` brings a leaf's value to its rows by
    selects (``ops/partition.per_row_lookup``). The gather it replaced,
    ``leaf_vals[row_leaf]`` from 255 entries, took 0.54 s of the
    benchmark cells' 1.85 and 2.99 s an iteration (PERF.md section 6)."""
    from lightgbm_tpu.ops import partition as part_ops
    assert "lgbm/score" in fused_iter_text
    assert not _row_sized_gathers(fused_iter_text, ITER_ROWS, "lgbm/score")
    # the helper alone gathers nothing, and the plain indexing it replaced
    # is a gather the detector sees
    s = _shapes(one_chip)
    args = s((255,), jnp.float32), s((ITER_ROWS,), jnp.int32)
    for fn, gathers in ((part_ops.per_row_lookup, False),
                        (lambda t, i: t[i], True)):
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert bool(_row_sized_gathers(text, ITER_ROWS, "")) == gathers


def test_wide_float_iteration_fits_the_chip(one_chip):
    """``epsilon-gpu63.train``'s program (PR 32): the float iteration at
    1,200,000 x 2000, 63 bins, 255 leaves, every ``tpu_*`` parameter at
    its default, compiled at its real size (two minutes: the one test
    here that is). The chip's compiler accepts it, what it holds (its
    arguments, the 2.4 GB of bins among them, and its temporaries) is over
    the 2.00 GiB a cell has to fill and fits the chip's 16 GB, the
    kernel is the many-block float one, and the row-sized fusions fall
    under the layers they do in the narrow cells' programs."""
    from lightgbm_tpu.obs.profile import parse_layer_table
    rows = 1_200_000
    compiled = _compile_fused_iter(one_chip, "float", features=2000,
                                   rows=rows)
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes >= 2000 * rows
    assert 2 * 2 ** 30 <= held < 16e9, mem
    table = parse_layer_table(compiled.as_text())
    kernels = {head.split(" = ")[0].split(".")[0] for head in table
               if head.startswith("%lgbm_hist_multi")}
    assert kernels == {KERNEL["float"]}
    assert "gradient" not in table.values()
    for shape, layer in ((f"s32[{rows}]", "partition"),
                         (f"f32[{rows}]", "score")):
        got = set(_row_ops(table, shape).values())
        assert got == {layer}, (shape, got)
    _one_partition_kernel_a_wave(compiled.as_text(), rows)


def test_packed_iteration_places_every_row_sized_operation(one_chip):
    """higgs-gpu15.train's program at its own size, 84,000,000 x 28 at 15
    bins (PR 35): PackedBins of 42,000,384 bytes a feature go in, the
    program fits the chip, its kernel is the byte-sectioned call, and
    every operation with a row-sized result (all the rows, or one
    bit-section's) carries an ``op_name`` under a layer, so the
    benchmark's ``layer_*_s`` place its seconds
    (``layer_unattributed_pct``)."""
    import re

    from lightgbm_tpu.obs.profile import parse_layer_table
    rows, section = 84_000_000, 42_000_384
    compiled = _compile_fused_iter(one_chip, "packed", rows=rows)
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes >= F * section + 3 * 4 * rows
    assert 2 * 2 ** 30 <= held < 16e9, mem
    text = compiled.as_text()
    assert f"u8[{F},{section}]" in text and f"u8[{F},{rows}]" not in text
    table = parse_layer_table(text)
    kernels = {head.split(" = ")[0].split(".")[0] for head in table
               if head.startswith("%lgbm_hist_multi")}
    assert kernels == {KERNEL["packed"]}
    assert "gradient" not in table.values()
    # a bit-section's rows (s32[section], s32[rows - section]) are no
    # longer written out: the kernel unpacks a row's bits in VMEM
    for shape, layer in ((f"s32[{rows}]", "partition"),
                         (f"s32[{section}]", None),
                         (f"s32[{rows - section}]", None),
                         (f"f32[{rows}]", "score")):
        got = set(_row_ops(table, shape).values())
        assert got == ({layer} if layer else set()), (shape, got)
    _one_partition_kernel_a_wave(text, rows)
    # outside the fused computations, whatever writes a row-sized result
    # and is no view, argument or asynchronous copy's half has a layer
    moves_nothing = (" bitcast(", " parameter(", " get-tuple-element(",
                     " tuple(", " copy-start(", " copy-done(", " constant(")
    unplaced, comp = [], ""
    for line in text.splitlines():
        if not line.startswith(" "):
            comp = line.removeprefix("ENTRY ").split(" ", 1)[0]
            continue
        m = re.match(r"\s+(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]*)\]", line)
        if (not m or "fused_computation" in comp
                or any(op in line for op in moves_nothing)):
            continue
        size = 1
        for d in filter(None, m.group(1).split(",")):
            size *= int(d)
        if size >= rows - section and "lgbm/" not in line:
            unplaced.append(line.strip()[:160])
    # the label average's reduce and an iota run before the tree (outside
    # any layer in every cell's program: 0.15% of the float cell's busy
    # seconds, PERF.md section 5)
    assert len(unplaced) <= 2, unplaced


@pytest.mark.parametrize("form", ["xla", "kernel"])
@pytest.mark.parametrize("rows,features", [(1 << 20, 28), (1 << 17, 2000)])
def test_wave_partition_keeps_row_sized_temporaries(one_chip, rows,
                                                    features, form):
    """``apply_wave_splits`` alone, one full wave, at the narrow and the
    wide shape: what the program holds beside its operands stays under
    four row-sized int32 vectors in the XLA form, so no [W, N] match
    matrix and no [F, N] select is ever written out; the kernel,
    which writes row_leaf in place (donated here, as the grower's is an
    intermediate), holds less than one."""
    from lightgbm_tpu.ops import histogram as hist_ops
    from lightgbm_tpu.ops import partition as part_ops
    s = _shapes(one_chip)
    w, b, leaves = SLOTS, 63, 255
    step = [s((w,), jnp.int32)] * 4 + [s((w,), jnp.bool_),
                                       s((w, b), jnp.bool_),
                                       s((w,), jnp.bool_)]
    meta = [s((features,), jnp.int32)] * 2 + [s((features,), jnp.bool_)]
    fn = functools.partial(part_ops.apply_wave_splits, num_leaves=leaves,
                           has_categorical=False, form=form)
    mp = pytest.MonkeyPatch()
    # the Mosaic kernel, not its interpret mode: the program is the chip's
    mp.setattr(hist_ops, "cpu_backend", lambda: False)
    try:
        compiled = jax.jit(fn, donate_argnums=0).lower(
            s((rows,), jnp.int32), s((features, rows), jnp.uint8), *step,
            *meta).compile()
    finally:
        mp.undo()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < (4 if form == "xla" else 1) * 4 * rows, \
        mem
    assert ("lgbm_partition_wave" in compiled.as_text()) == (form == "kernel")


@pytest.mark.parametrize("rows,features,max_bins,vpb", [
    (1 << 14, 15_328, 63, 1), (1 << 14, 15_328, 15, 2),
    (18_000, 1000, 3, 4)], ids=["widest-uint8", "widest-4bit", "2bit-wide"])
def test_partition_kernel_compiles_at_its_edges(one_chip, rows, features,
                                                max_bins, vpb):
    """The partition kernel on the widest tables it admits, whose 1,024-
    row steps come closest to its scoped VMEM (`pallas_partition.admits`:
    a tile more keeps the XLA form), and on a 2-bit table of two row
    blocks a section with its last bit-section empty: the chip's compiler
    takes each."""
    from lightgbm_tpu.ops import histogram as hist_ops
    from lightgbm_tpu.ops import pallas_partition
    from lightgbm_tpu.ops import partition as part_ops
    s = _shapes(one_chip)
    w, leaves = 5, 31
    bins = _bins(s, rows, max_bins, vpb=vpb, features=features)
    assert pallas_partition.admits(bins, max_bins, leaves)
    step = [s((w,), jnp.int32)] * 4 + [s((w,), jnp.bool_),
                                       s((w, max_bins), jnp.bool_),
                                       s((w,), jnp.bool_)]
    meta = [s((features,), jnp.int32)] * 2 + [s((features,), jnp.bool_)]
    fn = functools.partial(part_ops.apply_wave_splits, num_leaves=leaves,
                           has_categorical=False, form="kernel")
    mp = pytest.MonkeyPatch()
    mp.setattr(hist_ops, "cpu_backend", lambda: False)
    try:
        text = jax.jit(fn, donate_argnums=0).lower(
            s((rows,), jnp.int32), bins, *step, *meta).compile().as_text()
    finally:
        mp.undo()
    assert "lgbm_partition_wave" in text


def _one_partition_kernel_a_wave(text, rows):
    """A tree's 13 waves each write row_leaf once, by one kernel
    instruction under its own ``lgbm/partition/wNN`` scope and in the
    ``partition`` layer, and the partition layer holds no other
    operation with a row-sized result."""
    import re

    from lightgbm_tpu.obs.profile import parse_scope_tables
    table, scopes = parse_scope_tables(text)
    row_sized = re.compile(rf"[\[,]{rows}[\],]")
    ops = {head: scopes.get(head) for head, lay in table.items()
           if lay == "partition"
           and row_sized.search(head.split(" = ")[1].split("{")[0])}
    assert all(h.startswith("%lgbm_partition_wave") for h in ops), ops
    assert sorted(ops.values()) == [f"partition/w{i:02d}"
                                    for i in range(13)], ops


def test_int8_iteration_partitions_once_a_wave(one_chip):
    """higgs-gpu63-int8.train's program at its own size, 63,000,000 x
    28: one partition kernel a wave, in the partition layer. The packed
    cell's program is held to the same in
    test_packed_iteration_places_every_row_sized_operation, the wide
    one's in test_wide_float_iteration_fits_the_chip."""
    rows = 63_000_000
    compiled = _compile_fused_iter(one_chip, "int8", rows=rows)
    _one_partition_kernel_a_wave(compiled.as_text(), rows)
