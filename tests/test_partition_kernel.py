"""The wave partition's Pallas kernel (`ops/pallas_partition.py`), run in
interpret mode here, against the XLA form of `apply_wave_splits` and the
chain of `apply_split` passes it stands for, bit for bit; the form each
storage takes; and whole boosters trained through either form."""

import functools

import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.ops import histogram as hist_ops
from lightgbm_tpu.ops import pallas_partition
from lightgbm_tpu.ops import partition as part_ops
from lightgbm_tpu.ops.bin_pack import (pack_bins_host, pack_vpb,
                                       section_len, to_device)
from lightgbm_tpu.ops.split import MISSING_NAN

from test_waved import _sequential, _wave_case


def _wave(c, bins, L, form):
    return np.asarray(part_ops.apply_wave_splits(
        jnp.asarray(c["row_leaf"]), bins, jnp.asarray(c["leaves"]),
        jnp.asarray(c["rights"]), jnp.asarray(c["feats"]),
        jnp.asarray(c["thrs"]), jnp.asarray(c["dlefts"]),
        jnp.asarray(c["cmasks"]), jnp.asarray(c["valid"]),
        jnp.asarray(c["num_bins"]), jnp.asarray(c["missing"]),
        jnp.asarray(c["is_cat"]), L, None, False, form=form))


# N, F, B, L, W, live leaves, values a byte and what else the case sets:
# "nan" puts every split on a NaN-missing feature whose rows often hold
# its NaN bin, default_left both ways; "edges" puts thresholds at the
# first bin and at the feature's last ones; "leaf254" puts rows in leaf
# 254, which no split names (the table's second vreg, nothing moves)
CASES = {
    "dense-W1": dict(N=3000, F=28, B=63, L=255, W=1, live=8),
    "dense-W8": dict(N=3000, F=28, B=63, L=255, W=8, live=40),
    "dense-W42-leaf254": dict(N=3000, F=28, B=63, L=255, W=42, live=213,
                              leaf254=True),
    "dense-nan": dict(N=3000, F=28, B=63, L=255, W=8, live=40, nan=True),
    "dense-edges": dict(N=3000, F=28, B=63, L=255, W=8, live=40,
                        edges=True),
    # two row blocks, the last ragged; 6 stored rows: one word, two bytes
    # of it past the stored rows
    "dense-two-blocks": dict(N=70_000, F=6, B=16, L=15, W=5, live=8),
    # 2,000 stored rows: 500 words, 62 tiles and a part
    "dense-F2000": dict(N=3000, F=2000, B=63, L=255, W=42, live=213),
    # two rows a byte; the second bit-section 904 rows of 4,096
    "packed-4bit-W42": dict(N=5000, F=28, B=15, L=255, W=42, live=213,
                            vpb=2, nan=True),
    # four rows a byte: the third bit-section short, the fourth empty
    "packed-2bit-W8": dict(N=4500, F=6, B=3, L=15, W=5, live=8, vpb=4,
                           edges=True),
    # two byte blocks of 49 x 1,024 bytes, each deciding two row blocks
    "packed-4bit-two-blocks": dict(N=200_000, F=28, B=15, L=255, W=42,
                                   live=213, vpb=2),
    # four rows a byte on a wide table: two blocks of 3,072 a section,
    # the third section's second block short, the fourth section empty
    "packed-2bit-wide": dict(N=18_000, F=1000, B=3, L=15, W=5, live=8,
                             vpb=4),
}


def _case(rng, *, nan=False, edges=False, leaf254=False, vpb=1, **kw):
    c = _wave_case(rng, **kw, has_categorical=False)
    feats, B, W = c["feats"], kw["B"], kw["W"]
    if W == 1:
        c["valid"][:] = True
    if nan:
        c["missing"][feats] = MISSING_NAN
        c["dlefts"][:] = np.arange(W) % 2 == 0
        c["num_bins"][feats] = B
        for f in feats:
            c["bins"][f, rng.rand(kw["N"]) < 0.3] = B - 1
    if edges:
        c["thrs"][0::3] = 0
        c["thrs"][1::3] = c["num_bins"][feats[1::3]] - 1
        c["thrs"][2::3] = c["num_bins"][feats[2::3]] - 2
    if leaf254:
        c["row_leaf"][::7] = 254
        assert 254 not in c["leaves"][c["valid"]]
    if W > 1:
        assert not c["valid"].all()
    bins = jnp.asarray(c["bins"])
    if vpb > 1:
        bins = to_device(pack_bins_host(c["bins"], B))
        assert bins.vpb == vpb
    return c, bins


@pytest.mark.usefixtures("release_executables")
@pytest.mark.parametrize("case", list(CASES))
def test_kernel_matches_xla_form_and_split_chain(case):
    kw = dict(CASES[case])
    rng = np.random.RandomState(len(case))
    for trial in range(2):
        c, bins = _case(rng, **kw)
        kernel = _wave(c, bins, kw["L"], "kernel")
        np.testing.assert_array_equal(kernel, _wave(c, bins, kw["L"], "xla"))
        np.testing.assert_array_equal(kernel, _sequential(part_ops, c, bins))
        assert (kernel != c["row_leaf"]).any()


def test_record_fields_and_blocks():
    """A record is the stored row, the threshold, the NaN code and the
    right child, low bit first, in 32 bits or the storage keeps the XLA
    form; a row block is whole vregs, and on PackedBins divides the
    section."""
    assert pallas_partition._fields(28, 63, 255) == (5, 6, 6, 8)
    assert pallas_partition._fields(2000, 63, 255) == (11, 6, 6, 8)
    assert pallas_partition._fields(3, 3, 15) == (2, 2, 2, 4)
    dense = jnp.zeros((28, 100), jnp.uint8)
    assert pallas_partition.admits(dense, 63, 255)
    assert not pallas_partition.admits(dense.astype(jnp.uint16), 300, 255)
    assert not pallas_partition.admits(dense, 256, 1 << 16)
    assert pallas_partition._block_rows(28, 63_000_000, None) == 65536
    assert pallas_partition._block_rows(2000, 1_200_000, None) == 2048
    assert pallas_partition._block_rows(28, 3000, None) == 4096
    # the higgs-gpu15 cell's section: 42,000,384 = 2^13 x 3 x 1,709
    assert pallas_partition._block_rows(28, 84_000_000, 42_000_384) == 24576
    assert pallas_partition._block_rows(6, 4500, 2048) == 2048
    assert pallas_partition._block_rows(1000, 18_000, 6144) == 3072


def test_admits_a_grid_step_that_fits_vmem():
    """A table whose least row block (1,024 rows), pipelined, would not
    fit the kernel's scoped VMEM keeps the XLA form: 15,328 stored rows
    (479 tiles) are the most, dense or packed."""
    import jax
    widest = 15_328
    assert (pallas_partition._vmem_bytes(widest, 1024)
            <= pallas_partition._VMEM_LIMIT
            < pallas_partition._vmem_bytes(widest + 1, 1024))
    for f, fits in ((widest, True), (widest + 1, False), (16_000, False)):
        dense = jax.ShapeDtypeStruct((f, 1 << 20), jnp.uint8)
        packed = pallas_partition.PackedBins(
            jax.ShapeDtypeStruct((f, section_len(1 << 20, 2)), jnp.uint8),
            1 << 20, 2)
        assert pallas_partition.admits(dense, 63, 31) == fits, f
        assert pallas_partition.admits(packed, 15, 31) == fits, f


# (rows, stored rows, max_bin): one block a section, several, a short and
# an empty last section, a first section that is partly past the rows
SECTION_GRIDS = [(4500, 6, 3), (18_000, 1000, 3), (1000, 5000, 3),
                 (200_000, 28, 15), (84_000_000, 28, 15), (10_000, 2000, 15),
                 (5000, 28, 15)]


@pytest.mark.parametrize("n,features,max_bins", SECTION_GRIDS)
def test_section_blocks_run_one_after_another(n, features, max_bins):
    """On PackedBins the grid runs (row block i of a section, bit-section
    v), v fastest. A step with rows decides its own row_leaf block, and
    each block's steps run one after another: the pipeline writes an
    output block back whenever the next step's block differs, whether a
    step wrote it or not, so a block seen again later would be written
    back stale in between. Interpret mode reads and writes the array
    itself and cannot show this."""
    vpb = pack_vpb(max_bins)
    section = section_len(n, vpb)
    r = pallas_partition._block_rows(features, n, section)
    nsb, last = section // r, -(-n // r) - 1
    seq = []
    for i in range(nsb):
        for v in range(vpb):
            block = int(pallas_partition._section_block(i, v, nsb, last))
            if (i + v * nsb) * r < n:
                assert block == i + v * nsb, (i, v)
            seq.append(block)
    runs = [b for k, b in enumerate(seq) if k == 0 or seq[k - 1] != b]
    assert sorted(runs) == list(range(last + 1)), seq


def test_each_storage_takes_its_form(monkeypatch):
    """By static facts alone: the kernel for uint8 and packed storage
    with no EFB bundle and no categorical split on an accelerator
    backend; the XLA form for EFB, categorical splits, uint16 bins, COO
    and the CPU backend. Each form counts its traced call sites under
    its own `trace_counts` key and publishes the pass in
    `meta["partition_wave"]`."""
    from lightgbm_tpu.obs.metrics import global_metrics
    dense = jnp.zeros((6, 500), jnp.uint8)
    packed = to_device(pack_bins_host(np.zeros((6, 500), np.uint8), 15))
    bundle = (jnp.zeros(7, jnp.int32),) * 3
    sparse = part_ops.SparseBins(*(jnp.zeros(3, jnp.int32),) * 4)
    assert part_ops.wave_form(dense, 63, 255, None, False) == "xla"
    monkeypatch.setattr(hist_ops, "cpu_backend", lambda: False)
    form = functools.partial(part_ops.wave_form, max_bins=63,
                             num_leaves=255)
    assert form(dense, has_categorical=False) == "kernel"
    assert form(packed, max_bins=15, has_categorical=False) == "kernel"
    assert form(dense, bundle=bundle, has_categorical=False) == "xla"
    assert form(dense, has_categorical=True) == "xla"
    assert form(dense.astype(jnp.uint16), max_bins=300,
                has_categorical=False) == "xla"
    assert form(sparse, has_categorical=False) == "xla"
    monkeypatch.undo()

    rng = np.random.RandomState(3)
    c, bins = _case(rng, **CASES["dense-W8"])
    counts = global_metrics.trace_counts
    before = {k: counts.get(k, 0)
              for k in ("ops/partition_wave", "ops/partition_wave_kernel")}
    global_metrics.meta.pop("partition_wave", None)
    _wave(c, bins, 255, "kernel")
    _wave(c, bins, 255, "xla")
    for key, was in before.items():
        assert counts[key] == was + 1, key
    recs = {r["form"]: r for r in global_metrics.meta["partition_wave"]}
    assert recs["kernel"]["block_rows"] == 4096
    assert recs["xla"]["block_rows"] is None
    for r in recs.values():
        assert r["rows"] == 3000 and r["stored_rows"] == 28
        assert r["bytes"] == 28 * 3000 + 8 * 3000


def _kernel_on_the_cpu(monkeypatch):
    """The accelerator's routes on the CPU: `cpu_backend` False, and the
    Pallas kernels in interpret mode."""
    from lightgbm_tpu.ops import pallas_histogram
    monkeypatch.setattr(hist_ops, "cpu_backend", lambda: False)
    monkeypatch.setattr(pallas_histogram, "_resolve_interpret",
                        lambda interpret: True)
    kernel = pallas_partition.wave_partition
    monkeypatch.setattr(
        pallas_partition, "wave_partition",
        lambda *a, **k: kernel(*a, **{**k, "interpret": True}))


@pytest.mark.usefixtures("release_executables")
@pytest.mark.parametrize("quantized", [True, False], ids=["int8", "float"])
def test_booster_writes_the_xla_forms_model(monkeypatch, quantized):
    """A booster on the accelerator's route (Mosaic histogram kernels,
    the wave partition once a wave) writes the same model text with the
    partition's kernel as with its XLA form, on int8 and on float
    histograms."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs.metrics import global_metrics
    from conftest import make_binary
    X, y = make_binary(2000)
    X[::11, 2] = np.nan
    params = {"objective": "binary", "num_leaves": 31, "verbosity": -1,
              "min_data_in_leaf": 5, "use_quantized_grad": quantized}
    _kernel_on_the_cpu(monkeypatch)
    counts = global_metrics.trace_counts
    texts = {}
    for form in ("kernel", "xla"):
        if form == "xla":
            monkeypatch.setattr(part_ops, "wave_form", lambda *a, **k: "xla")
        key = ("ops/partition_wave_kernel" if form == "kernel"
               else "ops/partition_wave")
        before = counts.get(key, 0)
        texts[form] = lgb.train(params, lgb.Dataset(X, label=y),
                                num_boost_round=3).model_to_string()
        assert counts.get(key, 0) > before, form
    assert texts["kernel"] == texts["xla"]


@pytest.mark.usefixtures("release_executables")
@pytest.mark.parametrize("params,form", [
    ({"tpu_hist_impl": "xla"}, "kernel"),
    ({"deterministic_hist": True}, "kernel"),
    ({"tree_learner": "data", "tpu_num_shards": 4, "tpu_hist_impl": "xla",
      "tpu_hist_reduce": "psum"}, "xla"),
], ids=["one-chip-xla-hist", "one-chip-deterministic", "data-parallel-gspmd"])
def test_booster_routes_the_wave_partition(monkeypatch, params, form):
    """On one chip the partition takes the kernel whatever the histogram
    runs on; the XLA form is kept only where GSPMD partitions the rows
    (data-parallel XLA histograms reduced by psum)."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs.metrics import global_metrics
    from conftest import make_binary
    X, y = make_binary(1000)
    _kernel_on_the_cpu(monkeypatch)
    counts = global_metrics.trace_counts
    keys = {"kernel": "ops/partition_wave_kernel", "xla": "ops/partition_wave"}
    before = {k: counts.get(key, 0) for k, key in keys.items()}
    lgb.train({"objective": "binary", "num_leaves": 15, "verbosity": -1,
               "min_data_in_leaf": 5, **params}, lgb.Dataset(X, label=y),
              num_boost_round=2)
    for k, key in keys.items():
        assert (counts.get(key, 0) > before[k]) == (k == form), k


@pytest.mark.usefixtures("release_executables")
def test_grower_routes_and_shards_the_wave_partition(monkeypatch):
    """`learner._wave_partition`: the XLA form where GSPMD partitions the
    rows with no shard mesh (data-parallel XLA histograms, a sharded
    stream), else the kernel, one call a shard under a shard mesh (a
    pallas_call does not partition under GSPMD): bit for bit the XLA
    form, on rows that do not divide the mesh."""
    import jax
    from jax.sharding import Mesh
    from lightgbm_tpu.learner import _wave_partition
    from lightgbm_tpu.obs.metrics import global_metrics
    _kernel_on_the_cpu(monkeypatch)
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    c, bins = _case(np.random.RandomState(5), **dict(CASES["dense-W8"],
                                                     N=4001))
    wave = [jnp.asarray(c[k]) for k in ("leaves", "rights", "feats", "thrs",
                                        "dlefts", "cmasks", "valid",
                                        "num_bins", "missing", "is_cat")]
    row_leaf = jnp.asarray(c["row_leaf"])
    want = _wave(c, bins, 255, "xla")
    counts = global_metrics.trace_counts
    for mesh_, gspmd, form in ((None, True, "xla"), (None, False, "kernel"),
                               (mesh, False, "kernel")):
        key = ("ops/partition_wave_kernel" if form == "kernel"
               else "ops/partition_wave")
        before = counts.get(key, 0)
        fn = _wave_partition(mesh_, gspmd, 255, None, False)
        np.testing.assert_array_equal(np.asarray(fn(row_leaf, bins, *wave)),
                                      want)
        assert counts.get(key, 0) > before, (mesh_, gspmd)
