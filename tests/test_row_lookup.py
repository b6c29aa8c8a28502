"""``ops/partition.per_row_lookup`` (ISSUE 31): a row's value from an
[L] table by selects where the table is small, by a gather where it is
not; and the score update that calls it, against the gather it
replaced."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import boosting
from lightgbm_tpu.obs.metrics import global_metrics
from lightgbm_tpu.ops import partition as part_ops
from tests.conftest import make_binary

EDGE = part_ops.LOOKUP_SELECT_MAX


def _table(L, dtype, seed=0):
    t = (np.random.RandomState(seed + L).randn(L) * 100).astype(dtype)
    if dtype == np.float32:
        # what a lookup can get wrong: NaN and the infinities reach the
        # rows that name them and no other, -0.0 keeps its sign
        for j, v in enumerate((np.nan, -0.0, np.inf, -np.inf)):
            if j < L:
                t[(j * 7) % L] = v
    return t


def _form_counts():
    return {form: global_metrics.trace_counts.get(f"ops/row_lookup_{form}", 0)
            for form in ("select", "gather")}


@pytest.mark.parametrize("dtype", [np.float32, np.int32])
@pytest.mark.parametrize("L", [1, 2, 31, 255, EDGE, EDGE + 1])
def test_lookup_is_the_gather_bit_for_bit(L, dtype):
    t = _table(L, dtype)
    i = np.random.RandomState(L).randint(0, L, 4096).astype(np.int32)
    i[:2] = 0, L - 1
    before = _form_counts()
    got = np.asarray(jax.jit(part_ops.per_row_lookup)(jnp.asarray(t),
                                                      jnp.asarray(i)))
    assert got.dtype == dtype
    # bytes, so that -0.0 and a NaN's payload count
    assert got.tobytes() == t[i].tobytes()
    # the form is chosen by the table's static length alone, and the
    # counter says which was traced (one call site: one count)
    taken = "select" if L <= EDGE else "gather"
    after = _form_counts()
    assert {f: after[f] - before[f] for f in after} == {
        f: int(f == taken) for f in after}


@pytest.mark.parametrize("L", [1, 5, 255])
def test_select_form_reads_zero_outside_the_table(L):
    """What the docstring states: outside [0, L) the selects give 0
    (the gather would clamp to L - 1 or wrap a negative index). No
    grower hands the score update such an index; see the next test."""
    t = jnp.asarray(_table(L, np.int32) | 1)        # no zero entry
    bad = jnp.asarray([-1, -L, -(1 << 31), L, L + 1, 1 << 20, (1 << 31) - 1],
                      jnp.int32)
    assert not np.asarray(jax.jit(part_ops.per_row_lookup)(t, bad)).any()
    gathered = np.asarray(t[bad])
    assert gathered[0] == t[L - 1] and gathered[3] == t[L - 1]


# every composition that brings a leaf value to the rows: the fused
# program, the streamed one (two slabs), DART's two rules with a valid
# set beside, the host loop's twin, and a row shard with a padded tail
PROGRAMS = {
    "fused": (3001, {}),
    "fused+valid": (1500, {"valid": True, "use_quantized_grad": True}),
    "streamed": (3000, {"tpu_stream": "on", "tpu_stream_slab_rows": 2048,
                        "use_quantized_grad": True}),
    "dart": (1500, {"boosting": "dart", "drop_rate": 0.5, "max_drop": 5,
                    "valid": True}),
    "dart_host_loop": (1500, {"boosting": "dart", "drop_rate": 0.5,
                              "host_loop": True}),
    "host_loop": (1500, {"host_loop": True}),
    "multiclass": (1500, {"objective": "multiclass", "num_class": 3}),
    "row_shards": (1501, {"tree_learner": "data", "tpu_num_shards": 2}),
}


def _train(name):
    n, extra = PROGRAMS[name]
    extra = dict(extra)
    X, y = make_binary(n)
    if "num_class" in extra:
        y = (np.abs(X[:, 0] * 3).astype(int) % 3).astype(np.float32)
    params = {**dict(objective="binary", num_leaves=15, learning_rate=0.1,
                     max_bin=63, min_data_in_leaf=5, verbosity=-1), **extra}
    with_valid, host_loop = params.pop("valid", 0), params.pop("host_loop", 0)
    ds = lgb.Dataset(X, label=y, params=params)
    valid = []
    if with_valid:
        Xv, yv = make_binary(400, seed=5)
        valid = [lgb.Dataset(Xv, label=yv, params=params, reference=ds)]
    bst = lgb.Booster(params, ds)
    for i, v in enumerate(valid):
        bst.add_valid(v, f"valid_{i}")
    g = bst._gbdt
    if host_loop:
        g._fast_path_ok = lambda *a, **k: False
    if "tpu_stream" in params:
        assert g._stream.n_slabs == 2
    for _ in range(4):
        bst.update()
    return (bst.model_to_string(), np.asarray(g.scores),
            [np.asarray(v) for v in g._valid_scores])


def _opaque_gather(table, idx):
    return jax.pure_callback(
        lambda t, i: np.asarray(t)[np.asarray(i)],
        jax.ShapeDtypeStruct(idx.shape, table.dtype), table, idx)


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_training_is_the_gathers_bit_for_bit(name, monkeypatch):
    """The model and the scores, training and valid, that a gather of
    the same [L] table gives: the same run with the lookup put back to
    ``table[idx]``, as a callback XLA cannot look into. (A gather it can
    look into is not the chip's gather here: the CPU backend fuses the
    ``leaf_value * lr`` behind the table into it and contracts the
    multiply with the add that follows, one ulp away; the chip gathers
    from the rounded table, and so do the selects on both.) An index
    outside [0, num_leaves) would show here, where the two forms differ
    (the padded tail of the row shards is stored, so its scores are
    compared too)."""
    before = _form_counts()
    model, scores, valid = _train(name)
    after = _form_counts()
    assert after["select"] > before["select"]
    assert after["gather"] == before["gather"]
    monkeypatch.setattr(boosting, "per_row_lookup", _opaque_gather)
    model_g, scores_g, valid_g = _train(name)
    assert _form_counts() == after
    assert model == model_g
    assert scores.tobytes() == scores_g.tobytes()
    assert len(valid) == len(valid_g)
    for a, b in zip(valid, valid_g):
        assert a.tobytes() == b.tobytes()
