"""``metrics/layers.py`` on a hand-built event list and a hand-built
layer table: self seconds by layer, the chips averaged, what no entry
places, and the eight readers on a program that publishes a table and on
one that publishes none."""

import layers
import pytest
import run
import xtrace

# event names as the v5e trace gives them: the whole instruction, operand
# shapes after the opcode; the table's keys stop before the opcode
GATHER = ("%fusion.74 = u8[63000000]{0:T(1024)(128)(4,1)} fusion(u8[28,"
          "63000000]{1,0} %bins, s32[63000000]{0} %idx), kind=kLoop")
KERNEL = ("%lgbm_hist_multi_int8.3 = s32[4,504,128]{2,1,0:T(8,128)S(1)} "
          "custom-call(u8[32,63000576]{1,0} %pad.2), "
          'custom_call_target="tpu_custom_call"')
WHILE = ("%while.9 = (s32[], f32[255]{0}) while((s32[], f32[255]{0}) "
         "%tuple.4), condition=%cond, body=%body")
BODY_OP = "%fusion.12 = f32[255]{0} fusion(f32[255]{0} %p), kind=kLoop"
SCORE = "%fusion.3 = f32[63000000]{0:T(1024)} fusion(f32[255]{0} %lv)"
RELEAF = ("%fusion.5 = s32[63000000]{0:T(1024)} fusion(s32[63000000]{0} "
          "%rl), kind=kLoop")
STRAY = "%copy.1 = f32[8]{0} copy(f32[8]{0} %x)"

TABLE = {
    "%fusion.74 = u8[63000000]{0:T(1024)(128)(4,1)}": "partition",
    "%lgbm_hist_multi_int8.3 = s32[4,504,128]{2,1,0:T(8,128)S(1)}": "hist",
    "%while.9 = (s32[], f32[255]{0})": "split",
    "%fusion.12 = f32[255]{0}": "split",
    "%fusion.3 = f32[63000000]{0:T(1024)}": "score",
    "%fusion.5 = s32[63000000]{0:T(1024)}": "partition",
}

# one chip: gather, kernel, a while [400, 700] over one body op, the score
# update, the row-to-leaf fusion, and an instruction in no table
CHIP0 = [
    (GATHER, 0.0, 200.0, ""),
    (KERNEL, 200.0, 100.0, ""),
    (WHILE, 400.0, 300.0, ""),
    (BODY_OP, 450.0, 200.0, ""),
    (SCORE, 700.0, 50.0, ""),
    (RELEAF, 800.0, 40.0, ""),
    (STRAY, 900.0, 10.0, ""),
]
CHIP1 = [(GATHER, 0.0, 100.0, "")]


def head_of(text):
    """The program's ``instruction_head``, as the test would have it."""
    from lightgbm_tpu.obs.profile import instruction_head
    return instruction_head(text)


def test_self_seconds_land_on_each_instructions_layer():
    got = layers.sum_by_layer({"/device:TPU:0": CHIP0}, TABLE, head_of)
    assert got == {
        "partition": pytest.approx(240e-9),   # gather + row-to-leaf
        "hist": pytest.approx(100e-9),
        "split": pytest.approx(300e-9),       # the while AND its body
        "score": pytest.approx(50e-9),
        layers.UNATTRIBUTED: pytest.approx(10e-9),
    }
    assert sum(got.values()) == pytest.approx(
        xtrace.busy_ns(CHIP0) / 1e9)


def test_chips_are_averaged():
    got = layers.sum_by_layer({"/device:TPU:0": CHIP0,
                               "/device:TPU:1": CHIP1}, TABLE, head_of)
    assert got["partition"] == pytest.approx((240e-9 + 100e-9) / 2)
    assert got["hist"] == pytest.approx(100e-9 / 2)


def test_a_head_the_table_prints_otherwise_is_unattributed():
    """The match is on the whole head, layout and all: a table that
    prints another layout for an instruction does not place it."""
    table = {k: v for k, v in TABLE.items() if not k.startswith("%fusion.5")}
    table["%fusion.5 = s32[63000000]{0:T(512)}"] = "partition"
    got = layers.sum_by_layer({"/device:TPU:0": CHIP0}, table, head_of)
    assert got["partition"] == pytest.approx(200e-9)
    assert got[layers.UNATTRIBUTED] == pytest.approx(50e-9)


LAYER_READERS = {"layer_partition_s": 240e-9, "layer_hist_s": 100e-9,
                 "layer_split_s": 300e-9, "layer_score_s": 50e-9,
                 "layer_gradient_s": 0.0}


def _ctx():
    trace = xtrace.Trace(devices={"/device:TPU:0": CHIP0})
    return {"trace": trace, "busy_s": xtrace.busy_ns(CHIP0) / 1e9}


@pytest.mark.parametrize("name", sorted(LAYER_READERS))
def test_reader_reads_its_layer(monkeypatch, name):
    monkeypatch.setattr(layers, "published_table",
                        lambda: (TABLE, head_of))
    assert run.load_reader(name)(_ctx()) == pytest.approx(
        LAYER_READERS[name])


def test_unattributed_share_and_one_pass_for_all_readers(monkeypatch):
    calls = []

    def published():
        calls.append(1)
        return TABLE, head_of

    monkeypatch.setattr(layers, "published_table", published)
    ctx = _ctx()
    assert run.load_reader("layer_unattributed_pct")(ctx) == pytest.approx(
        100.0 * 10e-9 / ctx["busy_s"])
    for name in LAYER_READERS:
        run.load_reader(name)(ctx)
    assert calls == [1]


@pytest.mark.parametrize("name", sorted(LAYER_READERS)
                         + ["layer_unattributed_pct"])
def test_no_table_no_metric(monkeypatch, name):
    monkeypatch.setattr(layers, "published_table", lambda: None)
    assert run.load_reader(name)(_ctx()) is None


def test_first_dispatch_counters_come_from_the_programs_records(monkeypatch):
    from lightgbm_tpu.obs.xla import global_xla
    recs = [{"tag": "boosting/grow", "trace_lower_s": 9.0,
             "compile_or_load_s": 9.0},
            {"tag": "boosting/fused_iter", "trace_lower_s": 4.5,
             "compile_or_load_s": 13.25, "cache_hit": True},
            {"tag": "boosting/fused_iter", "trace_lower_s": 1.0,
             "compile_or_load_s": 1.0}]
    monkeypatch.setattr(global_xla, "records", lambda: recs)
    assert run.load_reader("first_iter_trace_lower_s")({}) == 4.5
    assert run.load_reader("first_iter_load_s")({}) == 13.25
    # a program that records nothing (the parent of PR 26, telemetry off)
    monkeypatch.setattr(global_xla, "records", lambda: [])
    assert run.load_reader("first_iter_trace_lower_s")({}) is None
    assert run.load_reader("first_iter_load_s")({}) is None
