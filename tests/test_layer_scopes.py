"""The layers inside the iteration program have names (ISSUE 26):
``lgbm/<layer>`` scopes in the lowered program, the layer table parsed
from optimised HLO, by-layer self seconds and idle-gap attribution from
an XSpace, program spans as ``TraceAnnotation``s while a profiler session
is live, and the always-on first-dispatch counters."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import profile as obs_profile
from lightgbm_tpu.obs import trace as obs_trace
from lightgbm_tpu.obs.xla import global_xla

LAYERS = ("gradient", "hist", "split", "partition", "score")
PATHS = {
    "waved": {},
    "waved-int8": {"use_quantized_grad": True, "num_grad_quant_bins": 126},
    "exact": {"tpu_wave_max": 0},
}


def _data(n=600, f=6, seed=0):
    r = np.random.RandomState(seed)
    x = r.randn(n, f)
    return x, ((x[:, 0] + x[:, 2]) > 0.1).astype(np.float64)


_lowered = {}


def _lowered_text(path: str) -> str:
    """StableHLO of the fused iteration at a tiny size, locations
    included (where a scope shows before any compiler touches it)."""
    if path not in _lowered:
        x, y = _data()
        bst = lgb.Booster(dict({"objective": "binary", "num_leaves": 7,
                                "verbosity": -1}, **PATHS[path]),
                          lgb.Dataset(x, label=y))
        g = bst._gbdt
        g._boost_from_average()
        _lowered[path] = g._make_fused().lower(
            g.bins_fm, tuple(g._valid_bins), g._obj_state(), g.scores,
            g._sample_mask, tuple(g._valid_scores), jnp.int32(0),
            jnp.float32(0.1)).as_text(debug_info=True)
    return _lowered[path]


# the programs the same stages are composed into besides the fused
# iteration (boosting.py): each names the layers its stages must keep
COMPOSED = {
    "boosting/fused_dart_iter": LAYERS + ("valid",),
    "boosting/stream_prep": ("gradient",),
    "boosting/stream_class_prep_0": ("gradient",),
    "boosting/stream_class_post_0": ("score", "valid"),
    "boosting/stream_dart_prep": ("gradient", "score", "valid"),
    "boosting/stream_dart_post_0": ("score", "valid"),
    "boosting/stream_dart_factors": ("score",),
}
_composed = {}


def _composed_layers(tag: str) -> set:
    """Layers in the table of a composed program, read from the
    executables of three tiny runs: resident DART, and GBDT and DART
    streamed in two slabs, each with a valid set and int8 gradients."""
    if not _composed:
        x, y = _data(3000)
        xv, yv = _data(300, seed=1)
        dart = {"boosting": "dart", "drop_rate": 0.9, "max_drop": 3}
        stream = {"tpu_stream": "on", "tpu_stream_slab_rows": 2048}
        for extra in (dart, stream, {**dart, **stream}):
            p = dict({"objective": "binary", "num_leaves": 7, "max_bin": 63,
                      "verbosity": -1}, **PATHS["waved-int8"], **extra)
            ds = lgb.Dataset(x, label=y, params=p)
            lgb.train(p, ds, 2, valid_sets=[
                lgb.Dataset(xv, label=yv, params=p, reference=ds)])
        for t in COMPOSED:
            _composed[t] = set(obs_profile.layer_table(t).values())
    return _composed[tag]


# (a) ------------------------------------------------------------------
@pytest.mark.parametrize(
    "path,layer",
    [(p, la) for p in sorted(PATHS) for la in LAYERS]
    + [(t, la) for t in sorted(COMPOSED) for la in COMPOSED[t]])
def test_fused_iteration_scopes_each_layer(path, layer):
    if path in COMPOSED:
        assert layer in _composed_layers(path), \
            f"no instruction under lgbm/{layer} in {path}"
        return
    # inside a scan body's private function a location's name is
    # relative ("lgbm/partition/..."), elsewhere it follows "jit(fused)/"
    assert re.search(rf'[/"]lgbm/{layer}[/"]', _lowered_text(path)), \
        f"no op under lgbm/{layer} in the {path} program"


def test_wave_passes_carry_their_wave():
    text = _lowered_text("waved-int8")
    for scope in ("lgbm/hist/root", "lgbm/hist/w00", "lgbm/split/w00",
                  "lgbm/split/apply/w00", "lgbm/gradient/quantize"):
        assert scope in text, scope


# (b) ------------------------------------------------------------------
HLO = '''HloModule jit_fused, is_scheduled=true

%fused_computation.1 (p.1: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  ROOT %add.9 = f32[8]{0} add(%p.1, %p.1), metadata={op_name="jit(fused)/lgbm/score/add" stack_frame_id=3}
}

%body.4 (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%arg), index=1
  %fusion.7 = f32[8]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(fused)/lgbm/split/apply/w03/while/body/lgbm/partition/select_n"}
  %all-reduce.2 = f32[8]{0} all-reduce(%fusion.7), to_apply=%sum, metadata={op_name="jit(fused)/lgbm/hist/w03/shard_map/lgbm/collective/psum"}
  ROOT %tuple.5 = (s32[], f32[8]{0}) tuple(%gte.0, %all-reduce.2)
}

ENTRY %main.9 (a: f32[8], b: u8[32,1024]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %b = u8[32,1024]{1,0:T(8,128)(4,1)} parameter(1)
  %lgbm_hist_multi_int8.1 = s32[4,504,128]{2,1,0:T(8,128)S(1)} custom-call(%b), custom_call_target="tpu_custom_call", metadata={op_name="jit(fused)/lgbm/hist/root/jit(hist_pallas_multi_int8)/lgbm_hist_multi_int8/pallas_call"}
  %while.3 = (s32[], f32[8]{0}) while(%tuple.1), condition=%cond.2, body=%body.4, metadata={op_name="jit(fused)/lgbm/split/apply/w03/while"}
  %fusion.8 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1
  %copy.4 = f32[8]{0} copy(%a), metadata={op_name="jit(fused)/copy"}
  ROOT %fusion.9 = f32[8]{0:T(256)} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(fused)/lgbm/score/add" stack_frame_id=3}
}
'''

TABLE_CASES = {
    # a fusion root of the entry computation, layout and all
    "%fusion.9 = f32[8]{0:T(256)}": "score",
    # a while and an instruction of its body; nested lgbm/: innermost wins
    "%while.3 = (s32[], f32[8]{0})": "split",
    "%fusion.7 = f32[8]{0}": "partition",
    "%all-reduce.2 = f32[8]{0}": "collective",
    # a Mosaic custom call: named by its kernel, whose own name holds
    # "lgbm_" but is no scope
    "%lgbm_hist_multi_int8.1 = s32[4,504,128]{2,1,0:T(8,128)S(1)}": "hist",
    # instructions inside a fused computation are in the table too
    "%add.9 = f32[8]{0}": "score",
}


@pytest.mark.parametrize("head", sorted(TABLE_CASES))
def test_layer_table_places(head):
    assert obs_profile.parse_layer_table(HLO)[head] == TABLE_CASES[head]


def test_layer_table_leaves_out_what_no_scope_claims():
    table = obs_profile.parse_layer_table(HLO)
    for head in ("%copy.4 = f32[8]{0}",            # op_name, no lgbm/
                 "%gte.1 = f32[8]{0}",              # no op_name
                 "%fusion.8 = f32[8]{0}",           # a fusion without one
                 "%a = f32[8]{0}", "%tuple.5 = (s32[], f32[8]{0})"):
        assert head not in table
    assert set(table.values()) == {"score", "split", "partition",
                                   "collective", "hist"}


def test_instruction_head_is_the_same_for_text_and_trace():
    in_text = ("  ROOT %fusion.74 = u8[63000000]{0:T(1024)(128)(4,1)} "
               "fusion(%bins, %idx), kind=kLoop")
    in_trace = ("%fusion.74 = u8[63000000]{0:T(1024)(128)(4,1)} "
                "fusion(u8[28,63000000]{1,0} %bins, s32[63000000]{0} %idx)")
    assert obs_profile.instruction_head(in_text) == \
        obs_profile.instruction_head(in_trace) == \
        "%fusion.74 = u8[63000000]{0:T(1024)(128)(4,1)}"
    assert obs_profile.instruction_head("jit_fused(123)") is None
    assert obs_profile.layer_of("jit(f)/lgbm/hist/w03/lgbm_hist/x") == "hist"
    assert obs_profile.layer_of("jit(f)/copy") is None


# (c) ------------------------------------------------------------------
XSPACE = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 9 offset_ps: 0 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 600000 }
    events { metadata_id: 2 offset_ps: 100000 duration_ps: 200000 }
    events { metadata_id: 3 offset_ps: 300000 duration_ps: 250000 }
    events { metadata_id: 4 offset_ps: 900000 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "%while.3 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %tuple.1), condition=%cond.2, body=%body.4" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %gte.1), kind=kLoop" } }
  event_metadata { key: 3 value { id: 3 name: "%all-reduce.2 = f32[8]{0} all-reduce(f32[8]{0} %fusion.7)" } }
  event_metadata { key: 4 value { id: 4 name: "%copy.4 = f32[8]{0} copy(f32[8]{0} %a)" } }
  event_metadata { key: 9 value { id: 9 name: "jit_fused_iter_impl(77)" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 7 name: "python" timestamp_ns: 500
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1050000 duration_ps: 500000 }
    events { metadata_id: 3 offset_ps: 0 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "lgbm/train/iteration" } }
  event_metadata { key: 2 value { id: 2 name: "lgbm/train/compile_or_load" } }
  event_metadata { key: 3 value { id: 3 name: "some/other/annotation" } }
}
'''


@pytest.fixture(scope="module")
def attributed():
    from jax.profiler import ProfileData
    return obs_profile.attribute_xspace(
        ProfileData.from_text_proto(XSPACE),
        {"_iter": "wrong/short", "fused_iter_impl": "boosting/fused_iter"},
        {"boosting/fused_iter": obs_profile.parse_layer_table(HLO)})


def test_self_seconds_by_layer(attributed):
    """The while [0, 600] covers a fusion (200) and an all-reduce (250):
    its own 150 are split's, theirs go to their own layers; the copy is in
    no scope."""
    assert attributed["by_layer"] == {
        "split": pytest.approx(150e-9),
        "partition": pytest.approx(200e-9),
        "collective": pytest.approx(250e-9)}
    assert attributed["unattributed_s"] == pytest.approx(100e-9)


def test_device_seconds_by_tag_longest_name_wins(attributed):
    assert attributed["by_tag"] == {
        "boosting/fused_iter": pytest.approx(1000e-9)}


def test_idle_gaps_name_the_innermost_program_span(attributed):
    """Host spans run [500, 2500] (iteration) and [1550, 2050]
    (compile_or_load); device ops cover [1000, 1600] and [1900, 2000]."""
    assert attributed["idle_gaps"] == [
        ("lgbm/train/iteration", pytest.approx(500e-9)),        # 500-1000
        ("lgbm/train/iteration", pytest.approx(500e-9)),        # 2000-2500
        ("lgbm/train/compile_or_load", pytest.approx(300e-9))]  # 1600-1900


# (d) ------------------------------------------------------------------
def test_span_is_the_shared_noop_without_tracer_or_profiler():
    tracer = obs_trace.Tracer()
    assert not tracer.enabled
    assert tracer.span("train/iteration") is obs_trace._NULL_SPAN


def test_span_annotates_only_while_a_profiler_session_is_live(tmp_path):
    from jax.profiler import ProfileData, TraceAnnotation
    tracer = obs_trace.Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        off = tracer.span("train/iteration")
        assert isinstance(off, TraceAnnotation)   # tracer off: only that
        with off:
            tracer.enable()
            with tracer.span("train/compile_or_load") as inner:
                inner.set_metadata(cache_hit=True)
            tracer.disable()
    finally:
        jax.profiler.stop_trace()
    assert tracer.span("train/iteration") is obs_trace._NULL_SPAN
    # the tracer's own record of the inner span, with its metadata
    (name, _, _, _, depth, _, args), = tracer._events
    assert (name, depth, args) == ("train/compile_or_load", 0,
                                   {"cache_hit": True})
    # and both spans in the profiler's trace, nested, on its clock
    data = ProfileData.from_file(obs_profile.find_xplane(str(tmp_path)))
    spans = {ev.name: (ev.start_ns, ev.start_ns + ev.duration_ns)
             for plane in data.planes for line in plane.lines
             for ev in line.events if ev.name.startswith("lgbm/")}
    outer = spans["lgbm/train/iteration"]
    inner = spans["lgbm/train/compile_or_load"]
    assert outer[0] <= inner[0] and inner[1] <= outer[1]


# (e) ------------------------------------------------------------------
def _train(request_table: bool):
    x, y = _data(seed=3)
    n0 = global_xla.n_programs
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbosity": -1, "seed": 1},
                    lgb.Dataset(x, label=y), num_boost_round=3)
    table = (obs_profile.layer_table("boosting/fused_iter")
             if request_table else None)
    return bst.model_to_string(), table, global_xla.records()[n0:]


def test_first_dispatch_counters_without_telemetry():
    assert not global_xla.enabled
    _, _, recs = _train(False)
    rec, = [r for r in recs if r["tag"] == "boosting/fused_iter"]
    assert rec["trace_lower_s"] > 0 and rec["compile_or_load_s"] > 0
    assert isinstance(rec["cache_hit"], bool)
    assert "flops" not in rec          # cost analysis stays behind telemetry


def test_model_is_the_same_with_the_table_requested_and_not():
    plain, _, _ = _train(False)
    asked, table, _ = _train(True)
    assert asked == plain
    assert set(LAYERS) <= set(table.values())
    # the table outlives the Booster that ran the program
    assert obs_profile.layer_table("boosting/fused_iter") is table
    assert obs_profile.layer_table("boosting/never_ran") is None


def test_taking_the_compiled_lowers_and_compiles_nothing_twice():
    """A train-phase boundary dispatches through jit and takes the
    program's Compiled afterwards from jit's own caches: one lowering
    and one backend compile for the program, donated arguments or not,
    and the table is that program's."""
    import jax.monitoring as monitoring
    from lightgbm_tpu.obs.xla import XlaIntrospector, instrumented_jit
    seen = []
    monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: seen.append(event.rsplit("/", 1)[-1]))

    def f(a, buf):
        with jax.named_scope("lgbm/score"):
            return buf + a.sum()

    reg = XlaIntrospector()
    g = instrumented_jit("test/taken", f, phase="train", registry=reg,
                         donate_argnums=(1,))
    a, buf, buf2 = jnp.ones((8, 8)), jnp.zeros(8), jnp.zeros(8)
    del seen[:]                    # the arrays' own programs are not f's
    out = g(a, buf)
    first = list(seen)
    g(a, buf2)                     # second call: nothing new
    assert seen == first
    assert first.count("jaxpr_to_mlir_module_duration") == 1
    assert first.count("backend_compile_duration") == 1
    assert buf.is_deleted() and float(out[0]) == 64.0
    assert reg.n_programs == 1
    assert set(obs_profile.layer_table("test/taken").values()) == {"score"}


@pytest.mark.parametrize("phase,root", [("train", "train"),
                                        ("grow", "train"),
                                        ("predict", "predict")])
def test_first_dispatch_spans_are_named_by_phase(phase, root):
    """The counters' two intervals as spans of the tracer, back to back
    and inside the span the first dispatch ran in."""
    from lightgbm_tpu.obs.trace import global_tracer
    from lightgbm_tpu.obs.xla import XlaIntrospector, instrumented_jit
    reg = XlaIntrospector()
    g = instrumented_jit("test/spans-" + phase, lambda x: x * 2,
                         phase=phase, registry=reg)
    was = global_tracer.enabled
    n0 = len(global_tracer._events)
    global_tracer.enable()
    try:
        with global_tracer.span("train/iteration"):
            g(jnp.ones(3))
            g(jnp.ones(3))
    finally:
        if not was:
            global_tracer.disable()
    events = {e[0]: e for e in global_tracer._events[n0:]}
    outer = events["train/iteration"]
    lower = events[root + "/trace_lower"]
    load = events[root + "/compile_or_load"]
    assert load[6]["cache_hit"] in (True, False)
    assert outer[1] <= lower[1] and lower[1] + lower[2] <= load[1] + 1000
    assert load[1] + load[2] <= outer[1] + outer[2]
    rec, = reg.records()
    assert lower[2] == pytest.approx(rec["trace_lower_s"] * 1e9, abs=2000)
    assert len(global_tracer._events) - n0 == 3   # one first dispatch
