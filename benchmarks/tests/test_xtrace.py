"""The trace reduction on a hand-built event list: busy union, Mosaic
kernel matching, idle gaps, self times. An event is (name, start_ns,
duration_ns, text)."""

import xtrace
from hist_kernels import is_hist_kernel, kernel_seconds

# an event's name is the whole HLO instruction, as the v5e trace gives it
KERNEL = ('%hist_pallas_multi_fused.14 = f32[4,504,128]{2,1,0:T(8,128)S(1)} '
          'custom-call(u8[32,63000576]{1,0:T(8,128)(4,1)} %pad.809), '
          'custom_call_target="tpu_custom_call"')
OTHER_CALL = ('%sort.3 = f32[128]{0} custom-call(f32[128]{0} %x), '
              'custom_call_target="tpu_custom_call"')

# one device line: a while loop [100, 900] holding two kernels and a
# fusion, then a gap, then a copy that overlaps nothing
EVENTS = [
    ("while.1", 100.0, 800.0, ""),
    (KERNEL, 150.0, 300.0, ""),
    ("fusion.7", 450.0, 100.0, ""),
    (KERNEL, 600.0, 200.0, ""),
    ("copy.2", 1000.0, 50.0, ""),
]


def test_busy_is_the_union_not_the_sum():
    assert xtrace.merged_intervals(EVENTS) == [[100.0, 900.0],
                                               [1000.0, 1050.0]]
    assert xtrace.busy_ns(EVENTS) == 850.0


def test_self_time_takes_children_out_of_their_parent():
    own = xtrace.self_times(EVENTS)
    assert own[KERNEL] == 500.0
    assert own["fusion.7"] == 100.0
    assert own["while.1"] == 800.0 - 600.0
    assert own["copy.2"] == 50.0
    assert sum(own.values()) == xtrace.busy_ns(EVENTS)


def test_kernel_seconds_sums_the_kernels_and_finds_nothing_in_plain_xla():
    trace = xtrace.Trace(devices={"/device:TPU:0": EVENTS})
    assert kernel_seconds({"trace": trace}) == 500.0 / 1e9
    plain = xtrace.Trace(devices={"/device:TPU:0": [EVENTS[0], EVENTS[2]]})
    assert kernel_seconds({"trace": plain}) is None
    assert is_hist_kernel((KERNEL, 0.0, 1.0, ""))
    assert not is_hist_kernel((OTHER_CALL, 0.0, 1.0, ""))
    assert not is_hist_kernel(EVENTS[2])


def test_short_names_add_the_passes_of_one_kernel_up():
    assert xtrace.short_name(KERNEL) == \
        "hist_pallas_multi_fused f32[4,504,128]"
    assert xtrace.short_name(
        "%fusion.74 = u8[63000000]{0:T(1024)(128)(4,1)} fusion(u8[28,6]"
        "{1,0} %a), kind=kCustom") == "fusion u8[63000000]"
    assert xtrace.short_name("while.1") == "while.1"


def test_idle_gaps_longest_first_and_clipped_to_the_window():
    gaps = xtrace.idle_gaps(EVENTS, 0.0, 1200.0)
    assert gaps == [(1050.0, 150.0), (0.0, 100.0), (900.0, 100.0)]
    assert xtrace.idle_gaps(EVENTS, 200.0, 800.0) == []


def test_a_gap_is_named_by_the_innermost_host_span():
    spans = [("bench/iteration", 0.0, 2000.0), ("bench/inner", 900.0, 150.0)]
    assert xtrace.span_at(spans, 950.0) == "bench/inner"
    assert xtrace.span_at(spans, 1500.0) == "bench/iteration"
    assert xtrace.span_at(spans, 5000.0) == "outside the harness's spans"
