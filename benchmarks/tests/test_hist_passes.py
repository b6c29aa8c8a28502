"""The three readers of the histogram passes' counts (``hist_turn_us``,
``hist_pass_fixed_s``, ``hist_fill_pct``) on a hand-built trace: thirteen
kernel events (the root's pass and twelve waves) whose device seconds are
a planted fixed part plus a planted cost a counted turn, among other
operations, and the newest tree's counts as the program publishes them.
The line gives the planted numbers back, whether the passes pair with
their events by scope or by order, and nothing is read where the program
publishes no counts or the events and passes differ in number."""

import pytest
import run
import xtrace

FIXED_S, TURN_S = 0.0123, 1.5e-6
# the twelve wave passes of a 255-leaf tree: counted turns, live rows
TURNS = [9000, 7600, 6100, 5800, 5200, 4300, 4300, 4100, 3700, 3500, 3300,
         3100]
LIVE = [31_000_000, 21_000_000, 16_400_000, 15_100_000, 13_900_000,
        10_100_000, 10_100_000, 9_500_000, 7_600_000, 6_900_000, 6_300_000,
        5_700_000]
ROOT_TURNS, ROOT_S = 30_768, 0.0787


def passes():
    root = {"pass": "root", "slots": 1, "rows_passed": 63_000_000,
            "rows_live": 63_000_000, "k_tiles": ROOT_TURNS,
            "chunks_squeezed": 0, "k_tiles_full": ROOT_TURNS,
            "k_tile": 2048, "feature_blocks": 1}
    waves = [{"pass": f"w{i:02d}", "slots": 1 << min(i, 5),
              "rows_passed": 63_000_000, "rows_live": live, "k_tiles": turns,
              "chunks_squeezed": 3846, "k_tiles_full": 61_536,
              "k_tile": 1024, "feature_blocks": 1}
             for i, (turns, live) in enumerate(zip(TURNS, LIVE))]
    return [root] + waves


def kernel(i):
    return (f"%lgbm_hist_multi_int8.{25 + i} = (s32[1,2048,128]{{2,1,0}}, "
            f"s32[1,8,128]{{2,1,0}}) custom-call(u8[32,63012864]{{1,0}} "
            f"%pad.2), custom_call_target=\"tpu_custom_call\"")


def head(i):
    return kernel(i).split(" custom-call(")[0]


OTHER = "%fusion.74 = s32[63012864]{0} fusion(s32[63012864]{0} %rl)"


def events(seconds):
    """The kernels in this order of time, a partition fusion after each."""
    out, at = [], 0.0
    for i, s in enumerate(seconds):
        out.append((kernel(i), at, s * 1e9, ""))
        out.append((OTHER, at + s * 1e9, 8e6, ""))
        at += s * 1e9 + 8e6
    return out


def planted():
    return [ROOT_S] + [FIXED_S + TURN_S * t for t in TURNS]


@pytest.fixture
def program(monkeypatch):
    """The program's two tables as a run would leave them: the newest
    tree's counted passes, and a scope table (None: none published)."""
    from lightgbm_tpu.obs import profile
    from lightgbm_tpu.obs.metrics import global_metrics
    monkeypatch.setitem(global_metrics.meta, "hist_live_rows",
                        [[{"pass": "root"}], passes()])
    scopes = {}

    def scope_table(tag):
        return scopes.get(tag)
    monkeypatch.setattr(profile, "scope_table", scope_table)
    return scopes


def ctx(evs):
    return {"trace": xtrace.Trace(devices={"/device:TPU:0": evs})}


def read_all(c):
    return {name: run.load_reader(name)(c)
            for name in ("hist_turn_us", "hist_pass_fixed_s",
                         "hist_fill_pct")}


FILL = 100.0 * sum(LIVE) / (1024 * sum(TURNS))


def test_the_line_gives_back_the_planted_numbers_by_order(program):
    got = read_all(ctx(events(planted())))
    assert got["hist_turn_us"] == pytest.approx(1e6 * TURN_S, rel=1e-9)
    assert got["hist_pass_fixed_s"] == pytest.approx(FIXED_S, rel=1e-9)
    assert got["hist_fill_pct"] == pytest.approx(FILL, rel=1e-12)


def test_passes_pair_by_scope_where_each_kernel_names_its_pass(program):
    """The kernels ran in another order than the passes are listed in (a
    trace in which order would mislead): the scope table puts each
    event's seconds with its own pass."""
    order = [0] + list(range(12, 0, -1))          # root, then w11 ... w00
    seconds = planted()
    program["boosting/fused_iter"] = {
        head(i): "hist/" + ("root" if k == 0 else f"w{k - 1:02d}")
        for i, k in enumerate(order)}
    got = read_all(ctx(events([seconds[k] for k in order])))
    assert got["hist_turn_us"] == pytest.approx(1e6 * TURN_S, rel=1e-9)
    assert got["hist_pass_fixed_s"] == pytest.approx(FIXED_S, rel=1e-9)
    # by order the same trace reads another line
    program.clear()
    wrong = read_all(ctx(events([seconds[k] for k in order])))
    assert wrong["hist_turn_us"] < 0


def test_one_callees_scope_for_every_kernel_pairs_by_order(program):
    program["boosting/fused_iter"] = {head(i): "hist/w00" for i in range(13)}
    got = read_all(ctx(events(planted())))
    assert got["hist_turn_us"] == pytest.approx(1e6 * TURN_S, rel=1e-9)
    assert got["hist_pass_fixed_s"] == pytest.approx(FIXED_S, rel=1e-9)


def test_chips_are_averaged(program):
    evs0, evs1 = events(planted()), events([2 * s for s in planted()])
    c = {"trace": xtrace.Trace(devices={"/device:TPU:0": evs0,
                                        "/device:TPU:1": evs1})}
    got = read_all(c)
    assert got["hist_turn_us"] == pytest.approx(1.5e6 * TURN_S, rel=1e-9)
    assert got["hist_pass_fixed_s"] == pytest.approx(1.5 * FIXED_S, rel=1e-9)


def test_nothing_is_read_where_events_and_passes_differ_in_number(program):
    short = [e for e in events(planted()) if e[0] != kernel(7)]
    assert set(read_all(ctx(short)).values()) == {None}
    extra = events(planted()) + [(kernel(40), 9e12, 1e6, "")]
    assert set(read_all(ctx(extra)).values()) == {None}


def test_nothing_is_read_where_the_program_publishes_no_counts(monkeypatch):
    """A program that counts nothing: no record, or records of a host
    reckoning (rows and K-sub-tiles without the kernel's counts)."""
    from lightgbm_tpu.obs.metrics import global_metrics
    monkeypatch.delitem(global_metrics.meta, "hist_live_rows",
                        raising=False)
    assert set(read_all(ctx(events(planted()))).values()) == {None}
    reckoned = [{"pass": p["pass"], "slots": p["slots"],
                 "rows_live": p["rows_live"], "rows_passed": 63_000_000,
                 "k_tiles": p["k_tiles"], "k_tiles_full": 61_536,
                 "squeeze": "columns"} for p in passes()]
    monkeypatch.setitem(global_metrics.meta, "hist_live_rows", [reckoned])
    assert set(read_all(ctx(events(planted()))).values()) == {None}
