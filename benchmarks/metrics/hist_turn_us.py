"""hist_turn_us (layer: kernels): the device cost of one sub-tile turn of
the multi-leaf histogram kernel's loop, in microseconds: the slope of a
least-squares line through the traced tree's wave passes, each pass's
kernel device seconds against the turns its steps counted.

The counts are the program's own. Every grid step of the kernel adds the
live lanes it found, its loop's turns and whether it squeezed into a small
int32 output beside the histograms; the grower keeps each pass's sums with
the tree, and the tree's record brings them to the host
(``global_metrics.meta["hist_live_rows"]``, one list of passes a tree, the
newest last). ``run.py`` asks for the model before it reads the trace, so
the newest tree is the traced iteration's. A program that publishes no
counts gives ``None``.

A pass is paired with its kernel event in the traced iteration (the
events ``hist_kernels.py`` finds) by the scope the program names it under
(``lgbm/hist/root``, ``lgbm/hist/w03``: ``obs.profile.scope_table``) where
every kernel event's scope names a pass of its own, else by order on the
chip's one device stream. Either way there must be as many kernel events
as passes, or nothing is read. The root's pass multiplies whole chunks at
another sub-tile and stays out of the line; ``hist_pass_fixed_s`` reads
its intercept and ``hist_fill_pct`` the same passes' counts.
"""

import hist_kernels  # metrics/ is on run.py's path
import layers

_KEY = "hist_wave_passes"


def counted_passes():
    """The newest tree's passes as the program counted them (dicts with
    ``pass``, ``rows_live``, ``k_tiles``, ``k_tile``,
    ``feature_blocks``, ...), or None where the program publishes none."""
    try:
        from lightgbm_tpu.obs.metrics import global_metrics
    except ImportError:
        return None
    kept = global_metrics.meta.get("hist_live_rows") or []
    if not kept or not all("chunks_squeezed" in p and "k_tile" in p
                           for p in kept[-1]):
        return None
    return kept[-1]


def published_scopes():
    """(the tags' scope tables merged, ``instruction_head``), or None
    where the program publishes none."""
    try:
        from lightgbm_tpu.obs.profile import instruction_head, scope_table
    except ImportError:
        return None
    merged = {}
    for tag in layers.TAGS:
        merged.update(scope_table(tag) or {})
    return (merged, instruction_head) if merged else None


def pair(events, passes, scopes):
    """[kernel seconds of each pass] on one chip, in the passes' order,
    or None where the chip's kernel events and the passes differ in
    number."""
    kernels = sorted((e for e in events if hist_kernels.is_hist_kernel(e)),
                     key=lambda e: e[1])
    if len(kernels) != len(passes):
        return None
    if scopes is not None:
        table, head_of = scopes
        named = {table.get(head_of(e[0]), ""): e[2] / 1e9 for e in kernels}
        want = ["hist/" + p["pass"] for p in passes]
        if sorted(named) == sorted(want):
            return [named[w] for w in want]
    return [e[2] / 1e9 for e in kernels]


def wave_passes(ctx):
    """[(pass, kernel seconds averaged over the chips)] of the traced
    tree's wave passes, once per run; None where nothing pairs."""
    if _KEY not in ctx:
        ctx[_KEY] = None
        passes = counted_passes()
        devices = ctx["trace"].devices
        if passes and devices:
            scopes = published_scopes()
            chips = [pair(events, passes, scopes)
                     for events in devices.values()]
            if all(c is not None for c in chips):
                ctx[_KEY] = [
                    (p, sum(c[i] for c in chips) / len(chips))
                    for i, p in enumerate(passes) if p["pass"] != "root"]
    return ctx[_KEY]


def turn_line(ctx):
    """(seconds a turn, seconds a pass beyond its turns): the
    least-squares line of the wave passes' kernel seconds against their
    counted turns; None with fewer than two passes or one turn count."""
    waves = wave_passes(ctx)
    if not waves or len(waves) < 2:
        return None
    x = [p["k_tiles"] for p, _ in waves]
    y = [s for _, s in waves]
    mx, my = sum(x) / len(x), sum(y) / len(y)
    sxx = sum((a - mx) ** 2 for a in x)
    if sxx <= 0:
        return None
    slope = sum((a - mx) * (b - my) for a, b in zip(x, y)) / sxx
    return slope, my - slope * mx


def read(ctx):
    line = turn_line(ctx)
    return None if line is None else 1e6 * line[0]
