"""device_idle_pct (layer: device): the share of the traced iteration in
which no operation ran on the device. 100 x (1 - busy / window): busy is
the union of the device-operation intervals of the trace (averaged over
the chips used), window the host-clock length of the traced iteration,
from the profiler's start to ``block_until_ready`` on the scores."""


def read(ctx):
    if not ctx["events"] or ctx["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
