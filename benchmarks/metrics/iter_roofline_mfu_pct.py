"""iter_roofline_mfu_pct (layer: iteration program): the whole step's
share of the chip's peak. The least time the chip could take for one
boosting iteration's work (``roofline.iteration_work``, from the
configuration's shapes only) over the seconds in which an operation ran on
the device during the traced iteration (``busy_s``: the union of the
device's operations in the trace, no host clock in it; what the device
sat idle is ``device_idle_pct``'s). It still bounds a gain when a later PR
takes a kernel off the path and that kernel's roofline falls silent."""

import roofline  # benchmarks/ is on the path of run.py


def read(ctx):
    if ctx["busy_s"] <= 0:
        return None
    s = ctx["shapes"]
    work = roofline.iteration_work(s["rows"], s["features"], s["max_bin"],
                                   s["num_leaves"])
    least = roofline.least_seconds(work, ctx["peaks"], s.get("int8", False))
    return 100.0 * least["seconds"] / ctx["busy_s"]
