"""hist_kernel_roofline (layer: kernels): the least time the chip could
take for one tree's histogram work (``roofline.histogram_work``, from the
configuration's shapes only) over the Mosaic histogram kernels' summed
device time in the traced iteration. ``roofline.least_seconds`` names the
bound: at 63M x 28 operations (the one-hot contraction) against the
bfloat16 peak, within 2% of the byte bound, and bytes against the int8
peak, which a configuration that sums quantized gradients is held to."""

from hist_kernels import kernel_seconds  # metrics/ is on run.py's path

import roofline  # benchmarks/ is on the path of run.py


def read(ctx):
    seconds = kernel_seconds(ctx)
    if not seconds:
        return None
    s = ctx["shapes"]
    work = roofline.histogram_work(s["rows"], s["features"], s["max_bin"],
                                   s["num_leaves"])
    least = roofline.least_seconds(work, ctx["peaks"], s.get("int8", False))
    return 100.0 * least["seconds"] / seconds
