"""hist_kernel_share_pct (layer: kernels): the Mosaic histogram kernels'
summed device time over the traced iteration's busy device time.

How the kernels are found: see ``hist_kernels.py`` beside this file."""

from hist_kernels import kernel_seconds  # metrics/ is on run.py's path


def read(ctx):
    seconds = kernel_seconds(ctx)
    if seconds is None or ctx["busy_s"] <= 0:
        return None
    return 100.0 * seconds / ctx["busy_s"]
