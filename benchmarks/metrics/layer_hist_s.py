"""layer_hist_s (layer: histogram pass): device self seconds under
``lgbm/hist``: the Mosaic kernels with the pads, transposes and
dequantisation around them (``hist_kernel_share_pct`` times the kernels
alone, from outside). See ``layers.py`` beside this file."""

from layers import layer_s  # metrics/ is on run.py's path


def read(ctx):
    return layer_s(ctx, "hist")
