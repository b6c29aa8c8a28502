"""layer_gradient_s (layer: gradient and quantisation): device self
seconds under ``lgbm/gradient``: the objective's gradient, the stochastic
rounding (two threefry draws) and quantisation, and the stacked kernel
operand. See ``layers.py`` beside this file."""

from layers import layer_s  # metrics/ is on run.py's path


def read(ctx):
    return layer_s(ctx, "gradient")
