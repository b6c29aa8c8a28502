"""hist_pass_fixed_s (layer: kernels): the device seconds a wave pass of
the multi-leaf histogram kernel costs beyond its sub-tile turns: the
intercept of ``hist_turn_us``' line through the traced tree's wave
passes (kernel seconds against the turns the kernel counted). It holds
what every grid step pays whatever its live rows: the live mask, the
prefix count, the squeeze and the step's own cycles.

How the passes are counted and paired with their kernel events: see
``hist_turn_us.py`` beside this file."""

from hist_turn_us import turn_line  # metrics/ is on run.py's path


def read(ctx):
    line = turn_line(ctx)
    return None if line is None else line[1]
