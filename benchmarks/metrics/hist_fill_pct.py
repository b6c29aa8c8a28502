"""hist_fill_pct (layer: kernels): of the lanes the multi-leaf histogram
kernel multiplied in the traced tree's wave passes, the share that held a
live row: 100 x the live rows each feature block's steps found / (the
loop's turns x the rows a turn multiplies), summed over the wave passes,
all as the kernel counted them. What the squeeze leaves ragged (the
tallest lane column sets a chunk's turns) and a part-filled last
sub-tile cost shows here.

How the passes are counted and paired with their kernel events: see
``hist_turn_us.py`` beside this file; a tree whose passes do not pair
with the traced iteration's kernel events is not read."""

from hist_turn_us import wave_passes  # metrics/ is on run.py's path


def read(ctx):
    waves = wave_passes(ctx)
    if not waves:
        return None
    multiplied = sum(p["k_tiles"] * p["k_tile"] for p, _ in waves)
    if multiplied <= 0:
        return None
    live = sum(p["rows_live"] * p["feature_blocks"] for p, _ in waves)
    return 100.0 * live / multiplied
