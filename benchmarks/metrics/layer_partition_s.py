"""layer_partition_s (layer: partition): device self seconds of the
instructions under the scope ``lgbm/partition`` in the traced iteration
(``part_ops.apply_wave_splits``: each row's bin on its leaf's split
feature, and the row-to-leaf update). See ``layers.py`` beside this file."""

from layers import layer_s  # metrics/ is on run.py's path


def read(ctx):
    return layer_s(ctx, "partition")
