"""layer_score_s (layer: score update): device self seconds under
``lgbm/score``: ``scores += leaf_value[row_leaf]``. See ``layers.py``
beside this file."""

from layers import layer_s  # metrics/ is on run.py's path


def read(ctx):
    return layer_s(ctx, "score")
