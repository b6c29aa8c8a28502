"""layer_split_s (layer: split search): device self seconds under
``lgbm/split``: root totals, the scan that applies stored splits, sibling
subtraction and the search at every wave boundary. See ``layers.py``
beside this file."""

from layers import layer_s  # metrics/ is on run.py's path


def read(ctx):
    return layer_s(ctx, "split")
