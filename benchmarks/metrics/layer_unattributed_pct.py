"""layer_unattributed_pct (layer: iteration program): 100 x the device
self seconds of events that the program's layer table does not place (no
entry, or an ``op_name`` under no ``lgbm/`` scope) over ``busy_s``: how
far the ``layer_*_s`` readings can be trusted. See ``layers.py``."""

from layers import UNATTRIBUTED, layer_seconds  # metrics/ is on the path


def read(ctx):
    seconds = layer_seconds(ctx)
    if seconds is None or ctx["busy_s"] <= 0:
        return None
    return 100.0 * seconds.get(UNATTRIBUTED, 0.0) / ctx["busy_s"]
