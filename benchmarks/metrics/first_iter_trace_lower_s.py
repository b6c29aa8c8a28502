"""first_iter_trace_lower_s (layer: iteration program; moves setup_s):
host seconds the first dispatch of ``boosting/fused_iter`` spent in
Python tracing and lowering (the call's start to the start of the
backend compile), as the program's first-dispatch counter
``trace_lower_s`` holds them (``obs/xla.py``)."""

from layers import first_dispatch  # metrics/ is on run.py's path


def read(ctx):
    return first_dispatch("trace_lower_s")
