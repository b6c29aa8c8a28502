"""first_iter_load_s (layer: iteration program; moves setup_s): host
seconds the first dispatch of ``boosting/fused_iter`` spent in the
backend compile (JAX's own duration event): a compile on a cold
persistent cache, the executable's load on a hit (the program's counter
``compile_or_load_s``, ``obs/xla.py``; its ``cache_hit`` says which)."""

from layers import first_dispatch  # metrics/ is on run.py's path


def read(ctx):
    return first_dispatch("compile_or_load_s")
