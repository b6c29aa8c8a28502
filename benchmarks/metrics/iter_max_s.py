"""iter_max_s (layer: grower / iteration): host clock, the slowest whole
iteration of the traced run's window; the iteration under the profiler is
not among them. Shows a stall that the rate averages away."""


def read(ctx):
    if not ctx["iteration_seconds"]:
        return None
    return max(ctx["iteration_seconds"])
