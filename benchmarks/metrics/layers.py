"""How the traced iteration's device time divides among the program's
named layers (shared by the ``layer_*`` readers; not a metric itself).

The program scopes its work as ``lgbm/<layer>`` (``jax.named_scope``:
sample, gradient, hist, split, partition, score, renew, valid, records,
collective) and publishes, for the executable that ran, a table from
HLO instruction to layer: ``lightgbm_tpu.obs.profile.layer_table(tag)``,
keyed by ``instruction_head``: the ``%name = shape`` an instruction's
text starts with. On the device plane's "XLA Ops" line an event's name is
that same instruction text (with operand shapes after the opcode), so:
per chip, ``xtrace.self_times`` by full instruction (a ``while`` less its
body), each instruction looked up by its head; summed by layer, averaged
over the chips, kept on ``ctx`` so that the six readers pay once. What no
table entry places is summed under ``UNATTRIBUTED``.

This file and the two ``first_iter_*`` readers are the only files of the
benchmark that import the program. A program that publishes no table
(the parent of PR 26, or a run whose program fell back from its AOT
route) gives ``None`` here and the readers leave their metrics out.
"""

import xtrace  # benchmarks/ is on the path of run.py

TAGS = ("boosting/fused_iter", "boosting/fused_dart_iter")
UNATTRIBUTED = ""
_KEY = "layer_seconds"


def published_table():
    """(the tags' tables merged, the program's ``instruction_head``), or
    None where the program publishes neither."""
    try:
        from lightgbm_tpu.obs.profile import instruction_head, layer_table
    except ImportError:
        return None
    merged = {}
    for tag in TAGS:
        merged.update(layer_table(tag) or {})
    return (merged, instruction_head) if merged else None


def sum_by_layer(devices: dict, table: dict, head_of) -> dict:
    """{layer: seconds} of one trace: self time of every device event by
    the layer of its instruction, averaged over the chips."""
    out = {}
    for events in devices.values():
        for name, ns in xtrace.self_times(events).items():
            layer = table.get(head_of(name), UNATTRIBUTED)
            out[layer] = out.get(layer, 0.0) + ns / 1e9
    n = max(1, len(devices))
    return {layer: s / n for layer, s in out.items()}


def layer_seconds(ctx):
    """``sum_by_layer`` of the traced iteration, once per run; None where
    the program publishes no table."""
    if _KEY not in ctx:
        published = published_table()
        ctx[_KEY] = None if published is None else sum_by_layer(
            ctx["trace"].devices, *published)
    return ctx[_KEY]


def layer_s(ctx, layer: str):
    seconds = layer_seconds(ctx)
    return None if seconds is None else seconds.get(layer, 0.0)


def first_dispatch(field: str):
    """One first-dispatch counter (``obs/xla.py``: ``trace_lower_s``,
    ``compile_or_load_s``) of the run's first ``boosting/fused_iter``
    program; None where the program records none."""
    from lightgbm_tpu.obs.xla import global_xla
    for rec in global_xla.records():
        if rec.get("tag") == TAGS[0] and field in rec:
            return rec[field]
    return None
