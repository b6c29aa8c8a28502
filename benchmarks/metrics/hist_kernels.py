"""How the Mosaic histogram kernels are found in a device trace (shared by
the two readers that need their time; not a metric itself).

The rule, from the first trace read by hand (PR 25, TPU v5 lite, jax 0.9.0):
on the device plane's "XLA Ops" line an event's name is the whole HLO
instruction, and a Mosaic kernel reads

    %hist_pallas_multi_fused.14 = f32[4,504,128]{...} custom-call(u8[32,63000576]... ),
        custom_call_target="tpu_custom_call", ...

No ``pallas_call`` of the program has a ``name=`` yet (ROADMAP S2): the
instruction is named after the jitted Python function around the call
(``hist_pallas_multi_fused``, ``hist_pallas_multi``, ``hist_pallas_*``).
So a histogram kernel is an event whose instruction name (the part before
" = ") contains ``hist`` and whose text contains ``tpu_custom_call``. A
later PR that names its kernels keeps ``hist`` in the name, or adds a
reader of its own.
"""

TARGET = "tpu_custom_call"
NAME_PART = "hist"


def is_hist_kernel(event) -> bool:
    name, _, _, text = event
    head, _, rest = name.partition(" = ")
    return NAME_PART in head and (TARGET in rest or TARGET in text)


def kernel_seconds(ctx):
    """Summed device seconds of the histogram kernels' events in the
    traced iteration, averaged over the chips; None where none is found."""
    total = 0.0
    found = False
    for events in ctx["trace"].devices.values():
        hits = [e for e in events if is_hist_kernel(e)]
        found = found or bool(hits)
        total += sum(e[2] for e in hits)
    if not found:
        return None
    return total / 1e9 / max(1, len(ctx["trace"].devices))
