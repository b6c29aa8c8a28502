"""bins_pack_s (layer: entry; moves setup_s): host seconds the booster
spent bit-packing the binned matrix and handing the packed bytes to the
device (``boosting._maybe_pack_bins``: ``ops/bin_pack.pack_bins_host``,
then the upload), summed over the matrices the run packed, from the
program's always-on counter ``global_metrics.meta["bin_pack"]`` (its
records also hold ``rows``, ``features``, ``vpb``, ``section``,
``bytes_raw`` and ``bytes_packed``; the tracer's span is
``data/pack_bins``). None where the program keeps no such record (the
parent of PR 35) or packed nothing (more than 15 bins)."""


def read(ctx):
    try:
        from lightgbm_tpu.obs.metrics import global_metrics
    except ImportError:
        return None
    records = global_metrics.meta.get("bin_pack")
    if not records:
        return None
    seconds = [r["seconds"] for r in records if "seconds" in r]
    return sum(seconds) if seconds else None
