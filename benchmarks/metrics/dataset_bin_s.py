"""dataset_bin_s (layer: entry; moves setup_s): host seconds inside the
program's span ``data/binning`` (``lgb.Dataset.construct``: find the bin
boundaries on the row sample, bin every row, try to bundle), summed over
the data sets the run constructed, from the program's always-on counter
``global_metrics.meta["data_binning"]`` (``lightgbm_tpu/dataset.py``; its
records also hold ``find_bins_s``, ``transform_s``, ``bundle_s``,
``columns``, ``sample_rows`` and ``workers``). None where the program
records none (the parent of PR 32)."""


def read(ctx):
    try:
        from lightgbm_tpu.obs.metrics import global_metrics
    except ImportError:
        return None
    records = global_metrics.meta.get("data_binning")
    if not records:
        return None
    seconds = [r["seconds"] for r in records if "seconds" in r]
    return sum(seconds) if seconds else None
