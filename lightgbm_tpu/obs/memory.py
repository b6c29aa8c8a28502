"""HBM memory observability: analytic peak model, per-phase watermarks,
and the preflight capacity planner.

The training cost model (docs/PERF_PROJECTION.md) says iterations are
HBM-bound; ROADMAP item 2 (datasets bigger than HBM) needs a *capacity*
model to decide, before allocation, whether bins/gradients/histograms
fit device memory or must stream — the decision "Out-of-Core GPU
Gradient Boosting" (arXiv:2005.09148) makes per batch. PR 4's
``hist_traffic_model`` did this for bandwidth; this module does it for
capacity. Three layers:

1. **Analytic peak-HBM model** — ``train_memory_model`` /
   ``predict_memory_model``: per-phase byte accounting for every
   device-resident buffer class (bins packed/unpacked, fused vs
   materialized gradients, histogram pool + wave slabs, partition/node
   state, ensemble packs), parameterized by shape + config knobs +
   mesh shards. Exact for what the *program* allocates (shapes are
   trace-time constants); XLA fusion temporaries are outside it, which
   is why the gate band (tools/perf_floor.json ``model_vs_measured``)
   is 1.5x, not 1.0x.

2. **Live per-phase watermarks** — ``PhaseWatermarks``: a
   span-boundary sampler registered on the tracer's sink chain that
   attributes ``peak_bytes_in_use`` growth to the phase whose span just
   closed, across ALL local devices. Auto-off on backends whose
   ``memory_stats()`` is None (CPU); a single attribute check when
   disabled.

3. **Preflight capacity planner** — ``preflight`` (training) /
   ``preflight_predict`` (serving): compares the predicted peak
   against device capacity and, when it doesn't fit, produces concrete
   knob recommendations (``tpu_bin_pack``, ``use_quantized_grad``,
   ``tpu_fused_grad``, ``tpu_num_shards``, ``tpu_predict_chunk``) with
   the bytes each one saves — so a too-big config fails fast with a
   plan instead of OOMing mid-run. Hooked into ``GBDT.__init__``
   (``tpu_preflight`` knob: warn/error/off) and
   ``serve.ModelRegistry.load``.

Capacity comes from ``device.memory_stats()["bytes_limit"]`` when the
backend reports it; the ``LGBM_TPU_HBM_BYTES`` env var overrides it
(testing, or planning for a different chip than the one attached).
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .metrics import global_metrics

F32 = 4
I32 = 4
F64 = 8


class PreflightError(RuntimeError):
    """Predicted peak HBM exceeds device capacity (tpu_preflight=error)."""


# ---------------------------------------------------------------------------
# device capacity
def device_capacity_bytes() -> Optional[int]:
    """Per-device HBM capacity in bytes, or None when unknown.

    ``LGBM_TPU_HBM_BYTES`` overrides (plan for a chip that isn't
    attached; also the test seam). Otherwise the MIN ``bytes_limit``
    over local devices — the planner asks "does the per-shard working
    set fit the smallest device", which is the OOM that matters.
    CPU backends report no memory_stats => None (preflight then has no
    verdict and stays silent)."""
    env = os.environ.get("LGBM_TPU_HBM_BYTES", "")
    if env:
        try:
            return int(float(env))
        except ValueError:
            pass
    stats = global_metrics.per_device_memory_stats()
    if not stats:
        return None
    limits = [s.get("bytes_limit") for s in stats
              if isinstance(s.get("bytes_limit"), (int, float))]
    return int(min(limits)) if limits else None


def measured_peak_bytes() -> Optional[int]:
    """Max ``peak_bytes_in_use`` across local devices (None on CPU)."""
    stats = global_metrics.per_device_memory_stats()
    if not stats:
        return None
    peaks = [s.get("peak_bytes_in_use", 0) or 0 for s in stats]
    return int(max(peaks)) if peaks else None


def _pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length() if n > 1 else 1


# ---------------------------------------------------------------------------
# analytic models
def packed_bin_bytes(num_data: int, num_features: int, max_bins: int,
                     pack_vpb: int = 1) -> int:
    """Device bytes of the [F, N] bin tensor under the given packing
    factor — uint8 (uint16 above 256 bins) unpacked; the split-section
    PACK_ALIGN-padded byte layout of ops/bin_pack.py when packed."""
    if pack_vpb > 1:
        from ..ops.bin_pack import section_len
        return num_features * section_len(num_data, pack_vpb)
    itemsize = 1 if max_bins <= 256 else 2
    return num_features * num_data * itemsize


def train_memory_model(*, num_data: int, num_features: int, max_bins: int,
                       num_leaves: int, num_class: int = 1,
                       num_iterations: int = 100,
                       pack_vpb: int = 1, quantized: bool = False,
                       fused_grad: bool = False, kernel_fused: bool = False,
                       waved: bool = True, wave_max: int = 42,
                       num_shards: int = 1, has_weight: bool = False,
                       valid_rows: Sequence[int] = (),
                       stream_slab_rows: int = 0) -> Dict[str, Any]:
    """Analytic per-device peak-HBM model of one training run.

    Accounts every buffer class the fused iteration program keeps
    resident or allocates per wave, per shard of the mesh data axis
    (row-indexed state divides by ``num_shards``; leaf/histogram state
    is replicated):

    - ``bins``        [F, N/s] uint8/16, or the packed byte layout
    - ``scores``      [K, N/s] f32 (+ per-valid-set scores)
    - ``objective``   label (+ weight) [N/s] f32
    - ``gradients``   grad/hess [K, N/s] f32 x2 — zero when the
                      gradient pass is fused (``tpu_fused_grad``)
    - ``ght``         the [N/s, 3] histogram operand — f32, int8 when
                      quantized, absent when fused IN-KERNEL
    - ``sample_mask`` / ``row_leaf`` [N/s]
    - ``hist_pool``   [L, F, B, 3] f32 parent-histogram pool
                      (subtraction needs parents resident)
    - ``hist_wave``   [S, F, B, 3] wave slab + split-scan gain tensors
    - ``partition``   the batched wave partition's per-row gather
                      transients
    - ``records``     per-iteration device TreeArrays (accumulate until
                      materialized)
    - ``valid``       per valid set: bins + scores

    Returns components, per-phase live-set sums, and
    ``peak_bytes`` = max over phases — the number bench.py publishes as
    ``mem_peak_model_bytes`` and tools/check_perf_gate.py floor-gates.
    """
    n = int(num_data)
    shards = max(int(num_shards), 1)
    n_s = -(-n // shards)  # rows per shard
    f = int(num_features)
    b = int(max_bins)
    l = int(num_leaves)
    k = max(int(num_class), 1)

    comp: Dict[str, int] = {}
    slab = int(stream_slab_rows)
    if slab > 0:
        # out-of-core streaming (tpu_stream): the [F, N] bin tensor is
        # HOST-resident; device HBM holds only the double-buffered slab
        # pair (slab k being consumed + slab k+1 uploading)
        comp["bins"] = 2 * packed_bin_bytes(min(slab, n_s), f, b, pack_vpb)
    else:
        comp["bins"] = packed_bin_bytes(n_s, f, b, pack_vpb)
    comp["scores"] = k * n_s * F32
    comp["objective"] = n_s * F32 * (2 if has_weight else 1)
    comp["sample_mask"] = n_s * F32
    comp["row_leaf"] = n_s * I32
    # materialized gradient buffers: grad + hess per class; the fused
    # gradient pass (tpu_fused_grad) derives them pointwise inside the
    # grower so they never exist as [N] buffers
    comp["gradients"] = 0 if fused_grad else 2 * k * n_s * F32
    # the [N, 3] (g*m, h*m, m) histogram operand: int8 when quantized,
    # absent entirely when the pallas kernel computes gh in VMEM
    if kernel_fused:
        comp["ght"] = 0
    else:
        comp["ght"] = n_s * 3 * (1 if quantized else F32)
    # parent-histogram pool for sibling subtraction: [L, F, B, 3] f32
    comp["hist_pool"] = l * f * b * 3 * F32
    # one wave's fresh histograms + the split scan's [S, F, B] stat/gain
    # tensors (~6 channels through find_best_split)
    from ..learner import HIST_SLOTS
    slots = min(max(int(wave_max), 1), HIST_SLOTS) if waved else 1
    comp["hist_wave"] = slots * f * b * 3 * F32
    comp["split_scan"] = slots * f * b * 6 * F32
    # batched wave partition: per-row split-feature id, gathered bin,
    # decision + new row_leaf (~16 B/row of transient)
    comp["partition"] = n_s * 16
    # device tree records pending materialization: ~12 L-sized f32/i32
    # arrays + the [L-1, B] categorical bitmask, per class per iteration
    comp["records"] = int(num_iterations) * k * (12 * l * F32 + (l - 1) * b)
    valid_bytes = 0
    for nv in valid_rows or ():
        nv_s = -(-int(nv) // shards)
        valid_bytes += packed_bin_bytes(nv_s, f, b, pack_vpb) \
            + k * nv_s * F32
    comp["valid"] = valid_bytes

    persistent = (comp["bins"] + comp["scores"] + comp["objective"]
                  + comp["sample_mask"] + comp["row_leaf"]
                  + comp["gradients"] + comp["hist_pool"]
                  + comp["records"] + comp["valid"])
    phases = {
        "gradients": persistent + comp["ght"],
        "histogram": persistent + comp["ght"] + comp["hist_wave"]
        + comp["split_scan"],
        "partition": persistent + comp["ght"] + comp["partition"],
    }
    peak_phase = max(phases, key=lambda p: phases[p])
    return {
        "kind": "train",
        "components": comp,
        "phases": phases,
        "persistent_bytes": persistent,
        "peak_bytes": phases[peak_phase],
        "peak_phase": peak_phase,
        "num_shards": shards,
        "stream_slab_rows": slab,
        "params": dict(num_data=n, num_features=f, max_bins=b,
                       num_leaves=l, num_class=k,
                       num_iterations=int(num_iterations),
                       pack_vpb=int(pack_vpb), quantized=bool(quantized),
                       fused_grad=bool(fused_grad),
                       kernel_fused=bool(kernel_fused), waved=bool(waved),
                       wave_max=int(wave_max), num_shards=shards,
                       has_weight=bool(has_weight),
                       valid_rows=[int(v) for v in (valid_rows or ())],
                       stream_slab_rows=slab),
    }


def stream_auto_slab_rows(kw: Dict[str, Any],
                          capacity_bytes: Optional[int]) -> int:
    """Auto slab size for out-of-core streaming (``tpu_stream`` with
    ``tpu_stream_slab_rows=0``): the largest section-aligned row count
    whose DOUBLE-BUFFERED slab pair fits the capacity left after the
    resident (non-bins) working set of the analytic model. Unknown
    capacity (CPU, no LGBM_TPU_HBM_BYTES) => one slab covering all
    rows — the degenerate plan that is bit-identical to resident
    training by construction. Never returns less than one aligned
    section even when nothing fits (preflight reports the shortfall
    separately)."""
    from ..ops.bin_pack import slab_align
    kw = {k: v for k, v in kw.items() if k != "stream_slab_rows"}
    n = int(kw["num_data"])
    align = slab_align(int(kw["max_bins"]))
    if capacity_bytes is None:
        return -(-n // align) * align
    resident = train_memory_model(**kw)
    non_bins = resident["peak_bytes"] - resident["components"]["bins"]
    budget = max(int(capacity_bytes) - non_bins, 0)
    bytes_per_row = max(
        packed_bin_bytes(align, int(kw["num_features"]),
                         int(kw["max_bins"]), int(kw["pack_vpb"])) / align,
        1e-9)
    rows = int(budget / (2 * bytes_per_row))
    rows = max(rows // align * align, align)
    return min(rows, -(-n // align) * align)


def _resolve_train_knobs(config, num_data: int, num_features: int,
                         num_class: int) -> Dict[str, Any]:
    """Config -> the model's semantic knobs, mirroring the resolution
    the booster itself performs (GBDT._maybe_pack_bins /
    _resolve_fused_grad / _resolved_wave_max) without needing a built
    booster — this is what lets ``preflight`` run BEFORE any device
    allocation."""
    from ..ops.bin_pack import pack_vpb as _pack_vpb
    from ..ops import histogram as hist_ops

    learner_kind = str(getattr(config, "tree_learner", "serial"))
    raw_shards = int(getattr(config, "tpu_num_shards", 0) or 0)
    if learner_kind in ("data", "voting"):
        shards = raw_shards
        if shards <= 0:
            try:
                import jax
                shards = len(jax.local_devices())
            except Exception:
                shards = 1
    else:
        shards = 1
    shards = max(shards, 1)

    # mirror _maybe_pack_bins exactly: packing refuses whenever
    # tpu_num_shards > 1 is SET, even on the serial learner
    vpb = 1
    if str(config.tpu_bin_pack) not in ("off", "0", "false", "False") \
            and learner_kind == "serial" and raw_shards <= 1:
        vpb = _pack_vpb(int(config.max_bin))

    k = max(int(num_class), 1)
    wave_max = int(config.tpu_wave_max)
    if wave_max < 0:  # auto: exact order for coupled multiclass
        coupled = (k > 1 and str(config.objective) != "multiclassova")
        wave_max = 0 if coupled else 42
    waved = wave_max > 0

    quantized = bool(config.use_quantized_grad) and waved \
        and int(config.num_grad_quant_bins) <= 126

    fused = False
    if str(config.tpu_fused_grad) not in ("off", "0", "false", "False"):
        fused = (waved and k == 1 and not quantized
                 and not bool(config.use_quantized_grad)
                 and str(config.data_sample_strategy) != "goss"
                 and str(config.objective) in ("binary", "regression"))
    kernel_fused = fused and \
        hist_ops.resolve_impl(str(config.tpu_hist_impl)) == "pallas"

    return dict(num_data=int(num_data), num_features=int(num_features),
                max_bins=int(config.max_bin), num_leaves=int(config.num_leaves),
                num_class=k, num_iterations=int(config.num_iterations),
                pack_vpb=vpb, quantized=quantized, fused_grad=fused,
                kernel_fused=kernel_fused, waved=waved,
                wave_max=max(wave_max, 1), num_shards=shards)


def predict_memory_model(*, num_rows: int, num_features: int,
                         num_trees: int, num_leaves: int,
                         num_class: int = 1, chunk_rows: int = 1 << 20,
                         pack_nbytes: Optional[int] = None,
                         resident_pack_bytes: int = 0,
                         contrib: bool = False,
                         shap_pack_nbytes: Optional[int] = None
                         ) -> Dict[str, Any]:
    """Analytic peak-HBM model of a serving dispatch: the device
    ensemble pack plus one chunk's traversal working set.

    - ``pack``      device + host-mirror packed ensemble tensors
                    (measured ``EnsemblePacker.nbytes*2`` when the pack
                    exists; otherwise the capacity-doubled analytic
                    estimate)
    - ``chunk_*``   per-chunk buffers at the effective chunk size
                    (``tpu_predict_chunk``, capped by the row-bucket the
                    request actually compiles): double-buffered f32
                    feature blocks, [B, T] int32 traversal state, [B, T]
                    leaf gather + [B, K] f64 output
    - ``resident_pack_bytes`` adds OTHER models' packs already resident
      (the serve registry's budgeted pool) so multi-tenant preflight
      sees the whole pool, not one model.

    With ``contrib=True`` the pred_contrib (TreeSHAP) dispatch is
    modeled instead of plain traversal: the depth-padded path-table
    pack (measured ``EnsemblePacker.shap_nbytes*2`` via
    ``shap_pack_nbytes`` when it exists; analytic T*L paths x padded
    depth x 14 f32 tables otherwise) plus the kernel's [B, Pc, D]
    pweight working set, which the packer sizes against its own
    128 MB budget (ops/predict._SHAP_BUDGET_BYTES) — the band
    tools/check_perf_gate.py check 13 holds the measured pack to."""
    t = int(num_trees)
    l = int(num_leaves)
    if pack_nbytes is None:
        max_i = _pow2(max(l - 1, 1))
        # 6 i32 fields + f64 threshold per internal slot, f32 leaf values
        pack_host = t * (max_i * (6 * I32 + F64) + _pow2(l) * F32)
    else:
        pack_host = int(pack_nbytes)
    chunk = min(int(chunk_rows), _pow2(max(int(num_rows), 16)))
    comp = {
        "pack": 2 * pack_host,
        "resident_packs": int(resident_pack_bytes),
        "chunk_features": 2 * chunk * int(num_features) * F32,
        "chunk_state": chunk * t * I32,
        "chunk_out": chunk * t * F32 + chunk * max(int(num_class), 1) * F64,
    }
    if contrib:
        from ..ops.predict import _SHAP_BUDGET_BYTES
        from ..ops.shap import MAX_CHUNK_ROWS
        paths = t * l
        # unique path elements ~ tree depth ~ log2(L) (+1 dummy slot),
        # padded to a multiple of 4 like the packer's depth bucketing
        d_est = max(l - 1, 1).bit_length() + 1
        depth = max(-(-d_est // 4) * 4, 4)
        if shap_pack_nbytes is None:
            # 13 path tables + leaf values: one 4-byte cell per slot
            shap_host = paths * depth * 14 * F32
        else:
            shap_host = int(shap_pack_nbytes)
        cchunk = min(chunk, MAX_CHUNK_ROWS)
        # [B, Pc, D] f32 recurrence tensors (~6 live at the extend/
        # unwind peak); Pc is the pow2 path-chunk the packer fits into
        # its budget, floored at 32 and capped at the path count
        per_path = cchunk * depth * F32 * 6
        pc = 1 << max(int(_SHAP_BUDGET_BYTES // max(per_path, 1)
                          ).bit_length() - 1, 0)
        pc = max(min(pc, _pow2(max(paths, 1))), 32)
        comp["shap_pack"] = 2 * shap_host
        comp["shap_chunk"] = pc * per_path
    peak = sum(comp.values())
    return {
        "kind": "predict",
        "components": comp,
        "phases": {"traverse": peak},
        "peak_bytes": peak,
        "peak_phase": "traverse",
        "chunk_rows": chunk,
        "params": dict(num_rows=int(num_rows),
                       num_features=int(num_features), num_trees=t,
                       num_leaves=l, num_class=int(num_class),
                       chunk_rows=int(chunk_rows),
                       contrib=bool(contrib)),
    }


# ---------------------------------------------------------------------------
# preflight planner
class PreflightReport:
    """Verdict of a capacity check. ``fits`` is True/False, or None when
    no capacity is known (CPU, no override). ``recommendations`` is a
    list of {knob, setting, saves_bytes, peak_bytes, reason} dicts,
    biggest saving first — each one re-runs the analytic model with
    that knob applied, so the numbers are projections, not guesses."""

    def __init__(self, model: Dict[str, Any], capacity_bytes: Optional[int],
                 recommendations: List[Dict[str, Any]],
                 stream: Optional[Dict[str, Any]] = None):
        self.model = model
        self.peak_bytes = int(model["peak_bytes"])
        self.capacity_bytes = capacity_bytes
        self.fits = (None if capacity_bytes is None
                     else self.peak_bytes <= int(capacity_bytes))
        self.headroom_bytes = (None if capacity_bytes is None
                               else int(capacity_bytes) - self.peak_bytes)
        self.recommendations = recommendations
        # out-of-core streaming verdict (training reports): `fits` stays
        # the RESIDENT verdict — honest about what a non-streamed run
        # would do — while `fits_streaming` says whether the tpu_stream
        # working set (host bins, double-buffered slab) fits. None when
        # capacity is unknown or the shape is stream-ineligible.
        self.stream = stream
        self.fits_streaming = (None if stream is None
                               else bool(stream.get("fits")))

    def render(self) -> str:
        gb = 1e9
        cap = ("unknown" if self.capacity_bytes is None
               else f"{self.capacity_bytes / gb:.2f} GB")
        lines = [f"predicted peak HBM {self.peak_bytes / gb:.2f} GB "
                 f"(phase: {self.model.get('peak_phase')}), "
                 f"device capacity {cap}"]
        if self.fits is False:
            lines[0] += " — DOES NOT FIT resident"
            for r in self.recommendations:
                setting = r["setting"]
                extra = (f" (slab_rows={r['slab_rows']})"
                         if "slab_rows" in r else "")
                lines.append(
                    f"  try {r['knob']}={setting}{extra}: predicted peak "
                    f"{r['peak_bytes'] / gb:.2f} GB "
                    f"(saves {r['saves_bytes'] / gb:.2f} GB) — {r['reason']}")
            if not self.recommendations:
                lines.append("  no single knob closes the gap; shrink the "
                             "dataset or shard it over more hosts")
        return "\n".join(lines)


def _rec(knob: str, setting, base_peak: int, model: Dict[str, Any],
         reason: str) -> Optional[Dict[str, Any]]:
    saved = base_peak - int(model["peak_bytes"])
    if saved <= 0:
        return None
    return {"knob": knob, "setting": setting, "saves_bytes": saved,
            "peak_bytes": int(model["peak_bytes"]), "reason": reason}


def _train_recommendations(kw: Dict[str, Any],
                           capacity: Optional[int],
                           stream_ok: bool = True) -> List[Dict[str, Any]]:
    """Knob projections that shrink the training peak, computed by
    re-running the model with one knob flipped at a time."""
    from ..ops.bin_pack import pack_vpb as _pack_vpb
    base = train_memory_model(**kw)["peak_bytes"]
    recs: List[Dict[str, Any]] = []

    if kw["pack_vpb"] == 1 and _pack_vpb(kw["max_bins"]) > 1:
        m = train_memory_model(**{**kw, "pack_vpb":
                                  _pack_vpb(kw["max_bins"])})
        r = _rec("tpu_bin_pack", "auto", base, m,
                 "bit-pack the bin tensor (ops/bin_pack.py)")
        if r:
            recs.append(r)
    elif kw["max_bins"] > 15:
        m = train_memory_model(**{**kw, "max_bins": 15, "pack_vpb": 2})
        r = _rec("max_bin", 15, base, m,
                 "15 bins admit 4-bit packed storage (tpu_bin_pack)")
        if r:
            recs.append(r)
    if not kw["quantized"]:
        m = train_memory_model(**{**kw, "quantized": True,
                                  "fused_grad": False,
                                  "kernel_fused": False})
        r = _rec("use_quantized_grad", True, base, m,
                 "int8 gradient operand for the histogram passes")
        if r:
            recs.append(r)
    if not kw["fused_grad"] and not kw["quantized"] and kw["waved"] \
            and kw["num_class"] == 1:
        m = train_memory_model(**{**kw, "fused_grad": True})
        r = _rec("tpu_fused_grad", "on", base, m,
                 "derive gradients in the histogram wave instead of "
                 "materializing [N] buffers")
        if r:
            recs.append(r)
    # shard the row-indexed state over the mesh: smallest power-of-two
    # device count whose per-shard peak fits (or the largest available)
    try:
        import jax
        n_dev = len(jax.local_devices())
    except Exception:
        n_dev = 1
    if n_dev > kw["num_shards"]:
        best = None
        s = kw["num_shards"] * 2
        while s <= n_dev:
            m = train_memory_model(**{**kw, "num_shards": s,
                                      "pack_vpb": 1})
            best = (s, m)
            if capacity is not None and m["peak_bytes"] <= capacity:
                break
            s *= 2
        if best is not None:
            r = _rec("tpu_num_shards", best[0], base, best[1],
                     "shard rows over the device mesh "
                     "(tree_learner=data)")
            if r:
                recs.append(r)
    if stream_ok:
        sm = stream_model(kw, capacity)
        r = _rec("tpu_stream", "on", base, sm["model"],
                 "keep bins host-resident and stream section-aligned "
                 "slabs through the histogram waves (io/streaming.py)")
        if r:
            r["slab_rows"] = sm["slab_rows"]
            recs.append(r)
    recs.sort(key=lambda r: -r["saves_bytes"])
    return recs


def stream_model(kw: Dict[str, Any],
                 capacity: Optional[int]) -> Dict[str, Any]:
    """The analytic model of the SAME shape trained out-of-core
    (tpu_stream): auto slab size + the streamed peak, with a fits
    verdict against `capacity`. Streaming keeps gradients materialized
    (the streamed prep program needs the [N] buffers), so fused-grad
    components are forced off."""
    kw = {**kw, "fused_grad": False, "kernel_fused": False}
    kw.pop("stream_slab_rows", None)
    slab = stream_auto_slab_rows(kw, capacity)
    model = train_memory_model(**kw, stream_slab_rows=slab)
    fits = (None if capacity is None
            else model["peak_bytes"] <= int(capacity))
    return {"model": model, "slab_rows": int(slab),
            "peak_bytes": int(model["peak_bytes"]), "fits": fits}


def stream_config_ineligible(config,
                             num_class: Optional[int] = None
                             ) -> Optional[str]:
    """Why a CONFIG cannot stream out-of-core, or None. This is THE
    config-level gate list — ``GBDT._stream_ineligible`` delegates to
    it (adding the storage-level gates only a built dataset knows: EFB
    bundling, COO sparsity), so ``preflight``'s recommendation and the
    booster's resolve decision cannot drift. A recommendation may still
    be optimistic about storage (preflight sees shapes, not bins)."""
    if getattr(config, "forcedsplits_filename", ""):
        return "forced splits need the exact (non-waved) grower"
    if getattr(config, "interaction_constraints", None):
        return "interaction constraints are not streamed"
    if bool(getattr(config, "linear_tree", False)):
        return "linear trees fit per-leaf models from raw rows"
    if getattr(config, "monotone_constraints", None) and \
            str(getattr(config, "monotone_constraints_method", "basic")) \
            in ("intermediate", "advanced"):
        return "pairwise monotone modes are not streamed"
    wm = int(getattr(config, "tpu_wave_max", -1))
    k = int(num_class if num_class is not None
            else getattr(config, "num_class", 1))
    coupled = k > 1 and str(getattr(config, "objective", "")) \
        != "multiclassova"
    if wm == 0 or (wm < 0 and coupled):
        return ("exact-order growth (tpu_wave_max=0; coupled "
                "multiclass objectives resolve to it) has no "
                "streamed twin")
    learner = str(getattr(config, "tree_learner", "serial"))
    if learner not in ("serial", "data"):
        return (f"tree_learner={learner} replaces the grower with its "
                "own adapter")
    try:
        import jax
        if jax.process_count() > 1:
            return ("multi-host training assembles globally-sharded "
                    "bins (per-host slab plans are not wired yet)")
    except RuntimeError:
        pass  # backend not initialized: single-process
    return None


def stream_config_eligible(config) -> bool:
    """True when the config admits out-of-core streaming AND the
    ``tpu_stream`` knob is not off — the screen ``preflight`` uses to
    decide whether a streaming recommendation/verdict is on the table."""
    if str(getattr(config, "tpu_stream", "auto")).lower() in (
            "off", "0", "false", "none"):
        return False
    return stream_config_ineligible(config) is None


def train_report(kw: Dict[str, Any],
                 capacity_bytes: Optional[int] = None,
                 stream_ok: bool = True) -> PreflightReport:
    """PreflightReport for already-resolved model kwargs — the entry the
    booster hook uses (it knows the ACTUAL resolved knobs: pack factor,
    fused/quantized state, mesh size), while ``preflight`` resolves them
    from a config for the before-any-allocation path.

    ``stream_ok``: the shape/config admits out-of-core streaming; the
    report then carries the streamed-model verdict (``fits_streaming``)
    and a ``tpu_stream`` recommendation when resident does not fit."""
    model = train_memory_model(**kw)
    cap = capacity_bytes if capacity_bytes is not None \
        else device_capacity_bytes()
    recs: List[Dict[str, Any]] = []
    stream = None
    active_slab = int(kw.get("stream_slab_rows", 0) or 0)
    if active_slab > 0:
        # the caller's model already IS the streamed one (tpu_stream on)
        stream = {"model": model, "slab_rows": active_slab,
                  "peak_bytes": int(model["peak_bytes"]),
                  "fits": (None if cap is None
                           else model["peak_bytes"] <= int(cap))}
    elif stream_ok:
        stream = stream_model(kw, cap)
    if cap is not None and model["peak_bytes"] > cap:
        recs = _train_recommendations(kw, cap, stream_ok=stream_ok)
    return PreflightReport(model, cap, recs, stream=stream)


def preflight(params=None, shape: Optional[Tuple[int, int]] = None, *,
              num_class: Optional[int] = None,
              valid_rows: Sequence[int] = (),
              capacity_bytes: Optional[int] = None) -> PreflightReport:
    """Capacity-check a training config BEFORE allocating anything.

    ``params`` is a params dict or a ``Config``; ``shape`` is
    ``(n_rows, n_features)``. Capacity defaults to the attached
    device's (``LGBM_TPU_HBM_BYTES`` overrides; None on CPU => no
    verdict). Returns a ``PreflightReport`` — callers decide whether a
    non-fit warns or raises (the booster's ``tpu_preflight`` knob)."""
    from ..config import Config
    if not isinstance(params, Config):
        params = Config.from_params(dict(params or {}))
    if shape is None:
        raise ValueError("preflight needs shape=(n_rows, n_features)")
    n_rows, n_features = int(shape[0]), int(shape[1])
    k = int(num_class if num_class is not None else params.num_class)
    kw = _resolve_train_knobs(params, n_rows, n_features, k)
    kw["valid_rows"] = list(valid_rows or ())
    return train_report(kw, capacity_bytes,
                        stream_ok=stream_config_eligible(params))


def preflight_predict(*, num_rows: int, num_features: int, num_trees: int,
                      num_leaves: int, num_class: int = 1,
                      chunk_rows: int = 1 << 20,
                      pack_nbytes: Optional[int] = None,
                      resident_pack_bytes: int = 0,
                      contrib: bool = False,
                      shap_pack_nbytes: Optional[int] = None,
                      capacity_bytes: Optional[int] = None
                      ) -> PreflightReport:
    """Serving-side capacity check (hooked into ModelRegistry.load):
    ensemble pack + chunk working set vs device capacity, recommending
    a smaller ``tpu_predict_chunk`` when the chunk buffers are what
    doesn't fit. ``contrib=True`` models the pred_contrib (TreeSHAP)
    dispatch — path-table pack + pweight working set — instead of
    plain traversal."""
    kw = dict(num_rows=num_rows, num_features=num_features,
              num_trees=num_trees, num_leaves=num_leaves,
              num_class=num_class, chunk_rows=chunk_rows,
              pack_nbytes=pack_nbytes,
              resident_pack_bytes=resident_pack_bytes,
              contrib=contrib, shap_pack_nbytes=shap_pack_nbytes)
    model = predict_memory_model(**kw)
    cap = capacity_bytes if capacity_bytes is not None \
        else device_capacity_bytes()
    recs: List[Dict[str, Any]] = []
    if cap is not None and model["peak_bytes"] > cap:
        base = model["peak_bytes"]
        chunk = int(model["chunk_rows"])
        while chunk > 1 << 14:
            chunk //= 2
            m = predict_memory_model(**{**kw, "chunk_rows": chunk})
            if m["peak_bytes"] <= cap or chunk == 1 << 14:
                r = _rec("tpu_predict_chunk", chunk, base, m,
                         "smaller serving chunks shrink the per-dispatch "
                         "working set")
                if r:
                    recs.append(r)
                break
        if resident_pack_bytes:
            m = predict_memory_model(**{**kw, "resident_pack_bytes": 0})
            r = _rec("serve_cache_bytes", "(lower)", base, m,
                     "LRU-evict other models' resident packs "
                     "(serve/registry.py)")
            if r:
                recs.append(r)
        recs.sort(key=lambda r: -r["saves_bytes"])
    return PreflightReport(model, cap, recs)


# ---------------------------------------------------------------------------
# live per-phase watermarks
class PhaseWatermarks:
    """Span-boundary HBM watermark sampler.

    Registered on the tracer sink chain: each completed span samples
    ``peak_bytes_in_use`` across all local devices and attributes the
    growth since the previous sample to the span that just closed — the
    live counterpart of the analytic model's per-phase peaks. The
    attribution is by closing order (a parent span inherits growth its
    unsampled children caused only if no child span closed in between),
    which is exactly right for the leaf phases the trainer emits
    (train/gradients, train/grow, train/iteration, ...).

    Disabled => one attribute check per span. ``enable()`` probes the
    backend once and stays off where ``memory_stats()`` is None (CPU),
    so the tracer can run everywhere with the sampler armed only where
    it means something. ``stats_fn`` is injectable for tests."""

    def __init__(self, stats_fn=None) -> None:
        self.enabled = False
        self._supported: Optional[bool] = None
        self._stats_fn = (stats_fn if stats_fn is not None
                          else global_metrics.per_device_memory_stats)
        self._lock = threading.Lock()
        self._last_peak: Optional[int] = None
        self.phases: Dict[str, Dict[str, int]] = {}

    def enable(self) -> bool:
        """Arm the sampler. Backend support is probed LAZILY on the
        first completed span, not here: enabling can happen at import
        time (LGBM_TPU_TELEMETRY in the environment) when probing
        devices could initialize — or hang on — a backend nobody asked
        for yet; a completed span implies jax is already running."""
        if self._supported is False:
            return False
        self.enabled = True
        return True

    def disable(self) -> None:
        self.enabled = False

    def reset(self) -> None:
        with self._lock:
            self.phases.clear()
            self._last_peak = None

    # the tracer sink: (name, dur_seconds, self_seconds)
    def sink(self, name: str, dur_s: float, self_s: float) -> None:
        if not self.enabled:
            return
        stats = self._stats_fn()
        if not stats:
            # no memory_stats on this backend (CPU): disarm for good —
            # the disabled check above keeps every later span O(1)
            self._supported = False
            self.enabled = False
            return
        self._supported = True
        peak = max(int(s.get("peak_bytes_in_use", 0) or 0) for s in stats)
        in_use = sum(int(s.get("bytes_in_use", 0) or 0) for s in stats)
        with self._lock:
            prev = self._last_peak
            self._last_peak = max(peak, prev or 0)
            ph = self.phases.get(name)
            if ph is None:
                ph = self.phases[name] = {
                    "delta_bytes": 0, "peak_bytes": 0,
                    "bytes_in_use": 0, "samples": 0}
            if prev is not None and peak > prev:
                ph["delta_bytes"] += peak - prev
            ph["peak_bytes"] = max(ph["peak_bytes"], peak)
            ph["bytes_in_use"] = max(ph["bytes_in_use"], in_use)
            ph["samples"] += 1

    def summary(self) -> Dict[str, Dict[str, int]]:
        with self._lock:
            return {name: dict(ph) for name, ph in self.phases.items()}


global_watermarks = PhaseWatermarks()

# span-boundary feed: every completed span samples device memory when
# the sampler is armed (obs/__init__ imports this module, so the sink
# is registered whenever obs is)
from .trace import global_tracer as _gt  # noqa: E402
_gt.add_sink(global_watermarks.sink)
if global_metrics.enabled:  # env-enabled telemetry arms the sampler too
    global_watermarks.enable()
