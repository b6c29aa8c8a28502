#!/usr/bin/env python
"""Perf-regression gate (ROADMAP item 4: convert "should be fast" into
driver-visible proof).

Fourteen checks, all against the recorded floor in tools/perf_floor.json:

1. **Histogram traffic model** — recomputes the static per-iteration
   HBM byte model (learner.hist_traffic_model) for the recorded
   benchmark fixture shape under the current scheduler/encodings and
   fails if bytes/iter regressed more than 10% over the recorded
   floor, or if the reduction vs the unpacked/no-subtraction oracle
   fell below the recorded minimum (1.8x — the ISSUE 7 acceptance
   number). A code change that silently widens a wave schedule, drops
   bin packing, or fattens the gh operand trips this without any
   hardware in the loop.

2. **Peak-memory model ceiling** — recomputes the analytic peak-HBM
   model (obs.memory.train_memory_model) for the recorded bench
   fixture and fails if the predicted peak grew more than 10% over the
   recorded ceiling (a silently-fattened resident buffer class). A
   candidate JSON carrying BOTH `mem_peak_model_bytes` and
   `mem_peak_measured_bytes` (accelerator runs) is additionally held
   to the recorded model-vs-measured band (1.5x either way) — the
   out-of-core streaming work needs a fit/doesn't-fit oracle it can
   trust.

3. **Bench trajectory** — reads the BENCH_*.json lines in the repo
   root (plus an optional candidate JSON passed as argv[1]); for each
   platform the best recorded `vs_baseline` is the floor, and the
   LATEST same-platform value must not drop more than 10% below it.
   A candidate JSON carrying `hist_bytes_per_iter` is additionally
   held to the byte floor.

4. **Phase-time trajectory** — over the obs phase summaries bench.py
   folds into its JSON line when telemetry is on (`phases`): per
   platform, a phase above the absolute-noise floor may not exceed its
   best (lowest) recorded time by the configured fraction. No recorded
   phase summaries => the check reports itself skipped.

5. **XLA cross-check of the analytic models** — compiles the actual
   packed+quantized wave histogram kernel for the recorded fixture
   shape and holds the analytic traffic/memory models to what XLA's
   OWN analyses say about the executable (obs/xla.py): the compiled
   program's argument bytes must agree with the traffic model's
   per-pass operand bytes within the declared band (so
   `hist_bytes_per_iter` = passes x per-pass is cross-validated
   end-to-end), XLA's `bytes accessed` must not fall BELOW the model
   (a model that claims more streaming than the program can touch is
   broken), and the memory model's operand/slab components must cover
   the executable's argument/output buffers. Independent, silicon-free
   proof; skips gracefully where the backend exposes no cost analysis.

6. **Comms health** — over the obs/health summaries bench.py folds
   into its JSON line (`health` field): the latest record's per-phase
   straggler skew (above an absolute-noise floor) must stay under the
   recorded ceiling, and the estimated collective time share (runtime
   collective bytes x the timed mesh probe's per-byte rate, over
   measured train seconds) must not make iterations comms-bound.
   No mesh run recorded => the check reports itself skipped — the
   same graceful-skip pattern as the other obs pillars.

7. **Checkpoint overhead** — over the ``resilience`` dict bench.py
   folds into its JSON line when a run checkpointed
   (resilience/checkpoint.py): the snapshot wall-time share of train
   wall-time must stay under the floor-configured ceiling — fault
   tolerance is only free if the snapshots are. Graceful skip when no
   checkpointing ran (the common bench config).

8. **Continual-loop overhead** — over the latest bench record carrying
   a ``continual`` summary (bench.py --continual,
   resilience/continual.py): the validated hot-swap share of continual
   wall-time and the total non-training overhead share must stay under
   the floor-configured caps — a long-lived model is only viable if
   accepting a generation is nearly free. Graceful skip when no
   continual bench ran.

9.  **Stream overhead** — streamed-vs-resident slowdown ceiling and
    upload/compute overlap floor over the latest ``stream`` bench
    record (check_stream_overhead). Graceful skip when absent.

10. **Cold start** — warm-start compile reduction, program-acquisition
    ratchet ceiling, and the serialized-artifact restore sub-checks
    over the latest ``coldstart`` bench record (check_coldstart).
    Graceful skip when absent.

11. **Device-time roofline** — over the latest bench record carrying a
    ``roofline`` summary (obs/profile.py window folded into bench.py's
    JSON line): the attributed-device-seconds coverage of the profile
    window's wall time must land inside the floor-configured band, and
    the best per-tag utilization vs the hostenv.device_peaks row
    must clear the RATCHETING ``min_utilization`` floor. Graceful skip
    when no profiled bench ran or the record is unattributable.

12. **Fleet availability** — over the latest bench record carrying a
    ``fleet`` summary (bench.py --fleet: open-loop load through the
    FleetRouter with one replica killed at the 40% mark): the served
    fraction must clear the ``min_availability`` floor (0.999), the
    killed replica must land in quarantine, and the served answers
    must stay bit-identical to a direct predict (check_fleet_
    availability). Graceful skip when no fleet bench ran.

13. **SHAP contributions** — over the latest bench record carrying a
    ``shap`` summary (bench.py --shap: the batched device TreeSHAP
    kernel vs the same-run host recursive oracle): the device speedup
    must clear the per-platform ``min_speedup_vs_host`` floor, the
    kernel must have matched the oracle on the parity subset, and the
    measured path-table pack bytes must land inside the configured
    band of the analytic memory model's ``shap_pack`` component
    (check_shap). Graceful skip when no shap bench ran.

14. **Collective scatter reduction** — recomputes the static
    per-iteration cross-device collective byte model
    (learner.collective_traffic_model) for the recorded fixture shape
    under both reductions and fails if the reduce-scatter learner's
    modeled collective bytes stopped beating the full-histogram psum
    oracle by the recorded factor at the fixture width (ISSUE 20
    acceptance: >= 1.8x at W=4). Purely analytic — no devices in the
    loop — so a code change that silently re-widens the all_gather
    payload or drops the feature partition trips this on any host.
    Graceful skip when no scatter floor is recorded.

Exit 0 = gate passed; exit 1 = regression, with one line per failure.
Wired into the quick verification tier via tests/test_perf_gate.py.

Usage: python tools/check_perf_gate.py [candidate_bench.json]
"""

import glob
import json
import os
import re
import sys

# a CI gate computes counts and byte models only: keep it off the chip
os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FLOOR_PATH = os.path.join(REPO, "tools", "perf_floor.json")
if REPO not in sys.path:  # runnable from anywhere
    sys.path.insert(0, REPO)


def _platform_of(unit: str) -> str:
    m = re.search(r"platform=(\w+)", unit or "")
    return m.group(1) if m else "tpu"


def _extract_metric_record(blob):
    """A bench contract record from either shape: the raw JSON line
    bench.py emits, or the driver's {"n", "cmd", "rc", "tail"} wrapper
    whose `tail` embeds that line in captured output."""
    if blob.get("metric") == "boosting_iters_per_sec_higgs_shape":
        return blob
    for line in reversed(str(blob.get("tail", "")).splitlines()):
        line = line.strip()
        if line.startswith("{") and '"metric"' in line:
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if rec.get("metric") == "boosting_iters_per_sec_higgs_shape":
                return rec
    return None


def _load_bench_lines(candidate_path=None):
    """[(round_tag, record)] for every train-metric BENCH line, oldest
    first; the candidate (if any) sorts last."""
    out = []
    for path in sorted(glob.glob(os.path.join(REPO, "BENCH_*.json"))):
        try:
            with open(path) as fh:
                rec = _extract_metric_record(json.load(fh))
        except (OSError, ValueError):
            continue
        if rec is not None:
            out.append((os.path.basename(path), rec))
    if candidate_path:
        with open(candidate_path) as fh:
            rec = _extract_metric_record(json.load(fh))
        if rec is not None:
            out.append((os.path.basename(candidate_path), rec))
    return out


def check_traffic_model(floor, failures):
    from lightgbm_tpu.learner import hist_traffic_model
    fx = floor["hist"]["fixture"]
    shape = dict(num_data=fx["num_data"],
                 storage_features=fx["storage_features"],
                 max_bins=fx["max_bins"], num_leaves=fx["num_leaves"],
                 wave_max=fx["wave_max"])
    # pack_vpb defaults from max_bins inside the model (tpu_bin_pack=auto)
    actual = hist_traffic_model(
        **shape, gh_read_bytes=fx.get("gh_read_bytes", 3), subtract=True,
        fused_grad=False)
    oracle = hist_traffic_model(**shape, pack_vpb=1, gh_read_bytes=12,
                                subtract=False, fused_grad=False)
    bytes_now = actual["hist_bytes_per_iter"]
    reduction = oracle["hist_bytes_per_iter"] / bytes_now
    max_bytes = floor["hist"]["max_bytes_per_iter"] * 1.10
    if bytes_now > max_bytes:
        failures.append(
            f"hist traffic model regressed: {bytes_now/1e9:.3f} GB/iter "
            f"> floor {floor['hist']['max_bytes_per_iter']/1e9:.3f} GB "
            f"(+10%)")
    if reduction < floor["hist"]["min_bytes_reduction"]:
        failures.append(
            f"hist byte reduction vs oracle fell to {reduction:.2f}x "
            f"< required {floor['hist']['min_bytes_reduction']}x")
    print(f"# traffic model: {bytes_now/1e9:.3f} GB/iter, "
          f"{reduction:.2f}x vs oracle "
          f"({actual['passes']} passes vs {oracle['passes']})")
    return actual


def check_memory_model(floor, failures, candidate_rec=None):
    """Analytic peak-HBM ceiling + model-vs-measured band (check 2)."""
    from lightgbm_tpu.obs.memory import train_memory_model
    mem = floor.get("memory")
    if not mem:
        print("# no memory floor recorded; memory check skipped")
        return
    model = train_memory_model(**mem["fixture"])
    peak = model["peak_bytes"]
    ceiling = mem["max_peak_model_bytes"] * 1.10
    if peak > ceiling:
        failures.append(
            f"peak-memory model regressed: {peak / 1e9:.3f} GB "
            f"> floor {mem['max_peak_model_bytes'] / 1e9:.3f} GB (+10%)")
    print(f"# memory model: {peak / 1e9:.3f} GB predicted peak "
          f"(phase: {model['peak_phase']})")
    if not candidate_rec:
        return
    modeled = candidate_rec.get("mem_peak_model_bytes")
    measured = candidate_rec.get("mem_peak_measured_bytes")
    if not modeled or not measured:
        return  # CPU runs carry no measured peak
    band = float(mem.get("model_vs_measured_band", 1.5))
    ratio = modeled / measured
    if ratio > band or ratio < 1.0 / band:
        failures.append(
            f"memory model {modeled / 1e9:.3f} GB is outside the "
            f"{band}x band of measured peak {measured / 1e9:.3f} GB "
            f"(ratio {ratio:.2f})")
    else:
        print(f"# memory model vs measured: {ratio:.2f}x "
              f"(band {1 / band:.2f}..{band:.2f})")


def check_phase_trajectory(floor, failures, lines):
    """Per-phase obs time summaries in BENCH lines (check 4): the
    latest same-platform run's phase seconds may not exceed the best
    (lowest) recorded value by more than the configured fraction, for
    phases above the absolute-noise floor — the ROADMAP item-4 gate
    over *where* iteration time goes, not just the headline rate."""
    cfg = floor.get("phases") or {}
    max_inc = float(cfg.get("max_seconds_increase", 0.5))
    min_abs = float(cfg.get("min_abs_seconds", 0.1))
    by_platform = {}
    for tag, rec in lines:
        phases = rec.get("phases")
        if isinstance(phases, dict) and phases:
            by_platform.setdefault(
                _platform_of(rec.get("unit", "")), []).append((tag, phases))
    if not by_platform:
        print("# no obs phase summaries recorded; phase check skipped")
        return
    for platform, recs in by_platform.items():
        tag, latest = recs[-1]
        checked = 0
        for name, seconds in latest.items():
            if not isinstance(seconds, (int, float)):
                continue
            history = [p[name] for _, p in recs[:-1]
                       if isinstance(p.get(name), (int, float))]
            if not history:
                continue
            best = min(history)
            if seconds < min_abs:
                continue  # latest is below the noise floor
            # a best below the noise floor is lifted TO the floor, not
            # exempted: a 0.09s phase regressing to 10s must still trip
            floor_s = max(best, min_abs)
            checked += 1
            if seconds > floor_s * (1.0 + max_inc):
                failures.append(
                    f"{tag}: {platform} phase '{name}' took {seconds:.3f}s "
                    f"> {1 + max_inc:.1f}x recorded floor {floor_s:.3f}s")
        print(f"# phases[{platform}]: {checked} phase(s) checked "
              f"against floor ({tag})")


def check_xla_cost_model(floor, failures):
    """XLA-vs-analytic-model band (check 5). Compiles the packed+int8
    wave histogram kernel (the exact program the quantized fixture
    trains through on every backend) at the recorded fixture shape and
    cross-validates both PR-4/5 models against the executable's own
    cost/memory analyses. Returns silently-skipped when the backend
    exposes neither analysis."""
    cfg = floor.get("xla")
    if not cfg:
        print("# no xla floor recorded; xla cross-check skipped")
        return
    fx = cfg["fixture"]
    n, f = int(fx["num_data"]), int(fx["storage_features"])
    b, s = int(fx["max_bins"]), int(fx["num_slots"])
    try:
        import jax
        import jax.numpy as jnp
        import numpy as np
        from lightgbm_tpu.learner import hist_traffic_model
        from lightgbm_tpu.obs.memory import train_memory_model
        from lightgbm_tpu.obs.xla import aot_cost_summary
        from lightgbm_tpu.ops import bin_pack as bp
        from lightgbm_tpu.ops import pallas_histogram as ph

        rng = np.random.RandomState(0)
        host = bp.pack_bins_host(
            rng.randint(0, b, size=(f, n)).astype(np.uint8), b)
        packed = bp.to_device(host)
        leaves, treedef = jax.tree_util.tree_flatten(packed)
        ghT = jnp.asarray(rng.randint(-8, 8, size=(n, 3)), jnp.int8)
        row_leaf = jnp.zeros(n, jnp.int32)
        leaf_ids = jnp.arange(s, dtype=jnp.int32)

        def run(leaves, ghT, row_leaf, leaf_ids):
            pb = jax.tree_util.tree_unflatten(treedef, leaves)
            return ph.hist_multi_int8_xla(pb, ghT, row_leaf, leaf_ids,
                                          max_bins=b, num_slots=s)

        cost = aot_cost_summary(run, leaves, ghT, row_leaf, leaf_ids)
    except Exception as exc:
        print(f"# xla cross-check skipped (introspection unavailable: "
              f"{exc!r})")
        return
    if cost is None:
        print("# xla cross-check skipped (no cost_analysis on this "
              "backend)")
        return

    traffic = hist_traffic_model(
        num_data=n, storage_features=f, max_bins=b,
        num_leaves=fx.get("num_leaves", 255), wave_max=s,
        gh_read_bytes=3, subtract=True)
    per_pass = traffic["bytes_per_pass"]
    band = float(cfg.get("arg_bytes_band", 1.25))

    arg = cost.get("argument_bytes")
    if arg:
        ratio = arg / per_pass
        if ratio > band or ratio < 1.0 / band:
            failures.append(
                f"xla cross-check: compiled wave-kernel argument bytes "
                f"{arg / 1e6:.2f} MB vs traffic model per-pass "
                f"{per_pass / 1e6:.2f} MB — ratio {ratio:.3f} outside "
                f"the {1 / band:.2f}..{band:.2f} band "
                f"(hist_bytes_per_iter no longer matches what XLA "
                f"streams)")
        else:
            print(f"# xla vs traffic model: argument bytes ratio "
                  f"{ratio:.3f} (band {1 / band:.2f}..{band:.2f}), "
                  f"compile {cost['compile_s']:.2f}s")
    ba = cost.get("bytes_accessed")
    min_ratio = float(cfg.get("min_bytes_accessed_ratio", 1.0))
    if ba is not None and ba < per_pass * min_ratio:
        failures.append(
            f"xla cross-check: XLA bytes-accessed {ba / 1e6:.2f} MB is "
            f"BELOW the analytic per-pass model {per_pass / 1e6:.2f} MB "
            f"x{min_ratio} — the traffic model overstates what the "
            f"program touches")

    # memory-model side: the model's operand components must cover the
    # executable's resident argument buffers (within the same band) and
    # the wave slab must cover the program's output
    mem = train_memory_model(
        num_data=n, num_features=f, max_bins=b,
        num_leaves=fx.get("num_leaves", 255), wave_max=s,
        pack_vpb=traffic["pack_vpb"], quantized=True)
    comp = mem["components"]
    operand_cover = comp["bins"] + comp["ght"] + comp["row_leaf"]
    if arg and operand_cover * band < arg:
        failures.append(
            f"xla cross-check: memory-model operand components "
            f"{operand_cover / 1e6:.2f} MB under-account the compiled "
            f"kernel's argument buffers {arg / 1e6:.2f} MB "
            f"(mem_peak_model_bytes misses a resident operand class)")
    out_b = cost.get("output_bytes")
    if out_b and comp["hist_wave"] * band < out_b:
        failures.append(
            f"xla cross-check: memory-model hist_wave slab "
            f"{comp['hist_wave'] / 1e6:.2f} MB smaller than the "
            f"compiled wave output {out_b / 1e6:.2f} MB")
    elif arg and out_b:
        print(f"# xla vs memory model: operands {operand_cover / 1e6:.2f}"
              f" MB cover args {arg / 1e6:.2f} MB; wave slab "
              f"{comp['hist_wave'] / 1e6:.3f} MB covers output "
              f"{out_b / 1e6:.3f} MB")


def check_health_summaries(floor, failures, lines):
    """Comms-health gate (check 6) over the obs/health summaries bench
    folds into its JSON line — the same pattern as the other obs
    pillars: the latest record carrying a `health` dict is held to the
    recorded straggler-skew ceiling (phases above the absolute-noise
    floor only) and to the collective-time-share ceiling (estimated
    collective seconds / measured train seconds). Runs without a mesh
    record nothing -> the check reports itself skipped."""
    cfg = floor.get("health")
    if not cfg:
        print("# no health floor recorded; health check skipped")
        return
    with_health = [(tag, rec) for tag, rec in lines
                   if isinstance(rec.get("health"), dict)]
    if not with_health:
        print("# no health summaries recorded (no mesh run); "
              "health check skipped")
        return
    tag, rec = with_health[-1]
    hs = rec["health"]
    max_skew = float(cfg.get("max_straggler_skew", 4.0))
    min_abs = float(cfg.get("min_abs_straggler_seconds", 0.05))
    strag = hs.get("straggler") or {}
    checked = 0
    for phase, ph in (strag.get("phases") or {}).items():
        if not isinstance(ph, dict):
            continue
        # the noise floor applies to the skew DENOMINATOR: a phase the
        # median host barely ran (host-local work like binning on
        # process 0) has a meaningless max/median ratio, not a straggler
        if float(ph.get("median_s", 0.0)) < min_abs:
            continue
        checked += 1
        skew = float(ph.get("skew", 1.0))
        if skew > max_skew:
            failures.append(
                f"{tag}: straggler skew {skew:.2f}x on phase '{phase}' "
                f"(worst shard {ph.get('worst')}) exceeds the "
                f"{max_skew}x ceiling")
    est = hs.get("collectives_est") or {}
    share = est.get("time_share")
    max_share = float(cfg.get("max_collective_time_share", 0.6))
    if isinstance(share, (int, float)) and share > max_share:
        failures.append(
            f"{tag}: estimated collective time share {share:.2%} "
            f"exceeds the {max_share:.0%} ceiling — comms-bound "
            f"iterations (est {est.get('est_seconds')}s of "
            f"{est.get('train_seconds')}s)")
    print(f"# health[{tag}]: {checked} straggler phase(s) checked"
          + (f", collective share {share:.2%}"
             if isinstance(share, (int, float)) else
             ", no collective share estimate"))


def check_resilience_overhead(floor, failures, lines):
    """Checkpoint-overhead ceiling (check 7): the latest record that
    actually checkpointed (bench `resilience` field) may not have spent
    more than the configured share of train wall-time writing
    snapshots. No checkpointing recorded => the check reports itself
    skipped — same graceful-skip pattern as the obs pillars."""
    cfg = floor.get("resilience")
    if not cfg:
        print("# no resilience floor recorded; checkpoint-overhead "
              "check skipped")
        return
    with_res = [(tag, rec) for tag, rec in lines
                if isinstance(rec.get("resilience"), dict)]
    if not with_res:
        print("# no checkpointing ran in any recorded bench; "
              "checkpoint-overhead check skipped")
        return
    tag, rec = with_res[-1]
    rs = rec["resilience"]
    ck_s = float(rs.get("checkpoint_seconds_total", 0.0))
    train_s = float(rs.get("train_seconds", 0.0))
    n = int(rs.get("checkpoints", 0))
    if n <= 0 or train_s <= 0.0:
        print(f"# resilience[{tag}]: no snapshots recorded; "
              "checkpoint-overhead check skipped")
        return
    share = ck_s / train_s
    max_share = float(cfg.get("max_checkpoint_time_share", 0.15))
    if share > max_share:
        failures.append(
            f"{tag}: checkpoint overhead {share:.2%} of train wall-time "
            f"({ck_s:.3f}s snapshots / {train_s:.3f}s train over {n} "
            f"snapshot(s)) exceeds the {max_share:.0%} ceiling")
    else:
        print(f"# resilience[{tag}]: checkpoint share {share:.2%} over "
              f"{n} snapshot(s) (ceiling {max_share:.0%})")


def _load_keyed_records(key, candidate_path=None):
    """[(tag, record)] for every bench line carrying a `key` summary
    dict (bench.py --continual / --stream), oldest first; candidate
    last. Accepts both a bare record blob and the driver's n/cmd/rc/
    tail wrapper (the summary line is fished out of the tail)."""
    out = []
    paths = sorted(glob.glob(os.path.join(REPO, "BENCH_*.json")))
    if candidate_path and os.path.exists(candidate_path):
        paths.append(candidate_path)
    for path in paths:
        try:
            with open(path) as fh:
                blob = json.load(fh)
        except (OSError, ValueError):
            continue
        rec = None
        if isinstance(blob.get(key), dict):
            rec = blob
        else:
            for line in reversed(str(blob.get("tail", "")).splitlines()):
                line = line.strip()
                if line.startswith("{") and f'"{key}"' in line:
                    try:
                        cand = json.loads(line)
                    except ValueError:
                        continue
                    if isinstance(cand.get(key), dict):
                        rec = cand
                        break
        if rec is not None:
            out.append((os.path.basename(path), rec))
    return out


def _load_continual_records(candidate_path=None):
    return _load_keyed_records("continual", candidate_path)


def check_continual_overhead(floor, failures, candidate_path=None):
    """Continual-loop overhead ceilings (check 8): over the latest
    bench record carrying a `continual` summary (bench.py --continual),
    the validated hot-swap share of continual wall-time and the total
    non-training overhead share (swap + rollback/snapshot bookkeeping +
    ingest) must stay under the floor-configured caps — a long-lived
    model is only viable if accepting a generation is nearly free.
    No continual bench recorded => the check reports itself skipped."""
    cfg = floor.get("continual")
    if not cfg:
        print("# no continual floor recorded; continual-overhead "
              "check skipped")
        return
    recs = _load_continual_records(candidate_path)
    if not recs:
        print("# no continual bench recorded; continual-overhead "
              "check skipped")
        return
    tag, rec = recs[-1]
    ct = rec["continual"]
    wall = float(ct.get("wall_seconds", 0.0))
    gens = int(ct.get("generations", 0))
    if wall <= 0.0 or gens <= 0:
        print(f"# continual[{tag}]: no generations recorded; "
              "continual-overhead check skipped")
        return
    swap_share = float(ct.get("swap_share",
                              float(ct.get("swap_seconds_total", 0.0))
                              / wall))
    overhead_share = float(ct.get("overhead_seconds", 0.0)) / wall
    max_swap = float(cfg.get("max_swap_share", 0.10))
    max_overhead = float(cfg.get("max_overhead_share", 0.25))
    if swap_share > max_swap:
        failures.append(
            f"{tag}: hot-swap share {swap_share:.2%} of continual "
            f"wall-time over {gens} generation(s) exceeds the "
            f"{max_swap:.0%} ceiling")
    if overhead_share > max_overhead:
        failures.append(
            f"{tag}: non-training overhead share {overhead_share:.2%} "
            f"of continual wall-time (swap + rollback + ingest) "
            f"exceeds the {max_overhead:.0%} ceiling")
    if swap_share <= max_swap and overhead_share <= max_overhead:
        print(f"# continual[{tag}]: swap share {swap_share:.2%}, "
              f"overhead share {overhead_share:.2%} over {gens} "
              f"generation(s), {int(ct.get('rollbacks', 0))} "
              f"rollback(s) (ceilings {max_swap:.0%}/{max_overhead:.0%})")


def check_stream_overhead(floor, failures, candidate_path=None):
    """Out-of-core streaming ceilings (check 9): over the latest bench
    record carrying a `stream` summary (bench.py --stream), the
    streamed run may not be more than the floor-configured factor
    slower than the same-run resident anchor, and the measured
    upload/compute overlap ratio must clear its floor — streaming is
    only a win if the double buffer actually hides the slab uploads.
    No streaming bench recorded => the check reports itself skipped."""
    cfg = floor.get("stream")
    if not cfg:
        print("# no stream floor recorded; stream-overhead check skipped")
        return
    recs = _load_keyed_records("stream", candidate_path)
    if not recs:
        print("# no streaming bench recorded; stream-overhead check "
              "skipped")
        return
    tag, rec = recs[-1]
    sm = rec["stream"]
    vs_resident = float(sm.get("vs_resident",
                               rec.get("vs_baseline", 0.0)) or 0.0)
    overlap = float(sm.get("stream_overlap_ratio",
                           sm.get("overlap_ratio", 0.0)) or 0.0)
    n_slabs = int(sm.get("n_slabs", 0))
    if vs_resident <= 0.0:
        print(f"# stream[{tag}]: no resident anchor recorded; "
              "stream-overhead check skipped")
        return
    platform = _platform_of(rec.get("unit", ""))
    key = f"max_overhead_vs_resident_{platform}"
    max_overhead = float(cfg.get(key, cfg.get("max_overhead_vs_resident",
                                              1.25)))
    min_overlap = float(cfg.get("min_overlap_ratio", 0.05))
    slowdown = 1.0 / vs_resident
    if slowdown > max_overhead:
        failures.append(
            f"{tag}: streamed training is {slowdown:.2f}x the resident "
            f"wall-time ({n_slabs} slabs, platform={platform}); ceiling "
            f"{max_overhead:.2f}x")
    if overlap < min_overlap:
        failures.append(
            f"{tag}: stream overlap ratio {overlap:.2%} is under the "
            f"{min_overlap:.0%} floor — uploads are not hiding behind "
            "device compute")
    if slowdown <= max_overhead and overlap >= min_overlap:
        print(f"# stream[{tag}]: {slowdown:.2f}x resident "
              f"({n_slabs} slabs), overlap {overlap:.2%} "
              f"(ceilings {max_overhead:.2f}x / >={min_overlap:.0%})")


def check_coldstart(floor, failures, candidate_path=None):
    """Warm-start ceilings (check 10): over the latest bench record
    carrying a `coldstart` summary (bench.py --coldstart):

    - the cache-warm rerun's REAL compile seconds must be at least
      ``min_compile_reduction`` x smaller than the cold run's (warm
      processes load, they don't compile — obs/xla attributes
      persistent-cache hits to cache_load_s, not compile_s);
    - total warm-start program-acquisition time (compile + cache load)
      must stay under the RATCHETING ``max_warm_acquire_s`` ceiling —
      lower it as cold start keeps shrinking;
    - a server restored from serialized artifacts must have served its
      first lowlat request with at most
      ``max_restore_lowlat_compiles`` serve/lowlat compiles (0: the
      whole ladder came from disk) — skipped, not failed, where the
      backend cannot serialize executables at all;
    - the restored executables' predictions must be bit-identical.

    No coldstart bench recorded => the check reports itself skipped."""
    cfg = floor.get("coldstart")
    if not cfg:
        print("# no coldstart floor recorded; coldstart check skipped")
        return
    recs = _load_keyed_records("coldstart", candidate_path)
    if not recs:
        print("# no coldstart bench recorded; coldstart check skipped")
        return
    tag, rec = recs[-1]
    cs = rec["coldstart"]
    cold = float(cs.get("cold_compile_s", 0.0))
    warm = float(cs.get("warm_compile_s", 0.0))
    if cold <= 0.0:
        print(f"# coldstart[{tag}]: no cold compile recorded; "
              "coldstart check skipped")
        return
    min_red = float(cfg.get("min_compile_reduction", 5.0))
    max_acquire = float(cfg.get("max_warm_acquire_s", 5.0))
    reduction = cold / max(warm, 1e-2)
    acquire = warm + float(cs.get("warm_cache_load_s", 0.0))
    if reduction < min_red:
        failures.append(
            f"{tag}: warm-start compile {warm:.3f}s is only "
            f"{reduction:.2f}x below the cold run's {cold:.3f}s "
            f"(floor {min_red:.1f}x) — the persistent compile cache "
            "is not biting")
    if acquire > max_acquire:
        failures.append(
            f"{tag}: warm-start program acquisition "
            f"(compile {warm:.3f}s + cache load "
            f"{cs.get('warm_cache_load_s', 0.0):.3f}s) exceeds the "
            f"{max_acquire:.1f}s ratchet ceiling")
    restore_ok = True
    if not cs.get("artifact_serialize_available", True):
        print(f"# coldstart[{tag}]: backend cannot serialize "
              "executables; artifact-restore sub-check skipped")
    else:
        max_restore = int(cfg.get("max_restore_lowlat_compiles", 0))
        restore = int(cs.get("restore_lowlat_compiles", 0))
        if restore > max_restore:
            restore_ok = False
            failures.append(
                f"{tag}: artifact-restored server paid {restore} "
                f"serve/lowlat compile(s) (ceiling {max_restore}) — "
                "the serialized-artifact path is not restoring")
        if cs.get("restore_bit_identical") is False:
            restore_ok = False
            failures.append(
                f"{tag}: artifact-restored predictions are NOT "
                "bit-identical to the exporter's")
    if reduction >= min_red and acquire <= max_acquire and restore_ok:
        print(f"# coldstart[{tag}]: compile {cold:.2f}s -> {warm:.2f}s "
              f"({reduction:.1f}x, floor {min_red:.0f}x), acquisition "
              f"{acquire:.2f}s (ceiling {max_acquire:.1f}s), restore "
              f"{int(cs.get('restore_lowlat_compiles', 0))} compile(s) "
              f"/ {int(cs.get('restore_aot_loads', 0))} load(s)")


def check_profile_roofline(floor, failures, candidate_path=None):
    """Device-time attribution + roofline (check 11): over the latest
    bench record carrying a ``roofline`` summary (the obs/profile.py
    post-loop window bench.py folds into its JSON line):

    - coverage — attributed device seconds over the profile window's
      wall time — must land inside the floor-configured band. Too low
      means the instrumented program boundaries are no longer where the
      time goes (an untagged hot program appeared); above the ceiling
      means double-counted or mis-rebased slices.
    - the best per-tag utilization (achieved bytes/s or flops/s over
      the hostenv.device_peaks row) must clear the RATCHETING
      ``min_utilization`` floor — raise it as the kernels improve.
    - the same record must carry non-empty ``device_seconds_by_tag``.

    No profiled bench recorded => the check reports itself skipped;
    records without a cost-analysis join skip the utilization sub-check
    (the backend exposes no bytes/flops there)."""
    cfg = floor.get("profile")
    if not cfg:
        print("# no profile floor recorded; roofline check skipped")
        return
    recs = _load_keyed_records("roofline", candidate_path)
    if not recs:
        print("# no profiled bench recorded; roofline check skipped")
        return
    tag, rec = recs[-1]
    rl = rec["roofline"]
    by_tag = rl.get("by_tag") or {}
    if not by_tag or not rec.get("device_seconds_by_tag"):
        print(f"# profile[{tag}]: no attributed device seconds; "
              "roofline check skipped")
        return
    n_fail0 = len(failures)
    coverage = rl.get("coverage")
    min_cov = float(cfg.get("min_coverage", 0.2))
    max_cov = float(cfg.get("max_coverage", 1.5))
    if coverage is None:
        print(f"# profile[{tag}]: no coverage recorded; coverage band "
              "sub-check skipped")
    elif not (min_cov <= float(coverage) <= max_cov):
        failures.append(
            f"{tag}: device-time coverage {float(coverage):.2%} of the "
            f"profile window is outside the [{min_cov:.0%}, "
            f"{max_cov:.0%}] band — attribution is missing hot "
            "programs or double-counting slices")
    with_util = [r for r in by_tag.values()
                 if "bytes_utilization" in r or "flops_utilization" in r]
    if with_util:
        best_util = max(
            max(float(r.get("bytes_utilization", 0.0) or 0.0),
                float(r.get("flops_utilization", 0.0) or 0.0))
            for r in with_util)
        min_util = float(cfg.get("min_utilization", 0.0))
        if best_util < min_util:
            failures.append(
                f"{tag}: best roofline utilization {best_util:.2e} is "
                f"under the {min_util:.0e} ratchet floor — the "
                "attributed programs are not moving bytes/flops at a "
                "credible rate for this platform")
    else:
        best_util = 0.0
        print(f"# profile[{tag}]: no cost-analysis join (backend "
              "exposes no bytes/flops); utilization sub-check skipped")
    if len(failures) == n_fail0:
        cov_s = ("n/a" if coverage is None
                 else f"{float(coverage):.2%}")
        verdicts = {t: r.get("verdict", "?") for t, r in
                    sorted(by_tag.items())}
        print(f"# profile[{tag}]: coverage {cov_s} (band "
              f"[{min_cov:.0%}, {max_cov:.0%}]), best utilization "
              f"{best_util:.2e}, {len(by_tag)} tag(s) {verdicts}")


def check_fleet_availability(floor, failures, candidate_path=None):
    """Fleet chaos availability (check 12): over the latest bench
    record carrying a ``fleet`` summary (bench.py --fleet — open-loop
    load through the FleetRouter with one replica killed at the 40%
    mark), the served fraction must clear the floor-configured
    ``min_availability`` (ISSUE 17: kill a replica under load, lose
    zero requests), the killed replica must have been quarantined, and
    every served answer must have stayed bit-identical to a direct
    predict (the pack contract that makes failover retries safe).
    No fleet bench recorded => the check reports itself skipped."""
    cfg = floor.get("fleet")
    if not cfg:
        print("# no fleet floor recorded; fleet-availability check "
              "skipped")
        return
    recs = _load_keyed_records("fleet", candidate_path)
    if not recs:
        print("# no fleet bench recorded; fleet-availability check "
              "skipped")
        return
    tag, rec = recs[-1]
    ft = rec["fleet"]
    total = int(ft.get("requests", 0))
    if total <= 0:
        print(f"# fleet[{tag}]: no requests recorded; "
              "fleet-availability check skipped")
        return
    n_fail0 = len(failures)
    availability = float(ft.get("availability", 0.0))
    min_avail = float(cfg.get("min_availability", 0.999))
    if availability < min_avail:
        failures.append(
            f"{tag}: fleet availability {availability:.4%} over {total} "
            f"request(s) with a mid-run replica kill is under the "
            f"{min_avail:.1%} floor — failover is dropping requests")
    if not ft.get("parity_ok", True):
        failures.append(
            f"{tag}: fleet answers diverged bitwise from a direct "
            "predict — the idempotent-failover pack contract is broken")
    if "killed_quarantined" in ft and not ft["killed_quarantined"]:
        failures.append(
            f"{tag}: the killed replica was never quarantined — the "
            "health probe loop is not converting dispatch failures "
            "into routing decisions")
    if len(failures) == n_fail0:
        print(f"# fleet[{tag}]: availability {availability:.4%} over "
              f"{total} request(s) ({int(ft.get('failovers', 0))} "
              f"failover(s), {int(ft.get('quarantines', 0))} "
              f"quarantine(s), fleet p99 {ft.get('p99_ms', 0)}ms vs "
              f"single {ft.get('single_p99_ms', 0)}ms; floor "
              f"{min_avail:.1%})")


def check_shap(floor, failures, candidate_path=None):
    """SHAP-contribution floors (check 13): over the latest bench
    record carrying a ``shap`` summary (bench.py --shap), the batched
    device kernel must be at least ``min_speedup_vs_host_<platform>`` x
    faster than the same-run host recursive oracle (the whole point of
    the path-decomposed reformulation), the parity subset must have
    matched (no PARITY-MISMATCH marker), and the measured path-table
    pack bytes must sit within ``pack_vs_model_band`` of the analytic
    memory model's shap_pack component — the band that keeps
    preflight's fit/doesn't-fit verdicts honest for explain traffic.
    No shap bench recorded => the check reports itself skipped."""
    cfg = floor.get("shap")
    if not cfg:
        print("# no shap floor recorded; shap check skipped")
        return
    recs = _load_keyed_records("shap", candidate_path)
    if not recs:
        print("# no shap bench recorded; shap check skipped")
        return
    tag, rec = recs[-1]
    sh = rec["shap"]
    speedup = float(rec.get("vs_baseline", 0.0) or 0.0)
    if speedup <= 0.0:
        print(f"# shap[{tag}]: no oracle anchor recorded; shap check "
              "skipped")
        return
    n_fail0 = len(failures)
    platform = _platform_of(rec.get("unit", ""))
    min_speedup = float(cfg.get(
        f"min_speedup_vs_host_{platform}",
        cfg.get("min_speedup_vs_host_cpu", 5.0)))
    if speedup < min_speedup:
        failures.append(
            f"{tag}: device TreeSHAP is only {speedup:.2f}x the host "
            f"recursive oracle (platform={platform}, floor "
            f"{min_speedup:.1f}x) — the batched kernel lost its edge")
    if "PARITY-MISMATCH" in str(rec.get("unit", "")):
        failures.append(
            f"{tag}: shap bench flagged PARITY-MISMATCH — device "
            "contributions diverged from the host oracle beyond f32 "
            "recurrence tolerance")
    pack = float(sh.get("pack_bytes", 0.0) or 0.0)
    model = float(sh.get("model_pack_bytes", 0.0) or 0.0)
    band = float(cfg.get("pack_vs_model_band", 2.0))
    if pack > 0.0 and model > 0.0:
        ratio = pack / model
        if ratio > band or ratio < 1.0 / band:
            failures.append(
                f"{tag}: measured path-table pack {pack / 1e6:.2f} MB is "
                f"outside the {band}x band of the analytic model's "
                f"{model / 1e6:.2f} MB (ratio {ratio:.2f}) — "
                "predict_memory_model(contrib=True) no longer tracks "
                "the packer")
    if len(failures) == n_fail0:
        print(f"# shap[{tag}]: {speedup:.1f}x vs host oracle "
              f"(platform={platform}, floor {min_speedup:.1f}x), "
              f"pack {pack / 1e6:.2f} MB vs model {model / 1e6:.2f} MB, "
              f"paths={int(sh.get('paths', 0))} "
              f"depth={int(sh.get('depth', 0))}")


def check_collective_scatter(floor, failures):
    """Reduce-scatter collective byte model vs psum oracle (check 14)."""
    sc = floor.get("scatter")
    if not sc:
        print("# no scatter floor recorded; collective-scatter check "
              "skipped")
        return
    from lightgbm_tpu.learner import collective_traffic_model
    fx = sc["fixture"]
    shape = dict(num_features=fx["num_features"], max_bins=fx["max_bins"],
                 num_leaves=fx["num_leaves"], wave_max=fx["wave_max"],
                 width=fx["width"])
    psum = collective_traffic_model(**shape, reduction="psum")
    scat = collective_traffic_model(**shape, reduction="scatter")
    ratio = (psum["collective_bytes_per_iter"]
             / scat["collective_bytes_per_iter"])
    min_red = float(sc["min_collective_reduction_w4"])
    if ratio < min_red:
        failures.append(
            f"collective scatter reduction fell to {ratio:.2f}x "
            f"< required {min_red}x at W={fx['width']} "
            f"(scatter {scat['collective_bytes_per_iter']/1e3:.0f} KB/iter "
            f"vs psum {psum['collective_bytes_per_iter']/1e3:.0f} KB/iter)")
    print(f"# collective scatter: {ratio:.2f}x vs psum at W={fx['width']} "
          f"({scat['collective_bytes_per_iter']/1e3:.0f} KB/iter vs "
          f"{psum['collective_bytes_per_iter']/1e3:.0f} KB/iter)")


def check_bench_trajectory(floor, failures, lines, candidate_rec=None):
    if not lines:
        print("# no BENCH_*.json lines found; trajectory check skipped")
        return
    drop = float(floor["bench"].get("max_value_drop", 0.10))
    by_platform = {}
    for tag, rec in lines:
        by_platform.setdefault(_platform_of(rec.get("unit", "")),
                               []).append((tag, rec))
    for platform, recs in by_platform.items():
        values = [r.get("vs_baseline", 0.0) or 0.0 for _, r in recs]
        best, latest = max(values), values[-1]
        tag = recs[-1][0]
        if best > 0 and latest < best * (1.0 - drop):
            failures.append(
                f"{tag}: {platform} vs_baseline {latest:.4f} dropped "
                f">{drop:.0%} below recorded floor {best:.4f}")
        else:
            print(f"# bench[{platform}]: latest {latest:.4f} vs floor "
                  f"{best:.4f} ({tag})")
    if candidate_rec:
        # the candidate's absolute bytes depend on its row count and
        # bin width (bench's train config is 63-bin/unpacked while the
        # floor fixture is the 15-bin packed shape) — so gate on the candidate's OWN
        # reduction ratio vs its oracle, which is N-invariant. The
        # subtraction-aware schedule + fused gradient pass alone give
        # >= ~1.35 at any config; losing either drops below the floor.
        red = candidate_rec.get("hist_bytes_reduction")
        min_red = float(floor["bench"].get("min_candidate_reduction", 1.3))
        if red is not None and red < min_red:
            failures.append(
                f"candidate hist_bytes_reduction {red:.2f}x < "
                f"floor {min_red}x (scheduler/encoding regression)")


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    candidate = argv[0] if argv else None
    with open(FLOOR_PATH) as fh:
        floor = json.load(fh)
    # one disk pass: every trajectory check reads the same line list
    lines = _load_bench_lines(candidate)
    candidate_rec = None
    if candidate and lines and \
            lines[-1][0] == os.path.basename(candidate):
        candidate_rec = lines[-1][1]
    failures = []
    actual = check_traffic_model(floor, failures)
    check_memory_model(floor, failures, candidate_rec)
    check_xla_cost_model(floor, failures)
    check_bench_trajectory(floor, failures, lines, candidate_rec)
    check_phase_trajectory(floor, failures, lines)
    check_health_summaries(floor, failures, lines)
    check_resilience_overhead(floor, failures, lines)
    check_continual_overhead(floor, failures, candidate)
    check_stream_overhead(floor, failures, candidate)
    check_coldstart(floor, failures, candidate)
    check_profile_roofline(floor, failures, candidate)
    check_fleet_availability(floor, failures, candidate)
    check_shap(floor, failures, candidate)
    check_collective_scatter(floor, failures)
    if failures:
        for f in failures:
            print(f"PERF GATE FAIL: {f}")
        return 1
    print(f"# perf gate OK ({actual['passes']}-pass schedule, "
          f"{actual['hist_bytes_per_iter']/1e9:.2f} GB/iter model)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
