"""Host-side environment helpers: process identity labels, the
per-device roofline peaks, and the environment recipe for CPU-only
helper processes.

A TPU chip belongs to one process at a time, so every subprocess that
should compute on the CPU (cluster workers, multi-device tests) gets
``cpu_child_env``: JAX_PLATFORMS=cpu, optionally N virtual CPU devices,
and the parent's persistent compile cache.
"""

from __future__ import annotations

import os
import socket
import sys
from typing import Dict, Optional


def host_labels() -> Dict[str, str]:
    """Host/process identity labels for trace metadata (obs/trace.py
    emits them as Chrome ``process_labels`` so multi-process Perfetto
    traces are tellable apart).

    Passive: reads ``jax.distributed.global_state`` only when jax is
    already imported and never initialises a backend. Process index /
    count appear only when jax.distributed is initialized."""
    labels = {"hostname": socket.gethostname(), "pid": str(os.getpid())}
    jax_mod = sys.modules.get("jax")
    if jax_mod is not None:
        try:
            state = jax_mod.distributed.global_state
            if getattr(state, "process_id", None) is not None:
                labels["process_index"] = str(state.process_id)
            if getattr(state, "num_processes", None):
                labels["num_processes"] = str(state.num_processes)
        except Exception:
            pass
    return labels


#: Per-chip peak throughputs feeding the roofline layer (obs/profile.py)
#: and perf-gate check 11, keyed by ``jax.Device.device_kind`` ("cpu"
#: for the CPU backend, whose row exists for the tests). A device that
#: is not in the table is an error, never a default: utilization against
#: another part's peaks is a wrong number. Env-overridable
#: (LGBM_TPU_PEAK_BYTES_PER_S / LGBM_TPU_PEAK_FLOPS).
_DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    # one modern x86 core: ~50 GF/s fp32 FMA, ~20 GB/s streaming DRAM
    "cpu": {"flops_per_s": 5.0e10, "bytes_per_s": 2.0e10},
    # TPU v5e, one chip (Google Cloud documentation, "TPU v5e"):
    # 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s
    "TPU v5 lite": {"flops_per_s": 1.97e14, "int8_ops_per_s": 3.93e14,
                    "bytes_per_s": 8.19e11},
}


def device_peaks(device_kind: str) -> Dict[str, float]:
    """Roofline peaks of one device of `device_kind` (see the table
    above). Passive: the caller supplies the kind; this module never
    probes a backend. Unknown kinds raise KeyError."""
    try:
        peaks = dict(_DEVICE_PEAKS[device_kind])
    except KeyError:
        raise KeyError(
            f"no roofline peaks recorded for device kind {device_kind!r} "
            f"(known: {sorted(_DEVICE_PEAKS)}); add its published peaks "
            "to hostenv._DEVICE_PEAKS") from None
    for env, key in (("LGBM_TPU_PEAK_FLOPS", "flops_per_s"),
                     ("LGBM_TPU_PEAK_BYTES_PER_S", "bytes_per_s")):
        raw = os.environ.get(env, "")
        if raw:
            try:
                peaks[key] = float(raw)
            except ValueError:
                pass
    return peaks


def cpu_child_env(n_devices: Optional[int] = None,
                  base: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """A copy of the environment made safe for a CPU-only child.

    n_devices: when given, force that many virtual CPU devices via
    --xla_force_host_platform_device_count (replacing any inherited
    setting of that flag).
    """
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    if n_devices is not None:
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "host_platform_device_count" not in f]
        flags.append(f"--xla_force_host_platform_device_count={n_devices}")
        env["XLA_FLAGS"] = " ".join(flags)
    # Persistent compilation cache, shared with the parent: an inherited
    # JAX_COMPILATION_CACHE_DIR stays, otherwise the repo-local default
    # (compile_cache.configure arms the same one in-process). Cache
    # everything, however small/fast, so a warmed program is a disk hit.
    from .compile_cache import prune_cache_once, repo_cache_dir
    env.setdefault("JAX_COMPILATION_CACHE_DIR", repo_cache_dir())
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    # best-effort LRU hygiene before handing the dir to another process:
    # the repo-local cache grows without bound on a long-lived host.
    # ONLY the repo-local default is pruned — an inherited
    # JAX_COMPILATION_CACHE_DIR is a user-managed directory this
    # library must never delete from.
    if env["JAX_COMPILATION_CACHE_DIR"] == repo_cache_dir():
        prune_cache_once(env["JAX_COMPILATION_CACHE_DIR"])
    return env
