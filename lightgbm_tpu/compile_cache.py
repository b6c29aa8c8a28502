"""Persistent XLA compile-cache policy — warm start as the default.

The only chip run on record before this policy (round 2; 112 s per
iteration; record deleted with the plug-in it was taken through) paid
108.9 s of warm-up and compile before the first useful iteration. This
module is the ONE place the cache policy lives, and every program-entry
boundary routes through it:

- ``Booster.__init__`` / ``engine.train`` / ``engine.cv`` (training),
- ``serve.ModelRegistry`` / ``serve_file`` (serving),
- ``bench.py``, ``chip_smoke.py`` and ``hostenv.cpu_child_env``.

Where the environment sets ``JAX_COMPILATION_CACHE_DIR``, that directory
is the cache and nothing here sets another: whoever runs the program
(an operator, the chip tool) placed it, so ``tpu_compile_cache_dir``
and ``configure("on", dir)`` give way to it. Where it is unset,
``configure(mode, cache_dir)`` arms ``jax.config.jax_compilation_cache_dir``:

- ``auto`` (the ``tpu_compile_cache`` default): enable the cache at the
  repo-local ``.jax_cache`` unless ``jax.config`` already names one
  (tests/conftest).
- ``on``: force the cache to ``cache_dir`` (or the repo-local
  directory), replacing any prior setting.
- ``off``: never touch jax config (an already-armed cache is left
  alone — "off" opts this entry point out, it does not disarm others).

No path is ever derived from a temp name, pid or time: the directory is
part of the cache key's lookup, so one that moves never hits.

Donation policy: ``donation_allowed()`` is consulted by every program
boundary that donates (``obs/xla.instrumented_jit``);
``LGBM_TPU_NO_DONATE`` force-drops donation (a memory optimisation
only).

Hygiene: the cache directory grows without bound on a long-lived host
(every shape bucket of every model adds entries). ``prune_cache()`` is
a best-effort LRU prune to the ``LGBM_TPU_COMPILE_CACHE_MAX_BYTES``
budget (default 4 GiB; <=0 disables), run at most once per directory
per process, and ONLY for directories this framework owns (our knob /
the repo-local default) — a ``JAX_COMPILATION_CACHE_DIR`` from the
environment may be shared with other projects and is never deleted
from. A pruned entry is only a future cache miss — XLA regenerates it —
so pruning can never break a running process.
"""

from __future__ import annotations

import os
from typing import Optional

_DEFAULT_MAX_BYTES = 4 << 30

# modes this module accepts for tpu_compile_cache
_MODES = ("auto", "on", "off")


def repo_cache_dir() -> str:
    """The checkout-local ``.jax_cache`` (git-ignored)."""
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")


def default_cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` where the environment sets it,
    else the repo-local directory."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or repo_cache_dir()


def cache_active() -> bool:
    """True when a persistent compilation cache is configured — via
    ``jax.config`` (which also absorbs ``JAX_COMPILATION_CACHE_DIR``)
    or, before jax is importable, the env var alone."""
    try:
        import jax
        return bool(jax.config.jax_compilation_cache_dir)
    except Exception:
        return bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))


def donation_allowed() -> bool:
    """THE donation policy for every program boundary (obs/xla's
    ``instrumented_jit`` consults this before passing donate_argnums):
    donation stays on unless ``LGBM_TPU_NO_DONATE`` is set."""
    return not os.environ.get("LGBM_TPU_NO_DONATE")


def configure(mode: str = "auto", cache_dir: Optional[str] = None) -> bool:
    """Arm the persistent compilation cache per the module docstring.

    Returns True when a cache is active after the call (whether this
    call armed it or an earlier configuration did). Best-effort: any
    jax config failure (too-old jax, read-only filesystem) returns
    False rather than raising — cold compiles are slow, not wrong.
    """
    mode = str(mode or "auto").lower()
    if mode not in _MODES:
        from . import log
        log.warning(f"tpu_compile_cache={mode!r} is not one of {_MODES}; "
                    "treating as 'auto'")
        mode = "auto"
    if mode == "off":
        return False
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if mode == "auto" and not env_dir and cache_active():
        return True
    path = env_dir or cache_dir or repo_cache_dir()
    # only ever prune a directory THIS framework owns: one named by our
    # knob or the repo-local default. A JAX_COMPILATION_CACHE_DIR from
    # the environment (possibly shared across projects) is used as-is
    # but never deleted from.
    owned = not env_dir
    try:
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
        # cache everything, however small/fast: warm start must make
        # compile_s_total ~0, and a skipped tiny program would still
        # recompile every process
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    except Exception:
        return False
    if owned:
        prune_cache_once(path)
    return True


_pruned_once: set = set()  # dirs already pruned in this process


def prune_cache_once(cache_dir: str) -> int:
    """``prune_cache``, at most once per directory per process — the
    hygiene pass costs a full os.walk/stat sweep, which must not repeat
    for every Booster a sweep or cv() constructs."""
    if cache_dir in _pruned_once:
        return 0
    _pruned_once.add(cache_dir)
    return prune_cache(cache_dir)


def cache_size_bytes(cache_dir: Optional[str] = None) -> int:
    """Total bytes under the cache directory (0 when absent)."""
    root = cache_dir or default_cache_dir()
    total = 0
    for dirpath, _dirnames, filenames in os.walk(root):
        for name in filenames:
            try:
                total += os.stat(os.path.join(dirpath, name)).st_size
            except OSError:
                continue
    return total


def prune_cache(cache_dir: Optional[str] = None,
                max_bytes: Optional[int] = None) -> int:
    """Best-effort LRU prune of the cache directory to `max_bytes`
    (default ``LGBM_TPU_COMPILE_CACHE_MAX_BYTES``, 4 GiB; <=0 =
    unbounded). Oldest entries — by last access where the filesystem
    tracks it, else last modification — go first. Returns the bytes
    removed. Never raises: a prune failure only means a bigger cache."""
    if max_bytes is None:
        try:
            max_bytes = int(os.environ.get(
                "LGBM_TPU_COMPILE_CACHE_MAX_BYTES", _DEFAULT_MAX_BYTES))
        except ValueError:
            max_bytes = _DEFAULT_MAX_BYTES
    if max_bytes <= 0:
        return 0
    root = cache_dir or default_cache_dir()
    entries = []  # (lru_stamp, size, path)
    total = 0
    try:
        for dirpath, _dirnames, filenames in os.walk(root):
            for name in filenames:
                path = os.path.join(dirpath, name)
                try:
                    st = os.stat(path)
                except OSError:
                    continue
                entries.append((max(st.st_atime, st.st_mtime),
                                st.st_size, path))
                total += st.st_size
    except OSError:
        return 0
    if total <= max_bytes:
        return 0
    removed = 0
    entries.sort()  # oldest first
    for _stamp, size, path in entries:
        if total - removed <= max_bytes:
            break
        try:
            os.unlink(path)
        except OSError:
            continue
        removed += size
    return removed
