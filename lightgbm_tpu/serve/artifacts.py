"""Serialized AOT serving artifacts — warm a replica from disk.

``LowLatencyPredictor``'s warm state is one compiled XLA executable per
(row-bucket, feature-width). Before this module that state existed only
in process memory: every replica restart, and every LRU pack eviction's
later re-admission, re-ran ``jit().lower().compile()`` for the whole
bucket ladder. This module persists those executables through
``jax.experimental.serialize_executable`` so a restarted ``ModelServer``
(or a re-admitted model) warms from disk in milliseconds with ZERO
``serve/lowlat`` compiles — asserted via obs counters by
``tools/check_coldstart.py`` and perf-gate check 10.

Keying / invalidation: every artifact carries a fingerprint —

- ``artifact_version`` (this module's on-disk format),
- ``jax`` / ``jaxlib`` versions and the backend platform + device kind
  and count (a serialized executable is machine code for ONE runtime),
- the packed-ensemble layout (``PackedEnsemble`` field names + per-
  field shapes/dtypes — the "pack version" of the serving tensors) and
  a content digest of the host-side trees (so a retrained/mutated
  model can never load a stale executable; see ``trees_digest``),
- the (row-bucket, feature-width) program identity.

``load`` returns None on ANY mismatch, missing file, or deserialize
failure; the caller then compiles exactly as before — artifacts are an
accelerator, never a correctness dependency, and predictions are
bit-identical either way (the deserialized executable IS the compiled
program that was serialized).

Counters (always-on ``obs.metrics``, exported as ``lgbmtpu_serve_*``):

- ``serve/aot_loads``           — executables restored from disk
- ``serve/aot_exports``         — executables serialized to disk
- ``serve/aot_load_failures``   — fingerprint mismatch / corrupt /
  failed deserialize (each one fell back to a real compile)
- ``serve/aot_export_failures`` — serialize or save-time validation
  failed (nothing was published; see ``ArtifactStore.save``)
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import tempfile
from typing import Any, Dict, Optional

import numpy as np

from ..obs.metrics import global_metrics

# on-disk format version: bump when the payload layout below changes
# (2: the payload records the ids of the devices it was compiled for)
ARTIFACT_VERSION = 2


def serialize_available() -> bool:
    """Whether this jax exposes executable serialization at all —
    callers skip the store gracefully when it doesn't."""
    try:
        from jax.experimental import serialize_executable  # noqa: F401
        return True
    except Exception:
        return False


def backend_fingerprint() -> Dict[str, Any]:
    """The runtime identity a serialized executable is only valid for."""
    import jax
    try:
        import jaxlib
        jaxlib_v = str(jaxlib.__version__)
    except Exception:
        jaxlib_v = "?"
    try:
        dev = jax.devices()[0]
        kind = str(getattr(dev, "device_kind", "?"))
        n_dev = int(jax.device_count())
    except Exception:
        kind, n_dev = "?", 0
    return {
        "artifact_version": ARTIFACT_VERSION,
        "jax": str(jax.__version__),
        "jaxlib": jaxlib_v,
        "platform": str(jax.default_backend()),
        "device_kind": kind,
        "n_devices": n_dev,
    }


def trees_digest(trees, num_tree_per_iteration: int = 1) -> str:
    """Content digest of the HOST-side trees — the model-identity half
    of the artifact key. Any retrain or mutation (apply_shrinkage,
    add_bias, refit) changes the hashed arrays, so a stale executable
    can never be loaded for a changed model. Hashing the trees instead
    of the packed device tensors keeps key construction free of
    device->host readbacks (the packed tensors' shapes/dtypes are keyed
    separately by the caller — they are host-known without transfer)."""
    h = hashlib.sha256()
    h.update(str(int(num_tree_per_iteration)).encode())
    h.update(str(len(trees)).encode())
    for tr in trees:
        n = int(tr.num_internal)
        h.update(str(n).encode())
        for arr in (tr.split_feature[:n], tr.threshold[:n],
                    tr.decision_type[:n], tr.left_child[:n],
                    tr.right_child[:n], tr.leaf_value):
            host = np.ascontiguousarray(arr)
            h.update(str(host.dtype).encode())
            h.update(host.tobytes())
        if getattr(tr, "num_cat", 0):
            h.update(np.ascontiguousarray(
                tr.cat_threshold, np.uint32).tobytes())
    return h.hexdigest()[:24]


class ArtifactStore:
    """Directory-backed store of serialized AOT executables.

    One file per executable, named by the SHA-256 of the canonical
    fingerprint JSON — models can share a directory without collisions,
    and a changed fingerprint is simply a different filename (the stale
    file ages out; it is never wrongly loaded). Writes are atomic
    (tempfile + rename) so a crashed export can't strand a torn
    artifact for a later replica to trip over.
    """

    def __init__(self, root: str):
        self.root = str(root)

    def _path(self, key: Dict[str, Any]) -> str:
        canon = json.dumps(key, sort_keys=True, separators=(",", ":"))
        name = hashlib.sha256(canon.encode()).hexdigest()[:32]
        return os.path.join(self.root, f"{name}.aotx")

    def has(self, key: Dict[str, Any]) -> bool:
        """Whether an artifact is stored under `key` (no load attempt)."""
        return os.path.exists(self._path(key))

    # ------------------------------------------------------------------
    def save(self, key: Dict[str, Any], compiled) -> bool:
        """Serialize `compiled` under `key`. Best-effort: False on any
        failure (backends without serialization, read-only disk).

        The payload is VALIDATED by deserializing it back before it is
        written: some backend/executable combinations serialize without
        error but produce a blob that cannot load (e.g. an executable
        that itself came out of the XLA disk cache can re-serialize with
        dangling fusion symbols). A store must never
        publish an artifact a restarted replica would trip over —
        counted under ``serve/aot_export_failures``."""
        try:
            from jax.experimental import serialize_executable as se
            payload, in_tree, out_tree = se.serialize(compiled)
            # deserialize_and_load places the executable on EVERY local
            # device unless told otherwise; a single-device program then
            # fails at call time on any host with more than one
            devices = compiled.runtime_executable().local_devices()
            se.deserialize_and_load(payload, in_tree, out_tree,
                                    execution_devices=devices)
            blob = pickle.dumps({"key": key, "payload": payload,
                                 "in_tree": in_tree, "out_tree": out_tree,
                                 "device_ids": [d.id for d in devices]},
                                protocol=pickle.HIGHEST_PROTOCOL)
            os.makedirs(self.root, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=self.root, suffix=".tmp")
            try:
                with os.fdopen(fd, "wb") as fh:
                    fh.write(blob)
                os.replace(tmp, self._path(key))
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
        except Exception:
            global_metrics.inc_counter("serve/aot_export_failures")
            return False
        global_metrics.inc_counter("serve/aot_exports")
        return True

    def load(self, key: Dict[str, Any]):
        """Deserialize the executable stored under `key`, or None on any
        miss/mismatch/corruption (the caller recompiles). A plain miss
        is silent; an EXISTING file that fails to load counts a
        ``serve/aot_load_failures``."""
        path = self._path(key)
        if not os.path.exists(path):
            return None
        try:
            from jax.experimental import serialize_executable as se
            with open(path, "rb") as fh:
                rec = pickle.load(fh)
            # defense in depth: the filename hash already encodes the
            # fingerprint, but verify the stored key verbatim so a hash
            # collision or a hand-renamed file can never smuggle a
            # foreign executable into this model
            if rec.get("key") != key:
                raise ValueError("artifact fingerprint mismatch")
            import jax
            by_id = {d.id: d for d in jax.devices()}
            compiled = se.deserialize_and_load(
                rec["payload"], rec["in_tree"], rec["out_tree"],
                execution_devices=[by_id[i] for i in rec["device_ids"]])
        except Exception:
            global_metrics.inc_counter("serve/aot_load_failures")
            return None
        global_metrics.inc_counter("serve/aot_loads")
        return compiled

    # ------------------------------------------------------------------
    def __len__(self) -> int:
        try:
            return sum(1 for n in os.listdir(self.root)
                       if n.endswith(".aotx"))
        except OSError:
            return 0


def open_store(artifact_dir: Optional[str]) -> Optional[ArtifactStore]:
    """An ArtifactStore for `artifact_dir`, or None when the dir is
    unset/empty or this jax cannot serialize executables at all."""
    if not artifact_dir:
        return None
    if not serialize_available():
        return None
    return ArtifactStore(artifact_dir)
