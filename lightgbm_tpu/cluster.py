"""Multi-process training orchestration (the Dask-module analog).

The reference ships dask.py (1749 LoC) to place data partitions on
workers, build the machine list, and run socket-collective training
(ref: python-package/lightgbm/dask.py:196 _train_part, :398
_machines_to_worker_map). This module is the same orchestration story
for the TPU build's jax.distributed backend — without requiring dask in
the image: `train_distributed` spawns one worker process per data
partition on this host (or joins an existing cluster when ranks are
launched externally, e.g. one process per TPU host), wires the
coordinator/rank env, syncs binning from rank 0, trains
`tree_learner=data` across all processes, and returns the model.

For real pods, launch one process per host with LGBM_TPU_RANK set and
call `worker_train` directly — exactly how dask.py's _train_part runs
inside each dask worker.
"""

from __future__ import annotations

import json
import os
import pickle
import socket
import subprocess
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def worker_train(params: Dict[str, Any], X: np.ndarray, y: np.ndarray,
                 *, coordinator: str, num_workers: int, rank: int,
                 weight=None, group=None, num_boost_round: int = 100,
                 out_model: Optional[str] = None) -> Optional[str]:
    """One worker's training step (the _train_part analog,
    ref: dask.py:196): join the runtime, sync bins with rank 0, train
    data-parallel, rank 0 returns/saves the model text."""
    from . import Booster, Dataset
    from .parallel import distributed as dist

    dist.init_distributed(coordinator_address=coordinator,
                          num_processes=num_workers, process_id=rank)
    params = dict(params)
    params.setdefault("tree_learner", "data")
    params.setdefault("enable_bundle", False)  # not yet multi-host safe
    ds = Dataset(X, label=y, weight=weight, group=group,
                 params=dict(params))
    ds.construct()
    dist.sync_dataset(ds)
    bst = Booster(params, ds)
    for _ in range(num_boost_round):
        if bst.update():
            break
    if rank == 0:
        text = bst.model_to_string()
        if out_model:
            Path(out_model).write_text(text)
        return text
    return None


_WORKER_MAIN = """
import os, pickle, sys
payload = pickle.load(open(sys.argv[1], "rb"))
# env (JAX_PLATFORMS=cpu, device count, compile cache) is prepared by
# the parent via hostenv.cpu_child_env — one copy of that recipe
sys.path[:0] = payload["sys_path"]
from lightgbm_tpu.cluster import worker_train
rank = int(sys.argv[2])
part = payload["parts"][rank]
text = worker_train(payload["params"], part["X"], part["y"],
                    coordinator=payload["coordinator"],
                    num_workers=len(payload["parts"]), rank=rank,
                    weight=part.get("weight"), group=part.get("group"),
                    num_boost_round=payload["num_boost_round"],
                    out_model=payload["out_model"] if rank == 0 else None)
print(f"worker {rank} finished", flush=True)
"""


def train_distributed(params: Dict[str, Any], parts: List[Dict[str, Any]],
                      num_boost_round: int = 100,
                      devices_per_worker: int = 1,
                      timeout: float = 1200.0):
    """Train one model over data partitions, one local worker process
    per partition (the LocalCluster shape of the reference's dask
    tests; on real multi-host TPU, launch workers yourself and call
    `worker_train`).

    parts: list of {"X": [n_i, F], "y": [n_i], optional "weight",
    optional "group" (per-partition query sizes, for ranking)} dicts.
    Returns a Booster loaded from the distributed model.
    """
    from . import Booster

    if not parts:
        raise ValueError("no partitions")
    sizes = [np.asarray(p["X"]).shape[0] for p in parts]
    if len(set(sizes)) > 1:
        # the multi-host assembly requires equal shards per process
        # (parallel/distributed.make_global_array; the reference's
        # distributed tests pre-partition equally too)
        raise ValueError(
            f"distributed training requires equal-size partitions, got "
            f"{sizes}; repartition the input (for ranking, choose a "
            "partition count that splits the queries into equal row "
            "blocks)")
    for n in sizes:
        if n % devices_per_worker != 0:
            raise ValueError(
                f"partition of {n} rows not divisible by "
                f"{devices_per_worker} devices per worker")

    port = _free_port()
    with tempfile.TemporaryDirectory() as td:
        out_model = os.path.join(td, "model.txt")
        payload = {
            "params": dict(params),
            "parts": [{k: np.asarray(v) for k, v in p.items()
                       if v is not None}
                      for p in parts],
            "coordinator": f"127.0.0.1:{port}",
            "num_boost_round": int(num_boost_round),
            "devices_per_worker": int(devices_per_worker),
            "out_model": out_model,
            "sys_path": [str(Path(__file__).resolve().parent.parent)],
        }
        blob = os.path.join(td, "payload.pkl")
        with open(blob, "wb") as fh:
            pickle.dump(payload, fh)
        main_py = os.path.join(td, "worker_main.py")
        Path(main_py).write_text(_WORKER_MAIN)

        # per-rank log files, not PIPEs: a worker that fills a ~64KB
        # pipe buffer blocks on write inside a collective and stalls
        # the whole gang until the timeout reaps it
        logs = [open(os.path.join(td, f"worker{rank}.log"), "w+")
                for rank in range(len(parts))]
        from .hostenv import cpu_child_env
        worker_env = cpu_child_env(int(devices_per_worker))
        procs = [subprocess.Popen(
            [sys.executable, main_py, blob, str(rank)],
            stdout=log, stderr=subprocess.STDOUT, text=True,
            env=worker_env)
            for rank, log in enumerate(logs)]
        try:
            deadline = time.monotonic() + timeout
            for proc in procs:
                proc.wait(timeout=max(1.0, deadline - time.monotonic()))
            failed = [r for r, proc in enumerate(procs)
                      if proc.returncode != 0]
            if failed:
                r = failed[0]
                logs[r].seek(0)
                out = logs[r].read()
                raise RuntimeError(
                    f"distributed worker {r} failed:\n{out[-4000:]}")
        finally:
            # a crashed/timed-out rank leaves siblings blocked inside a
            # collective: always reap the whole gang
            for proc in procs:
                if proc.poll() is None:
                    proc.kill()
                    proc.wait()
            for log in logs:
                log.close()
        return Booster(model_file=out_model)
