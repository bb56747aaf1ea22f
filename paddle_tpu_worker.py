"""DataLoader worker-process entry point.

TOP-LEVEL module on purpose: worker children (forkserver/spawn) import the
target's module at bootstrap, and importing anything under `paddle_tpu.`
would pull the whole framework + JAX (~seconds per worker, and on a TPU
host every worker would try to claim the chip its parent already holds).
This module imports only stdlib + numpy, and loads the shm-ring ctypes
binding straight from its file path so no package __init__ runs.

Reference analog: `python/paddle/fluid/dataloader/worker.py` _worker_loop.
"""
from __future__ import annotations

import importlib.util
import os
import pickle
import traceback

import numpy as np

_DONE_TAG = 2 ** 63 - 1
_ERR_TAG = 2 ** 63 - 2


def _shm_ring_cls():
    here = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(here, "paddle_tpu", "io", "shm_ring.py")
    spec = importlib.util.spec_from_file_location("_pt_shm_ring", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.ShmRing


def np_collate(batch):
    """Numpy-only default collate (mirror of
    paddle_tpu.io.dataloader.default_collate_fn minus Tensor wrapping)."""
    sample = batch[0]
    if hasattr(sample, "numpy") and not isinstance(sample, np.ndarray):
        return np.stack([np.asarray(s.numpy()) for s in batch])
    if isinstance(sample, np.ndarray):
        return np.stack(batch)
    if isinstance(sample, (int, np.integer)):
        return np.asarray(batch, np.int64)
    if isinstance(sample, (float, np.floating)):
        return np.asarray(batch, np.float32)
    if isinstance(sample, (list, tuple)):
        return [np_collate(list(s)) for s in zip(*batch)]
    if isinstance(sample, dict):
        return {k: np_collate([d[k] for d in batch]) for k in sample}
    return batch


def _strip(x):
    if hasattr(x, "numpy") and not isinstance(x, np.ndarray):
        return np.asarray(x.numpy())
    if isinstance(x, (list, tuple)):
        return type(x)(_strip(v) for v in x)
    if isinstance(x, dict):
        return {k: _strip(v) for k, v in x.items()}
    return x


class UserCollate:
    """Picklable wrapper running a user collate_fn, then stripping any
    framework tensors down to numpy."""

    def __init__(self, fn):
        self.fn = fn

    def __call__(self, samples):
        return _strip(self.fn(samples))


def worker_main(ring_name, job_blob, worker_id, nw):
    """`job_blob` is cloudpickle-serialized (dataset, collate, batches,
    worker_init_fn) — cloudpickle so datasets/collates defined in local
    scopes or __main__ survive the forkserver/spawn boundary.

    The DONE frame carries this worker's telemetry (batches produced,
    busy seconds — collate + pickle, ring-write backpressure excluded);
    the parent folds it into the profiler registry and tolerates an
    empty payload."""
    import time

    import cloudpickle

    ShmRing = _shm_ring_cls()
    dataset, collate, batches, worker_init_fn = cloudpickle.loads(job_blob)
    wring = ShmRing(ring_name, create=False)
    try:
        if worker_init_fn is not None:
            worker_init_fn(worker_id)
        busy = 0.0
        produced = 0
        for bi in range(worker_id, len(batches), nw):
            t0 = time.perf_counter()
            payload = pickle.dumps(
                collate([dataset[i] for i in batches[bi]]), protocol=4)
            busy += time.perf_counter() - t0
            wring.write(payload, tag=bi)
            produced += 1
        wring.write(pickle.dumps({"n_batches": produced, "busy_s": busy}),
                    tag=_DONE_TAG)
    except BaseException as e:  # surface the real error to the parent
        wring.write(pickle.dumps(
            (type(e).__name__, str(e), traceback.format_exc())),
            tag=_ERR_TAG)
    finally:
        wring.close()
