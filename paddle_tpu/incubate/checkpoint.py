"""Fault-tolerant checkpoint engine (ISSUE 4 tentpole, levels 1–2).

The GPT-6.7B north star trains for days on preemptible v5p pods: every
layer here exists so a SIGKILL at any instant loses at most one save
interval and never a checkpoint.

Checkpoint layout — one directory per step under the user's base dir::

    <dir>/ckpt-00000042/
        data-rank00000.pkl        payload: pickled numpy-snapshot nest
        data-rank00001.pkl        (per-rank shards in distributed runs)
        MANIFEST-rank00001.json   per-shard integrity record (ranks > 0)
        MANIFEST.json             rank 0's record + global commit marker

Write protocol (per rank): serialize the snapshot in memory → payload
via tmp+fsync+rename → manifest via tmp+fsync+rename, LAST.  The
manifest doubles as the commit marker: a crash at any point leaves
either a fully-valid checkpoint or a prefix that `load_latest` skips
(missing manifest, checksum mismatch, or truncated pickle all count as
"not committed").

MANIFEST.json schema (v1)::

    {"schema": 1, "step": 42, "epoch": 3, "time": 1722700000.0,
     "rank": 0, "world_size": 1,
     "files": {"data-rank00000.pkl": {"crc32": 912..., "bytes": 10240}},
     "rng": {"data": [1818844716, 7], "typed": true},
     "user": {...}}                        # caller-supplied metadata

Async saves: `save()` snapshots device buffers to host numpy on the
caller (train) thread — the only part that must see a consistent
step boundary — and hands serialization + disk I/O to a single writer
thread, so the train loop never blocks on storage.  Retention keeps the
newest `max_to_keep` committed checkpoints; pruning runs on the writer
thread after each commit and never touches the checkpoint just written.

Telemetry (PR-3 registry): `checkpoint.saves/async_saves/restores/
skipped_corrupt/pruned` counters, `checkpoint:save.snapshot/save.write/
restore` timings, and `checkpoint_save`/`checkpoint_restore`/
`checkpoint_skip` explainer events — every recovery is observable.
"""
from __future__ import annotations

import json
import os
import pickle
import queue
import re
import shutil
import signal
import threading
import time
import zlib

import numpy as np

from ..core.tensor import Tensor
from ..framework import (_from_saveable, _merge_saveable, _shard_saveable,
                         _to_saveable, atomic_write_bytes)
from ..profiler import explainer as _explain
from ..profiler import registry as _registry
from ..testing import faults as _faults

__all__ = ["CheckpointManager", "CheckpointHook", "load_latest",
           "load_resharded", "save_checkpoint", "latest_step",
           "capture_training_state", "restore_training_state",
           "WorldSizeMismatchError"]

SCHEMA = 1
_CKPT_RE = re.compile(r"^ckpt-(\d{8})$")

_counters = _registry.scoped_counters("checkpoint", {
    "saves": 0, "async_saves": 0, "restores": 0, "skipped_corrupt": 0,
    "pruned": 0, "emergency_saves": 0, "sharded_saves": 0,
    "reshard_loads": 0})


class WorldSizeMismatchError(RuntimeError):
    """A checkpoint written at world-size N was opened by a world-size-M
    job without requesting resharding. Loading a per-rank shard (or a
    wrong-world replica) raw would surface as a shape error deep inside
    ``set_value`` — this error carries both sizes and names the reshard
    entrypoint instead."""

    def __init__(self, saved_world_size, world_size, step=None, dir=None,
                 sharded=False):
        self.saved_world_size = int(saved_world_size)
        self.world_size = int(world_size)
        self.step = step
        self.dir = dir
        self.sharded = bool(sharded)
        where = f" (step {step})" if step is not None else ""
        what = ("a sharded checkpoint" if sharded else "a checkpoint")
        super().__init__(
            f"{what}{where} saved at world_size="
            f"{self.saved_world_size} cannot load raw into a job with "
            f"world_size={self.world_size}. Pass reshard=True "
            f"(CheckpointManager.load_latest / CheckpointHook) or call "
            f"paddle_tpu.incubate.checkpoint.load_resharded"
            f"({dir!r}, rank, world_size) to merge/re-slice the "
            f"per-rank payloads through the manifest.")


def _ckpt_dir(base, step):
    return os.path.join(base, f"ckpt-{int(step):08d}")


def _payload_name(rank):
    return f"data-rank{int(rank):05d}.pkl"


def _manifest_name(rank):
    return "MANIFEST.json" if rank == 0 else f"MANIFEST-rank{int(rank):05d}.json"


def list_steps(base):
    """Committed-or-partial checkpoint steps under `base`, ascending."""
    try:
        entries = os.listdir(base)
    except OSError:
        return []
    steps = []
    for e in entries:
        m = _CKPT_RE.match(e)
        if m:
            steps.append(int(m.group(1)))
    return sorted(steps)


# -- RNG state ----------------------------------------------------------------

def _rng_snapshot():
    """Global PRNG key → JSON-able blob (typed keys via key_data)."""
    import jax

    from ..core import random as prandom

    k = prandom.get_rng_state()
    try:
        data = jax.random.key_data(k)
        typed = True
    except (TypeError, ValueError):
        data, typed = k, False
    return {"data": np.asarray(data).astype(np.uint32).tolist(),
            "typed": typed}


def _rng_restore(blob):
    import jax
    import jax.numpy as jnp

    from ..core import random as prandom

    if not blob:
        return
    data = jnp.asarray(np.asarray(blob["data"], np.uint32))
    key = jax.random.wrap_key_data(data) if blob.get("typed") else data
    prandom.set_rng_state(key)


# -- manager ------------------------------------------------------------------

class CheckpointManager:
    """Atomic + async checkpoint writer with rolling retention.

    Usage::

        mgr = CheckpointManager(dir, max_to_keep=3)
        mgr.save(state, step=i)        # returns before the disk write
        ...
        mgr.wait()                     # barrier (end of training / tests)

    `state` is any `paddle_tpu.save`-able nest (Tensors are snapshotted
    to numpy on the calling thread). Distributed runs construct one
    manager per rank with `rank`/`world_size`; each rank writes its own
    shard + manifest and only rank 0 prunes.
    """

    def __init__(self, dir, max_to_keep=3, async_save=True, rank=0,
                 world_size=1, shard=False):
        self.dir = str(dir)
        self.max_to_keep = max(1, int(max_to_keep)) if max_to_keep else None
        self.rank = int(rank)
        self.world_size = int(world_size)
        # sharded saves: each rank persists only its 1/world_size flat
        # chunk of every tensor leaf (framework._shard_saveable), cutting
        # per-rank write volume for replicated state; restore goes through
        # load_resharded, which merges ALL shards — at any target world
        self.shard = bool(shard) and self.world_size > 1
        self._async = bool(async_save)
        self._q: queue.Queue = queue.Queue(maxsize=2)
        self._writer = None
        self._error = None
        os.makedirs(self.dir, exist_ok=True)

    # -- save ---------------------------------------------------------------
    def save(self, state, step, epoch=None, user_meta=None, block=False):
        """Snapshot `state` and commit it as checkpoint `step`.

        Returns once the snapshot (device→host copy) is taken; the
        serialization + write happen on the writer thread unless the
        manager is synchronous or `block=True`. A failed write surfaces
        on the NEXT save()/wait() call."""
        self._reraise()
        with _registry.time_block("save.snapshot", scope="checkpoint"):
            payload = _to_saveable(state)
            if self.shard:
                # numpy views onto the snapshot — the writer thread
                # pickles only this rank's chunks
                payload = _shard_saveable(payload, self.rank,
                                          self.world_size)
                _counters["sharded_saves"] += 1
            rng = _rng_snapshot()
        job = {"step": int(step), "epoch": epoch, "payload": payload,
               "rng": rng, "user": user_meta}
        if self._async and not block:
            if self._writer is None:
                self._writer = threading.Thread(
                    target=self._writer_loop, daemon=True,
                    name="ckpt-writer")
                self._writer.start()
            self._q.put(job)  # maxsize bounds in-flight host copies
            _counters["async_saves"] += 1
        else:
            self._write(job)
        return _ckpt_dir(self.dir, step)

    def wait(self):
        """Block until every queued save is durable; re-raise the first
        writer error if one occurred."""
        if self._writer is not None:
            self._q.join()
        self._reraise()

    def _reraise(self):
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    def _writer_loop(self):
        while True:
            job = self._q.get()
            try:
                self._write(job)
            except BaseException as e:  # surfaced on next save()/wait()
                self._error = e
            finally:
                self._q.task_done()

    def _write(self, job):
        t0 = time.perf_counter()
        step = job["step"]
        d = _ckpt_dir(self.dir, step)
        os.makedirs(d, exist_ok=True)
        blob = pickle.dumps(job["payload"], protocol=4)
        payload_path = os.path.join(d, _payload_name(self.rank))
        atomic_write_bytes(blob, payload_path)
        if _faults.ACTIVE:
            # deterministic torn-write simulation: fires AFTER the commit
            # so load_latest's skip-and-fall-back path is what's tested
            _faults.fire("truncate_checkpoint", path=payload_path)
        manifest = {
            "schema": SCHEMA, "step": step, "epoch": job["epoch"],
            "time": time.time(), "rank": self.rank,
            "world_size": self.world_size, "sharded": self.shard,
            "files": {_payload_name(self.rank):
                      {"crc32": zlib.crc32(blob), "bytes": len(blob)}},
            "rng": job["rng"], "user": job["user"],
        }
        atomic_write_bytes(
            json.dumps(manifest, indent=1).encode(),
            os.path.join(d, _manifest_name(self.rank)))
        dt = time.perf_counter() - t0
        _registry.timing("save.write", dt, scope="checkpoint")
        _counters["saves"] += 1
        _explain.record("checkpoint_save", op="save",
                        why=f"step {step} committed in {dt * 1e3:.1f} ms",
                        step=step, dir=d, bytes=len(blob))
        if self.rank == 0 and self.max_to_keep:
            self._prune()

    # -- load ---------------------------------------------------------------
    def load_latest(self, reshard=False):
        """Newest valid checkpoint as (state, manifest) — (None, None) on
        a fresh directory. The saved world size is checked against this
        manager's: a mismatch (N→M resume) or a sharded checkpoint raises
        :class:`WorldSizeMismatchError` unless ``reshard=True``, which
        merges every saved rank's payload into the full state
        (:func:`load_resharded`)."""
        return load_latest(self.dir, rank=self.rank,
                           world_size=self.world_size, reshard=reshard)

    def _prune(self):
        steps = list_steps(self.dir)
        committed = [s for s in steps if os.path.exists(
            os.path.join(_ckpt_dir(self.dir, s), "MANIFEST.json"))]
        if not committed:
            return
        keep = set(committed[-self.max_to_keep:])
        newest = committed[-1]
        for s in steps:
            # anything newer than the newest commit may be mid-commit
            # (another rank's writer); uncommitted leftovers OLDER than
            # it are dead writers and go with the retention sweep
            if s in keep or s >= newest:
                continue
            shutil.rmtree(_ckpt_dir(self.dir, s), ignore_errors=True)
            _counters["pruned"] += 1


# -- load ---------------------------------------------------------------------

def _read_manifest(d, rank):
    try:
        with open(os.path.join(d, _manifest_name(rank))) as f:
            m = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(m, dict) or m.get("schema") != SCHEMA:
        return None
    return m


def _load_one(base, step, rank, raw=False):
    """One checkpoint dir → (state, manifest) or (None, reason).

    ``raw=True`` returns the verified pickled nest WITHOUT materializing
    Tensors — the reshard path merges raw shard nests from every rank
    before a single `_from_saveable` pass, and integrity probes
    (`latest_step`) never need live Tensors at all."""
    d = _ckpt_dir(base, step)
    commit = _read_manifest(d, 0)
    if commit is None:
        return None, "no commit marker (MANIFEST.json missing/invalid)"
    manifest = commit if rank == 0 else _read_manifest(d, rank)
    if manifest is None:
        return None, f"rank {rank} shard manifest missing/invalid"
    name = _payload_name(rank)
    rec = (manifest.get("files") or {}).get(name)
    if rec is None:
        return None, f"manifest has no record for {name}"
    try:
        with open(os.path.join(d, name), "rb") as f:
            blob = f.read()
    except OSError as e:
        return None, f"payload unreadable ({e})"
    if len(blob) != rec.get("bytes") or zlib.crc32(blob) != rec.get("crc32"):
        return None, (f"payload checksum mismatch (got {len(blob)} bytes, "
                      f"manifest says {rec.get('bytes')})")
    try:
        state = pickle.loads(blob)
        if not raw:
            state = _from_saveable(state)
    except Exception as e:
        return None, f"payload unpicklable ({type(e).__name__}: {e})"
    return state, commit


def load_latest(base, rank=0, world_size=None, reshard=False):
    """Newest VALID checkpoint under `base` → (state, manifest), or
    (None, None) when none exists. Corrupt/partial checkpoints (torn
    payload, missing manifest, bad checksum) are skipped with a
    `checkpoint_skip` explainer event — never a crash.

    `world_size` (when given) is validated against the manifest: a
    mismatch — or any SHARDED checkpoint, whose per-rank payload is a
    slice rather than a full state — raises :class:`WorldSizeMismatchError`
    up front instead of a shape error deep in ``set_value``, unless
    ``reshard=True`` routes through :func:`load_resharded`."""
    if reshard:
        return load_resharded(base, rank=rank,
                              world_size=world_size or 1)
    t0 = time.perf_counter()
    for step in reversed(list_steps(base)):
        commit = _read_manifest(_ckpt_dir(base, step), 0)
        if commit is not None:
            saved_w = int(commit.get("world_size", 1))
            if commit.get("sharded") or (
                    world_size is not None and saved_w != int(world_size)):
                raise WorldSizeMismatchError(
                    saved_w, world_size if world_size is not None else 1,
                    step=step, dir=base,
                    sharded=bool(commit.get("sharded")))
        state, man = _load_one(base, step, rank)
        if state is not None:
            _registry.timing("restore", time.perf_counter() - t0,
                             scope="checkpoint")
            _counters["restores"] += 1
            _explain.record("checkpoint_restore", op="load_latest",
                            why=f"restored step {man['step']} from "
                                f"{_ckpt_dir(base, step)}",
                            step=man["step"], rank=rank)
            return state, man
        _counters["skipped_corrupt"] += 1
        _explain.record("checkpoint_skip", op="load_latest",
                        why=f"skipping ckpt-{step:08d}: {man}",
                        step=step, rank=rank)
    return None, None


def load_resharded(base, rank=0, world_size=1, step=None):
    """Load the newest valid checkpoint REGARDLESS of the world size it
    was saved at: verify + read every saved rank's payload through its
    checksummed manifest, merge the per-leaf flat chunks back into full
    tensors (bitwise — pure concatenation/reshape), and return
    ``(full_state, commit_manifest)``.

    This is the N→M entrypoint: M ranks each call it and get the same
    full state (N→1 and 1→M are the degenerate cases); a job that wants
    per-rank slices again simply re-saves with ``shard=True`` at its own
    world size — re-slicing happens on the next save, merging on load.
    Unsharded checkpoints (replicated full state per rank) merge
    trivially by taking rank 0's payload. A checkpoint with ANY
    unreadable shard is skipped whole — partial merges would silently
    lose parameters. RNG state rides the returned commit manifest, same
    as `load_latest`."""
    t0 = time.perf_counter()
    steps = [step] if step is not None else list(reversed(list_steps(base)))
    for s in steps:
        commit = _read_manifest(_ckpt_dir(base, s), 0)
        if commit is None:
            reason = "no commit marker (MANIFEST.json missing/invalid)"
        else:
            saved_w = int(commit.get("world_size", 1))
            shards, reason = [], None
            for r in range(saved_w):
                raw, why = _load_one(base, s, r, raw=True)
                if raw is None:
                    reason = f"shard {r}/{saved_w}: {why}"
                    break
                shards.append(raw)
            if reason is None:
                state = _from_saveable(_merge_saveable(shards))
                _registry.timing("restore", time.perf_counter() - t0,
                                 scope="checkpoint")
                _counters["reshard_loads"] += 1
                _counters["restores"] += 1
                _explain.record(
                    "checkpoint_reshard", op="load_resharded",
                    why=f"step {commit['step']}: merged {saved_w} shard(s)"
                        f" -> world_size {world_size} (rank {rank})",
                    step=commit["step"], saved_world_size=saved_w,
                    world_size=int(world_size), rank=rank)
                return state, commit
        _counters["skipped_corrupt"] += 1
        _explain.record("checkpoint_skip", op="load_resharded",
                        why=f"skipping ckpt-{s:08d}: {reason}",
                        step=s, rank=rank)
    return None, None


def latest_step(base, rank=0):
    """Step of the newest valid checkpoint, or None. Validity here is
    integrity (manifest + checksum + unpickle), not world-size fit —
    sharded and foreign-world checkpoints count (the serving checkpoint
    watcher polls this against live training output)."""
    for step in reversed(list_steps(base)):
        if _load_one(base, step, rank, raw=True)[0] is not None:
            return step
    return None


def save_checkpoint(base, state, step, epoch=None, user_meta=None,
                    max_to_keep=None, rank=0, world_size=1, shard=False):
    """One-shot synchronous checkpoint commit (atomic, checksummed)."""
    mgr = CheckpointManager(base, max_to_keep=max_to_keep, async_save=False,
                            rank=rank, world_size=world_size, shard=shard)
    return mgr.save(state, step, epoch=epoch, user_meta=user_meta)


# -- training-state capture/restore ------------------------------------------

def capture_training_state(network, optimizer=None):
    """Model params/buffers + optimizer slots as one saveable nest.

    The nest ALIASES the live Tensors (zero-copy): hand it straight to
    `CheckpointManager.save`, which snapshots to host numpy on the
    calling thread before the train loop mutates anything."""
    net = getattr(network, "network", network)  # hapi Model or raw Layer
    state = {"model": dict(net.state_dict())}
    if optimizer is not None:
        state["optimizer"] = optimizer.state_dict()
    return state


def restore_training_state(network, optimizer, state):
    """Restore params + optimizer slots IN PLACE.

    Identity preservation is the point: the lazy step-capture engine
    (core/lazy.py) keys its captured plans on leaf Tensor identity and
    avals — restoring by `set_value` into the live Tensors means a
    resume continues replaying the already-captured whole-step
    executable instead of re-tracing. Only when a restored aval differs
    (shape/dtype change — a different model) are the thread's capture
    plans dropped, explicitly and observably."""
    net = getattr(network, "network", network)
    sd = state.get("model", state)
    own = net.state_dict()
    changed = []
    for name, t in own.items():
        if name not in sd:
            continue
        v = sd[name]
        arr = v.numpy() if isinstance(v, Tensor) else np.asarray(v)
        if tuple(arr.shape) == tuple(t._data.shape):
            t.set_value(arr)  # dtype follows the live param (set_value casts)
        else:
            import jax.numpy as jnp

            t._data = jnp.asarray(arr)
            changed.append(name)
    if optimizer is not None and "optimizer" in state:
        optimizer._ensure_accumulators()
        optimizer.set_state_dict(state["optimizer"])
    if changed:
        from ..core import lazy

        lazy.drop_plans(
            f"checkpoint restore changed avals of {changed[:3]}"
            + ("…" if len(changed) > 3 else ""))
    return changed


# -- TrainStep-level hook -----------------------------------------------------

class CheckpointHook:
    """Step-loop driver tying the manager to preemption + injection.

    Wire it into any train loop (hand-rolled, TrainStep, or lazy)::

        hook = CheckpointHook(dir, net, opt, save_interval=50)
        start = hook.restore()                  # 0 on a fresh run
        for step in range(start, total):
            loss = train_step(batch(step))
            if hook.on_step_end(step) == "preempted":
                break                            # emergency ckpt written
        hook.wait()

    On SIGTERM (TPU preemption grace) the handler only sets a flag; the
    NEXT `on_step_end` writes a synchronous emergency checkpoint and
    reports "preempted", so the save always lands on a step boundary
    with consistent param/optimizer state.
    """

    def __init__(self, dir, network, optimizer=None, save_interval=100,
                 max_to_keep=3, async_save=True, rank=0, world_size=1,
                 shard=False, reshard=False, install_sigterm=True,
                 elastic=None):
        self.manager = CheckpointManager(dir, max_to_keep=max_to_keep,
                                         async_save=async_save, rank=rank,
                                         world_size=world_size, shard=shard)
        # reshard=True lets restore() resume from a checkpoint written at
        # a DIFFERENT world size (preemption resize): shards are merged
        # through the manifest, then re-sliced on this job's next save
        self.reshard = bool(reshard)
        # elastic: a fleet.elastic.ElasticTrainContext (or anything with
        # its shape). Wires the step loop into the elastic training loop
        # (ISSUE 13): the step watchdog re-arms at each boundary, a
        # SIGTERM is ANNOUNCED through the store so every rank saves its
        # emergency shard at the SAME step (consistent manifest set for
        # the resharder), and the generation fence runs before every
        # save — a stale-generation zombie can never write a checkpoint.
        self.elastic = elastic
        self._net = network
        self._opt = optimizer
        self.save_interval = max(1, int(save_interval))
        self._preempt = threading.Event()
        self._old_handler = None
        if install_sigterm:
            self.install_sigterm()

    def install_sigterm(self):
        """Install the preemption handler (main thread only — elsewhere
        the caller owns signal routing and uses request_preempt())."""
        if threading.current_thread() is not threading.main_thread():
            return False
        try:
            self._old_handler = signal.signal(
                signal.SIGTERM, lambda signum, frame: self._preempt.set())
        except ValueError:
            return False
        return True

    def uninstall_sigterm(self):
        if self._old_handler is not None:
            try:
                signal.signal(signal.SIGTERM, self._old_handler)
            except ValueError:
                pass
            self._old_handler = None

    def request_preempt(self):
        """Programmatic preemption (tests; non-main-thread callers)."""
        self._preempt.set()

    @property
    def preempt_requested(self):
        return self._preempt.is_set()

    def restore(self):
        """Resume from the newest valid checkpoint: restores params,
        optimizer slots, and RNG in place; returns the step to run next
        (0 on a fresh start). With ``reshard=True`` a checkpoint written
        at any world size resumes here (merged via the manifests);
        otherwise a world-size mismatch raises
        :class:`WorldSizeMismatchError`."""
        state, man = self.manager.load_latest(reshard=self.reshard)
        if state is None:
            return 0
        restore_training_state(self._net, self._opt, state)
        _rng_restore(man.get("rng"))
        return int(man["step"]) + 1

    def on_step_end(self, step, epoch=None, user_meta=None):
        """Call once per completed step. Returns "preempted" after an
        emergency save (caller should exit cleanly), "fenced" when this
        rank's elastic generation went stale (caller must exit WITHOUT
        saving — the world was resized past it), else "saved" or "ok"."""
        if _faults.ACTIVE:
            _faults.fire("kill_at_step", step=step)
            _faults.fire("rank_preempt", step=step)
            # step_hang sleeps with the watchdog still armed for THIS
            # step — it must fire before the boundary tick below
            _faults.fire("step_hang", step=step)
        el = self.elastic
        coordinator = getattr(el, "coordinator", None) if el else None
        if el is not None:
            el.step_boundary(step)
        if coordinator is not None:
            if self._preempt.is_set() and not coordinator.triggered:
                # a stale-generation rank must not publish preemption
                # notices: the NEW world would take a spurious
                # fleet-wide emergency checkpoint on a zombie's behalf
                if el is not None and not el.fence_check(
                        "preemption announce"):
                    return "fenced"
                # local SIGTERM: make the preemption FLEET-WIDE so every
                # rank's emergency shard lands on one consistent step
                coordinator.announce(step)
            elif coordinator.triggered:
                # another rank announced; adopt at this boundary
                self._preempt.set()
        if self._preempt.is_set():
            if coordinator is not None and not coordinator.should_save(step):
                return "ok"  # fleet target is a later boundary
            if el is not None and not el.fence_check("emergency save"):
                return "fenced"
            coordinated = None
            if coordinator is not None:
                # rendezvous under the fleet TARGET step (a rank that
                # adopted the notice a boundary late still acks the same
                # key); the manifest records the LOCAL step — it names
                # the state actually saved, and fabricating the target
                # step for a drifted rank would lie about the payload.
                # In lockstep training (per-step collectives, the dp
                # case) local == target and the manifest set is
                # consistent by construction; a drifted rank's manifest
                # carries preempt_target so the divergence is visible
                # to the resharder/operator instead of silent.
                coordinated = coordinator.barrier(
                    coordinator.save_step(step))
            state = capture_training_state(self._net, self._opt)
            meta = {"emergency": True, **(user_meta or {})}
            if coordinated is not None:
                meta["coordinated"] = coordinated
                meta["preempt_target"] = coordinator.save_step(step)
            self.manager.save(state, step, epoch=epoch, block=True,
                              user_meta=meta)
            _counters["emergency_saves"] += 1
            _explain.record(
                "checkpoint_save", op="emergency",
                why=f"SIGTERM: emergency checkpoint at step boundary {step}"
                    + (f" ({coordinated} ranks coordinated)"
                       if coordinated is not None else ""),
                step=step)
            return "preempted"
        if (step + 1) % self.save_interval == 0:
            if el is not None and not el.fence_check("periodic save"):
                return "fenced"
            state = capture_training_state(self._net, self._opt)
            self.manager.save(state, step, epoch=epoch, user_meta=user_meta)
            return "saved"
        return "ok"

    def wait(self):
        self.manager.wait()

    def close(self):
        self.wait()
        self.uninstall_sigterm()
