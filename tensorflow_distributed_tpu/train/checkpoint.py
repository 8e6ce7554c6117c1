"""Step-tagged checkpoint save/restore.

Replaces the reference's ``tf.train.Supervisor`` checkpointing
(mnist_python_m.py:236-253, SURVEY.md N7) minus its defining bug: the
reference checkpointed to a fresh ``tempfile.mkdtemp()`` (:236), making
cross-run resume impossible by construction (SURVEY.md Appendix B.3).
Here checkpoints go to a durable directory, tagged by step, with
explicit resume.

Design:
- One directory per checkpoint: ``<dir>/step_00001234/`` containing the
  full train-state pytree (params + optimizer state + step) as msgpack
  plus a small JSON manifest. Writes are atomic (tmp dir + rename), so
  a crash mid-save never corrupts the latest checkpoint — the recovery
  story the Supervisor's background saver provided (:245,:252).
- NATIVE backend (default): only the chief process writes
  (parallel.mesh.is_chief); every process restores. Leaves fully
  addressable on this host come back via ``jax.device_get``; leaves
  sharded ACROSS processes (FSDP over a multi-host data axis,
  cross-process TP) are first allgathered to a replicated layout — a
  collective, so ``save`` must be (and is) called by every process,
  with only the chief writing the bytes. Fine for the model sizes this
  framework targets.
- ORBAX backend (``backend="orbax"`` / ``--checkpoint-backend orbax``,
  the scale path): sharded OCDBT saves — every process writes and
  reads ITS OWN shards, no allgather; completeness is published via a
  chief-written commit marker (see ``_orbax_save``), and ``restore``
  auto-detects which backend wrote a checkpoint.
- Restore places leaves back on the mesh with the *current* state's
  shardings, so a checkpoint saved on one mesh shape restores onto
  another (e.g. train on 8 chips, fine-tune on 1).
"""

from __future__ import annotations

import concurrent.futures
import hashlib
import json
import os
import shutil
import threading
import time
from typing import Any, List, Optional

import jax
import numpy as np
from flax import serialization

from tensorflow_distributed_tpu.observe import goodput as _goodput
from tensorflow_distributed_tpu.observe.registry import emit_event
from tensorflow_distributed_tpu.utils.atomicio import atomic_write_json
from tensorflow_distributed_tpu.parallel.mesh import (
    is_chief, mesh_shape_dict)

_STEP_PREFIX = "step_"
_QUARANTINE_PREFIX = "quarantined_"
_MESH_MANIFEST = "mesh.json"


class CheckpointCorruptError(RuntimeError):
    """A checkpoint failed integrity verification (checksum mismatch,
    truncated/undecodable state file). restore() quarantines the
    offender and falls back to the newest verifiable step; this only
    escapes when an EXPLICIT step was requested or no verifiable
    checkpoint remains."""


class MeshMismatchError(RuntimeError):
    """A restore failed because the checkpoint was written on a
    different mesh than the template requests — surfaced with both
    topologies named instead of the opaque orbax/XLA placement error
    underneath. Cross-mesh restore is :func:`restore_resharded`'s job:
    it re-lays the checkpoint out onto the target mesh and verifies
    the resulting layout against the sharding contract."""


def _format_mesh(shape: Optional[dict]) -> str:
    """``data=4,model=2``-style rendering of a mesh-shape dict for
    operator-facing messages (axes of size 1 elided)."""
    if not shape:
        return "unknown mesh"
    parts = [f"{k}={v}" for k, v in shape.items() if int(v) != 1]
    return ",".join(parts) if parts else "single-device"


def _tree_mesh(tree: Any) -> Optional[dict]:
    """The mesh shape a live pytree sits on (first sharded leaf's
    mesh), or None for host trees."""
    for leaf in jax.tree_util.tree_leaves(tree):
        sharding = getattr(leaf, "sharding", None)
        mesh = getattr(sharding, "mesh", None)
        if mesh is not None and getattr(mesh, "shape", None) is not None:
            return mesh_shape_dict(mesh)
    return None


def _mesh_manifest(state: Any) -> Optional[dict]:
    """The mesh/sharding manifest written beside the sha256 manifest:
    mesh axis sizes, process count, device count, and the per-leaf
    PartitionSpecs — everything :func:`restore_resharded` (and an
    operator wondering which steps fit the current topology) needs to
    know about the layout a checkpoint was WRITTEN with. None for a
    state with no sharded leaves (host-only tests)."""
    tree = serialization.to_state_dict(state)
    shape = _tree_mesh(tree)
    if shape is None:
        return None
    from tensorflow_distributed_tpu.analysis.runtime import (
        sharding_spec_strings)
    return {
        "mesh": shape,
        "process_count": int(jax.process_count()),
        "devices": int(np.prod(list(shape.values()))),
        "specs": sharding_spec_strings(tree),
    }


def read_mesh_manifest(ckpt_dir: str, step: int) -> Optional[dict]:
    """The mesh manifest a step was written with, or None (pre-elastic
    checkpoints, unreadable file — absence degrades to 'unknown', it
    never blocks a restore)."""
    path = os.path.join(_step_dir(ckpt_dir, step), _MESH_MANIFEST)
    try:
        with open(path) as f:
            out = json.load(f)
        return out if isinstance(out, dict) else None
    except (OSError, ValueError):
        return None


def steps_with_mesh(ckpt_dir: str) -> List[tuple]:
    """``[(step, written-mesh dict or None), ...]`` for every complete
    checkpoint — the operator view of which steps are restorable onto
    which topology (``available_steps`` keeps its plain-int contract
    for the callers that schedule around it)."""
    return [(s, (read_mesh_manifest(ckpt_dir, s) or {}).get("mesh"))
            for s in available_steps(ckpt_dir)]


def _describe_available(ckpt_dir: str, steps: List[int]) -> str:
    """Error-message rendering of the available steps WITH the
    topology each was written on, so the operator can see which are
    restorable onto the current mesh: ``[12, 16] (written on mesh
    data=4)`` when uniform, per-step annotations when mixed."""
    if not steps:
        return "none"
    meta = steps_with_mesh(ckpt_dir)
    meshes = {_format_mesh(m) for _, m in meta if m}
    if not meshes:
        return str(steps)  # pre-elastic checkpoints: no manifests
    if len(meshes) == 1:
        return f"{steps} (written on mesh {meshes.pop()})"
    return "[" + ", ".join(
        f"{s} (mesh {_format_mesh(m)})" if m else str(s)
        for s, m in meta) + "]"


# --- save-I/O retry policy (capped exponential backoff) -----------------
# Module-level so save() call sites don't thread it through; the train
# loop configures it from cfg.resilience at run start.

_io_retries = 2
_io_backoff_s = 0.05
_io_backoff_max_s = 2.0
# Injected write failures (resilience.faults arms these for drills):
# the next N write attempts raise OSError INSIDE the retry loop, so a
# plan with N <= retries proves save-retry recovery end to end.
_injected_io_failures = 0


def set_io_policy(retries: int = 2, backoff_s: float = 0.05,
                  backoff_max_s: float = 2.0) -> None:
    global _io_retries, _io_backoff_s, _io_backoff_max_s
    _io_retries, _io_backoff_s = retries, backoff_s
    _io_backoff_max_s = backoff_max_s


def arm_io_fault(count: int = 1) -> None:
    global _injected_io_failures
    _injected_io_failures = count


def _retry_io(fn, step: int):
    """Run a save-I/O callable with capped-exponential-backoff retries;
    each retry is a recovery event and a goodput count."""
    delay = _io_backoff_s
    for attempt in range(_io_retries + 1):
        try:
            return fn()
        except OSError as e:
            if attempt == _io_retries:
                raise
            emit_event("recovery", kind="ckpt_retry", step=step,
                       attempt=attempt + 1, budget=_io_retries,
                       error=str(e), backoff_s=round(delay, 4))
            _goodput.incr("ckpt_retry")
            time.sleep(delay)
            delay = min(delay * 2, _io_backoff_max_s)


def _identity(a):
    # Module-level so jax.jit's cache keys on ONE function object and
    # hits per (shape, sharding) — a per-call lambda would recompile
    # the allgather for every leaf at every checkpoint.
    return a


def _fetch_host(state: Any, values: bool = True) -> Any:
    """Device->host copy of a state pytree, cross-process-sharding safe.

    A leaf partitioned over an axis that spans processes (FSDP params
    under a multi-host data axis, cross-process TP) is neither fully
    addressable nor fully replicated, so plain ``jax.device_get``
    raises. Such leaves are allgathered to a replicated layout first —
    a COLLECTIVE: every process must reach this call (save/restore are
    structured so they all do). Fully-replicated leaves (the default
    layout) skip the collective and copy from local shards.

    ``values=False``: participate in the collectives (mandatory on
    every process) but skip the host copies — what non-chief processes
    do in ``save``. Returns None.
    """
    if jax.process_count() == 1:
        return jax.device_get(state) if values else None
    from jax.sharding import NamedSharding, PartitionSpec

    def one(x):
        if (isinstance(x, jax.Array) and not x.is_fully_addressable
                and not x.is_fully_replicated):
            x = jax.jit(_identity,
                        out_shardings=NamedSharding(
                            x.sharding.mesh, PartitionSpec()))(x)
        return jax.device_get(x) if values else None

    out = jax.tree_util.tree_map(one, state)
    return out if values else None


def host_step(state: Any) -> int:
    """The state's step counter as a host int, replica-stack safe.

    Local-SGD states carry a replica-stacked step [R] (identical
    values by construction): index BEFORE device_get — an [R] array
    sharded over a cross-process data axis is neither addressable
    nor replicated (the _fetch_host restriction), but the [0]
    indexing op produces a replicated scalar every process can
    read."""
    leaf = state.step
    if getattr(leaf, "ndim", 0):
        leaf = leaf[0]
    return int(jax.device_get(leaf))


def _step_dir(ckpt_dir: str, step: int) -> str:
    return os.path.join(ckpt_dir, f"{_STEP_PREFIX}{step:08d}")


def available_steps(ckpt_dir: str) -> List[int]:
    """COMPLETE checkpoints only: native dirs are atomic (presence
    implies a full state.msgpack), orbax dirs count once the chief's
    commit marker lands — an in-flight or crashed orbax save is
    invisible here, so latest_step never shadows an intact older
    checkpoint.

    Everything else in the directory is ignored by construction:
    ``step_XXXXXXXX.tmp`` staging dirs (crashed mid-write), dirs
    missing both the msgpack and the commit marker, stray non-dir
    files that happen to parse as a step, quarantined_* dirs the
    integrity fallback renamed aside, and any other non-step entry —
    a crashed or corrupt save can never make ``latest_step`` point at
    garbage."""
    if not os.path.isdir(ckpt_dir):
        return []
    out = []
    for name in os.listdir(ckpt_dir):
        if not name.startswith(_STEP_PREFIX):
            continue
        try:
            step = int(name[len(_STEP_PREFIX):])
        except ValueError:
            continue  # step_X.tmp staging dirs, misnamed entries
        d = os.path.join(ckpt_dir, name)
        if not os.path.isdir(d):
            continue  # a stray FILE named like a step dir
        if (os.path.exists(os.path.join(d, "state.msgpack"))
                or os.path.exists(os.path.join(d, _ORBAX_MARKER))):
            out.append(step)
    return sorted(out)


def latest_step(ckpt_dir: str) -> Optional[int]:
    steps = available_steps(ckpt_dir)
    return steps[-1] if steps else None


def _save_barrier(step: int) -> None:
    """All processes leave ``save`` only after the chief's rename.

    Without this, a same-cluster resume (train -> train(resume=True))
    races the write: non-chief processes could read ``latest_step``
    before the chief finished renaming the new step dir and restore a
    different (older) checkpoint than the chief. Single-process: no-op.
    """
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(f"tfd_ckpt_save_{step}")


_ORBAX_DIRNAME = "orbax"
_ORBAX_MARKER = "ORBAX_COMMITTED"
_orbax_ckptr = None
_orbax_pending: List[tuple] = []  # (ckpt_dir, step, keep) awaiting commit


def _orbax():
    """Lazy singleton StandardCheckpointer (its save is internally
    async; ``orbax_wait`` flushes AND publishes)."""
    global _orbax_ckptr
    if _orbax_ckptr is None:
        import orbax.checkpoint as ocp

        _orbax_ckptr = ocp.StandardCheckpointer()
    return _orbax_ckptr


def _orbax_save(ckpt_dir: str, step: int, state: Any, keep: int,
                background: bool) -> str:
    """Sharded save via orbax (the scale path): every process writes
    ITS OWN shards — no allgather-to-host, no chief gating (orbax
    coordinates the processes itself). Layout:
    ``<dir>/step_xxxxxxxx/orbax/`` plus a chief-written COMMIT MARKER
    file, published only after orbax confirms the write — the step dir
    itself appears early, so ``available_steps`` treats an unmarked
    orbax dir as in-flight/crashed and skips it: a crash mid-save can
    never shadow the intact previous checkpoint, and pruning (also
    deferred to the marker phase) can never delete the last good one.
    restore() auto-detects the layout, so --resume works regardless of
    which backend wrote the checkpoint."""
    # Capture the live state's mesh manifest BEFORE the async write:
    # it publishes with the commit marker in orbax_wait, where the
    # state itself is long gone.
    mesh_manifest = _mesh_manifest(state)
    final = _step_dir(ckpt_dir, step)
    os.makedirs(ckpt_dir, exist_ok=True)
    if background and _orbax_pending:
        # Publish the PREVIOUS background save before scheduling the
        # next (at most one unpublished save in flight — the native
        # writer's bound): without this, markers would only land at
        # the end-of-run wait() and a hard crash mid-training would
        # lose every cadence checkpoint.
        orbax_wait()
    tree = serialization.to_state_dict(state)
    _orbax().save(os.path.join(final, _ORBAX_DIRNAME), tree, force=True)
    _orbax_pending.append((ckpt_dir, step, keep, mesh_manifest))
    if not background:
        orbax_wait()
        _save_barrier(step)
    return final


def orbax_wait() -> None:
    """Flush orbax's internal async write (blocks until every
    process's shards are committed), then publish: the chief writes
    the commit markers and prunes old steps — strictly AFTER the
    commit, so a failed write leaves previous checkpoints untouched
    and unmarked debris behind."""
    global _orbax_pending
    # Pop BEFORE the flush: if the shard write failed, the popped
    # entries are dropped un-marked (correct — they stay invisible
    # debris) instead of being re-published as committed by a later
    # call after the error was already consumed.
    pend, _orbax_pending[:] = _orbax_pending[:], []
    if _orbax_ckptr is not None:
        _orbax_ckptr.wait_until_finished()
    if not is_chief():
        return
    for ckpt_dir, step, keep, mesh_manifest in pend:
        step_path = _step_dir(ckpt_dir, step)
        if mesh_manifest is not None:
            # The mesh manifest lands WITH the commit marker (both
            # chief-written, post-confirmation), so an unmarked crashed
            # save never carries a manifest either.
            atomic_write_json(os.path.join(step_path, _MESH_MANIFEST),
                              mesh_manifest)
        marker = os.path.join(step_path, _ORBAX_MARKER)
        with open(marker, "w"):
            pass
        for old in available_steps(ckpt_dir)[:-keep]:
            shutil.rmtree(_step_dir(ckpt_dir, old), ignore_errors=True)


def _orbax_restore(path: str, state: Any) -> Any:
    """Sharded restore: each process reads its own shards directly into
    the template's shardings — the inverse of the no-allgather save.

    Mirrors _restore_from_raw's compatibility contract: an EMA toggle
    across the save (reconciled via the checkpoint's metadata — newly
    enabled EMA seeds from the restored params, newly disabled drops
    the saved average), and a CLEAR error for replica-stacked vs plain
    shape mismatches (a --param-sync-every flip)."""
    item = os.path.join(path, _ORBAX_DIRNAME)
    tmpl = serialization.to_state_dict(state)
    saved = _orbax().metadata(item).item_metadata.tree

    t_flat = dict(jax.tree_util.tree_flatten_with_path(
        tmpl.get("params", {}))[0])
    s_flat = dict(jax.tree_util.tree_flatten_with_path(
        saved.get("params", {}))[0])
    for pth, leaf in t_flat.items():
        m = s_flat.get(pth)
        if m is not None and tuple(m.shape) != tuple(np.shape(leaf)):
            raise ValueError(
                f"checkpoint leaf shape {tuple(m.shape)} != template "
                f"{tuple(np.shape(leaf))} at {jax.tree_util.keystr(pth)};"
                " was this run saved with a different --param-sync-every"
                " (replica-stacked vs plain state)?")

    want_ema = tmpl.get("ema") is not None
    saved_ema = bool(saved.get("ema"))
    abstract = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=a.sharding)
        if isinstance(a, jax.Array) else a, tmpl)
    if want_ema and not saved_ema:
        abstract["ema"] = None          # restore what was saved ...
    if saved_ema and not want_ema:
        # StandardCheckpointer cannot restore a strict subtree (probed:
        # both a missing key and ema=None raise structure-mismatch), so
        # the dropped average is read once and discarded — one extra
        # params-sized read on this rare toggle path.
        abstract["ema"] = abstract["params"]  # ema mirrors params
    restored = _orbax().restore(item, abstract)
    if want_ema and not saved_ema:
        restored["ema"] = restored["params"]  # ... then seed the average
    if saved_ema and not want_ema:
        restored["ema"] = None
    return serialization.from_state_dict(state, restored)


# Single background writer: serializes at most one checkpoint at a
# time (overlapping saves queue), so tmp dirs and pruning never race.
_writer_lock = threading.Lock()
_writer: Optional[concurrent.futures.ThreadPoolExecutor] = None
_pending: List[concurrent.futures.Future] = []


def _write(ckpt_dir: str, step: int, host_state: Any, keep: int,
           mesh_manifest: Optional[dict] = None) -> str:
    """Serialize + atomically publish one checkpoint (chief only).

    The state blob's sha256 is recorded in the manifest next to the
    step metadata and verified on restore — bit rot or a truncated
    write surfaces as :class:`CheckpointCorruptError` (quarantine +
    fallback) instead of silently restoring garbage. The whole I/O
    sequence retries under the capped-backoff policy (serialization
    happens once, outside the retries)."""
    final = _step_dir(ckpt_dir, step)
    blob = serialization.to_bytes(host_state)
    manifest = {
        "step": step,
        "param_bytes": int(sum(
            np.asarray(x).nbytes
            for x in jax.tree_util.tree_leaves(host_state.params))),
        "format": "flax-msgpack-v1",
        "sha256": hashlib.sha256(blob).hexdigest(),
    }

    def attempt() -> None:
        global _injected_io_failures
        if _injected_io_failures > 0:
            _injected_io_failures -= 1
            raise OSError(
                f"injected checkpoint I/O failure at step {step} "
                f"(resilience fault drill)")
        os.makedirs(ckpt_dir, exist_ok=True)
        tmp = final + ".tmp"
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        with open(os.path.join(tmp, "state.msgpack"), "wb") as f:
            f.write(blob)
        atomic_write_json(os.path.join(tmp, "manifest.json"), manifest)
        if mesh_manifest is not None:
            # Mesh/sharding manifest beside the sha256 manifest: the
            # topology and per-leaf layout the state was WRITTEN with,
            # so restore_resharded (and the operator) can reason about
            # mesh compatibility without decoding the blob.
            atomic_write_json(os.path.join(tmp, _MESH_MANIFEST),
                              mesh_manifest)
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)

    _retry_io(attempt, step)
    for old in available_steps(ckpt_dir)[:-keep]:
        shutil.rmtree(_step_dir(ckpt_dir, old), ignore_errors=True)
    return final


@_goodput.accounted("checkpoint")
def save(ckpt_dir: str, state: Any, keep: int = 3,
         background: bool = False, backend: str = "native") -> str:
    """Write state at its current step; prune to the newest ``keep``.

    Goodput: the MAIN-THREAD time spent here (device->host snapshot,
    sync writes, background-queue backpressure) is charged to the
    "checkpoint" category on the active observe.goodput counter; the
    background writer thread's IO overlaps training and is deliberately
    not charged.

    Collective under multi-host (every process must call it; only the
    chief writes bytes): cross-process-partitioned leaves are fetched
    via an allgather, and all processes barrier on the completed write
    before returning, so ``latest_step`` is coherent cluster-wide the
    moment ``save`` returns anywhere.

    ``background=True``: the device->host snapshot still happens here
    (it must — the state is donated/overwritten by the next step, and
    its collectives must stay on the main thread), but serialization
    and the atomic write move to a single writer thread — the
    reference Supervisor's background saver (mnist_python_m.py:245),
    TPU-shaped. No per-save barrier is taken; call ``wait()`` (the
    train loop does, at exit) before relying on ``latest_step``
    cluster-wide. A crash mid-write loses at most that checkpoint —
    the previous one is intact because publication is tmp+rename."""
    step = host_step(state)
    if backend == "orbax":
        return _orbax_save(ckpt_dir, step, state, keep, background)
    if backend != "native":
        raise ValueError(f"unknown checkpoint backend {backend!r}")
    final = _step_dir(ckpt_dir, step)
    # Mesh manifest from the LIVE state (host copies carry no
    # shardings); chief-only like every other native write.
    mesh_manifest = _mesh_manifest(state) if is_chief() else None
    # Collective fetch BEFORE the chief gate: cross-process-partitioned
    # leaves need every process in the allgather. Non-chief processes
    # run the collectives only; the chief also copies values to host.
    host_state = _fetch_host(state, values=is_chief())
    if not is_chief():
        if not background:
            _save_barrier(step)
        return final
    if background:
        global _writer
        with _writer_lock:
            if _writer is None:
                _writer = concurrent.futures.ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="tfd-ckpt")
            prior = [f for f in _pending if not f.done()]
        # Bound the queue to ONE write in flight (outside the lock —
        # wait() needs it): every queued entry pins a full host copy
        # of the state, so an unbounded queue would grow by one model
        # copy per cadence save whenever the disk is slower than the
        # cadence. Blocking here degrades async saving to sync pacing
        # instead of OOMing the chief. Errors stay in the futures for
        # wait() to re-raise.
        if prior:
            concurrent.futures.wait(prior)
        with _writer_lock:
            # Prune futures that completed CLEANLY (failed ones must
            # stay for wait() to re-raise) so _pending doesn't grow by
            # one entry per cadence save over a long run.
            _pending[:] = [f for f in _pending
                           if not f.done() or f.exception() is not None]
            _pending.append(
                _writer.submit(_write, ckpt_dir, step, host_state, keep,
                               mesh_manifest))
        return final
    _write(ckpt_dir, step, host_state, keep, mesh_manifest)
    _save_barrier(step)
    return final


@_goodput.accounted("checkpoint")
def wait() -> None:
    """Block until outstanding background saves land (both the
    native writer thread and orbax's internal async write);
    re-raise the first writer error; barrier so ``latest_step`` is
    coherent cluster-wide afterwards. No-op when nothing is
    pending."""
    with _writer_lock:
        pending, _pending[:] = _pending[:], []
    try:
        first_err = None
        # Orbax flush INSIDE the try: a failed shard write on one
        # process must still fall through to the finally barrier, or
        # the other processes hang waiting for it.
        try:
            orbax_wait()
        except BaseException as e:  # noqa: BLE001 — re-raised below
            first_err = e
        for fut in pending:
            try:
                fut.result()
            except BaseException as e:  # noqa: BLE001 — re-raised below
                first_err = first_err or e
        if first_err is not None:
            raise first_err  # writer exceptions surface in the caller
    finally:
        # Barrier in a finally, and unconditionally under multi-host:
        # non-chief processes never have pending futures, and a chief
        # that raised must still show up — otherwise the other
        # processes hang in the barrier until the runtime timeout
        # instead of seeing a clean failure. Every process must call
        # wait() (the train loop does).
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("tfd_ckpt_flush")


@_goodput.accounted("restore")
def restore_averaged(ckpt_dir: str, state: Any,
                     step: Optional[int] = None) -> Any:
    """Restore a REPLICA-STACKED (local SGD) checkpoint into a PLAIN
    template by averaging the replica dim on host — the mode=eval
    path for local-SGD runs, independent of the evaluating mesh's
    data-axis size (train on 8 replicas, validate on 1). Float
    leaves average; integer leaves (step, opt counters) take
    replica 0 (identical by construction). Both backends' layouts are
    read (native msgpack and orbax OCDBT, auto-detected like
    restore()) — local SGD and sharded checkpointing compose.

    Same integrity contract as restore(): ``step=None`` means the
    newest VERIFIABLE step (a corrupt latest is quarantined with
    fallback to the next-newest); an explicit ``step`` is exact."""
    steps = available_steps(ckpt_dir)

    def read_raw(s: int):
        return _read_raw(_step_dir(ckpt_dir, s))

    if step is not None:
        if step not in steps:
            raise FileNotFoundError(
                f"no checkpoint for step {step} under {ckpt_dir}; "
                f"available steps: {_describe_available(ckpt_dir, steps)}")
        path, raw = read_raw(step)
    else:
        if not steps:
            raise FileNotFoundError(
                f"no checkpoints under {ckpt_dir} — is this a "
                f"--resume/mode=eval on an empty or absent checkpoint "
                f"dir, or the wrong --checkpoint-dir?")
        last_err: Optional[CheckpointCorruptError] = None
        for s in reversed(steps):
            try:
                path, raw = read_raw(s)
                step = s
                break
            except CheckpointCorruptError as e:
                _quarantine(ckpt_dir, s, str(e))
                last_err = e
        else:
            raise CheckpointCorruptError(
                f"every checkpoint under {ckpt_dir} failed "
                f"verification (all quarantined); last error: "
                f"{last_err}")
    if not (isinstance(raw, dict) and isinstance(raw.get("step"),
                                                 np.ndarray)
            and raw["step"].ndim == 1):
        raise ValueError(
            f"checkpoint at {path} is not replica-stacked (was it "
            "saved with --param-sync-every > 1?)")

    def mean0(x):
        if isinstance(x, np.ndarray) and x.ndim:
            if np.issubdtype(x.dtype, np.floating):
                return x.mean(axis=0)
            return x[0]
        return x

    for key in ("params", "opt_state", "step"):
        if key in raw:
            raw[key] = jax.tree_util.tree_map(mean0, raw[key])
    return _restore_from_raw(raw, state)


def _read_raw(step_path: str):
    """Read one checkpoint's state dict to HOST numpy, either backend
    (orbax OCDBT via the commit marker, else native msgpack with
    checksum verification). Returns (path, raw). Shared by
    restore_averaged and restore_params — the paths that need the raw
    tree rather than a templated restore."""
    opath = os.path.join(step_path, _ORBAX_DIRNAME)
    if os.path.exists(os.path.join(step_path, _ORBAX_MARKER)):
        # Orbax OCDBT layout, detected via the COMMIT MARKER exactly
        # like restore() — a crashed orbax re-save into a dir holding
        # an intact native state.msgpack must fall through to the
        # msgpack, not dispatch onto unmarked shard debris.
        # Template-free restore reads the SAVED tree as host numpy:
        # the shapes come from the checkpoint, which is the point.
        # Warning-free topology safety doesn't apply: host arrays
        # carry no sharding to mismatch.
        import warnings

        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            return opath, jax.tree_util.tree_map(
                np.asarray, _orbax().restore(opath))
    # Same read+verify path as restore(): a checksum-mismatched or
    # truncated blob raises CheckpointCorruptError.
    return os.path.join(step_path, "state.msgpack"), _load_native_raw(
        step_path)


def _host_finite(tree: Any) -> bool:
    """True when every float leaf of a HOST tree is fully finite."""
    for leaf in jax.tree_util.tree_leaves(tree):
        arr = np.asarray(leaf)
        if (np.issubdtype(arr.dtype, np.floating)
                and not np.isfinite(arr).all()):
            return False
    return True


@_goodput.accounted("restore")
def restore_params(ckpt_dir: str, params: Any,
                   step: Optional[int] = None,
                   prefer_ema: bool = True):
    """PARAMS-ONLY restore for live weight swap: read the newest
    verifiable checkpoint's params (EMA preferred, matching the serve/
    eval restore convention) into the structure and shardings of the
    LIVE ``params`` tree, without touching optimizer state or needing a
    full TrainState template. Returns ``(new_params, step)``. A leaf of
    ``params`` is an array or its ``jax.ShapeDtypeStruct`` with a
    sharding: serve_run keeps the training layout that way, without the
    buffers its engine no longer holds.

    The serving engine swaps these in BETWEEN decode steps: same
    shapes/dtypes/shardings as the running params (the engine asserts
    the sharding contract), so the hot decode program is a jit cache
    hit — no drain, no recompile, in-flight KV caches untouched.

    Integrity contract mirrors restore(): ``step=None`` walks back from
    the newest step past anything that fails the sha256/decode check
    (quarantined) or carries NON-FINITE params (skipped with a recovery
    event, NOT quarantined — the bytes are intact and a training-side
    rewind may still want to forensically inspect them); an explicit
    ``step`` is exact and raises instead of recovering around damage.
    Replica-stacked (local SGD) checkpoints are averaged over the
    replica dim, like restore_averaged."""
    steps = available_steps(ckpt_dir)
    candidates = ([step] if step is not None else list(reversed(steps)))
    if step is not None and step not in steps:
        raise FileNotFoundError(
            f"no checkpoint for step {step} under {ckpt_dir}; "
            f"available steps: {_describe_available(ckpt_dir, steps)}")
    if not steps:
        raise FileNotFoundError(
            f"no checkpoints under {ckpt_dir} — live weight swap needs "
            f"at least one completed save")
    last_err: Optional[Exception] = None
    got = None
    for s in candidates:
        try:
            path, raw = _read_raw(_step_dir(ckpt_dir, s))
        except CheckpointCorruptError as e:
            if step is not None:
                raise
            _quarantine(ckpt_dir, s, str(e))
            last_err = e
            continue
        tree = raw.get("ema") if (prefer_ema and isinstance(raw, dict)
                                  and raw.get("ema") is not None) \
            else raw.get("params") if isinstance(raw, dict) else None
        if tree is None:
            raise ValueError(
                f"checkpoint at {path} carries no params tree")
        if (isinstance(raw.get("step"), np.ndarray)
                and raw["step"].ndim == 1):
            # Replica-stacked local-SGD save: average the replica dim
            # (float leaves mean, ints take replica 0), matching
            # restore_averaged's convention.
            tree = jax.tree_util.tree_map(
                lambda x: (x.mean(axis=0)
                           if np.issubdtype(x.dtype, np.floating)
                           else x[0])
                if isinstance(x, np.ndarray) and x.ndim else x, tree)
        if not _host_finite(tree):
            msg = (f"params at step {s} are non-finite — not a swap "
                   f"target")
            if step is not None:
                raise ValueError(msg)
            emit_event("recovery", kind="swap_skip", step=s,
                       reason="non-finite params")
            last_err = ValueError(msg)
            continue
        got = (s, tree)
        break
    if got is None:
        raise CheckpointCorruptError(
            f"no verifiable swap target under {ckpt_dir}; last error: "
            f"{last_err}")
    s, tree = got
    placed_kinds = (jax.Array, jax.ShapeDtypeStruct)
    skeleton = jax.tree_util.tree_map(
        lambda leaf: np.zeros(leaf.shape, leaf.dtype)
        if isinstance(leaf, placed_kinds) else leaf, params)
    host = serialization.from_state_dict(skeleton, tree)

    def place(tmpl, val):
        if (isinstance(tmpl, placed_kinds)
                and np.shape(val) != tmpl.shape):
            raise ValueError(
                f"checkpoint param shape {np.shape(val)} != live "
                f"{tmpl.shape}: live weight swap needs an identical "
                f"architecture (same config, same sharding)")
        if (isinstance(tmpl, placed_kinds) and tmpl.sharding is not None
                and not tmpl.sharding.is_fully_addressable):
            arr = np.asarray(val)
            return jax.make_array_from_callback(
                arr.shape, tmpl.sharding, lambda idx: arr[idx])
        return jax.device_put(val, getattr(tmpl, "sharding", None))

    try:
        placed = jax.tree_util.tree_map(place, params, host)
    except ValueError:
        raise  # our own clear shape/architecture messages
    except Exception as e:
        # Same diagnosis as _load_step_checked: a placement failure
        # across a mesh change names both topologies instead of
        # surfacing the runtime's opaque error.
        written = read_mesh_manifest(ckpt_dir, s) or {}
        want = _tree_mesh(params)
        if written.get("mesh") and want and written["mesh"] != want:
            raise MeshMismatchError(
                f"live weight swap from step {s} failed: checkpoint "
                f"written on mesh {_format_mesh(written['mesh'])}, "
                f"live params on mesh {_format_mesh(want)} "
                f"[{type(e).__name__}: {e}]. restore_resharded() "
                f"handles cross-mesh restores for full states.") from e
        raise
    return placed, s


def _quarantine(ckpt_dir: str, step: int, reason: str) -> str:
    """Rename a corrupt step dir aside (``quarantined_step_XXXXXXXX``)
    so available_steps/latest_step never see it again, preserving the
    bytes for forensics instead of deleting them. Chief-only rename
    (shared FS under multi-host — every process computed the same
    verification verdict from the same bytes, so the fallback order
    agrees)."""
    name = f"{_STEP_PREFIX}{step:08d}"
    dst = os.path.join(ckpt_dir, _QUARANTINE_PREFIX + name)
    # Written-mesh metadata rides the event (read BEFORE the rename):
    # the operator triaging a quarantine sees which topology the bytes
    # belong to, i.e. whether the surviving steps still fit the
    # current mesh.
    written = (read_mesh_manifest(ckpt_dir, step) or {}).get("mesh")
    if is_chief():
        if os.path.exists(dst):
            shutil.rmtree(dst, ignore_errors=True)
        try:
            os.rename(os.path.join(ckpt_dir, name), dst)
        except OSError:
            pass  # already moved/removed — the skip is what matters
    emit_event("recovery", kind="quarantine", step=step, reason=reason,
               mesh=_format_mesh(written) if written else None)
    _goodput.incr("quarantine")
    return dst


def _procs_sync(tag: str) -> None:
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(tag)


def quarantine_from(ckpt_dir: str, step: int, reason: str) -> List[int]:
    """Quarantine every available checkpoint at/after ``step``.

    The rewind policy's companion: a bad update applies at step K but
    is detected a few steps later (the loop retires metrics with lag),
    so cadence saves taken in between hold the POISONED state — their
    bytes are intact (checksums pass) but they must never be a resume
    target. Called before the rewind restore so ``latest_step`` lands
    on the newest pre-damage checkpoint. Returns the quarantined
    steps (chief's view).

    Multi-host protocol: COLLECTIVE — every process must call it.
    Barrier on entry (nobody lists the dir while a previous
    operation's renames are in flight), chief-only renames, barrier
    on exit (the renames are visible on the shared FS before any
    process recomputes ``latest_step``) — so all processes proceed to
    the same restore target."""
    _procs_sync(f"tfd_quarantine_enter_{step}")
    bad: List[int] = []
    if is_chief():
        bad = [s for s in available_steps(ckpt_dir) if s >= step]
        for s in bad:
            _quarantine(ckpt_dir, s, reason)
    _procs_sync(f"tfd_quarantine_exit_{step}")
    return bad


def _load_native_raw(step_path: str) -> Any:
    """Read + VERIFY a native checkpoint's state dict. Raises
    CheckpointCorruptError on unreadable bytes, a manifest-checksum
    mismatch, or an undecodable msgpack blob. Pre-integrity
    checkpoints (no "sha256" in the manifest) skip the checksum and
    still get the decode check."""
    path = os.path.join(step_path, "state.msgpack")
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except OSError as e:
        raise CheckpointCorruptError(f"unreadable {path}: {e}") from e
    expected = None
    man_path = os.path.join(step_path, "manifest.json")
    if os.path.exists(man_path):
        try:
            with open(man_path) as f:
                expected = json.load(f).get("sha256")
        except (OSError, ValueError):
            expected = None  # unreadable manifest: decode check remains
    if expected is not None:
        got = hashlib.sha256(blob).hexdigest()
        if got != expected:
            raise CheckpointCorruptError(
                f"checksum mismatch for {path}: manifest sha256 "
                f"{expected[:12]}…, file {got[:12]}… (truncated or "
                f"bit-flipped write)")
    try:
        return serialization.msgpack_restore(blob)
    except Exception as e:  # msgpack raises library-specific types
        raise CheckpointCorruptError(
            f"undecodable {path}: {e}") from e


def _load_step(ckpt_dir: str, step: int, state: Any) -> Any:
    step_path = _step_dir(ckpt_dir, step)
    if os.path.exists(os.path.join(step_path, _ORBAX_MARKER)):
        # Auto-detect via the COMMIT MARKER (not the orbax subdir):
        # a crashed orbax re-save into a dir holding an intact
        # native state.msgpack must fall through to the msgpack,
        # not dispatch onto incomplete shard debris.
        return _orbax_restore(step_path, state)
    return _restore_from_raw(_load_native_raw(step_path), state)


def _load_step_checked(ckpt_dir: str, step: int, state: Any) -> Any:
    """_load_step with mesh-mismatch diagnosis: a cross-mesh restore
    that dies inside orbax/XLA placement used to surface as that
    library's opaque error — when the written mesh (from the mesh
    manifest) differs from the template's, re-raise as
    :class:`MeshMismatchError` naming both topologies and pointing at
    :func:`restore_resharded`. Errors this layer already makes clear
    (corruption, missing files, shape/param-sync ValueErrors) pass
    through untouched; same-mesh failures are not mesh problems and
    propagate as themselves."""
    try:
        return _load_step(ckpt_dir, step, state)
    except (CheckpointCorruptError, FileNotFoundError, ValueError,
            MeshMismatchError):
        raise
    except Exception as e:
        written = read_mesh_manifest(ckpt_dir, step) or {}
        want = _tree_mesh(state)
        if written.get("mesh") and want \
                and written["mesh"] != want:
            raise MeshMismatchError(
                f"restore of step {step} under {ckpt_dir} failed: the "
                f"checkpoint was written on mesh "
                f"{_format_mesh(written['mesh'])} "
                f"({written.get('process_count', '?')} process(es)) "
                f"but the template requests mesh {_format_mesh(want)} "
                f"[{type(e).__name__}: {e}]. Use restore_resharded() "
                f"to re-lay a checkpoint out onto a different mesh "
                f"with the sharding contract verified.") from e
        raise


@_goodput.accounted("restore")
def restore(ckpt_dir: str, state: Any, step: Optional[int] = None) -> Any:
    """Restore into the structure/shardings of ``state`` (a freshly
    created template).

    ``step=None`` means the newest VERIFIABLE step: native checkpoints
    are checksum-verified against their manifest, and a corrupt/
    truncated candidate is quarantined (renamed aside, recovery event
    emitted) with automatic fallback to the next-newest step — a
    damaged latest checkpoint costs `checkpoint_every` steps of
    progress, never the run. An EXPLICIT ``step`` is exact: missing
    raises FileNotFoundError listing the steps actually available;
    corrupt raises CheckpointCorruptError without touching the dir
    (an explicitly requested step is being inspected, not recovered
    around)."""
    steps = available_steps(ckpt_dir)
    if step is not None:
        if step not in steps:
            raise FileNotFoundError(
                f"no checkpoint for step {step} under {ckpt_dir}; "
                f"available steps: {_describe_available(ckpt_dir, steps)}")
        return _load_step_checked(ckpt_dir, step, state)
    if not steps:
        raise FileNotFoundError(
            f"no checkpoints under {ckpt_dir} — is this a --resume "
            f"on an empty or absent checkpoint dir, or the wrong "
            f"--checkpoint-dir?")
    last_err: Optional[CheckpointCorruptError] = None
    for s in reversed(steps):
        try:
            return _load_step_checked(ckpt_dir, s, state)
        except CheckpointCorruptError as e:
            _quarantine(ckpt_dir, s, str(e))
            last_err = e
    raise CheckpointCorruptError(
        f"every checkpoint under {ckpt_dir} failed verification "
        f"(all quarantined); last error: {last_err}")


@_goodput.accounted("reshard")
def restore_resharded(ckpt_dir: str, state: Any,
                      step: Optional[int] = None,
                      verify: bool = True):
    """Restore a checkpoint written on mesh A into a template laid out
    on mesh B — the elastic-restart path. Returns ``(state, info)``.

    The values are the written ones bit-for-bit (the host round trip
    is layout-free; resharding only changes which device holds which
    slice), re-placed leaf by leaf onto the template's shardings: any
    combination of data/fsdp/tensor axis sizes whose product matches
    the template mesh's devices works, including growing onto MORE
    devices than wrote the checkpoint. ``verify=True`` (default)
    asserts the restored params/EMA against the template's declared
    layout via the sharding-contract checker (analysis/runtime.py) —
    the same contract ``--check`` holds the train step to — so a
    resharded resume starts from a PROVEN layout, not an assumed one.

    ``info`` carries ``step``, ``from_mesh`` (the written manifest's
    topology, None for pre-elastic checkpoints), ``to_mesh``,
    ``resharded`` (False when the topologies match) and ``seconds``
    (the resize window — the wall the goodput ledger charges to the
    "reshard" category). An actual mesh change emits one
    ``kind="reshard_restore"`` recovery event.

    Integrity contract is :func:`restore`'s: ``step=None`` walks back
    from the newest verifiable step; an explicit step is exact."""
    t0 = time.perf_counter()
    restored = restore(ckpt_dir, state, step=step)
    got_step = host_step(restored)
    written = read_mesh_manifest(ckpt_dir, got_step) or {}
    to_mesh = _tree_mesh(state)
    from_mesh = written.get("mesh")
    resharded = bool(from_mesh and to_mesh and from_mesh != to_mesh)
    if verify:
        from tensorflow_distributed_tpu.analysis import (
            runtime as graftcheck)
        graftcheck.assert_sharding_contract(
            restored.params, graftcheck.sharding_tree(state.params),
            what="resharded params")
        if getattr(state, "ema", None) is not None:
            graftcheck.assert_sharding_contract(
                restored.ema, graftcheck.sharding_tree(state.ema),
                what="resharded ema")
    info = {"step": got_step, "from_mesh": from_mesh,
            "to_mesh": to_mesh, "resharded": resharded,
            "seconds": round(time.perf_counter() - t0, 4)}
    if resharded:
        emit_event("recovery", kind="reshard_restore", **info)
        _goodput.incr("reshard_restore")
    return restored, info


def _align_masked_opt(skel: Any, raw: Any) -> Any:
    """Reconcile optax.masked wrappers across a resume: adding/removing
    a weight-decay mask wraps a chain member in MaskedState — an extra
    {"inner_state": ...} level whose own leaves are all empty — so a
    checkpoint written on one side of the change restores on the other
    by inserting/stripping that level to match the template skeleton.
    Purely structural: no array values are invented or dropped."""
    if not (isinstance(skel, dict) and isinstance(raw, dict)):
        return raw
    if (set(skel.keys()) == {"inner_state"}
            and set(raw.keys()) != {"inner_state"}):
        return {"inner_state": _align_masked_opt(skel["inner_state"],
                                                 raw)}
    if (set(raw.keys()) == {"inner_state"}
            and set(skel.keys()) != {"inner_state"}):
        return _align_masked_opt(skel, raw["inner_state"])
    return {k: (_align_masked_opt(skel[k], v) if k in skel else v)
            for k, v in raw.items()}


def _restore_from_raw(raw: Any, state: Any) -> Any:
    """Place a host state-dict into the template's structure and
    shardings (the shared tail of restore/restore_averaged)."""
    # from_state_dict only needs the pytree STRUCTURE (plus leaf shapes
    # for shape-checking) — a zeros skeleton costs no device transfers
    # or collectives, unlike fetching the throwaway template's values.
    skeleton = jax.tree_util.tree_map(
        lambda leaf: np.zeros(leaf.shape, leaf.dtype)
        if isinstance(leaf, jax.Array) else leaf, state)
    # EMA toggled between the saved run and this config must not brick
    # the restore: newly-enabled EMA seeds from the restored params
    # (the natural warm start); newly-disabled EMA drops the average.
    # Checkpoints written before TrainState grew the ema field have no
    # "ema" key at all — from_state_dict would raise on the missing
    # field even with EMA disabled, so absence means "EMA off".
    if isinstance(raw, dict) and isinstance(raw.get("opt_state"),
                                            dict):
        raw["opt_state"] = _align_masked_opt(
            serialization.to_state_dict(state).get("opt_state", {}),
            raw["opt_state"])
    if isinstance(raw, dict) and hasattr(state, "ema"):
        raw.setdefault("ema", None)
        want, have = state.ema is not None, raw["ema"] is not None
        if want and not have:
            raw["ema"] = raw["params"]
        elif have and not want:
            raw["ema"] = None
    host_state = serialization.from_state_dict(skeleton, raw)

    # Re-place every leaf with the template's sharding (mesh-shape
    # agnostic restore). Templates sharded across processes can't take
    # a plain device_put of the full host value; each process supplies
    # its addressable shards via the callback form instead.
    def place(tmpl, host):
        if (isinstance(tmpl, jax.Array)
                and np.shape(host) != tmpl.shape):
            # Catches replica-stacked vs plain state mismatches (a
            # param_sync_every flip across --resume / mode=eval)
            # with a clear error instead of an opaque shard_map
            # shape failure — or silent garbage — downstream.
            raise ValueError(
                f"checkpoint leaf shape {np.shape(host)} != template "
                f"{tmpl.shape}; was this run saved with a different "
                "--param-sync-every (replica-stacked vs plain "
                "state)?")
        if isinstance(tmpl, jax.Array) and not tmpl.is_fully_addressable:
            arr = np.asarray(host)
            return jax.make_array_from_callback(
                arr.shape, tmpl.sharding, lambda idx: arr[idx])
        return jax.device_put(host, tmpl.sharding)

    return jax.tree_util.tree_map(place, state, host_state)
