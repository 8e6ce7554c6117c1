"""Overlap-aware gradient sync: bucketed reduce-scatter / all-gather.

The implicit SPMD train step (train/step.py) pays gradient aggregation
as one GSPMD-inserted allreduce after the backward pass — a serial
communication tail the device sits idle behind. This module restates
the weight update the way "Automatic Cross-Replica Sharding of Weight
Update in Data-Parallel Training" (arXiv 2004.13336, PAPERS.md)
prescribes, with every collective written out by hand so it is
censusable (analysis/jaxprcheck) and schedulable:

1. the grad pytree is partitioned into deterministic, size-bounded,
   dtype-keyed **buckets** (:func:`plan_buckets` — the ladder idea of
   serve's prefill buckets applied to gradient leaves);
2. each bucket is **reduce-scattered** (``lax.psum_scatter``) over the
   "data" axis as one fused collective. Because each bucket depends
   only on its own leaves' backward contributions, XLA's latency-hiding
   scheduler is free to start a bucket's reduce-scatter while the
   backward pass for earlier layers is still computing — the collective
   hides under compute instead of trailing it;
3. the optimizer update runs **sharded** (ZeRO-1): each device updates
   only its 1/N slice of every bucket, against optimizer slots that
   live permanently sharded over "data" (``param_partition=zero1``'s
   exact layout — ``parallel.sharding.fsdp_scatter_dim`` is the shared
   dim rule, so the scattered gradient block lands on the device that
   already holds the matching m/v block);
4. updated params are **all-gathered** back per bucket (again fused,
   again free to interleave), restoring the replicated layout the next
   forward expects. Slots are never gathered — they stay sharded.

Numerics: the serial and overlap formulations are BIT-IDENTICAL —
psum_scatter + all_gather compute the same per-element sums as the
pmean they replace, and the elementwise optimizer math is blocking-
invariant (pinned by tests/test_overlap.py, including the
``skip_nonfinite`` discarded-step path, Adam slots, and EMA).

Leaves too small to shard (below ``fsdp_min_size``, or with no dim
divisible by the axis — the same threshold ZeRO-1 slot placement uses)
ride replicated psum buckets and take a full local update, exactly as
they do under plain zero1.

Builders:
- :func:`make_explicit_train_step` — the full-featured step
  (``grad_sync="overlap"`` / ``"serial"`` / ``"unsynced"``), reached
  from the CLI as ``--grad-sync`` via train/step.py's dispatch.
  "serial" is the A/B baseline: same shard_map skeleton, one monolithic
  pmean, full-tree replicated update — the serial psum tail, made
  explicit. "unsynced" drops the collectives entirely (WRONG math: a
  compute floor for an exposed-communication estimate; the harness
  that ran it went in PR 30 and the mode has no caller, ROADMAP.md
  C4).
- :func:`plan_buckets` / :func:`comm_bytes_per_step` — the partition
  and its per-device traffic estimate (observe surfaces the
  exposed-vs-hidden split from it).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, PartitionSpec as P

from tensorflow_distributed_tpu.observe import device as observe_device
from tensorflow_distributed_tpu.observe import health as observe_health
from tensorflow_distributed_tpu.parallel.mesh import AXIS_DATA
from tensorflow_distributed_tpu.parallel.sharding import (
    FSDP_MIN_SIZE, fsdp_scatter_dim, path_key)
from tensorflow_distributed_tpu.train.state import TrainState, ema_update
from tensorflow_distributed_tpu.train.step import (
    Batch, LossFn, Metrics, _pop_taps, default_batch_shardings, loss_fn)
from tensorflow_distributed_tpu.utils import prng

GRAD_SYNC_MODES = ("serial", "overlap", "unsynced")

#: Default bucket bound. ~4 MB keeps a GPT-2-small grad tree (~500 MB
#: f32) in ~100 collectives — large enough to amortize collective
#: launch latency, small enough that the first reduce-scatter can
#: start long before the backward pass finishes.
DEFAULT_BUCKET_BYTES = 4 << 20


# --- bucket planning (deterministic; shapes only) -----------------------

@dataclasses.dataclass(frozen=True)
class LeafPlan:
    """One grad leaf's place in the sync plan."""

    index: int                 # position in jax tree-flatten order
    path: Tuple[str, ...]      # param path (diagnostics / module attribution)
    shape: Tuple[int, ...]
    dtype: str
    scatter_dim: int           # -1 = replicated psum path
    size: int = 0              # elements (host-computed at plan time)
    nbytes: int = 0


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """The full partition: scatter buckets (reduce-scatter + sharded
    update + all-gather) and replicated buckets (fused psum + full
    local update)."""

    axis_size: int
    bucket_bytes: int
    scatter: Tuple[Tuple[LeafPlan, ...], ...]
    replicated: Tuple[Tuple[LeafPlan, ...], ...]
    n_leaves: int

    @property
    def scatter_bytes(self) -> int:
        return sum(lp.nbytes for b in self.scatter for lp in b)

    @property
    def replicated_bytes(self) -> int:
        return sum(lp.nbytes for b in self.replicated for lp in b)

    def describe(self) -> dict:
        """Serializable summary (bench artifacts, plan records)."""
        return {
            "axis_size": self.axis_size,
            "bucket_bytes": self.bucket_bytes,
            "scatter_buckets": len(self.scatter),
            "replicated_buckets": len(self.replicated),
            "scatter_bytes": self.scatter_bytes,
            "replicated_bytes": self.replicated_bytes,
            "leaves": self.n_leaves,
        }


def plan_buckets(params: Any, axis_size: int,
                 bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                 fsdp_min_size: int = FSDP_MIN_SIZE) -> BucketPlan:
    """Partition a param/grad pytree into size-bounded buckets.

    Deterministic by construction: leaves are visited in jax
    tree-flatten order and greedily packed into the current bucket for
    their (scatterable?, dtype) key; a bucket closes when adding the
    next leaf would exceed ``bucket_bytes`` (a single leaf larger than
    the bound gets its own bucket). Dtype-keyed because a fused
    collective is one array — mixed dtypes can't concatenate.

    A leaf is scatterable when it meets the SAME rule ZeRO-1 slot
    placement applies (``parallel.sharding``): total size >=
    ``fsdp_min_size`` and some dim divisible by ``axis_size`` (the
    largest such dim, ``fsdp_scatter_dim``). Everything else is
    replicated: psum'd fused, updated in full on every device.
    """
    if axis_size < 1:
        raise ValueError(f"axis_size must be >= 1, got {axis_size}")
    if bucket_bytes < 1:
        raise ValueError(f"bucket_bytes must be >= 1, got {bucket_bytes}")
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    leaves: List[LeafPlan] = []
    for i, (path, leaf) in enumerate(flat):
        shape = tuple(int(s) for s in getattr(leaf, "shape", ()))
        dtype = np.dtype(getattr(leaf, "dtype", np.float32)).name
        size = int(np.prod(shape, dtype=np.int64)) if shape else 1
        dim = -1
        if axis_size > 1 and size >= fsdp_min_size:
            dim = fsdp_scatter_dim(shape, axis_size)
        leaves.append(LeafPlan(
            index=i, path=path_key(path), shape=shape, dtype=dtype,
            scatter_dim=dim, size=size,
            nbytes=size * np.dtype(dtype).itemsize))

    open_buckets: dict = {}   # (scatterable, dtype) -> (leaves, bytes)
    scatter: List[Tuple[LeafPlan, ...]] = []
    replicated: List[Tuple[LeafPlan, ...]] = []

    def close(key):
        group, _ = open_buckets.pop(key)
        (scatter if key[0] else replicated).append(tuple(group))

    for lp in leaves:
        key = (lp.scatter_dim >= 0, lp.dtype)
        group, nbytes = open_buckets.get(key, ([], 0))
        if group and nbytes + lp.nbytes > bucket_bytes:
            close(key)
            group, nbytes = [], 0
        group.append(lp)
        open_buckets[key] = (group, nbytes + lp.nbytes)
    # Close in deterministic key order (open_buckets insertion order
    # follows leaf order, which is already deterministic).
    for key in list(open_buckets):
        close(key)
    return BucketPlan(axis_size=axis_size, bucket_bytes=bucket_bytes,
                      scatter=tuple(scatter), replicated=tuple(replicated),
                      n_leaves=len(leaves))


def comm_bytes_per_step(plan: BucketPlan) -> float:
    """Estimated per-device collective traffic of ONE overlap step:
    reduce-scatter of every grad bucket + all-gather of every updated
    param bucket (ring cost: each moves (N-1)/N of the full tree per
    device), plus the allreduce (2x ring) of the replicated leaves.
    The serial psum pays the same total — the overlap win is hiding
    it, not shrinking it; observe uses this as the comm term of the
    exposed-vs-hidden estimate."""
    n = plan.axis_size
    if n <= 1:
        return 0.0
    ring = (n - 1) / n
    return (2.0 * ring * plan.scatter_bytes
            + 2.0 * ring * plan.replicated_bytes)


# --- block layout helpers -----------------------------------------------
#
# Canonical forms for a scatterable leaf of shape S with scatter dim d
# over an axis of size N:
#   rows:  [N, size/N]  — moveaxis(d, 0) then reshape; row i flattened
#          is device i's block. What psum_scatter consumes (fused along
#          columns) and all_gather produces.
#   block: S with S[d]/N at position d — the per-device shard in
#          ORIGINAL dim order, i.e. exactly the slot shard a zero1
#          NamedSharding (P with "data" at d) hands shard_map.

def _leaf_to_rows(x: jax.Array, dim: int, n: int) -> jax.Array:
    return jnp.moveaxis(x, dim, 0).reshape(n, -1)


def _moved_shape(lp: LeafPlan) -> Tuple[int, ...]:
    """lp.shape with the scatter dim moved to the front (what
    moveaxis(d, 0) produces — remaining dims keep relative order)."""
    s, d = lp.shape, lp.scatter_dim
    return (s[d],) + s[:d] + s[d + 1:]


def _rows_to_leaf(rows: jax.Array, lp: LeafPlan, n: int) -> jax.Array:
    x = rows.reshape(_moved_shape(lp))
    return jnp.moveaxis(x, 0, lp.scatter_dim)


def _flat_to_block(flat: jax.Array, lp: LeafPlan, n: int) -> jax.Array:
    moved = _moved_shape(lp)
    block_moved = (moved[0] // n,) + moved[1:]
    return jnp.moveaxis(flat.reshape(block_moved), 0, lp.scatter_dim)


def _block_to_flat(block: jax.Array, lp: LeafPlan) -> jax.Array:
    return jnp.moveaxis(block, lp.scatter_dim, 0).reshape(-1)


def _block_slice(full: jax.Array, lp: LeafPlan, n: int,
                 idx: jax.Array) -> jax.Array:
    """This device's block of a REPLICATED full leaf (local read)."""
    blk = lp.shape[lp.scatter_dim] // n
    return jax.lax.dynamic_slice_in_dim(full, idx * blk, blk,
                                        axis=lp.scatter_dim)


# --- the sync engines (traced context, inside shard_map) ----------------

def _sync_overlap(grads: Any, plan: BucketPlan) -> Any:
    """Bucketed reduce-scatter: returns the grad tree with scatterable
    leaves replaced by this device's mean-reduced BLOCK and replicated
    leaves by the full mean (fused psums)."""
    n = plan.axis_size
    flat, treedef = jax.tree_util.tree_flatten(grads)
    out: List[Any] = list(flat)
    for bucket in plan.scatter:
        rows = [_leaf_to_rows(flat[lp.index], lp.scatter_dim, n)
                for lp in bucket]
        fused = rows[0] if len(rows) == 1 else jnp.concatenate(rows,
                                                               axis=1)
        shard = jax.lax.psum_scatter(fused, AXIS_DATA,
                                     scatter_dimension=0,
                                     tiled=False) / n
        off = 0
        for lp in bucket:
            k = lp.size // n
            out[lp.index] = _flat_to_block(
                jax.lax.slice_in_dim(shard, off, off + k), lp, n)
            off += k
    for bucket in plan.replicated:
        fused = (flat[bucket[0].index].reshape(-1)
                 if len(bucket) == 1 else jnp.concatenate(
                     [flat[lp.index].reshape(-1) for lp in bucket]))
        red = jax.lax.psum(fused, AXIS_DATA) / n
        off = 0
        for lp in bucket:
            out[lp.index] = jax.lax.slice_in_dim(
                red, off, off + lp.size).reshape(lp.shape)
            off += lp.size
    return jax.tree_util.tree_unflatten(treedef, out)


def _gather_params(new_blocks: Any, plan: BucketPlan) -> Any:
    """Bucketed all-gather of updated param blocks back to full
    (replicated) leaves; replicated leaves pass through."""
    n = plan.axis_size
    flat, treedef = jax.tree_util.tree_flatten(new_blocks)
    out: List[Any] = list(flat)
    for bucket in plan.scatter:
        fused = (_block_to_flat(flat[bucket[0].index], bucket[0])
                 if len(bucket) == 1 else jnp.concatenate(
                     [_block_to_flat(flat[lp.index], lp)
                      for lp in bucket]))
        rows = jax.lax.all_gather(fused, AXIS_DATA, axis=0, tiled=False)
        off = 0
        for lp in bucket:
            k = lp.size // n
            out[lp.index] = _rows_to_leaf(
                jax.lax.slice_in_dim(rows, off, off + k, axis=1), lp, n)
            off += k
    return jax.tree_util.tree_unflatten(treedef, out)


def _shard_params(params: Any, plan: BucketPlan) -> Any:
    """Per-device param view matching the scattered grads: blocks for
    scatterable leaves (local slices of the replicated full arrays),
    full leaves otherwise."""
    n = plan.axis_size
    idx = jax.lax.axis_index(AXIS_DATA)
    flat, treedef = jax.tree_util.tree_flatten(params)
    out = list(flat)
    for bucket in plan.scatter:
        for lp in bucket:
            out[lp.index] = _block_slice(flat[lp.index], lp, n, idx)
    return jax.tree_util.tree_unflatten(treedef, out)


def _sharded_sq_norms(tree: Any, plan: BucketPlan,
                      by_module: bool = False):
    """Per-tree (or per-top-level-module) sum-of-squares split into the
    part that needs a psum (block leaves — each device holds 1/N) and
    the part that doesn't (replicated leaves). Caller psums the first
    and adds the second."""
    flat = jax.tree_util.tree_flatten(tree)[0]
    scatter_idx = {lp.index for b in plan.scatter for lp in b}
    modules: dict = {}
    lps = sorted((lp for b in plan.scatter for lp in b),
                 key=lambda lp: lp.index) + sorted(
        (lp for b in plan.replicated for lp in b),
        key=lambda lp: lp.index)
    for lp in lps:
        mod = lp.path[0] if (by_module and lp.path) else ""
        sc, rep = modules.get(mod, (jnp.zeros((), jnp.float32),
                                    jnp.zeros((), jnp.float32)))
        sq = jnp.sum(jnp.square(flat[lp.index].astype(jnp.float32)))
        if lp.index in scatter_idx:
            sc = sc + sq
        else:
            rep = rep + sq
        modules[mod] = (sc, rep)
    return modules


def _global_grad_norm(shard_grads: Any, plan: BucketPlan) -> jax.Array:
    """The TRUE global gradient norm from the sharded view: one scalar
    psum over the block contributions (device blocks partition each
    leaf, so the psum'd sum-of-squares is exact) plus the replicated
    leaves' local sum."""
    (sc, rep), = _sharded_sq_norms(shard_grads, plan).values()
    return jnp.sqrt(jax.lax.psum(sc, AXIS_DATA) + rep)


def _clip_tree(tree: Any, g_norm: jax.Array, max_norm: float) -> Any:
    """Clip-by-global-norm with a CALLER-supplied norm — optax's exact
    elementwise semantics (`lax.select` on `g_norm < max_norm`, scale
    by `max_norm / g_norm` otherwise), detached from optax's own
    `global_norm` so both explicit grad-sync modes can feed the SAME
    psum-reconstructed scalar:

    - overlap: the norm comes from the scattered blocks
      (:func:`_global_grad_norm` — psum of block sums-of-squares);
    - serial: the norm comes from the SAME formulation applied to the
      pmean'd full tree's local block slices (``_shard_params``), so
      the scalar — and therefore the clipped update — is bit-identical
      to overlap's, which is what lets the serial-vs-overlap identity
      gate keep running under clip (tests/test_overlap.py).

    The chain clip in train/optim.py is correspondingly OMITTED for
    explicit grad-sync runs: inside the shard_map tx sees grad BLOCKS,
    and a chain clip would use each device's local norm."""
    trigger = g_norm < max_norm

    def clip_leaf(t):
        return jax.lax.select(
            jnp.broadcast_to(trigger, t.shape), t,
            (t / g_norm.astype(t.dtype)) * jnp.asarray(
                max_norm, t.dtype))

    return jax.tree_util.tree_map(clip_leaf, tree)


def _sharded_health(params: Any, shard_grads: Any, shard_updates: Any,
                    plan: BucketPlan, step: jax.Array,
                    health_every: int) -> dict:
    """observe.health's per-module vitals from the SHARDED grad/update
    view: block sum-of-squares are combined across devices with ONE
    fused psum of a small stacked vector (grads + updates per module),
    params are replicated so their norms are local. Same keys and emit
    flag as observe_health.stats; unlike the implicit step's variant
    the reductions run unconditionally (a collective inside a
    lax.cond branch is scheduling trouble) — the blocks are 1/N-sized,
    so the per-step cost is the sharded update's own order."""
    g_mods = _sharded_sq_norms(shard_grads, plan, by_module=True)
    u_mods = _sharded_sq_norms(shard_updates, plan, by_module=True)
    names = sorted(g_mods)
    stacked = jnp.stack([g_mods[m][0] for m in names]
                        + [u_mods[m][0] for m in names])
    stacked = jax.lax.psum(stacked, AXIS_DATA)
    out: dict = {}
    import math
    p_flat = jax.tree_util.tree_flatten_with_path(params)[0]
    for i, m in enumerate(names):
        g = jnp.sqrt(stacked[i] + g_mods[m][1])
        u = jnp.sqrt(stacked[len(names) + i] + u_mods[m][1])
        leaves = [leaf for path, leaf in p_flat
                  if (path_key(path)[0] if path_key(path) else "") == m]
        p = optax.global_norm(leaves).astype(jnp.float32)
        size = sum(x.size for x in leaves)
        key = m or "params"
        out[f"{observe_health.PREFIX}{key}/grad_norm"] = g
        out[f"{observe_health.PREFIX}{key}/update_ratio"] = (
            u / (p + 1e-12))
        out[f"{observe_health.PREFIX}{key}/param_rms"] = (
            p / math.sqrt(max(size, 1)))
    emit = ((step + 1) % health_every) == 0
    out[observe_health.EMIT_KEY] = emit.astype(jnp.float32)
    return out


# --- the step builder ---------------------------------------------------

def make_explicit_train_step(mesh: Mesh, state_template: TrainState,
                             seed: int = 0, loss: LossFn = loss_fn,
                             batch_shardings: Any = None,
                             grad_sync: str = "overlap",
                             bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                             fsdp_min_size: int = FSDP_MIN_SIZE,
                             donate: bool = True,
                             grad_norm_metric: bool = False,
                             ema_decay: float = 0.0,
                             params_out_shardings: Any = None,
                             skip_nonfinite: bool = False,
                             health_every: int = 0,
                             grad_clip_norm: float = 0.0,
                             jit: bool = True
                             ) -> Callable[[TrainState, Batch],
                                           Tuple[TrainState, Metrics]]:
    """Build the explicit-collective train step for a pure-data mesh.

    ``state_template`` pins the state pytree (and, for "overlap", the
    zero1 slot shardings the per-bucket update runs against — pass the
    state the loop will actually thread through, created with
    ``opt_fsdp=True`` and the SAME ``fsdp_min_size``; an abstract
    ``ShapeDtypeStruct`` state from train.state.abstract_train_state
    works too, which is how the auto-layout planner scores this
    strategy without allocating).

    Per-shard semantics (shared with parallel.collectives'
    ``make_shardmap_train_step`` and documented there): the loss is the
    mean over each device's LOCAL shard and the synced gradient the
    mean of per-shard means — identical to the global mean for
    uniformly-weighted losses, a slight reweighting for masked losses
    with unequal per-shard mask counts (the grad_accum_steps caveat,
    verbatim); dropout draws an independent stream per data shard;
    BatchNorm models normalize with local per-shard stats.

    The optimizer must be ELEMENTWISE for "overlap" (adam/adamw/sgd —
    each element's update depends only on that element's grad/slots,
    so a block computes exactly the full update's slice); adafactor's
    factored second moments are not, and config.validate rejects the
    combination. ``skip_nonfinite`` / EMA / ``params_out_shardings`` /
    ``health_every`` compose exactly as in train.step — skip selects
    on the full param view and the slot blocks, EMA tracks the
    gathered params, health reads the sharded grads/updates through
    psum-reconstructed full-tree norms.

    ``grad_clip_norm`` > 0 clips by the TRUE global norm before the
    elementwise update, reconstructed from block sums-of-squares with
    one scalar psum — the identical formulation in both modes, so
    serial+clip and overlap+clip stay bit-equal (see
    :func:`_clip_tree`; the optax chain clip is omitted for explicit
    grad-sync runs by train/optim.py — pass the UNCLIPPED tx here).
    """
    if grad_sync not in GRAD_SYNC_MODES:
        raise ValueError(f"unknown grad_sync {grad_sync!r}; have "
                         f"{GRAD_SYNC_MODES}")
    axis_size = mesh.shape[AXIS_DATA]
    nondata = {a: int(s) for a, s in mesh.shape.items()
               if a != AXIS_DATA and int(s) > 1}
    if nondata:
        raise ValueError(
            f"explicit grad-sync needs a pure data mesh; axes "
            f"{nondata} > 1 (tensor/seq/pipe/expert params are managed "
            f"by GSPMD or shard_map schedules the explicit formulation "
            f"doesn't reproduce)")
    if grad_sync == "overlap" and axis_size < 2:
        raise ValueError(
            "grad_sync=overlap reduce-scatters over the data axis; "
            f"data={axis_size} leaves nothing to scatter — use the "
            "implicit step on a single data shard")
    if batch_shardings is None:
        batch_shardings = default_batch_shardings(mesh)
    plan = plan_buckets(state_template.params, axis_size,
                        bucket_bytes=bucket_bytes,
                        fsdp_min_size=fsdp_min_size)

    state_specs = jax.tree_util.tree_map(
        lambda a: a.sharding.spec, state_template)
    batch_specs = jax.tree_util.tree_map(
        lambda s: s.spec, batch_shardings)

    def per_shard(state: TrainState, batch: Batch):
        dkey = prng.step_key(seed, state.step)
        # Independent dropout stream per data shard (the precedent and
        # the caveat live in parallel.collectives' docstring).
        dkey = jax.random.fold_in(dkey, jax.lax.axis_index(AXIS_DATA))
        grad_fn = jax.value_and_grad(
            partial(loss, state.apply_fn), has_aux=True)
        (_, (metrics, new_extra)), grads = grad_fn(
            state.params, state.extra, batch, dkey, True)
        metrics, new_extra = _pop_taps(metrics, new_extra)
        metrics = jax.lax.pmean(metrics, AXIS_DATA)
        new_extra = jax.lax.pmean(new_extra, AXIS_DATA)

        if grad_sync == "overlap":
            shard_grads = _sync_overlap(grads, plan)
            shard_params = _shard_params(state.params, plan)
            norm = None
            if grad_clip_norm or grad_norm_metric or skip_nonfinite:
                norm = _global_grad_norm(shard_grads, plan)
            if grad_norm_metric:
                metrics = dict(metrics, grad_norm=norm)
            ok = None
            if skip_nonfinite:
                ok = jnp.isfinite(metrics["loss"]) & jnp.isfinite(norm)
                metrics = dict(metrics,
                               skipped_nonfinite=jnp.where(ok, 0.0, 1.0))
            if grad_clip_norm:
                # Clip by the psum-reconstructed TRUE global norm
                # before the elementwise update (the chain clip is
                # omitted for explicit grad-sync — train/optim.py).
                # Pre-clip norm feeds the metric and the skip flag,
                # matching the implicit step's semantics.
                shard_grads = _clip_tree(shard_grads, norm,
                                         grad_clip_norm)
            # The ZeRO-1 sharded update: slots arrive as blocks (their
            # persisted sharding IS the in_spec), params as local
            # slices, grads as scattered blocks. Elementwise optimizer
            # math makes each block exactly the full update's slice.
            updates, new_opt = state.tx.update(
                shard_grads, state.opt_state, shard_params)
            if health_every:
                metrics = dict(metrics, **_sharded_health(
                    state.params, shard_grads, updates, plan,
                    state.step, health_every))
                metrics = observe_health.gate(
                    metrics, metrics[observe_health.EMIT_KEY] > 0)
            new_blocks = jax.tree_util.tree_map(
                lambda p, u: p + u.astype(p.dtype), shard_params,
                updates)
            new_params = _gather_params(new_blocks, plan)
        else:
            if grad_sync == "serial":
                # THE serial psum tail, written out: one monolithic
                # mean-allreduce, then every device repeats the full
                # update.
                grads = jax.lax.pmean(grads, AXIS_DATA)
            norm = None
            if grad_clip_norm:
                # The SAME block-partitioned reconstruction overlap
                # uses (this device's local slices of the full tree →
                # block sums-of-squares → one psum), NOT
                # optax.global_norm: the scalar is bit-identical to
                # the overlap path's, so clipped serial and clipped
                # overlap stay bit-equal — the identity gate's
                # requirement.
                norm = _global_grad_norm(_shard_params(grads, plan),
                                         plan)
            if grad_norm_metric:
                metrics = dict(metrics,
                               grad_norm=(norm if norm is not None
                                          else optax.global_norm(grads)))
            ok = None
            if skip_nonfinite:
                skip_norm = (norm if norm is not None
                             else optax.global_norm(grads))
                ok = (jnp.isfinite(metrics["loss"])
                      & jnp.isfinite(skip_norm))
                metrics = dict(metrics,
                               skipped_nonfinite=jnp.where(ok, 0.0, 1.0))
            if grad_clip_norm:
                grads = _clip_tree(grads, norm, grad_clip_norm)
            updates, new_opt = state.tx.update(
                grads, state.opt_state, state.params)
            if health_every:
                metrics = dict(metrics, **observe_health.stats(
                    state.params, grads, updates, state.step,
                    health_every))
                metrics = observe_health.gate(
                    metrics, metrics[observe_health.EMIT_KEY] > 0)
            new_params = jax.tree_util.tree_map(
                lambda p, u: p + u.astype(p.dtype), state.params,
                updates)

        if ok is not None:
            # Discard the whole update on a non-finite step — the
            # train.step contract, applied to the full param view and
            # the per-device slot blocks alike (where is elementwise;
            # the old blocks are exactly the in_spec'd state views).
            def keep_old(new, old):
                return jax.tree_util.tree_map(
                    lambda a, b: jnp.where(ok, a, b), new, old)

            new_params = keep_old(new_params, state.params)
            new_opt = keep_old(new_opt, state.opt_state)
            new_extra = keep_old(new_extra, state.extra)
        new_ema = state.ema
        if ema_decay and state.ema is not None:
            new_ema = ema_update(state.ema, new_params, ema_decay,
                                 state.step)
            if ok is not None:
                new_ema = jax.tree_util.tree_map(
                    lambda a, b: jnp.where(ok, a, b), new_ema,
                    state.ema)
        new_state = state.replace(step=state.step + 1,
                                  params=new_params, opt_state=new_opt,
                                  extra=new_extra, ema=new_ema)
        return new_state, metrics

    shmapped = jax.shard_map(per_shard, mesh=mesh,
                             in_specs=(state_specs, batch_specs),
                             out_specs=(state_specs, P()),
                             check_vma=False)

    def step(state: TrainState, batch: Batch):
        new_state, metrics = shmapped(state, batch)
        if params_out_shardings is not None:
            # The zero1 invariant from train.step: pin the gathered
            # params back to their state-creation layout so GSPMD
            # never propagates a stray sharding into later steps.
            new_params = jax.tree_util.tree_map(
                jax.lax.with_sharding_constraint, new_state.params,
                params_out_shardings)
            new_state = new_state.replace(params=new_params)
        return new_state, metrics

    # The built step carries its own plan so callers (train/loop's
    # grad_sync record) read the EXACT partition the compiled program
    # executes instead of re-deriving it.
    if not jit:
        step.bucket_plan = plan
        return step
    with mesh:
        wrapped = observe_device.instrument_jit(
            f"train_step_{grad_sync}", step,
            in_shardings=(None, batch_shardings),
            donate_argnums=(0,) if donate else (),
        )
    wrapped.bucket_plan = plan
    return wrapped
