"""Explicit collective formulations of gradient sync.

Three formulations of the same synchronous data-parallel semantics, used
to *prove* and to *measure* what `train.step` does implicitly:

1. ``make_shardmap_train_step`` — the reference's
   ``SyncReplicasOptimizer`` (mnist_python_m.py:210-233, SURVEY.md N5)
   re-expressed the TPU way: each data shard computes grads, one
   ``lax.pmean`` over the "data" axis is the entire sync protocol (no
   accumulators, token queues, or chief thread). Tests assert it is
   numerically identical to the implicit-jit step *with dropout
   disabled and no BatchNorm*; with dropout on, this formulation draws
   an independent mask per data shard (fold_in by axis_index, like the
   reference's workers' independent draws) while the implicit-jit step
   draws one mask over the global batch — same distribution, different
   streams. BatchNorm models likewise normalize with local per-shard
   stats here vs global-batch stats in the jit step (see NOTE inline).

2. ``ps_style_grad_sync`` — an honest emulation of the reference's
   parameter-server topology for the BASELINE.json latency A/B: per-shard
   grads leave the device mesh to a single host "ps" (numpy), are
   averaged there, and re-broadcast — weights and gradients crossing the
   host boundary every step exactly as they crossed TCP in the reference
   (2x full pull + 2x full push per step, SURVEY.md §5 "communication
   backend").

3. ``allreduce_latency_probe`` — times a bare psum of grad-sized buffers
   over ICI, the number the "allreduce vs ps grad-sync latency" metric
   compares against.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tensorflow_distributed_tpu.parallel.mesh import AXIS_DATA
from tensorflow_distributed_tpu.train.state import TrainState
from tensorflow_distributed_tpu.train.step import loss_fn
from tensorflow_distributed_tpu.utils import prng


def make_shardmap_train_step(mesh: Mesh, seed: int = 0):
    """Train step with the gradient psum written out by hand.

    Semantics parity with the reference's sync mode, term by term:
    - ``replicas_to_aggregate == mesh data-axis size`` by construction
      (the reference required exactly N-of-N too: :216-219 with both
      flags defaulting to num_workers).
    - gradient aggregation is a mean (``lax.pmean``), matching the
      ConditionalAccumulator's take_grad mean.
    - one optimizer apply per aggregate, then step += 1 — the
      reference's ps-side ApplyAdam + global_step bump.
    """
    data_size = mesh.shape[AXIS_DATA]

    def per_shard(state: TrainState, images, labels):
        dkey = prng.step_key(seed, state.step)
        # Distinct dropout stream per data shard (the reference's workers
        # likewise had independent dropout draws).
        dkey = jax.random.fold_in(dkey, jax.lax.axis_index(AXIS_DATA))
        grad_fn = jax.value_and_grad(
            partial(loss_fn, state.apply_fn), has_aux=True)
        (_, (metrics, new_extra)), grads = grad_fn(
            state.params, state.extra, (images, labels), dkey, True)
        # THE sync protocol: one mean-allreduce over ICI. NOTE on
        # BatchNorm models: normalization here uses LOCAL per-shard
        # batch stats (torch-DDP-without-SyncBN semantics), and the
        # running stats are the mean of the per-shard updates — NOT
        # bitwise the jit step's global-batch (sync-BN) stats. The
        # numerical-parity contract with the jit step therefore holds
        # for stat-free models only; BN models agree in expectation.
        grads = jax.lax.pmean(grads, AXIS_DATA)
        metrics = jax.lax.pmean(metrics, AXIS_DATA)
        new_extra = jax.lax.pmean(new_extra, AXIS_DATA)
        updates, new_opt = state.tx.update(grads, state.opt_state, state.params)
        new_params = jax.tree_util.tree_map(
            lambda p, u: p + u.astype(p.dtype), state.params, updates)
        return state.replace(step=state.step + 1, params=new_params,
                             opt_state=new_opt, extra=new_extra), metrics

    state_specs = P()  # params/opt-state replicated across data shards
    shmapped = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(state_specs, P(AXIS_DATA), P(AXIS_DATA)),
        out_specs=(state_specs, state_specs),
        check_vma=False)

    with mesh:
        return jax.jit(lambda state, batch: shmapped(state, batch[0], batch[1]))


def make_per_shard_grads(mesh: Mesh, seed: int = 0):
    """Jitted per-shard gradient computation with NO cross-shard sync —
    the 'workers computed, nothing aggregated yet' intermediate the ps
    emulation needs. Returns grads stacked along a leading shard axis."""

    def per_shard(state: TrainState, images, labels):
        dkey = prng.step_key(seed, state.step)
        dkey = jax.random.fold_in(dkey, jax.lax.axis_index(AXIS_DATA))
        grad_fn = jax.grad(
            lambda p, b: loss_fn(state.apply_fn, p, state.extra, b,
                                 dkey, True)[0])
        grads = grad_fn(state.params, (images, labels))
        return jax.tree_util.tree_map(lambda g: g[None], grads)

    return jax.jit(jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(), P(AXIS_DATA), P(AXIS_DATA)),
        out_specs=P(AXIS_DATA),
        check_vma=False))


def _ps_round_trip(mesh: Mesh, stacked_grads: Any) -> Any:
    """One full ps round-trip on per-shard-stacked grads: device -> host
    numpy (the gradient "push", mnist_python_m.py:222 / N4's Send),
    numpy mean (the ps accumulator take_grad), device_put of the
    averaged grads to every device (the weight "pull")."""
    host_grads = jax.tree_util.tree_map(np.asarray, stacked_grads)
    mean_grads = jax.tree_util.tree_map(
        lambda g: g.mean(axis=0), host_grads)
    device_grads = jax.tree_util.tree_map(
        lambda g: jax.device_put(g, NamedSharding(mesh, P())), mean_grads)
    # block_until_ready is the barrier: the pull has landed on every
    # device when it returns.
    jax.block_until_ready(device_grads)
    return device_grads


def ps_style_grad_sync(mesh: Mesh, seed: int = 0):
    """The reference's star topology, emulated honestly on TPU hosts.

    Used only by the latency A/B benchmark — this is the baseline the
    psum path beats.
    """
    grad_step = make_per_shard_grads(mesh, seed)

    def sync(state: TrainState, batch) -> Tuple[Any, float]:
        t0 = time.perf_counter()
        stacked = grad_step(state, batch[0], batch[1])
        device_grads = _ps_round_trip(mesh, stacked)
        return device_grads, time.perf_counter() - t0

    return sync


def ps_style_sync_probe(mesh: Mesh, stacked_grads: Any) -> Callable[[], float]:
    """Time ONLY the sync portion of the ps emulation — the apples-to-
    apples counterpart of ``allreduce_latency_probe``.

    Input is a per-shard-stacked grads pytree already resident on the
    mesh (what ``make_per_shard_grads`` produces). One probe call is one
    full ps round-trip (``_ps_round_trip``): device->host pull of every
    shard's gradients (the reference's 2x full gradient push over TCP,
    SURVEY.md §5), host-side numpy mean (the ConditionalAccumulator
    take_grad, mnist_python_m.py:216-219), and device_put of the
    averaged result to every device (the weight pull). Grad
    *computation* is excluded from the timed span, exactly as it is in
    the allreduce probe.

    jax.Array caches its host copy after the first ``np.asarray``, which
    would let every timed iteration after the first skip the
    device->host transfer entirely; each probe call therefore first
    materializes FRESH device arrays (an untimed on-device identity op)
    so the pull is genuinely paid every time.
    """
    refresh = jax.jit(partial(jax.tree_util.tree_map, lambda g: g + 0))

    def probe() -> float:
        fresh = refresh(stacked_grads)
        # Same honest barrier as the allreduce probe: make sure the
        # refresh op has truly finished before t0, or its execution
        # would be charged to the timed ps round-trip.
        leaf = jax.tree_util.tree_leaves(fresh)[0]
        float(jax.device_get(jax.numpy.ravel(leaf)[0]))
        t0 = time.perf_counter()
        _ps_round_trip(mesh, fresh)
        return time.perf_counter() - t0

    return probe


def allreduce_latency_probe(mesh: Mesh, grads_like: Any) -> Callable[[], float]:
    """Time one psum-mean over the data axis for grad-shaped buffers.

    The returned probe is WARM: one untimed dispatch (with the same
    dependent-scalar readback the timed path uses) runs here, so the
    first timed call measures the collective, not trace+compile wall.
    For a usable communication floor take :func:`min_latency` over
    several
    calls — the minimum is the schedulable cost; the median carries
    host scheduling noise.
    """
    psum = jax.jit(
        jax.shard_map(
            lambda t: jax.lax.pmean(t, AXIS_DATA), mesh=mesh,
            in_specs=P(), out_specs=P(), check_vma=False))

    def probe() -> float:
        t0 = time.perf_counter()
        out = psum(grads_like)
        jax.block_until_ready(out)  # the barrier, as on the ps side
        return time.perf_counter() - t0

    # Warm-up dispatch: psum compile wall must never leak into the
    # first timed sample (it used to — the probe was unusable as a
    # comm floor until its caller happened to add its own warmup).
    warm = psum(grads_like)
    leaf = jax.tree_util.tree_leaves(warm)[0]
    float(jax.device_get(jax.numpy.ravel(leaf)[0]))
    return probe


def min_latency(probe: Callable[[], float], iters: int = 10) -> float:
    """Min-of-N of a latency probe, in seconds: the schedulable cost
    of the operation, robust to host scheduling noise: a
    communication floor."""
    if iters < 1:
        raise ValueError(f"iters must be >= 1, got {iters}")
    return min(probe() for _ in range(iters))
