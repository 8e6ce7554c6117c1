"""Local SGD: the TPU-native runnable analog of the reference's async mode.

The reference's ``sync_replicas=False`` path (mnist_python_m.py:208,222,
247-253, SURVEY.md N6) lets each worker push updates to the ps without
waiting — workers train on stale, mutually-diverged parameters between
ps round-trips. A TPU mesh has no parameter server and SPMD programs
are synchronous by construction, so a literal port is impossible AND
undesirable (round 4 read a 19.9x allreduce-vs-ps gap on one v5e; not
re-measured, PERF.md "Before the benchmark").
What survives contact with the hardware is the async family's actual
training-dynamics content: REPLICAS THAT DIVERGE BETWEEN SYNC POINTS.

That is local SGD / periodic parameter averaging (a.k.a. post-local
SGD): every data-parallel replica takes ``sync_every`` optimizer steps
on its own batch shard with NO gradient sync, then replicas average
their parameters — one pmean every H steps instead of one psum every
step, an H-fold cut in sync frequency, which is precisely the
communication behavior async-ps buys (at the cost of divergence, which
is also exactly async-ps's cost). At H=1 with plain SGD it IS
synchronous data parallelism: avg(p - lr*g_r) == p - lr*avg(g_r) —
pinned as an exact parity test.

Mechanics: the train state's params/opt-state/step carry a leading
replica dim [R, ...] sharded over the "data" mesh axis; the step runs
in a shard_map manualizing only that axis, so each device updates its
own replica locally (per-replica dropout keys included), and a
``lax.cond``-gated ``pmean`` averages params every H-th step. Plain-DP
meshes only (model/seq/pipe/expert == 1) — the same scope the
reference's async mode had.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tensorflow_distributed_tpu.parallel.mesh import AXIS_DATA
from tensorflow_distributed_tpu.train.state import TrainState
from tensorflow_distributed_tpu.train.step import (
    default_batch_shardings, loss_fn)
from tensorflow_distributed_tpu.utils import prng


def stack_state(state: TrainState, mesh: Mesh) -> TrainState:
    """Broadcast params/opt_state/step to [R, ...] replica-stacked
    leaves sharded over the data axis. The replicas start identical
    (the reference's workers also all began from the chief's init,
    mnist_python_m.py:272-275) and diverge from the first local step."""
    if state.extra:
        raise ValueError(
            "local SGD supports models without mutable extra state "
            f"(got collections {list(state.extra)}); divergent per-"
            "replica statistics have no principled average")
    if state.ema is not None:
        raise ValueError("local SGD does not compose with ema_decay "
                         "(average-of-averages ambiguity); disable one")
    R = mesh.shape[AXIS_DATA]

    # Jitted with sharded out_shardings: XLA writes only each
    # device's 1/R shard of the broadcast — no transient R-fold
    # replicated copy of params + optimizer slots (an OOM risk at
    # exactly the scale local SGD targets).
    def bcast_tree(tree):
        return jax.jit(
            lambda t: jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x[None], (R,) + x.shape),
                t),
            out_shardings=NamedSharding(mesh, P(AXIS_DATA)))(tree)

    return state.replace(
        step=bcast_tree(jnp.asarray(state.step)),
        params=bcast_tree(state.params),
        opt_state=bcast_tree(jax.tree_util.tree_map(
            jnp.asarray, state.opt_state)))


def averaged_view(state: TrainState) -> TrainState:
    """The cross-replica mean view for eval/reporting: PARAMS average
    over the replica dim (int leaves take replica 0); the opt state
    takes replica 0 unaveraged — no consumer reads it (eval uses
    params only) and element-wise-averaged Adam moments would not be
    a principled warm start anyway."""
    def mean0(x):
        x = jnp.asarray(x)
        if jnp.issubdtype(x.dtype, jnp.floating):
            return jnp.mean(x, axis=0)
        return x[0]

    return state.replace(
        step=state.step[0],
        params=jax.tree_util.tree_map(mean0, state.params),
        opt_state=jax.tree_util.tree_map(lambda o: jnp.asarray(o)[0],
                                         state.opt_state))


def make_local_sgd_train_step(mesh: Mesh, sync_every: int, seed: int = 0,
                              loss: Any = loss_fn,
                              batch_shardings: Any = None,
                              donate: bool = True,
                              grad_norm_metric: bool = False
                              ) -> Callable[[TrainState, Any],
                                            Tuple[TrainState, Dict]]:
    """Build the jitted local-SGD step (see module docstring).

    Consumes/produces the replica-stacked TrainState from
    ``stack_state``. Metrics are replica means every step. Parameters
    are averaged when ``(step + 1) % sync_every == 0``, so step counts
    H-1 local steps then a sync step, repeating."""
    if sync_every < 1:
        raise ValueError(f"sync_every must be >= 1, got {sync_every}")
    if batch_shardings is None:
        batch_shardings = default_batch_shardings(mesh)
    batch_specs = jax.tree_util.tree_map(
        lambda s: s.spec, batch_shardings,
        is_leaf=lambda s: isinstance(s, NamedSharding))

    def step(state: TrainState, batch: Any) -> Tuple[TrainState, Dict]:
        apply_fn, tx = state.apply_fn, state.tx

        def per_replica(params_s, opt_s, step_s, local_batch):
            params = jax.tree_util.tree_map(lambda p: p[0], params_s)
            opt = jax.tree_util.tree_map(lambda o: o[0], opt_s)
            stp = step_s[0]
            r = jax.lax.axis_index(AXIS_DATA)
            # Distinct dropout per replica per step — replicas must
            # diverge by data AND noise, like the reference's workers.
            dkey = jax.random.fold_in(prng.step_key(seed, stp), r)
            grad_fn = jax.value_and_grad(partial(loss, apply_fn),
                                         has_aux=True)
            (_, (metrics, _)), grads = grad_fn(params, {}, local_batch,
                                               dkey, True)
            if grad_norm_metric:
                import optax
                metrics = dict(metrics,
                               grad_norm=optax.global_norm(grads))
            updates, new_opt = tx.update(grads, opt, params)
            new_params = jax.tree_util.tree_map(
                lambda p, u: p + u.astype(p.dtype), params, updates)
            do_sync = (stp + 1) % sync_every == 0
            new_params = jax.lax.cond(
                do_sync,
                lambda p: jax.tree_util.tree_map(
                    lambda t: jax.lax.pmean(t, AXIS_DATA), p),
                lambda p: p, new_params)
            metrics = jax.tree_util.tree_map(
                lambda m: jax.lax.pmean(m, AXIS_DATA), metrics)
            restack = lambda t: jax.tree_util.tree_map(  # noqa: E731
                lambda x: x[None], t)
            return (restack(new_params), restack(new_opt),
                    (stp + 1)[None], metrics)

        new_params, new_opt, new_step, metrics = jax.shard_map(
            per_replica, mesh=mesh, axis_names={AXIS_DATA},
            in_specs=(P(AXIS_DATA), P(AXIS_DATA), P(AXIS_DATA),
                      batch_specs),
            out_specs=(P(AXIS_DATA), P(AXIS_DATA), P(AXIS_DATA), P()),
            check_vma=False)(state.params, state.opt_state, state.step,
                             batch)
        return state.replace(step=new_step, params=new_params,
                             opt_state=new_opt), metrics

    with mesh:
        from tensorflow_distributed_tpu.observe import (
            device as observe_device)
        return observe_device.instrument_jit(
            "local_sgd_step", step,
            in_shardings=(None, batch_shardings),
            donate_argnums=(0,) if donate else ())
