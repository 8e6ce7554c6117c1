"""Multi-step runner: K train steps per host dispatch.

The reference paid a full host->runtime round-trip per step (feed_dict
+ sess.run, SURVEY.md N14) and so did our plain loop — one dispatch,
one batch transfer, one step. On TPU the idiomatic fix is to move the
loop onto the device: stack K batches, ship them in one transfer, and
``lax.scan`` the train step K times inside one jitted program. Host
work (and PCIe latency) amortizes K-fold; XLA overlaps the next
scan iteration's data slice with compute.

Composes with the ``preprocess`` hook so the transfer can carry raw
uint8 pixels (4x fewer bytes than f32) and normalization runs on
device — move bytes, not floats.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tensorflow_distributed_tpu.observe import device as observe_device
from tensorflow_distributed_tpu.train.state import TrainState
from tensorflow_distributed_tpu.train.step import (
    LossFn, Metrics, default_batch_shardings, loss_fn, make_train_step)


def stacked_batch_shardings(mesh: Mesh, batch_shardings: Any = None) -> Any:
    """Shift each batch sharding right one dim for the leading K dim."""
    if batch_shardings is None:
        batch_shardings = default_batch_shardings(mesh)
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, P(None, *s.spec)), batch_shardings)


def make_multi_step(mesh: Mesh, seed: int = 0, loss: LossFn = loss_fn,
                    batch_shardings: Any = None,
                    preprocess: Optional[Callable[[Any], Any]] = None,
                    accum_steps: int = 1,
                    health_every: int = 0,
                    grad_sync: str = "implicit",
                    state_template: Any = None,
                    grad_sync_bucket_bytes: int = 0,
                    grad_sync_min_size: int = 0,
                    grad_clip_norm: float = 0.0
                    ) -> Callable[[TrainState, Any],
                                  Tuple[TrainState, Metrics]]:
    """Build ``fn(state, stacked_batches) -> (state, metrics_of_last)``.

    ``stacked_batches`` leaves carry a leading K dim (any K; one compile
    per K). ``preprocess`` runs on-device on each scanned slice before
    the step (e.g. u8 -> f32 normalize). ``health_every`` threads the
    per-module health cadence into the inner step (train.step); the
    returned metrics being the LAST scanned step's, a cadence that
    divides K reports the vitals of that dispatch's final step.
    ``grad_sync`` != "implicit" scans the EXPLICIT collective step
    (parallel.overlap; needs ``state_template`` like train.step's
    dispatch) — the bucketed reduce-scatter/all-gather schedule runs
    inside every scan iteration, so K on-device steps keep the same
    overlap window a dispatched-per-step loop gets.
    """
    base = make_train_step(mesh, seed=seed, loss=loss,
                           batch_shardings=batch_shardings,
                           accum_steps=accum_steps, jit=False,
                           health_every=health_every,
                           grad_sync=grad_sync,
                           state_template=state_template,
                           grad_sync_bucket_bytes=grad_sync_bucket_bytes,
                           grad_sync_min_size=grad_sync_min_size,
                           grad_clip_norm=grad_clip_norm)

    def run(state: TrainState, batches: Any) -> Tuple[TrainState, Metrics]:
        def body(s, b):
            if preprocess is not None:
                b = preprocess(b)
            return base(s, b)

        state, metrics = jax.lax.scan(body, state, batches)
        # Last step's metrics: enough for cadence logging, and keeps the
        # output transfer O(1) in K.
        return state, jax.tree_util.tree_map(lambda m: m[-1], metrics)

    with mesh:
        return observe_device.instrument_jit(
            "multi_step", run,
            in_shardings=(None, stacked_batch_shardings(mesh,
                                                        batch_shardings)),
            donate_argnums=(0,),
        )
