"""graftcheck's runtime layer: the ``--check`` mode.

The lint pass reads source and the jaxpr pass reads traces; this
module checks the two contracts only a LIVE run can check, cheaply
enough to leave on in CI runs and drills:

- **transfer guard**: the inner train/decode loops run under
  ``jax.transfer_guard("disallow")`` — any IMPLICIT host↔device
  transfer (a numpy array silently fed to a jitted call, a tracer
  coerced on host) raises at its source line instead of quietly
  serializing the pipeline every step. Explicit transfers
  (``jax.device_put`` / ``jax.device_get`` — everything the loop does
  on purpose) stay allowed.
- **sharding contract**: after the first optimizer step, every state
  leaf's ACTUAL sharding must still be the layout declared at state
  creation. GSPMD is free to propagate shardings through the step —
  that is the mechanism by which a missing ``with_sharding_constraint``
  silently re-lays-out the params (the exact bug class train/step.py's
  ZeRO-1 ``params_out_shardings`` exists to stop) — so the contract is
  asserted where drift would first appear, not assumed.

Wired into ``train/loop.py`` and ``serve/engine.py`` behind the
``--check`` flag (config.TrainConfig.check); zero cost when off.
"""

from __future__ import annotations

import contextlib
from typing import Any, Iterator

import jax


class ShardingContractError(AssertionError):
    """Actual leaf shardings drifted from the declared layout."""


def sharding_tree(tree: Any) -> Any:
    """The declared-layout snapshot: each leaf's live sharding."""
    return jax.tree_util.tree_map(
        lambda leaf: getattr(leaf, "sharding", None), tree)


def _describe(sharding: Any) -> str:
    spec = getattr(sharding, "spec", None)
    return str(spec) if spec is not None else str(sharding)


def sharding_spec_strings(tree: Any) -> dict:
    """``{"/"-joined leaf path: str(PartitionSpec)}`` for every sharded
    leaf — the serializable layout record the checkpoint layer writes
    into its mesh manifest (train/checkpoint.py), kept here so the
    contract checker and the manifest agree on how a layout is
    described."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None:
            key = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                           for k in path)
            out[key] = _describe(sharding)
    return out


def assert_sharding_contract(tree: Any, declared: Any,
                             what: str = "params") -> None:
    """Raise ShardingContractError listing every leaf whose actual
    sharding is not equivalent to the declared one.

    Equivalence, not equality: two shardings that place every element
    identically (``P()`` vs ``P(None)``) satisfy the contract.
    """
    mismatches = []

    def compare(path, leaf, want):
        have = getattr(leaf, "sharding", None)
        if want is None or have is None:
            return leaf
        ndim = getattr(leaf, "ndim", None)
        try:
            ok = (have.is_equivalent_to(want, ndim)
                  if ndim is not None else have == want)
        except (AttributeError, TypeError):
            ok = have == want
        if not ok:
            mismatches.append(
                f"  {jax.tree_util.keystr(path)}: declared "
                f"{_describe(want)}, actual {_describe(have)}")
        return leaf

    jax.tree_util.tree_map_with_path(compare, tree, declared)
    if mismatches:
        raise ShardingContractError(
            f"--check: {what} sharding drifted from the declared "
            f"layout after the first step ({len(mismatches)} "
            f"leaves):\n" + "\n".join(mismatches[:20])
            + ("\n  ..." if len(mismatches) > 20 else "")
            + "\n(a step function is missing a with_sharding_"
              "constraint, or an input reached it with the wrong "
              "placement)")


@contextlib.contextmanager
def transfer_guard(enabled: bool) -> Iterator[None]:
    """``jax.transfer_guard("disallow")`` when enabled; transparent
    otherwise — call sites wrap unconditionally and pass cfg.check."""
    if enabled:
        with jax.transfer_guard("disallow"):
            yield
    else:
        yield


@contextlib.contextmanager
def transfer_allowed(enabled: bool) -> Iterator[None]:
    """Re-allow transfers inside a guarded region; transparent when
    ``enabled`` is False (pass cfg.check: with --check off this must
    not override a user's own JAX_TRANSFER_GUARD setting). For the
    cold recovery paths only: a rewind's checkpoint restore
    legitimately performs implicit transfers (the restored leaves'
    placement, the finite-params verdict) — the guard exists to police the
    STEADY-STATE loop, and a recovery that crashes on its own restore
    would turn --check from a diagnostic into an outage."""
    if enabled:
        with jax.transfer_guard("allow"):
            yield
    else:
        yield
