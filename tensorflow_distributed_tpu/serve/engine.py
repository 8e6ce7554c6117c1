"""Slot-based continuous-batching decode engine.

One jitted single-token decode program runs over a fixed
``[num_slots, max_len]`` KV cache for the life of the process. Slots
are independently occupied and freed BETWEEN steps, so the request set
changes with zero recompilation:

- **insert**: a bucketed prefill program (one compile per bucket
  length, shared with generate()'s prefill via
  models.generate.prefill_cache) fills a fresh ``[1, max_len]`` cache
  row, and one jitted ``dynamic_update_slice`` per cache leaf drops it
  into the slot — the slot index is a traced scalar, so every slot
  uses the SAME program;
- **decode**: per-row positions (models/transformer.py writes each
  row's K/V at ITS position and masks attention past it) let slot 0
  sit at depth 700 while slot 3 is at depth 12 — one program, any
  mix of depths;
- **free**: host-side bookkeeping only. A freed slot keeps riding the
  batched step (static shapes), writing into its own row at position
  0 with its mask clamped to one column — garbage that the next
  insert's full-row overwrite replaces, and that no other row can
  attend (attention never crosses rows).

One cache, updated in place: every program that returns a cache
(insert, decode, verify, the poison drill; the paged engine's and the
draft speculator's likewise) DONATES the one it was handed, so XLA
aliases output to input and a step costs no second cache — in bytes
or in copying. The rule for callers: a cache object is dead after the
dispatch that took it; nothing may keep one across a call
(tests/test_serve_donation.py).

One decode step in flight: ``step()`` launches step N+1 from step N's
tokens ON THE DEVICE (the program selects, per slot, the previous
step's output or a token the host uploads) before step N ends, so the
device goes from one step straight into the next while the host wakes,
retires and comes back. It launches it LATE: when step N is about to
end by the engine's own clock of its own steps, not when it begins.
Until then the device's queue holds nothing behind the step that is
running, the wait for it is made in short slices, and between them a
hook (``on_wait``, the scheduler's) looks for arrivals: an admission it
makes there (``prefill(..., fetch=False)``) runs straight behind the
RUNNING step and not behind its successor. What lags by one step is
exact all the same: a slot that finished, was quarantined or changed
hands while a step was in flight is never handed that step's token
(``step_valid``), and nothing is in flight across a verify, a weight
swap, a poison drill or the end of a run (``drain``).

Greedy sampling only: the engine's contract (pinned in
tests/test_serve.py) is token-identical output to one-shot greedy
``generate()`` per request — continuous batching must not change
results.

Serve-under-fire surface (README "Serving under faults"; all optional,
zero cost unconfigured): the decode program carries a per-slot
finiteness flag (``take_bad_slots`` — the scheduler's quarantine
signal), ``poison_slot`` injects a genuinely-NaN KV row for drills,
``swap_params`` installs fresh weights between steps without draining
slots or recompiling (structure/shape/dtype/sharding asserted), the
token fetch runs under an optional decode watchdog, and ``warmup``
moves every program's first-dispatch cost out of the first requests'
TTFT.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from tensorflow_distributed_tpu.analysis import runtime as graftcheck
from tensorflow_distributed_tpu.models.generate import (
    decode_token, lookup_program, prefill_cache)
from tensorflow_distributed_tpu.observe import device as observe_device
from tensorflow_distributed_tpu.observe.trace import HostSpans
from tensorflow_distributed_tpu.ops import kv_attend
from tensorflow_distributed_tpu.serve.buckets import (
    default_buckets, pick_bucket)


@functools.lru_cache(maxsize=64)
def _compiled_prefill(model, bucket: int):
    """One jitted prefill program per (model, bucket length): prompt
    padded to ``bucket`` -> (cache row [1, max_len, ...], greedy first
    token from the TRUE last position). ``true_len`` is a traced
    scalar, so every prompt length sharing a bucket shares the
    executable. The padding's rows of a position-indexed leaf are
    harmless (the decode steps write over them before any attend
    reaches them); a leaf WITHOUT a position axis (a recurrent state)
    has no such excuse, so a family that keeps one is handed
    ``true_len`` and returns the state at it (``prefill_true_len``)."""

    def run(params, prompt, true_len):
        # What a family asks of its prefill program, by attribute: the
        # prompt's TRUE length (a recurrent state must be the state at
        # the last prompt token, not at the end of the padded bucket;
        # rows indexed by position do not care), and the one row of
        # logits the admission needs instead of [bucket, V].
        kw = ({"true_len": true_len}
              if getattr(model, "prefill_true_len", False) else {})
        if getattr(model, "last_logits_only", False):
            logits, cache = prefill_cache(model, params, prompt,
                                          logits_at=true_len - 1, **kw)
            last = logits[:, 0]
        else:
            logits, cache = prefill_cache(model, params, prompt, **kw)
            last = jax.lax.dynamic_index_in_dim(
                logits, true_len - 1, axis=1, keepdims=False)   # [1, V]
        return cache, jnp.argmax(last, axis=-1).astype(jnp.int32)

    return observe_device.instrument_jit(f"serve_prefill_b{bucket}", run)


@functools.lru_cache(maxsize=8)
def _compiled_verify(model, k: int):
    """THE speculative verify program: feed each slot's pending token
    plus its ``k`` proposals in ONE forward at positions
    ``pos .. pos + k`` (the decode cache path already writes per-row
    contiguous spans), take the greedy argmax at every fed position,
    and flag per-slot finiteness like the decode step. The host
    compares proposals against the argmax chain (speculate.
    accept_length) — everything emitted is the TARGET model's own
    greedy token, so speculation cannot change output, only how many
    tokens one dispatch yields. Fixed shapes per (model, k): one
    executable for the engine's lifetime, censused as ``serve_verify``
    in the jaxpr goldens."""

    def run(params, cache, toks, pos):
        # toks [S, k+1] (pending token + proposals), pos [S].
        positions = pos[:, None] + jnp.arange(k + 1)[None, :]
        logits, state = model.apply(
            {"params": params, "cache": cache}, toks, decode=True,
            positions=positions, mutable=["cache"])
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [S, k+1]
        ok = jnp.isfinite(logits).all(axis=(-1, -2))
        return state["cache"], nxt, ok

    return observe_device.instrument_jit(f"serve_verify_k{k}", run,
                                         donate_argnums=(1,))


def step_inputs(prev, host):
    """A decode step's ``(tok, pos)`` from what a launch hands it:
    ``prev`` [S], the previous step's token output, still on the
    device, and ``host`` [3, S] int32, the ONE upload of a launch: the
    host's tokens, the positions, and a mask of the slots whose token
    comes from the host (a fresh prefill, a free slot, a launch with
    nothing in flight). A slot not in the mask continues from ``prev``
    without the host having seen that token; positions always come from
    the host, which knows them without the fetch."""
    return jnp.where(host[2] != 0, host[0], prev), host[1]


def tokens_placement(params):
    """Where a decode step's token output is declared to live:
    replicated over the devices that hold ``params``. The step's next
    launch takes that output back as an argument, and the first launch
    takes a placeholder the engine uploads; jit keys its executables on
    an argument's placement (and on whether it was placed at all), so
    the two must agree EXACTLY or the first step launched ahead
    compiles the program a second time, inside the serving window (an
    8 s stall at GPT-2 large; my chip run, PR 29). Declared on the
    program's output and used for the placeholder, they agree by
    construction. None where nothing placed the parameters (fresh from
    ``init``: no output is placed either, and an uploaded placeholder
    already agrees) or their placement is of a kind this cannot read
    (the program then decides, as before)."""
    from jax.sharding import (
        NamedSharding, PartitionSpec, SingleDeviceSharding)

    leaves = jax.tree_util.tree_leaves(params)
    if not leaves or not getattr(leaves[0], "committed", False):
        return None
    sharding = leaves[0].sharding
    if isinstance(sharding, NamedSharding):
        return NamedSharding(sharding.mesh, PartitionSpec())
    return sharding if isinstance(sharding, SingleDeviceSharding) else None


def step_out_shardings(tokens_at, stats: bool) -> dict:
    """The jit keywords that pin a decode program's token output to
    ``tokens_at`` (:func:`tokens_placement`) and leave the cache, the
    flags and the counters to the compiler."""
    if tokens_at is None:
        return {}
    return {"out_shardings": (None, tokens_at, None)
            + ((None,) if stats else ())}


@functools.lru_cache(maxsize=8)
def _compiled_step(model, tokens_at=None):
    """THE decode program: one greedy token for every slot at its own
    depth (the token fed is chosen on the device, :func:`step_inputs`),
    plus a per-slot ``ok`` flag — logits fully finite. The flag
    is the engine's NaN containment sensor: a poisoned KV row (or a
    genuinely diverged slot) shows up HERE, on device, as part of the
    same program and the same host fetch, costing one row-wise
    reduction and zero extra transfers or collectives (census-pinned).
    Compiled once per (model, num_slots) — the shapes come from the
    arguments, so one engine reuses one executable forever."""

    def run(params, cache, prev, host):
        tok, pos = step_inputs(prev, host)
        last, cache = decode_token(model, params, cache, tok, pos)
        ok = jnp.isfinite(last).all(axis=-1)
        return cache, jnp.argmax(last, axis=-1).astype(jnp.int32), ok

    def run_with_stats(params, cache, prev, host):
        # A family that counts what a step did returns its ``stats``
        # collection (small integer arrays, whatever the model sowed) in
        # the step's one fetch; the engine only sums it over the run.
        tok, pos = step_inputs(prev, host)
        last, cache, stats = decode_token(model, params, cache, tok, pos,
                                          stats=True)
        ok = jnp.isfinite(last).all(axis=-1)
        # ONE buffer, whatever the model counts: each output buffer of
        # the step costs the host about 50 us in the fetch and as much
        # again at the next launch (7 more leaves: 0.7 ms an iteration
        # on a v5e; my chip run, PR 28)
        return (cache, jnp.argmax(last, axis=-1).astype(jnp.int32), ok,
                ravel_pytree(stats)[0])

    stats = bool(getattr(model, "decode_stats", False))
    return observe_device.instrument_jit(
        "serve_decode_step", run_with_stats if stats else run,
        donate_argnums=(1,), **step_out_shardings(tokens_at, stats))


def _insert_row_jit(cache, row, slot):
    """Drop a prefilled [1, ...] cache row into ``slot`` of the engine
    cache — ``slot`` is traced, so all slots share the program. Scalar
    leaves (the compat ``index``) pass through: positions are the
    authority on depth."""

    def put(c, r):
        if getattr(r, "ndim", 0) and r.shape[:1] == (1,):
            return jax.lax.dynamic_update_slice(
                c, r.astype(c.dtype), (slot,) + (0,) * (c.ndim - 1))
        return c

    return jax.tree_util.tree_map(put, cache, row)


_insert_row = observe_device.instrument_jit(
    "serve_insert_row", _insert_row_jit, donate_argnums=(0,))


@functools.partial(jax.jit, donate_argnums=(0,))
def _poison_row_jit(cache, slot):
    """NaN-fill the float leaves of ``slot``'s cache row (the slot_nan
    fault drill): the poison flows through the REAL attention math, so
    that slot's next logits are genuinely non-finite — exactly what a
    corrupted KV row or a diverged slot produces. ``slot`` is traced,
    so every slot shares one program; integer leaves (token ids, the
    compat index) pass through untouched."""

    def bad(c):
        if (getattr(c, "ndim", 0)
                and jnp.issubdtype(c.dtype, jnp.floating)):
            row = jnp.full((1,) + c.shape[1:], jnp.nan, c.dtype)
            return jax.lax.dynamic_update_slice(
                c, row, (slot,) + (0,) * (c.ndim - 1))
        return c

    return jax.tree_util.tree_map(bad, cache)


def tp_width(model) -> int:
    """The model's tensor-parallel width: the "model" axis of the mesh
    it was built on (1 when mesh-less or unsharded). The ONE derivation
    every piece of per-device serve arithmetic divides by."""
    mesh = getattr(model, "mesh", None)
    if mesh is None:
        return 1
    return int(dict(mesh.shape).get("model", 1))


def shard_cache(model, cache):
    """Place a decode-cache pytree for ``model``'s tensor-parallel
    mesh: the head axis (dim 2 of every [.., .., nk, dh] / [.., .., nk]
    leaf — dense rows, int8 scales, and the paged pool all put heads
    there) shards over "model"; scalar leaves (the compat ``index``)
    replicate. A no-op at TP width 1, so the single-device engine's
    arrays are untouched. One explicit placement here is what lets
    GSPMD keep every subsequent decode/insert/verify output in the
    same layout (asserted by the engine's first-step sharding
    contract)."""
    if tp_width(model) == 1:
        return cache
    from jax.sharding import NamedSharding, PartitionSpec

    mesh = model.mesh

    def put(c):
        spec = (PartitionSpec(None, None, "model")
                if getattr(c, "ndim", 0) >= 3 else PartitionSpec())
        return jax.device_put(c, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map(put, cache)


def zero_cache(model, params, num_slots: int):
    """A zeroed [num_slots, max_len, ...] decode-cache pytree for
    ``model``, shaped via eval_shape (no device work, no params
    flops). Shared by the engine and the draft speculator's mirrored
    cache (serve/speculate.py); int8 quantized caches come back with
    their scale leaves included. On a TP mesh the head axis comes back
    sharded over "model" (see :func:`shard_cache`)."""
    tok = jnp.zeros((num_slots, 1), jnp.int32)
    pos = jnp.zeros((num_slots, 1), jnp.int32)
    shapes = jax.eval_shape(
        lambda p, t, q: model.apply(
            {"params": p}, t, decode=True, positions=q,
            mutable=["cache"])[1]["cache"],
        params, tok, pos)
    return shard_cache(model, jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), shapes))


@dataclasses.dataclass
class _InFlight:
    """A decode step launched and not fetched yet: its outputs (device
    arrays) and ``rows``, the slots it was launched for that have not
    changed hands since. ``free`` clears a row; only a row still set at
    the fetch hands its token to the host."""

    no: int
    nxt: Any
    ok: Any
    stats: list
    rows: np.ndarray
    ahead: bool          # launched from the previous step's device tokens
    #: When it began on the device, by the engine's clock: its launch
    #: onto an idle device, moved to its predecessor's end where the
    #: host saw that end (a fetch that had to wait); None where it
    #: stands behind work whose end nobody fetches.
    began: Optional[float] = None


#: One slice of ``step()``'s wait for the step in flight, in seconds:
#: how late the engine sees that the step ended, and the scheduler's
#: hook an arrival. (A host without fine timers gives a millisecond for
#: it: ``_wait_to_launch`` measures what it gets, and near the deadline
#: it only yields the core.)
_WAIT_SLICE_S = 2e-4
#: The lead the device needs: a step enqueued less than about 2 ms
#: before its predecessor's end starts late, however short the host's
#: launch of it was (my chip runs, PR 45: Nemotron's launch is 1.1 ms
#: and its device waited 0.45, 0.09 and 0.02 ms a step for a successor
#: whose launch had returned 1.0, 1.6 and 2.2 ms before; GPT-2 large's
#: is 3.4 ms and one launch in nine, forty and none came too late with
#: 1.2, 2.1 and 3.0 ms). At 2.5 ms itself the device's wait inside the
#: fetch read 0.015, 0.030 and 0.031 ms a step for Nemotron, granite
#: and GPT-2 large, their parent's 0.013, 0.028 and 0.030 (call 9,
#: traced; PERF.md section 6). Read on ONE host and runtime, the dense
#: engine on one v5e chip, steps of 11-18 ms: the one term of the
#: margin the engine does not observe. Too short, it shows as idle
#: inside ``serve.token_fetch`` (``serve.idle_fetch_ms_per_step``);
#: where the margin reaches the step the launch is at once, as before
#: PR 45.
_LAUNCH_LEAD_S = 2.5e-3
#: What a new reading keeps of the recent worst launch, late slice and
#: early end where it is not worse itself: a stall of the host's is
#: forgotten in some tens of readings.
_WORST_KEEP = 0.9


def _is_ready(x) -> bool:
    """Has the device finished ``x``? A host array (what a test's fake
    program returns) always has."""
    ready = getattr(x, "is_ready", None)
    return ready is None or ready()


class EngineSurface:
    """What the scheduler (serve/scheduler.py) may call or read of its
    engine beyond what every engine supplies (``num_slots``, ``max_len``,
    ``buckets``, ``prefills``, ``prefill_compiles``, ``decode_steps``,
    ``fits``, ``free_slots``, ``occupancy``, ``prefill``, ``step``,
    ``free``; for drills and speculation ``poison_slot``, ``swap_params``,
    ``verify_step``), each with what a dense, synchronous, non-speculative
    engine gives. Three rules come with it:

    - Of a ``step()``'s return only the rows ``step_valid`` marks are
      their slots' tokens (None: every live row). An engine a step ahead
      computed the others for an owner that left or has yet to arrive.
    - A step launched and dropped (``drain``) is computed again from the
      same tokens: a model that keeps a recurrent state folds a token in
      ONCE (its ``state_pos``; :meth:`SlotDecodeEngine.drain`).
    - ``prefill(..., fetch=False)``, asked only from inside ``on_wait``
      and so only of an engine that calls it, returns nothing and owes a
      ``first_token()`` before the next ``step()``.
    """

    paged = False     # True: prefill takes max_new_tokens= and session=,
    #                   release retains the sequence it is given
    on_wait = None    # a run's hook, for a step()'s wait to call: True =
    #                   it dispatched an admission behind the step
    step_valid = spans = model = None
    set_spec_k = None                  # (k): retune a speculative engine
    spec_tokens = swaps = steps_ahead = ahead_rows_dropped = admits_first = 0
    # Positions the prefill programs were handed (their buckets) and those
    # of them that were prompt: what a family that counts its prefills'
    # work reports (``model.summarize_prefills``).
    prefill_bucket_positions = prefill_prompt_positions = 0
    tp_width = 1
    last_verify_fallback = ()          # the last verify's plain-path slots

    def release(self, slot, tokens=None, session=""):
        self.free(slot)

    def can_admit(self, prompt_len, max_new_tokens):      # room NOW?
        return True

    def reservation_fits(self, prompt_len, max_new_tokens):   # ... EVER?
        return True

    def take_bad_slots(self):          # live slots whose last step was NaN
        return []

    def drain(self):                   # leave no step in flight
        pass

    def can_verify(self):
        return False

    def verify_fallback_slots(self):   # None: plain step; []: full verify
        return [] if self.can_verify() else None

    def model_stats(self):             # both into serve_summary
        return {}

    def paging_stats(self):
        return {}

    def cache_bytes_per_slot(self):    # per device; None: no cache modelled
        return None


class SlotDecodeEngine(EngineSurface):
    """The slot cache + the programs (prefill/insert/step, plus the
    speculative verify when ``spec_tokens > 0``), with host-side slot
    bookkeeping. The scheduler (serve/scheduler.py) decides WHEN to
    prefill vs decode; this class owns WHAT runs on device."""

    # When the step ahead is launched (``step``). Class-level, so an
    # engine built without ``__init__`` (a test's, over fake programs)
    # has them: the admission dispatched and not fetched
    # (``prefill(fetch=False)``), the last step's time on the device and
    # the recent worst of what the launch must allow for, in seconds.
    # (``on_wait`` and ``admits_first``, the admissions that found no
    # decode step queued: :class:`EngineSurface`.)
    _admitting = None
    _clock = staticmethod(time.perf_counter)
    _sleep = staticmethod(time.sleep)
    _step_s: Optional[float] = None
    _launch_s = _late_s = _early_s = 0.0
    # The K/V leaves a decode step attends to each row's depth
    # (``_kv_attend_leaves``), and what the launches' attends covered.
    _kv_attends = None
    kv_attend_visited = kv_attend_seen = 0

    def __init__(self, model, params, num_slots: int,
                 buckets: Optional[Sequence[int]] = None,
                 min_bucket: int = 16, check: bool = False,
                 fault_plan=None, watchdog=None, spec_tokens: int = 0,
                 tracer=None):
        cfg = model.cfg
        if not cfg.causal:
            raise ValueError("SlotDecodeEngine needs a causal model")
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if spec_tokens < 0:
            raise ValueError(
                f"spec_tokens must be >= 0, got {spec_tokens}")
        self.spec_tokens = spec_tokens
        self.model = model
        self.params = params
        self._tokens_at = tokens_placement(params)
        self.num_slots = num_slots
        self.max_len = cfg.max_len
        self.buckets: Tuple[int, ...] = (
            tuple(buckets) if buckets
            else default_buckets(cfg.max_len, min_bucket,
                                 cap=cfg.max_len))
        if max(self.buckets) > cfg.max_len:
            raise ValueError(
                f"largest bucket {max(self.buckets)} exceeds the "
                f"model's max_len {cfg.max_len}")
        # Tensor parallelism: the width comes off the mesh the model
        # was built on — the engine itself has no TP knob. At width > 1
        # the cache's head axis is sharded over "model"
        # (shard_cache), per-device accounting divides by the width,
        # and the first-step sharding contract is ALWAYS armed (layout
        # drift under TP re-lays-out every subsequent step — too
        # expensive to leave to an opt-in flag).
        self.tp_width = tp_width(model)
        self.cache = self._zero_cache()
        self.tok = np.zeros((num_slots,), np.int32)
        self.pos = np.zeros((num_slots,), np.int32)
        self.active = np.zeros((num_slots,), bool)
        # Distinct prefill programs this engine has invoked — one per
        # bucket actually used, each a single compiled executable (the
        # bench asserts <= len(buckets)); generate.compile_cache_stats()
        # carries the process-wide hit/miss view.
        self._buckets_used: set = set()
        self.prefills = 0
        self.decode_steps = 0
        self.swaps = 0
        # One decode step ahead (module docstring): the step launched
        # and not fetched, the rows of the last step() that belong to
        # the slots' present owners, and how often it engaged.
        self._ahead: Optional[_InFlight] = None
        self._no_prev = None
        self.step_valid = np.zeros((num_slots,), bool)
        self.steps_ahead = 0
        self.ahead_rows_dropped = 0
        # Serve-under-fire hooks (both optional; zero cost when None):
        # the fault plan's decode_stall is consumed INSIDE the watched
        # token fetch so the decode watchdog sees exactly the hang a
        # wedged device produces, and _last_ok carries the decode
        # program's per-slot finiteness flags for take_bad_slots().
        self._plan = fault_plan
        self._watchdog = watchdog
        # The span seam (observe/trace.py): each phase of a dispatch —
        # upload, launch, blocking fetch — is one tfd.serve.* span on
        # the profiler's clock, in ``tracer``'s Chrome trace when
        # --observe.trace configured one, and in the always-on phase
        # totals. The scheduler shares this object.
        self.spans = HostSpans(
            chrome=tracer.tracer if tracer is not None else None)
        self._last_ok: Optional[np.ndarray] = None
        self._last_verify_fallback: list = []
        # What the decode program counted (a family with decode_stats),
        # summed over the run: the model's own tree, flat and opaque
        # here (its shapes are taken once, abstractly, to unflatten it).
        self._step_stats = None
        self._stats_shape = None
        if getattr(model, "decode_stats", False):
            vec = jax.ShapeDtypeStruct((num_slots,), jnp.int32)
            self._stats_shape = jax.eval_shape(
                lambda p, c, t, q: decode_token(model, p, c, t, q,
                                                stats=True)[2],
                self.params, self.cache, vec, vec)
        self._kv_attends = self._kv_attend_leaves()
        self._build_programs()
        self.verify_steps = 0
        # --check (graftcheck's runtime layer): the decode step runs
        # under jax.transfer_guard("disallow"), and the cache layout
        # after the first step is asserted against the layout the
        # cache was created with (analysis/runtime.py).
        self._check = check
        self._declared_cache = (graftcheck.sharding_tree(self.cache)
                                if check or self.tp_width > 1 else None)

    def _zero_cache(self):
        return zero_cache(self.model, self.params, self.num_slots)

    def _build_programs(self) -> None:
        """Bind the decode/verify executables. The paged subclass
        (serve/paging/engine.py) overrides this to bind the paged
        variants — same names, same one-program discipline, plus the
        page-table input."""
        self._step_fn = lookup_program(_compiled_step, self.model,
                                       self._tokens_at)
        self._verify_fn = (lookup_program(_compiled_verify, self.model,
                                          self.spec_tokens)
                           if self.spec_tokens else None)

    def set_spec_k(self, k: int) -> None:
        """Live speculation-depth change between decode steps — the
        autopilot's loop-3 actuator. Rebinds the verify executable at
        the new k through the same ``lookup_program`` cache the ctor
        used: a k this engine has already run is a dict hit; a new k
        pays its compile once, on the next verify dispatch. Safe with
        slots live — ``can_verify``/``verify_fallback_slots`` read
        ``spec_tokens`` per call for the headroom guard, and greedy
        verify is token-identical at any k by construction. Only an
        engine BUILT speculative can retune: k=0 engines compiled no
        verify program and the scheduler wires no speculator."""
        k = int(k)
        if k < 1:
            raise ValueError(f"spec k must be >= 1, got {k}")
        if not self.spec_tokens:
            raise ValueError(
                "set_spec_k needs an engine built with spec_tokens "
                "> 0 (a k=0 engine has no verify program to retune)")
        if k == self.spec_tokens:
            return
        self.spec_tokens = k
        self._build_programs()

    def _dispatch_step(self, prev, host):
        """One decode-program dispatch (the paged subclass appends the
        page tables); returns (cache, next tokens, per-slot ok).
        ``prev``, ``host``: :func:`step_inputs`."""
        with graftcheck.transfer_guard(self._check):
            return self._step_fn(self.params, self.cache, prev, host)

    def _dispatch_verify(self, tok, pos):
        """One verify-program dispatch (paged subclass: + tables)."""
        with graftcheck.transfer_guard(self._check):
            return self._verify_fn(self.params, self.cache, tok, pos)

    def _h2d(self, a):
        """Host->device upload of a guarded-dispatch input. At TP
        width 1 this is plain ``jnp.asarray``. Under TP the upload
        places explicitly REPLICATED on the engine's mesh: a bare
        asarray lands uncommitted on one device, and the compiled
        program's broadcast to the other shards would then be a
        device-to-device transfer INSIDE the transfer guard — tripping
        --check on the engine's own designed input path."""
        if self.tp_width == 1:
            return jnp.asarray(a)
        from jax.sharding import NamedSharding, PartitionSpec
        return jax.device_put(
            a, NamedSharding(self.model.mesh, PartitionSpec()))

    def cache_bytes_per_slot(self) -> int:
        """PER-DEVICE HBM the decode cache spends per slot (scale
        leaves of an int8 cache included) — the number the "choosing
        num_slots under an HBM budget" math divides by (README
        "Serving"; tests/test_serve_slo.py and test_serve_tp.py hold
        the int8 and TP ratios).
        Under TP every counted leaf is head-sharded over the "model"
        axis (shard_cache's placement), so each device holds
        ``1/tp_width`` of the logical bytes — the division below is
        exact, not an estimate, and collapses to a no-op at width 1."""
        total = sum(
            int(np.prod(c.shape)) * c.dtype.itemsize
            for c in jax.tree_util.tree_leaves(self.cache)
            if getattr(c, "ndim", 0)
            and c.shape[:1] == (self.num_slots,))
        return total // (self.num_slots * self.tp_width)

    def _kv_attend_leaves(self) -> Optional[Tuple[int, int, int]]:
        """(layers, max_len, positions a block) of the dense ``[slots,
        max_len, heads, head_dim]`` float key leaves a decode step
        attends (each with its value leaf), or None: a paged, windowed or
        int8 cache, a family with its own cache kinds (which counts its
        attends itself). The block is ``ops.kv_attend``'s for a leaf it
        takes, by shape and dtype whatever the backend (the unit its
        attends are counted in everywhere), and 0 for one it does not
        (``max_len`` not whole lane tiles): the XLA attend over the whole
        leaf, every slot's every position."""
        cfg = self.model.cfg
        if getattr(cfg, "kv_page_size", 0) or getattr(cfg, "attn_window", 0):
            return None
        keys = [c for path, c in
                jax.tree_util.tree_leaves_with_path(self.cache)
                if str(getattr(path[-1], "key", path[-1])) == "key"
                and c.ndim == 4 and c.shape[0] == self.num_slots
                and jnp.issubdtype(c.dtype, jnp.floating)]
        if not keys:
            return None
        shape, dtype = keys[0].shape, keys[0].dtype
        return (len(keys), shape[1], kv_attend.block(shape, dtype)
                if kv_attend.supported(shape, dtype) else 0)

    def _count_attends(self, pos: np.ndarray) -> None:
        """Fold one launch's attends into the run's counts, a layer: the
        cached positions the attends' blocks cover (a row at a position
        past 0 its blocks ``0 .. pos // block``, a free slot, at 0, none;
        without the kernel every slot's whole row) and those the rows'
        queries see (``pos + 1``). The host knows both at the launch."""
        if self._kv_attends is None:
            return
        layers, max_len, bt = self._kv_attends
        live = pos[pos > 0].astype(np.int64)
        self.kv_attend_visited += layers * (
            int(((live // bt + 1) * bt).sum()) if bt
            else self.num_slots * max_len)
        self.kv_attend_seen += layers * int((live + 1).sum())

    def cache_bytes_per_slot_by_kind(self) -> dict:
        """``cache_bytes_per_slot`` by KIND of leaf: the cache variable's
        name, whatever the leaf's axes after the slot axis are (rows a
        position: ``latent``, ``index_keys``, ``kv``; rows a pooled
        window: ``pooled_keys``; no position axis at all: ``state``, a
        fixed size whatever the depth, and the ``state_pos`` count it
        is stamped with; a ring of a convolution's last inputs, row
        ``position mod taps``: ``conv``, which a repeated step rewrites
        identically and which therefore needs no stamp; a ring of the
        last ``sliding_window`` rows of K and V, row ``position mod
        window``: ``kv_ring``, likewise)."""
        out: dict = {}
        for path, c in jax.tree_util.tree_leaves_with_path(self.cache):
            if getattr(c, "ndim", 0) and c.shape[:1] == (self.num_slots,):
                kind = str(getattr(path[-1], "key", path[-1]))
                out[kind] = out.get(kind, 0) + (
                    int(np.prod(c.shape)) * c.dtype.itemsize
                    // (self.num_slots * self.tp_width))
        return out

    @property
    def prefill_compiles(self) -> int:
        """Distinct prefill programs invoked (one per bucket used)."""
        return len(self._buckets_used)

    def warmup(self, speculator=None) -> None:
        """Dispatch every engine program once — each bucket's prefill,
        the row insert, the decode step (and the verify program when
        speculation is armed) — against throwaway inputs, then replace
        the cache they scribbled on with a zero one. First-dispatch
        cost (trace/compile or persistent-cache deserialize, ~hundreds
        of ms per program on this box) moves to startup instead of
        landing in the first requests' TTFT — and, under a restart,
        inside the recovery window. The programs run on the LIVE cache
        (each consumes the one it is handed; there is no spare to roll
        back to), which is then dropped BEFORE the zero cache is
        built: one cache alive at every point. Host bookkeeping is
        untouched, so a warmed engine equals a fresh one.

        ``speculator``: a draft-model speculator's mirror programs
        (its bucketed prefills, row insert, and the proposal scan) are
        warmed too via its own ``warmup()`` — without this, the FIRST
        speculative round paid the draft's compiles inside the serving
        wall (pinned by a compile-counter test in
        tests/test_serve_observe.py)."""
        self._warmup_guard()
        for b in self.buckets:
            fn = lookup_program(_compiled_prefill, self.model, b)
            row, _ = fn(self.params, jnp.zeros((1, b), jnp.int32),
                        jnp.asarray(1, jnp.int32))
            self.cache = _insert_row(self.cache, row,
                                     jnp.asarray(0, jnp.int32))
        self.cache, *_ = self._step_fn(self.params, self.cache,
                                       *self._step_args(None))
        if self._verify_fn is not None:
            self.cache, _, _ = self._verify_fn(
                self.params, self.cache,
                jnp.zeros((self.num_slots, self.spec_tokens + 1),
                          jnp.int32),
                jnp.zeros((self.num_slots,), jnp.int32))
        self._rezero_cache()
        warm = getattr(speculator, "warmup", None)
        if warm is not None:
            warm()

    def _warmup_guard(self) -> None:
        if self.prefills:
            raise RuntimeError(
                "warmup() zeroes the cache: call it before the first "
                "admission")

    def _rezero_cache(self) -> None:
        """Warmup's last step: drain the dispatches, drop the cache,
        THEN build the zero one — never two caches alive."""
        # graftcheck: disable=host-sync-in-loop -- startup-only drain
        # of the warmup dispatches; runs once per process, never in
        # the decode loop
        jax.block_until_ready(self.cache)
        self.cache = None
        self.cache = self._zero_cache()

    def free_slots(self):
        return [s for s in range(self.num_slots) if not self.active[s]]

    def occupancy(self) -> float:
        return float(self.active.sum()) / self.num_slots

    def fits(self, prompt_len: int, max_new_tokens: int) -> bool:
        """Would this request's full trajectory fit the cache?
        Deliberately WITHOUT speculative slack: a tightly-sized cache
        still serves every request — ``can_verify()`` makes the
        scheduler fall back to the plain decode step for the
        iterations where a slot lacks verify write headroom
        (serve/run.py sizes the default cache with ``spec_tokens`` of
        slack so that fallback stays rare)."""
        return (prompt_len <= max(self.buckets)
                and prompt_len + max_new_tokens <= self.max_len)

    def can_verify(self) -> bool:
        """Every active slot has verify write headroom (a continuation
        resumed onto a tightly-sized cache may not — those slots take
        the PLAIN path inside the verify dispatch instead; see
        :meth:`verify_fallback_slots`)."""
        if self._verify_fn is None:
            return False
        act = self.active
        return bool((self.pos[act] + self.spec_tokens + 1
                     <= self.max_len).all())

    def verify_fallback_slots(self) -> Optional[list]:
        """Which ACTIVE slots lack verify write headroom this
        iteration. ``None`` = speculation cannot run at all
        (``spec_tokens`` off, or a tight slot is too shallow to
        re-feed — the scheduler takes the whole-batch plain step);
        ``[]`` = full verify; a non-empty list = MIXED dispatch: the
        named slots take the plain path INSIDE the verify program
        (``verify_step``'s ``tails``) while every other slot
        speculates — one tight slot no longer costs the whole batch
        its speculation."""
        if self._verify_fn is None:
            return None
        k = self.spec_tokens
        out = []
        for s in range(self.num_slots):
            if not self.active[s]:
                continue
            if self.pos[s] + k + 1 <= self.max_len:
                continue
            if self.pos[s] < k:
                # Too shallow to re-feed a k-token window (only
                # possible when max_len < ~2k: a tiny user-pinned
                # cache) — whole-batch fallback keeps correctness.
                return None
            out.append(s)
        return out

    def verify_step(self, props: np.ndarray, tails=None
                    ) -> Tuple[np.ndarray, np.ndarray]:
        """One SPECULATIVE decode step: verify ``props``
        [num_slots, spec_tokens] draft proposals for every slot in one
        program dispatch. Returns ``(toks, acc)`` — ``toks``
        [num_slots, spec_tokens + 1] is the target model's greedy
        chain at each fed position and ``acc[s]`` how many of its
        leading entries slot ``s`` emits this step (accepted proposals
        + the bonus token); inactive rows are garbage the scheduler
        never reads. Rollback-on-reject is pure position bookkeeping:
        a rejected proposal's cache row sits PAST the slot's new
        authoritative position, and the next verify (or insert) writes
        over it before any attend can reach it — positions, not the
        cache, are the source of truth on depth.

        **Per-slot fallback** (``tails``): a slot named by
        :meth:`verify_fallback_slots` lacks ``pos + k + 1`` write
        headroom, so instead of proposals it is fed its OWN last ``k``
        accepted tokens plus the pending one at positions
        ``pos-k .. pos`` — deterministic re-computation rewrites
        bit-identical K/V over what the cache already holds (K/V at a
        position depend only on that position's token and the cache
        BELOW it, all unchanged), and the argmax at the LAST fed
        position is exactly the plain step's next token. Same program,
        same shapes, zero census drift; the slot retires 1 token
        (``acc == 1``, surfaced in ``toks[s, 0]``) while every other
        slot speculates. ``tails[s]`` must hold the slot's last
        ``k + 1`` history tokens (ending in the pending token — the
        scheduler's ``prompt + tokens`` tail). Which slots fell back
        this dispatch is readable at ``last_verify_fallback``."""
        from tensorflow_distributed_tpu.serve.speculate import (
            accept_length)
        if self._verify_fn is None:
            raise RuntimeError(
                "verify_step needs the engine built with "
                "spec_tokens > 0")
        k = self.spec_tokens
        # A verify cannot follow a step in flight: how far each slot
        # advances, so its next positions, comes with the fetch.
        self.drain()
        # graftcheck: disable=host-sync-in-loop -- normalizes the HOST
        # proposal array the speculator handed in; no device value
        props = np.asarray(props, np.int32).reshape(self.num_slots, k)
        tails = dict(tails or {})
        fallback = []
        start = self.pos.copy()
        toks_in = np.concatenate([self.tok[:, None], props], axis=1)
        for s in range(self.num_slots):
            if not self.active[s]:
                continue
            if self.pos[s] + k + 1 <= self.max_len:
                continue
            tail = tails.get(s)
            if tail is None or len(tail) < k + 1 or self.pos[s] < k:
                raise RuntimeError(
                    f"slot {s} lacks verify headroom and no usable "
                    f"tail was provided — verify_fallback_slots() is "
                    f"the guard (the scheduler supplies tails or "
                    f"falls back to step())")
            # graftcheck: disable=host-sync-in-loop -- normalizes the
            # HOST history tail the scheduler handed in (no device
            # value); only the rare headroom-starved slots
            window = np.asarray(list(tail)[-(k + 1):], np.int32)
            if window[-1] != self.tok[s]:
                raise RuntimeError(
                    f"slot {s} fallback tail must end in the pending "
                    f"token {int(self.tok[s])}, got {int(window[-1])}")
            toks_in[s] = window
            start[s] = self.pos[s] - k
            fallback.append(s)
        step_no = self.decode_steps + 1
        with self.spans.span("serve.verify_upload", step=step_no):
            tok, pos = self._h2d(toks_in), self._h2d(start)
        with self.spans.span("serve.verify_dispatch", step=step_no):
            self.cache, nxt, ok = self._dispatch_verify(tok, pos)

        def fetch():
            if self._plan:
                self._plan.decode_stall_sleep(step_no)
            # graftcheck: disable=host-sync-in-loop -- the engine's
            # OUTPUT, same contract as step(): ONE fetch per dispatch
            # (the [S, k+1] chain + per-slot ok flags) drives
            # acceptance, streaming, and NaN containment
            return jax.device_get((nxt, ok))

        with self.spans.span("serve.verify_fetch", step=step_no,
                             live=int(self.active.sum()),
                             fallback=len(fallback)):
            if (self._watchdog is not None
                    and self._watchdog.sync_timeout_s > 0):
                nxt, ok = self._watchdog.decode(fetch, step_no)
            else:
                nxt, ok = fetch()
        self._last_ok = ok
        # graftcheck: disable=host-sync-in-loop -- nxt is already the
        # fetched HOST array (the one watched fetch above); this is a
        # view, not a second sync
        nxt = np.asarray(nxt).copy()
        acc = np.zeros((self.num_slots,), np.int32)
        for s in range(self.num_slots):
            if not self.active[s]:
                continue
            if s in fallback:
                # Plain path inside the verify dispatch: the target's
                # next token sits at the LAST fed index (after the
                # pending token); surface it where the scheduler reads
                # retired tokens (toks[s, :acc]).
                nxt[s, 0] = nxt[s, k]
                acc[s] = 1
                self.tok[s] = nxt[s, 0]
                self.pos[s] += 1
                continue
            a = accept_length(props[s], nxt[s])
            acc[s] = a + 1                       # + the bonus token
            self.tok[s] = nxt[s, a]
            self.pos[s] += a + 1
        self.decode_steps += 1
        self.verify_steps += 1
        self._last_verify_fallback = fallback
        return nxt, acc

    @property
    def last_verify_fallback(self) -> list:
        """Slots that took the per-slot plain path in the most recent
        verify dispatch (the scheduler excludes them from speculation
        accounting)."""
        return list(self._last_verify_fallback)

    def prefill(self, prompt: np.ndarray, slot: int,
                fetch: bool = True) -> Optional[int]:
        """Admit a request into ``slot``: bucketed prefill, row insert,
        greedy first token. Returns the first generated token.

        Two halves, because dispatches are async: the DISPATCH of the
        two programs, and :meth:`first_token`, the fetch. With
        ``fetch=False`` only the first is made and nothing is returned:
        what the scheduler's hook does from inside a step's wait
        (``step``), where the programs go behind the step that is
        running, the caller fetches and retires that step while they
        run, and ``first_token()`` comes after, before the next
        ``step()``. ``admits_first`` counts the dispatches that found no
        decode step queued (an idle engine's too): the prefill is the
        next thing the device does."""
        # graftcheck: disable=host-sync-in-loop -- normalizes the HOST
        # prompt the scheduler handed in; no device value involved
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        plen = len(prompt)
        if plen < 1:
            raise ValueError("empty prompt")
        if self.active[slot]:
            raise ValueError(f"slot {slot} is occupied")
        bucket = pick_bucket(plen, self.buckets)
        # The launch is the host's share of an admission (pad, two
        # program dispatches); first_token's span is where it waits for
        # the device to have computed the prefill.
        with self.spans.span("serve.prefill_launch", bucket=bucket):
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :plen] = prompt
            fn = lookup_program(_compiled_prefill, self.model, bucket)
            self._buckets_used.add(bucket)
            row, first = fn(self.params, jnp.asarray(padded),
                            jnp.asarray(plen, jnp.int32))
            self.cache = _insert_row(self.cache, row,
                                     jnp.asarray(slot, jnp.int32))
        self.prefill_bucket_positions += bucket
        self.prefill_prompt_positions += plen
        self._dispatched(slot, plen, first)
        return self.first_token() if fetch else None

    def _dispatched(self, slot: int, plen: int, first) -> None:
        """An admission's programs are on the device's queue."""
        self.admits_first += self._ahead is None
        self._admitting = (slot, plen, first)

    def first_token(self) -> int:
        """The second half of an admission: fetch the first token of
        the prefill dispatched last, and hand the slot to its request."""
        slot, plen, first = self._admitting
        self._admitting = None
        with self.spans.span("serve.first_token_fetch"):
            # graftcheck: disable=host-sync-in-loop -- the TTFT point:
            # the first token must reach the host to be streamed; one
            # scalar per ADMISSION, not per decode step
            first_tok = int(jax.device_get(first)[0])
        self.tok[slot] = first_tok
        self.pos[slot] = plen
        self.active[slot] = True
        self.prefills += 1
        return first_tok

    def _step_args(self, prev: Optional[_InFlight]):
        """What a launch hands the decode program (:func:`step_inputs`):
        ``prev``'s tokens, on the device, and the one upload. A row
        ``prev`` still holds continues from its token one position on;
        every other slot (fresh from a prefill, free, or all of them
        with nothing in flight) enters with the host's token at the
        host's position."""
        if prev is None:
            if self._no_prev is None:
                # Placed as the program's own token output is
                # (tokens_placement): one executable for both.
                zeros = np.zeros((self.num_slots,), np.int32)
                self._no_prev = (
                    self._h2d(zeros) if self._tokens_at is None
                    else jax.device_put(zeros, self._tokens_at))
            return self._no_prev, self._h2d(np.stack(
                [self.tok, self.pos, np.ones_like(self.tok)]))
        return prev.nxt, self._h2d(np.stack(
            [self.tok, self.pos + prev.rows, ~prev.rows]))

    def _can_follow(self, prev: _InFlight) -> bool:
        """May a step be launched from ``prev``'s un-fetched tokens? Its
        inputs are then known without the fetch (the plain greedy step's
        always are); it needs a live slot to compute for, and room: the
        row a finishing request's last step leaves at ``max_len`` is
        the scheduler's to free before anything is launched for it."""
        act = self.active
        return bool(act.any() and (
            (self.pos + prev.rows)[act] < self.max_len).all())

    def _launch(self, prev: Optional[_InFlight]) -> _InFlight:
        """Dispatch one decode step, from ``prev``'s tokens on the
        device or (``None``) from the host's, and do not wait for it."""
        step_no = self.decode_steps + (1 if prev is None else 2)
        t_in = self._clock()
        # Host->device conversion of the slot scalars stays OUTSIDE the
        # transfer guard: this one tiny explicit upload is the engine's
        # designed input path.
        with self.spans.span("serve.step_upload", step=step_no):
            if (prev is None
                    and (self.pos[self.active] >= self.max_len).any()):
                raise RuntimeError(
                    "an active slot is at max_len — the scheduler "
                    "admitted a request that cannot fit (fits() is "
                    "the guard)")
            args = self._step_args(prev)
            # the positions the step is handed (``_step_args``)
            self._count_attends(
                self.pos if prev is None else self.pos + prev.rows)
        with self.spans.span("serve.step_dispatch", step=step_no,
                             ahead=int(prev is not None)):
            self.cache, nxt, ok, *stats = self._dispatch_step(*args)
        now = self._clock()
        self._launch_s = max(now - t_in, _WORST_KEEP * self._launch_s)
        if self._declared_cache is not None and step_no == 1:
            # First decode step: the cache must come back in the
            # layout it was created with — sharding drift here
            # re-lays-out every subsequent step. Armed by --check, and
            # ALWAYS under TP (a drifted head shard silently
            # re-gathers the cache every step).
            graftcheck.assert_sharding_contract(
                self.cache, self._declared_cache, what="decode cache")
        return _InFlight(step_no, nxt, ok, stats, self.active.copy(),
                         ahead=prev is not None, began=now)

    def _launch_due(self, cur: _InFlight) -> Optional[float]:
        """When ``cur``'s successor has to be launched for the device to
        find it queued as ``cur`` ends: the end the engine expects, less
        a margin. The end: when ``cur`` began, and as long as the last
        step took whose begin and end the host saw (steps grow with the
        live rows and their depths: the last one, not a constant). The
        margin: the recent worst launch (``serve.step_upload`` +
        ``serve.step_dispatch``: the host's time to get a step onto the
        device's queue), the lead the device needs after it
        (``_LAUNCH_LEAD_S``), and the most a recent step ended before
        it was expected to. None without a hook to
        look while waiting, or with nothing to reckon from (a run's
        first step, the steps after a ``drain``, a step behind one that
        is not fetched): launch at once."""
        if (self.on_wait is None or cur.began is None
                or self._step_s is None):
            return None
        return (cur.began + self._step_s
                - self._launch_s - _LAUNCH_LEAD_S - self._early_s)

    def _wait_to_launch(self, cur: _InFlight, due: float) -> bool:
        """Wait, in slices, until ``due`` (``_launch_due``) or ``cur``
        has ended, with nothing queued behind ``cur``, and let the hook
        look between the slices. True: the hook dispatched an admission
        behind ``cur``, and no successor is to follow it."""
        now = self._clock()
        while now < due and not _is_ready(cur.nxt):
            if self.on_wait():
                return True
            # A slice is a sleep while the deadline is further off than
            # a sleep has lately come back late, twice over; nearer, it
            # only yields the core.
            nap = (_WAIT_SLICE_S
                   if now + _WAIT_SLICE_S + 2.0 * self._late_s < due
                   else 0.0)
            self._sleep(nap)
            then, now = now, self._clock()
            if nap:
                self._late_s = max(now - then - nap,
                                   _WORST_KEEP * self._late_s)
        return False

    def step(self) -> np.ndarray:
        """One decode step over every slot; returns the [num_slots]
        next-token array. Only the rows ``step_valid`` marks are their
        slots' tokens: an inactive slot's entry is garbage, and so is
        that of a slot admitted while this step was already in flight
        (its first decoded token comes with the next one).

        Runs ONE step ahead, launched LATE: the step returned (N) was
        launched by the previous call (or here, with nothing in
        flight), and its successor is launched from its un-fetched
        tokens when N is about to end (``_launch_due``), not when it
        begins. The device still goes from one into the other while the
        host wakes from the fetch, retires and comes back; but until
        then nothing is queued behind N, the wait is made in slices,
        and ``on_wait`` (the scheduler's hook) looks for arrivals
        between them. An admission it dispatches there
        (``prefill(fetch=False)``) goes behind the RUNNING step: no
        successor is launched, the caller retires N while the prefill
        runs, fetches the first token (``first_token``), and the next
        call launches N+1 from the host's tokens, the new row in it. An
        arrival later than the launch finds N+1 queued and goes behind
        it. Without a hook, or with nothing to reckon N's end from,
        the successor is launched at once.

        What the host learns one step late costs a row-step, never a
        token: a request that ended (EOS, budget), a quarantined slot
        and a preempted one each leave one row of the step in flight
        to be dropped (``free`` clears it; ``ahead_rows_dropped``).
        What that row did to the cache is replaced wholesale by the
        slot's next insert, which the cache's data dependency orders
        after it: the rows it wrote past the depth ``pos`` declares,
        and equally a recurrent state it moved (a leaf with no position
        axis: the insert overwrites the state and the count of tokens
        it holds together)."""
        if self._admitting is not None:
            raise RuntimeError(
                "an admission was dispatched and not fetched: "
                "first_token() comes before the next step()")
        cur, self._ahead = self._ahead, None
        if cur is None or not cur.rows.any():
            # Nothing in flight, or every row of it changed hands since
            # the launch: nothing of that step is anyone's, so it is
            # not fetched, and the launch here is ordered after it by
            # the cache it produced.
            unfetched = cur is not None
            cur = self._launch(None)
            if unfetched:
                cur.began = None
        step_no = cur.no

        def fetch():
            # An injected decode_stall sleeps here, INSIDE the watched
            # region, so the watchdog sees exactly the hang a wedged
            # device would produce.
            if self._plan:
                self._plan.decode_stall_sleep(step_no)
            # graftcheck: disable=host-sync-in-loop -- the engine's
            # OUTPUT: tokens + per-slot ok flags must land on host
            # every step for EOS/budget termination, streaming, and
            # NaN containment; ONE [num_slots] fetch per step is the
            # contract. The NEXT step is launched by now (or a prefill
            # stands in its place), so this wait ends when step_no's
            # program does and the device does not idle behind it
            # (tfd.serve.* spans; PERF.md section 3)
            return jax.device_get((cur.nxt, cur.ok, cur.stats))

        with self.spans.span("serve.token_fetch", step=step_no,
                             live=int(cur.rows.sum())):
            # The wait for the launch is bounded by the step's expected
            # end, so it needs no watchdog; the fetch is watched.
            due = self._launch_due(cur)
            by_clock = due is not None and self._clock() < due
            if (not (by_clock and self._wait_to_launch(cur, due))
                    and self._can_follow(cur)):
                self._ahead = self._launch(cur)
            waits = not _is_ready(cur.nxt)
            if (self._watchdog is not None
                    and self._watchdog.sync_timeout_s > 0):
                nxt, ok, stats = self._watchdog.decode(fetch, step_no)
            else:
                nxt, ok, stats = fetch()
        if waits:
            # The fetch had to wait, so it returned as the step ended:
            # the host saw when, which is also when a successor queued
            # behind it began.
            ended = self._clock()
            if cur.began is not None:
                if self._step_s is not None:
                    self._early_s = max(
                        cur.began + self._step_s - ended,
                        _WORST_KEEP * self._early_s)
                self._step_s = ended - cur.began
            if self._ahead is not None:
                self._ahead.began = ended
        elif by_clock and self._ahead is not None:
            # The successor was launched by the clock and the step had
            # ended all the same: the clock is wrong. (A fetch the host
            # came back late from reads as a longer step and a later
            # begin, and would make every launch after it late with
            # nothing left to time a step by.) Launch at once until a
            # step is timed again.
            self._step_s = None
        valid = cur.rows
        # A row that changed hands is nobody's: neither its token nor
        # its flag (take_bad_slots) reaches the slot's new owner.
        self._last_ok = ok | ~valid
        self.step_valid = valid
        if stats:
            self._count_step(stats[0])
        self.tok[valid] = nxt[valid]
        self.pos[valid] += 1
        self.decode_steps += 1
        self.steps_ahead += cur.ahead
        return nxt

    def drain(self) -> None:
        """Leave nothing in flight: wait for the step launched ahead and
        DROP it. The host's ``tok``/``pos`` never moved for it, so the
        next launch computes the same step again from them. That is
        exact for whatever a cache leaf is: the rows the dropped step
        wrote at position ``pos`` are written again identically; a
        leaf with no position axis (a recurrent state the step folded
        the token into) cannot be written twice, so the MODEL that keeps
        one stamps it with the number of tokens it holds, folds a token
        only at that count and reads the state either way
        (models/minicpm_sala.py, models/granitemoehybrid.py and
        models/nemotron_h.py ``state_pos``; the convolution ring of the
        latter two is indexed by position and is simply written again;
        an expert layer keeps nothing): the step computed again
        finds the token already in and leaves logits and state as one
        undisturbed step does (tests/test_minicpm_sala.py). Called where
        the next dispatch is not a plain step from those tokens: before
        a verify (its positions come with the fetch), a weight swap (the
        step after it runs the new weights, as between synchronous
        steps), a poison drill (the next step retired sees it), and by
        the scheduler at the end of a run."""
        ahead, self._ahead = self._ahead, None
        # What follows is not the steps timed so far (new weights, a
        # verify between, another run): the next steps are launched at
        # once until one has been timed again.
        self._step_s = None
        if ahead is None:
            return
        self.ahead_rows_dropped += int(ahead.rows.sum())
        with self.spans.span("serve.drain", step=ahead.no):
            # graftcheck: disable=host-sync-in-loop -- waits out the
            # ONE step in flight at a swap, a drill or the run's end;
            # never in the decode loop
            jax.block_until_ready(ahead.nxt)

    def _count_step(self, flat) -> None:
        """Fold one decode step's counters (a host array already: it
        came with the step's fetch) into the run's, in int64."""
        flat = flat.astype(np.int64)
        self._step_stats = (flat if self._step_stats is None
                            else self._step_stats + flat)

    def model_stats(self) -> dict:
        """What ``serve_summary`` carries for a family whose decode
        program counts (empty for the others): the cache's bytes a slot
        by kind of leaf, the held experts' plan of the decode step and
        of each bucket (``model.moe_plan``, static by shape, a family
        with routed experts), the model's own summary of the counters
        the engine summed (``model.summarize_stats``) and of the positions
        its prefills were handed (``model.summarize_prefills``)."""
        if not getattr(self.model, "decode_stats", False):
            # The dense transformer counts nothing on the device; what
            # its decode attends covered the host counted at each launch.
            return ({} if self._kv_attends is None else {
                "kv_attend_positions_visited": self.kv_attend_visited,
                "kv_attend_positions_seen": self.kv_attend_seen})
        out = {"cache_bytes_per_slot_by_kind":
               self.cache_bytes_per_slot_by_kind()}
        plan = getattr(self.model, "moe_plan", None)
        if plan is not None:
            out["moe_plan"] = plan(self.num_slots, self.buckets)
        if self._step_stats is not None:
            leaves, treedef = jax.tree_util.tree_flatten(self._stats_shape)
            cuts = np.cumsum([int(np.prod(x.shape)) for x in leaves])[:-1]
            totals = jax.tree_util.tree_unflatten(treedef, [
                part.reshape(x.shape) for part, x in zip(
                    np.split(self._step_stats, cuts), leaves)])
            out.update(self.model.summarize_stats(totals,
                                                  self.decode_steps))
        prefills = getattr(self.model, "summarize_prefills", None)
        if prefills is not None:
            out.update(prefills(self.prefill_bucket_positions,
                                self.prefill_prompt_positions))
        return out

    def free(self, slot: int) -> None:
        """Release a slot (host bookkeeping only; the row's stale cache
        is replaced wholesale by the next insert)."""
        self.active[slot] = False
        self.tok[slot] = 0
        self.pos[slot] = 0
        ahead = self._ahead
        if ahead is not None and ahead.rows[slot]:
            # The step in flight computed a token for the owner that
            # just left: dropped at its fetch, whoever holds the slot
            # by then.
            ahead.rows[slot] = False
            self.ahead_rows_dropped += 1

    # -- serve-under-fire surface (scheduler-facing) ----------------------

    def take_bad_slots(self):
        """ACTIVE slots whose last decode step produced non-finite
        logits — the containment signal the scheduler acts on
        (quarantine + re-prefill of ONLY those slots). Rides the decode
        program's per-slot ok flags; no extra device work. Inactive
        rows are excluded by construction: a freed slot's stale NaN row
        keeps flagging until the next insert overwrites it, and that is
        garbage nobody reads."""
        if self._last_ok is None:
            return []
        return [s for s in range(self.num_slots)
                if self.active[s] and not self._last_ok[s]]

    def poison_slot(self, slot: int) -> None:
        """slot_nan fault drill: NaN-fill ``slot``'s KV-cache row ON
        DEVICE, so the next decode step's logits for that slot are
        genuinely non-finite through the real attention math (not a
        spoofed flag). A step in flight ran before the poison: it is
        dropped (``drain``), so the NEXT step retired is the one that
        reads it, on the drill's own step clock."""
        if not 0 <= slot < self.num_slots:
            raise ValueError(
                f"slot_nan slot {slot} out of range [0, "
                f"{self.num_slots})")
        floats = sum(
            1 for c in jax.tree_util.tree_leaves(self.cache)
            if getattr(c, "ndim", 0)
            and jnp.issubdtype(c.dtype, jnp.floating))
        if not floats:
            raise ValueError(
                "slot_nan: the decode cache has no float leaves to "
                "poison")
        self.drain()
        self.cache = _poison_row_jit(self.cache,
                                     jnp.asarray(slot, jnp.int32))

    def swap_params(self, new_params) -> None:
        """LIVE WEIGHT SWAP: replace the serving params between decode
        steps without draining slots or recompiling. The contract that
        makes this safe — identical tree structure, leaf shapes/dtypes,
        and sharding layout — is asserted here (shapes/dtypes by direct
        comparison, placement via the graftcheck sharding-contract
        checker), because any mismatch would silently retrace the hot
        decode program instead of hitting its jit cache. In-flight KV
        caches are untouched: swapping to the same checkpoint is
        token-identical by construction (pinned in
        tests/test_serve_fire.py)."""
        if (jax.tree_util.tree_structure(new_params)
                != jax.tree_util.tree_structure(self.params)):
            raise ValueError(
                "live weight swap: new params tree structure differs "
                "from the serving params (different architecture?)")
        mismatches = []

        def cmp(path, old, new):
            if (getattr(old, "shape", None) != getattr(new, "shape",
                                                       None)
                    or getattr(old, "dtype", None) != getattr(
                        new, "dtype", None)):
                mismatches.append(
                    f"  {jax.tree_util.keystr(path)}: "
                    f"{getattr(old, 'shape', '?')}/"
                    f"{getattr(old, 'dtype', '?')} -> "
                    f"{getattr(new, 'shape', '?')}/"
                    f"{getattr(new, 'dtype', '?')}")
            return old

        jax.tree_util.tree_map_with_path(cmp, self.params, new_params)
        if mismatches:
            raise ValueError(
                "live weight swap: leaf shape/dtype drift (the hot "
                "decode program would retrace):\n"
                + "\n".join(mismatches[:10]))
        graftcheck.assert_sharding_contract(
            new_params, graftcheck.sharding_tree(self.params),
            what="swapped params")
        # The step in flight ran the old weights: dropped, so the swap
        # falls between two steps as it always did.
        self.drain()
        self.params = new_params
        self.swaps += 1
