"""Admission + prefill/decode interleaving for the slot engine.

Policy: **decode-priority with a starvation bound**: at least
``decode_priority`` decode iterations between two admissions. The
clock counts EVERY decode iteration retired since the last admission,
whoever is or is not waiting, so a request that comes due on an engine
that has decoded that many iterations since it last admitted, with a
slot free, is admitted in the iteration it comes due; under a burst
of arrivals the live rows still decode ``decode_priority`` times
between one admission and the next, and the request the policy would
admit next waits at most that many iterations with a slot free
(``queue_steps``; capacity waits don't count against the policy). An
idle engine admits immediately.

One ``run()`` is a :class:`_Run`, its state, stepped by ``_iterate``
through the phases the spans name: ``_poll``, then ``_admit`` and
``_admitted`` (the two halves of every admission), or ``_dispatch``,
``_retire`` and ``_tail``. What the engine owes it, and it the engine,
is :class:`~.engine.EngineSurface`.

The loop looks for arrivals at its top, and INSIDE the engine's wait
for the decode step in flight (``_on_wait``, the hook the engine calls
between the slices of that wait while no successor is queued behind
the step: serve/engine.py ``step``). An admission made there is the
same admission by the same rule, the step in flight counting as a
decode iteration since the last one; its prefill is dispatched behind
the RUNNING step (``_admit(behind=True)``), the step is retired while
it runs, and the first token is fetched after (``_admitted``).

Admission order is the **policy** knob:

- ``fifo`` (default): arrival order, the original behavior.
- ``slo``: SLO classes (``high`` > ``standard`` > ``batch``) pick the
  admitted request — a high-class arrival never queues behind a
  lower class while a slot frees (pinned in tests/test_serve_slo.py).
  Two more levers ride the class order:

  * **per-tenant token quotas** (``tenant_quota``): a tenant at/over
    its decoded-token quota is DEFERRED while any under-quota request
    waits — requeued behind, never dropped, and still served when
    nothing under-quota is waiting (work-conserving, so exhaustion
    cannot starve).
  * **preempt-and-requeue** (``preempt``): when a higher-class
    request has waited ``decode_priority`` decode iterations with no
    free slot (a wait of the queue's own, not the admission clock: an
    arrival never evicts on sight), the worst live lower-class (or
    over-quota) request is
    preempted — freed and re-queued as a CONTINUATION (prompt +
    tokens-so-far, remaining budget; the PR-6 machinery, so it is
    journal-compatible) — and greedy determinism makes its final
    stream token-identical to the unpreempted run.

**Speculative decoding** (``speculator`` + an engine built with
``spec_tokens > 0``): each decode iteration proposes k tokens per
slot (serve/speculate.py) and retires ``accepted + 1`` of them from
ONE verify dispatch — token-identical to plain greedy, with
accepted-length telemetry in the summary (``accept_rate``). Falls
back to the plain step whenever a slot lacks verify headroom.

Termination is per request (EOS or its max-token budget), tokens
stream to the host as they retire (``on_token``), and every request's
lifecycle lands in the observe registry: ``serve_request`` records
(TTFT, per-token latency, queue steps, class/tenant, and where both
times went by kind of scheduler iteration: ``wait_ms``, ``prefill_ms``,
``decode_ms``, ``admits_endured``) plus one final
``serve_summary`` (aggregate tokens/s, mean slot occupancy, accept
rate, preemptions, the wall by host phase and by kind of iteration) —
summarized by ``observe.report`` next to the training numbers.

Serve-under-fire (all optional; zero cost unconfigured):

- **fault plan**: consulted between decode steps on the engine's
  decode-step clock — slot_nan poisons a KV row, reload triggers a
  live weight swap, sigterm/sigkill self-signal (resilience/faults.py;
  decode_stall is consumed inside the engine's watched fetch).
- **slot-level retry**: a slot whose decode step produced non-finite
  logits is quarantined — freed and its request re-queued at the head
  as a CONTINUATION (prompt + the good tokens so far, remaining
  budget) — so one poisoned slot costs one re-prefill, never an
  engine restart, and greedy determinism keeps the final token stream
  identical. A per-request retry budget (``slot_retries``) turns
  repeated quarantine of the SAME request into
  :class:`SlotRetryExhausted` — the serve-mode divergence signal
  (exit 2; the supervisor does not hot-loop restarts on it).
- **journal**: admits/tokens/completions append to a
  :class:`serve.journal.RequestJournal`, flushed per scheduler
  iteration, so a SIGKILL'd leg is resumable at token granularity.
- **live weight swap**: ``reload_fn`` (serve/run.py wires it to
  train.checkpoint.restore_params) supplies fresh params; the engine
  swaps them in between steps with slots live; swap latency lands in
  the summary and a ``weight_swap`` recovery event.

Serve observatory (README "Serve tracing & SLO monitoring"; all
optional, zero cost unconfigured):

- **tracer** (observe/serve_trace.py): every request becomes an async
  span tree in one Perfetto trace (queue -> prefill -> decode),
  quarantine/swap/preempt drop instant markers, and counter tracks
  carry occupancy/queue/tokens-per-s/accept-rate per decode step.
- **slo_monitor** (observe/slo.py): per-completion window accounting
  + per-step multi-window burn-rate evaluation on the decode-step
  clock; ``slo_alert``/``slo_ok`` records flow through the registry.
- **metrics_snapshot() / export**: a point-in-time JSON-able view of
  the engine (queue depth, occupancy, rolling tokens/s, per-class
  TTFT percentiles, SLO budget state), emitted as
  ``metrics_snapshot`` records on ``export_every`` and atomically
  rewritten at ``export_path`` for a router/supervisor to poll.
- **status_fn/status_every**: the periodic one-line live status
  print.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from tensorflow_distributed_tpu.utils.atomicio import atomic_write_json
from tensorflow_distributed_tpu.observe.slo import percentile
from tensorflow_distributed_tpu.observe.trace import HostSpans
from tensorflow_distributed_tpu.serve.buckets import pick_bucket
from tensorflow_distributed_tpu.serve.engine import SlotDecodeEngine

#: SLO classes, best first — admission under policy="slo" prefers the
#: lowest rank; everything else (request files without a class, the
#: synthetic default) is "standard".
SLO_CLASSES = ("high", "standard", "batch")
_RANK = {c: i for i, c in enumerate(SLO_CLASSES)}

#: The KIND of scheduler iteration a host span belongs to, for
#: ``HostSpans.elapsed_by``: an admission (nothing live advances while
#: one runs), a decode iteration, and ``other`` for the rest (poll,
#: tail, a sleeping engine, any span not named here). The three tile
#: the serving wall as ``phase_ms`` does.
ITER_KINDS = ("admit", "step", "other")
_ITER_KIND = {
    **dict.fromkeys(("serve.admit", "serve.prefill_launch",
                     "serve.first_token_fetch"), "admit"),
    **dict.fromkeys(("serve.step_upload", "serve.step_dispatch",
                     "serve.token_fetch", "serve.propose",
                     "serve.verify_upload", "serve.verify_dispatch",
                     "serve.verify_fetch", "serve.drain",
                     "serve.retire"), "step")}


def parse_slo_mix(spec: str) -> Dict[str, float]:
    """``--serve.slo-mix`` grammar: ``"high:0.25,batch:0.25"`` —
    class:fraction pairs, remainder implicitly "standard". Returns the
    full {class: fraction} map (standard filled in)."""
    out: Dict[str, float] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if ":" not in part:
            raise ValueError(
                f"slo_mix entry {part!r} is not class:fraction")
        name, frac = (x.strip() for x in part.split(":", 1))
        if name not in SLO_CLASSES:
            raise ValueError(
                f"unknown SLO class {name!r}; have {SLO_CLASSES}")
        f = float(frac)
        if not 0.0 <= f <= 1.0:
            raise ValueError(
                f"slo_mix fraction for {name!r} must be in [0, 1], "
                f"got {f}")
        if name in out:
            raise ValueError(f"slo_mix names {name!r} twice")
        out[name] = f
    rest = 1.0 - sum(out.values())
    if rest < -1e-9:
        raise ValueError(
            f"slo_mix fractions sum to {sum(out.values()):g} > 1")
    out["standard"] = out.get("standard", 0.0) + max(rest, 0.0)
    return out


class SlotRetryExhausted(RuntimeError):
    """The same request was slot-quarantined past its retry budget —
    serve mode's DIVERGED equivalent (deterministic greedy decode will
    poison the same way again; restarting would hot-loop). The CLI
    maps this to exit code 2, which the supervisor refuses to
    restart."""


@dataclasses.dataclass
class Request:
    """One inference request. ``arrival_s`` is the open-loop offset
    (seconds from run start) at which the request becomes visible to
    the scheduler; 0 = present from the start. ``slo``/``tenant``
    drive the SLO scheduler (policy="slo"); FIFO ignores them."""

    rid: int
    prompt: np.ndarray
    max_new_tokens: int
    eos_id: int = -1          # -1 = run to the full budget
    arrival_s: float = 0.0
    slo: str = "standard"     # high | standard | batch
    tenant: str = ""          # quota bucket (policy="slo")
    # Multi-turn conversation id (serve/paging): a finished request
    # tagged with a session retains its KV pages under this key, and
    # a follow-up turn whose prompt extends the conversation
    # re-attaches them instead of re-prefilling. Journaled with the
    # admit record, so a resumed leg keeps the linkage. Ignored by
    # the dense engine (turns still serve correctly — they just
    # recompute).
    session: str = ""
    # The scheduler's and the journal replay's own, never a caller's
    # (and left behind by ``dataclasses.replace``): decode iterations
    # waited while admittable; a continuation's tokens from before it
    # (journal replay, slot retry, SLO preemption: the completion
    # reports them first); whether those are policy's, a preemption's,
    # and not a recovery's.
    _waited: int = dataclasses.field(
        default=0, init=False, repr=False, compare=False)
    _base_tokens: Sequence[int] = dataclasses.field(
        default=(), init=False, repr=False, compare=False)
    _policy_base: bool = dataclasses.field(
        default=False, init=False, repr=False, compare=False)


@dataclasses.dataclass
class Completion:
    """A finished request with its serving metrics."""

    rid: int
    prompt_len: int
    tokens: List[int]
    finish: str               # "eos" | "length"
    ttft_s: float             # arrival -> first token (queue + prefill)
    decode_s: float           # first token -> last token
    queue_steps: int          # decode steps endured while admittable
    retries: int = 0          # slot quarantines this request survived
    preempts: int = 0         # SLO preemptions this request survived
    slo: str = "standard"
    tenant: str = ""
    recovery_window: bool = False  # a recovery event (quarantine/
    #                                swap/restart continuation) fell
    #                                inside arrival->first token —
    #                                the report's p99-TTFT-during-
    #                                recovery population
    decoded: int = 0          # tokens decoded THIS leg (excludes a
    #                           continuation's journal-replayed base —
    #                           those were decoded by the dead leg)

    @property
    def tok_ms(self) -> float:
        """Mean inter-token latency (ms) over THIS leg's decode phase
        (a continuation's base tokens were decoded by the dead leg —
        charging them here would deflate the latency)."""
        n = self.decoded or len(self.tokens)
        return 1e3 * self.decode_s / max(1, n - 1)


@dataclasses.dataclass
class _Live:
    req: Request
    slot: int
    tokens: List[int]
    t_first: float
    queue_steps: int
    base: List[int]           # the request's ``_base_tokens``


def _parts_ms(then: List[float], now_: List[float]) -> Dict[str, float]:
    """The milliseconds between two reads of :meth:`_Run.clocks`, by
    kind of iteration."""
    return {k: round(1e3 * (b - a), 3)
            for k, a, b in zip(ITER_KINDS, then, now_)}


class _Run:
    """The state of one ``run()``: what the phases of an iteration hand
    each other, and what ``metrics_snapshot`` reads (the scheduler
    keeps it after the run returns)."""

    def __init__(self, clock, spans: HostSpans,
                 requests: Sequence[Request]):
        self.clock, self.spans = clock, spans
        self.pending = collections.deque(
            sorted(requests, key=lambda r: (r.arrival_s, r.rid)))
        self.queue: List[Request] = []
        self.live: Dict[int, _Live] = {}      # slot -> _Live
        self.done: List[Completion] = []
        self.t0 = clock()
        # Decode iterations retired since the last admission (the
        # admission clock), and of those the ones a request waited
        # through (the SLO preemption branch's wait).
        self.steps_since_admit = 0
        self.waited_since_admit = 0
        self.admitted_at_once = 0     # admissions with queue_steps 0
        self.retries: dict = {}       # rid -> quarantines survived
        self.preempts: dict = {}      # rid -> SLO preemptions survived
        self.first_seen: dict = {}    # rid -> first-token time (the
        #                               TTFT point survives retries)
        self.tenant_tokens: Dict[str, int] = {}  # decoded this run
        self.total_retries = self.total_preempts = 0
        self.spec_stats = {"verify_steps": 0, "proposed": 0,
                           "accepted": 0, "fallback_slots": 0}
        # Quarantine/swap times, for the recovery-window TTFT flag.
        self.recovery_ts: List[float] = []
        spans.start_run()
        self.admit_ms: dict = {}      # rid -> wall of its first admit
        # Where a request's milliseconds went (serve_request.wait_ms,
        # .decode_ms, .admits_endured): rid -> what the seam's running
        # clocks read at its events — taken from pending ("due"), its
        # first admission's start (the difference, kept as "wait_ms")
        # and its first token ("first"); _finish takes the last.
        self.marks: Dict[int, Dict[str, Any]] = {}
        # The kind of the iteration before this one: what ran while a
        # request taken from pending NOW was coming due.
        self.last_iter = "other"
        # Session turn-ordering applies only when some request carries
        # a session id — a plain workload must not pay a per-iteration
        # scan of pending+queue+live for a constraint that cannot bind.
        self.has_sessions = any(r.session for r in requests)
        # THIS run's decode-step tallies (the engine counters span its
        # whole lifetime — reuse would skew the occupancy mean) plus
        # the decoded-token count.
        self.tally = {"steps": 0, "occ_sum": 0.0, "decoded": 0}
        # Rolling (t, decoded) samples for the tokens/s counter track
        # and the snapshot's windowed rate.
        self.rate_win: collections.deque = collections.deque(maxlen=64)
        # Rolling (t, accepted_cum, proposed_cum) samples: the
        # windowed accept rate beside the cumulative one — a regime
        # shift in acceptance is invisible to any controller reading
        # only the lifetime ratio.
        self.spec_win: collections.deque = collections.deque(maxlen=64)
        # Feed items read inside a step's wait and left for the loop's
        # top (a command, and whatever the file holds after it), and
        # when the file was last read there.
        self.fed: collections.deque = collections.deque()
        self.fed_at = 0.0
        # The admission ``_on_wait`` dispatched behind the step in
        # flight (what ``_admitted`` takes), for the iteration to
        # finish once that step is retired.
        self.behind: Optional[tuple] = None

    def now(self) -> float:
        return self.clock() - self.t0

    def clocks(self) -> List[float]:
        """Seconds of this run by kind of iteration so far (open
        spans' parts included), then the admissions closed."""
        by, admits = self.spans.elapsed_by(_ITER_KIND, "other",
                                           count="serve.admit")
        return [by[k] for k in ITER_KINDS] + [admits]


class Scheduler:
    """Drives a :class:`SlotDecodeEngine` (any
    :class:`~.engine.EngineSurface`) over a request workload."""

    def __init__(self, engine: SlotDecodeEngine, decode_priority: int = 1,
                 registry=None,
                 on_token: Optional[Callable[[int, int, bool], None]] = None,
                 clock=time.perf_counter, fault_plan=None, journal=None,
                 reload_fn=None, slot_retries: int = 2,
                 summary_extra=None, policy: str = "fifo",
                 tenant_quota: int = 0, preempt: bool = True,
                 speculator=None, tracer=None, slo_monitor=None,
                 anomaly_hub=None, autopilot=None,
                 export_every: float = 0.0, export_path: str = "",
                 status_fn=None, status_every: int = 0,
                 feed=None, served_ckpt_step=None):
        if decode_priority < 1:
            raise ValueError(
                f"decode_priority must be >= 1, got {decode_priority}")
        if slot_retries < 0:
            raise ValueError(
                f"slot_retries must be >= 0, got {slot_retries}")
        if policy not in ("fifo", "slo"):
            raise ValueError(
                f"unknown policy {policy!r}; have ('fifo', 'slo')")
        if tenant_quota < 0:
            raise ValueError(
                f"tenant_quota must be >= 0, got {tenant_quota}")
        self.engine = engine
        self.decode_priority = decode_priority
        self.registry = registry
        self.on_token = on_token
        self.clock = clock
        self.fault_plan = fault_plan
        self.journal = journal
        self.reload_fn = reload_fn    # () -> (params, ckpt_step)
        self.slot_retries = slot_retries
        self.policy = policy
        self.tenant_quota = tenant_quota
        self.preempt = preempt
        self.speculator = speculator
        # The serve observatory (observe/serve_trace.py + observe/
        # slo.py + snapshot export): every hook below is None-safe so
        # an unobserved run pays nothing.
        self.tracer = tracer
        # The span seam (observe/trace.py), shared with the engine so
        # one iteration's phases — poll, admit, the engine's upload /
        # dispatch / fetch, retire, tail — tile it in one PhaseTotals
        # and one vocabulary. An engine without one (the test fakes)
        # leaves only the scheduler's own phases.
        self.spans: HostSpans = (
            engine.spans if engine.spans is not None else HostSpans(
                chrome=tracer.tracer if tracer is not None else None))
        self.slo_monitor = slo_monitor
        # Incident detection (observe/anomaly.py): fed the TTFT /
        # decode-dispatch-wall / queue-depth values this loop already
        # holds on host, on the deterministic decode-step clock.
        self.anomaly_hub = anomaly_hub
        # The online controller (observe/autopilot.py): consulted on
        # the decode-step clock; its decisions come back as "tune"
        # commands through the SAME control path fleet drain/swap/
        # cancel commands take, so every actuation lands between
        # decode steps and token identity holds by construction.
        self.autopilot = autopilot
        # Effective live-slot cap, tunable below the engine's
        # allocated num_slots (loop 2: fewer live slots pin fewer
        # pages). 0 = uncapped.
        self._slot_cap = 0
        self._tunes = 0
        if autopilot is not None:
            autopilot.bind_scheduler(
                num_slots=int(engine.num_slots),
                spec_k=int(engine.spec_tokens),
                decode_priority=decode_priority,
                has_spec=speculator is not None)
        if export_every < 0:
            raise ValueError(
                f"export_every must be >= 0, got {export_every}")
        self.export_every = float(export_every)
        self.export_path = export_path
        self.status_fn = status_fn
        self.status_every = int(status_every)
        # Streaming intake (fleet/replica.py InboxFeed, or any object
        # with poll() -> (requests, commands)): with a feed, run()
        # serves an OPEN-ENDED stream — it keeps polling for work and
        # control commands ("swap"/"drain"/"cancel"/"hold_export")
        # until a drain command lands and the engine runs dry.
        self.feed = feed
        # The checkpoint step the served weights came from (run.py
        # sets it from the startup restore; _swap updates it) — the
        # fleet controller's model-staleness feed.
        self.served_ckpt_step = served_ckpt_step
        self.draining = False
        self._export_hold_until = 0.0
        # Monotonic snapshot sequence + wall timestamp + pid: a
        # poller can tell a FROZEN snapshot file (stale seq) from a
        # healthy idle replica (seq keeps advancing) — the fleet
        # router's liveness probe.
        self._snap_seq = 0
        # Run-identity fields (seed, trace name) merged into the
        # serve_summary RECORD so the JSONL artifact is reproducible
        # standalone (a reader can re-derive the workload from it).
        self.summary_extra = dict(summary_extra or {})
        # The run in progress, or the last one. The loop's own methods
        # are handed it; what is called from OUTSIDE the loop with no
        # run to hand (a poller's ``metrics_snapshot``, the engine's
        # ``_on_wait``) reads it here.
        self._state: Optional[_Run] = None

    def _emit(self, event: str, **fields) -> None:
        if self.registry is not None:
            self.registry.emit(event, **fields)

    def _trace_instant(self, name: str, **args) -> None:
        if self.tracer is not None:
            self.tracer.instant(name, **args)

    # -- SLO selection helpers -------------------------------------------

    def _over_quota(self, tenant: str, tenant_tokens: Dict[str, int]
                    ) -> bool:
        return (self.tenant_quota > 0
                and tenant_tokens.get(tenant, 0) >= self.tenant_quota)

    def _pick_index(self, queue: List[Request],
                    tenant_tokens: Dict[str, int],
                    skip: frozenset = frozenset()) -> int:
        """Which queued request admits next. FIFO: the head. SLO:
        under-quota before over-quota (deferral, never starvation —
        over-quota requests win when nothing else waits), then class
        rank, then arrival order. ``skip``: rids NOT admissible this
        iteration (session turns waiting on an earlier turn); -1 when
        nothing qualifies."""
        if self.policy != "slo" or len(queue) <= 1:
            if not skip:
                return 0
            for i, req in enumerate(queue):
                if req.rid not in skip:
                    return i
            return -1
        best, best_key = -1, None
        for i, req in enumerate(queue):
            if req.rid in skip:
                continue
            key = (1 if self._over_quota(req.tenant, tenant_tokens)
                   else 0, _RANK.get(req.slo, 1), i)
            if best_key is None or key < best_key:
                best, best_key = i, key
        return best

    @staticmethod
    def _session_blocked(pending, queue, live) -> frozenset:
        """Queued rids whose session has an EARLIER unfinished turn —
        a client cannot send turn j+1 before it has turn j's reply, so
        those arrivals wait for their predecessor (which also makes
        the paged engine's session re-attach deterministic instead of
        an interleaving-dependent cache miss). Requests without a
        session are never blocked."""
        earliest: Dict[str, int] = {}
        for r in list(pending) + list(queue) + [
                lv.req for lv in live.values()]:
            s = r.session
            if s and (s not in earliest or r.rid < earliest[s]):
                earliest[s] = r.rid
        return frozenset(r.rid for r in queue
                         if r.session and earliest[r.session] != r.rid)

    def _pick_unblocked(self, run: _Run) -> int:
        """The policy's pick among the queued requests no earlier turn
        of their session stands before (-1: every one is blocked)."""
        return self._pick_index(
            run.queue, run.tenant_tokens,
            skip=(self._session_blocked(run.pending, run.queue, run.live)
                  if run.has_sessions else frozenset()))

    def _pick_victim(self, live: Dict[int, _Live], cand: Request,
                     tenant_tokens: Dict[str, int]) -> Optional[_Live]:
        """The live request SLO preemption evicts for ``cand``:
        strictly lower class (or over-quota while cand's tenant is
        under) — among those, the lowest class with the most tokens
        already delivered (it loses the least). None = nobody
        preemptible (equal-class work is never evicted — that would
        just swap places and thrash). A victim whose continuation
        prompt would outgrow the bucket ladder is skipped too:
        preemption is ELECTIVE, and crashing the run over a
        user-pinned tight --serve.buckets would turn policy into
        failure (quarantine keeps the loud error — its slot is
        unrecoverable either way)."""
        cand_rank = _RANK.get(cand.slo, 1)
        cand_over = self._over_quota(cand.tenant, tenant_tokens)
        ladder = max(self.engine.buckets)
        victims = []
        for lv in live.values():
            lower = _RANK.get(lv.req.slo, 1) > cand_rank
            quota_evict = (not cand_over and self._over_quota(
                lv.req.tenant, tenant_tokens)
                and lv.req.tenant != cand.tenant)
            fits_ladder = (len(lv.req.prompt) + len(lv.tokens)
                           <= ladder)
            if (lower or quota_evict) and fits_ladder:
                victims.append(lv)
        if not victims:
            return None
        return max(victims, key=lambda lv: (_RANK.get(lv.req.slo, 1),
                                            len(lv.tokens)))

    # -- a run: its state, one iteration, its summary -------------------

    def run(self, requests: Sequence[Request]) -> List[Completion]:
        """Serve every request to completion; returns completions in
        finish order (sort by ``rid`` for submission order)."""
        if self.speculator is None:
            # The engine's for the run (a verify never follows a step
            # in flight; an engine whose step() waits for nothing never
            # calls it).
            self.engine.on_wait = self._on_wait
        try:
            return self._run(requests)
        finally:
            self.engine.on_wait = None    # it reaches the run's queues

    def _run(self, requests: Sequence[Request]) -> List[Completion]:
        eng = self.engine
        for r in requests:
            if not eng.fits(len(r.prompt), r.max_new_tokens):
                raise ValueError(
                    f"request {r.rid}: prompt {len(r.prompt)} + "
                    f"{r.max_new_tokens} new tokens does not fit "
                    f"(buckets up to {max(eng.buckets)}, max_len "
                    f"{eng.max_len})")
            if r.max_new_tokens < 1:
                raise ValueError(
                    f"request {r.rid}: max_new_tokens must be >= 1")
        self._swap_seconds = 0.0
        run = self._state = _Run(self.clock, self.spans, requests)
        self._last_export = run.t0
        while run.pending or run.queue or run.live or (
                self.feed is not None and not self.draining):
            self._iterate(run)
        # The step the engine launched ahead of the last retire was
        # computed for requests that have all finished: nothing stays
        # in flight past a run.
        eng.drain()
        self._summarize(run)
        return run.done

    def _iterate(self, run: _Run) -> None:
        """One scheduler iteration, in the phases its spans name: poll,
        then an admission, or a decode dispatch, its retirement, the
        admission ``_on_wait`` put behind it, and the tail."""
        run.spans.step = run.tally["steps"] + 1
        pick = self._poll(run)
        if pick is None:
            return
        if pick >= 0:
            self._admitted(run, *self._admit(run, pick))
            return
        flight = self._dispatch(run)
        run.last_iter = "step"
        self._retire(run, *flight)
        if run.behind is not None:
            self._admitted(run, *run.behind)
            run.behind = None
        self._tail(run)

    def _release(self, run: _Run, lv: _Live, retain: bool) -> None:
        """``lv`` leaves its slot. A paged engine RETAINS what it is
        handed (``retain``: the request's full sequence feeds the
        prefix cache / its session; quarantine passes False — poisoned
        pages must never be cached); for any other ``release`` is the
        plain free."""
        if retain and self.engine.paged:
            # graftcheck: disable=host-sync-in-loop -- builds the
            # retention token list from HOST arrays (no device
            # value); once per request lifetime event
            self.engine.release(
                lv.slot,
                tokens=[int(t) for t in lv.req.prompt] + lv.tokens,
                session=lv.req.session)
        else:
            self.engine.release(lv.slot)
        del run.live[lv.slot]
        if self.speculator is not None:
            self.speculator.observe_free(lv.slot)

    def _finish(self, run: _Run, lv: _Live, why: str) -> None:
        t = run.now()
        self._release(run, lv, retain=True)
        tokens = lv.base + lv.tokens
        t_first = run.first_seen.get(lv.req.rid, lv.t_first)
        n_retries = run.retries.get(lv.req.rid, 0)
        n_preempts = run.preempts.get(lv.req.rid, 0)
        # Recovery population: a quarantine/swap fell inside this
        # request's arrival->first-token window, OR the request is
        # a restart continuation (its base tokens crossed a
        # process death — the resumed leg consumed the plan, so
        # recovery_ts alone would miss exactly the requests the
        # restart hit). A PREEMPTION continuation's base is policy,
        # not recovery — excluded.
        window = (any(lv.req.arrival_s <= rt <= t_first
                      for rt in run.recovery_ts)
                  or (bool(lv.base) and not lv.req._policy_base))
        mark = run.marks.pop(lv.req.rid, {})
        at_end = run.clocks()
        at_first = mark.get("first", at_end)
        comp = Completion(
            rid=lv.req.rid,
            prompt_len=len(lv.req.prompt) - len(lv.base),
            tokens=tokens, finish=why,
            ttft_s=t_first - lv.req.arrival_s,
            decode_s=t - t_first, queue_steps=lv.queue_steps,
            retries=n_retries, preempts=n_preempts,
            slo=lv.req.slo, tenant=lv.req.tenant,
            recovery_window=window,
            decoded=len(lv.tokens))
        run.done.append(comp)
        if self.slo_monitor is not None:
            self.slo_monitor.observe(comp.slo, 1e3 * comp.ttft_s,
                                     comp.tok_ms, run.tally["steps"])
        if self.anomaly_hub is not None:
            self.anomaly_hub.observe_completion(
                run.tally["steps"], 1e3 * comp.ttft_s)
        if self.tracer is not None:
            self.tracer.request_done(comp.rid, why, len(comp.tokens),
                                     1e3 * comp.ttft_s)
        self._emit("serve_request", rid=comp.rid,
                   prompt_len=comp.prompt_len,
                   new_tokens=len(comp.tokens), finish=why,
                   ttft_ms=round(1e3 * comp.ttft_s, 3),
                   tok_ms=round(comp.tok_ms, 4),
                   queue_steps=comp.queue_steps,
                   prefill_ms=run.admit_ms.pop(comp.rid, None),
                   wait_ms=mark.get("wait_ms"),
                   decode_ms=_parts_ms(at_first, at_end),
                   # its own first admission closed after its
                   # first token: not one it endured
                   admits_endured=max(0, at_end[3] - at_first[3] - 1),
                   retries=n_retries, preempts=n_preempts,
                   slo=comp.slo, tenant=comp.tenant,
                   recovery_window=window,
                   arrival_s=round(lv.req.arrival_s, 4),
                   t_first_s=round(t_first, 4))
        if self.journal is not None:
            self.journal.done(comp.rid)
        if self.on_token is not None:
            self.on_token(comp.rid, comp.tokens[-1], True)

    @staticmethod
    def _count_token(run: _Run, req: Request) -> None:
        if req.tenant:
            run.tenant_tokens[req.tenant] = (
                run.tenant_tokens.get(req.tenant, 0) + 1)

    def _admit(self, run: _Run, pick: int, behind: bool = False) -> tuple:
        """The first half of admitting ``run.queue[pick]`` into a free
        slot: its wait ends. Returns what :meth:`_admitted`, the second
        half, takes. At the loop's top that follows at once and makes
        the prefill; ``behind`` (from inside the engine's wait for the
        step in flight, ``_on_wait``) the prefill is DISPATCHED here,
        behind that step, and ``_admitted`` fetches its first token
        once that step is retired."""
        eng = self.engine
        req = run.queue.pop(pick)
        run.admitted_at_once += not req._waited
        slot = eng.free_slots()[0]
        bucket = pick_bucket(len(req.prompt), eng.buckets)
        mark = run.marks.get(req.rid)
        at_start = run.clocks()
        if mark is not None and "due" in mark:
            # Its FIRST admission starts: the wait is over.
            at_due, kind, late_s = mark.pop("due")
            wait = _parts_ms(at_due, at_start)
            wait[kind] = round(wait[kind] + 1e3 * late_s, 3)
            mark["wait_ms"] = wait
        if self.autopilot is not None:
            # One host int per admission: the prompt-length
            # distribution the bucket/num-pages advisories size
            # from.
            self.autopilot.observe_prompt(len(req.prompt))
        span_args = dict(rid=req.rid, slot=slot, bucket=bucket,
                         prompt_len=len(req.prompt), live=len(run.live),
                         queue=len(run.queue))
        traced = contextlib.ExitStack()
        if self.tracer is not None:
            traced.enter_context(
                self.tracer.prefill(req.rid, bucket, slot))
        kw = {}
        if eng.paged:
            # Admission context the paged engine needs: the budget
            # sizes its page reservation, the session keys
            # conversation re-attach.
            kw.update(max_new_tokens=req.max_new_tokens,
                      session=req.session)
        first = functools.partial(eng.prefill, req.prompt, slot, **kw)
        if behind:
            first(fetch=False)
            first = eng.first_token
        return req, slot, span_args, traced, at_start, first

    def _admitted(self, run: _Run, req: Request, slot: int,
                  span_args: dict, traced, at_start: List[float],
                  first) -> None:
        """The second half of an admission, under ``tfd.serve.admit``:
        ``first`` makes the prefill, or fetches the first token of
        one dispatched already, and the slot is the request's. The
        ``prefill_ms`` runs from the first half's start, on the
        seam's clock: the prefill and what was ahead of it on the
        device, which adds up to TTFT with ``wait_ms`` (a
        continuation's re-prefill is not what the client waited
        for its first token on)."""
        with run.spans.span("serve.admit", **span_args):
            with traced:
                first = first()
            lv = self._admit_into(run, req, slot, first)
            if self.journal is not None:
                self.journal.flush()
            at_end = run.clocks()
        run.admit_ms.setdefault(req.rid, round(sum(_parts_ms(
            at_start, at_end).values()), 3))
        if lv.tokens[0] == req.eos_id or req.max_new_tokens == 1:
            with run.spans.span("serve.retire", live=len(run.live)):
                self._finish(run, lv, "eos" if lv.tokens[0] == req.eos_id
                             else "length")
        # The admission clock starts anew. (Behind a step, this runs
        # once that step is retired: it was launched before the
        # admission, and the clock counts none since.)
        run.steps_since_admit = run.waited_since_admit = 0
        run.last_iter = "admit"

    def _admit_into(self, run: _Run, req: Request, slot: int,
                    first: int) -> _Live:
        run.tally["decoded"] += 1
        if self.speculator is not None:
            self.speculator.observe_admit(slot, req.prompt, first)
        base = list(req._base_tokens)
        lv = _Live(req=req, slot=slot, tokens=[first],
                   t_first=run.now(), queue_steps=req._waited,
                   base=base)
        run.live[slot] = lv
        if req.rid not in run.first_seen:
            if not base and self.journal is not None:
                # First-ever admission of this request (a replayed
                # continuation was journaled by the previous leg).
                self.journal.admit(req.rid, req.prompt,
                                   req.max_new_tokens, req.eos_id,
                                   slo=req.slo, tenant=req.tenant,
                                   session=req.session)
            run.first_seen[req.rid] = lv.t_first
            run.marks.setdefault(req.rid, {})["first"] = run.clocks()
        if self.journal is not None:
            self.journal.token(req.rid, first, run.now())
        self._count_token(run, req)
        if self.on_token is not None and not (
                first == req.eos_id or req.max_new_tokens == 1):
            self.on_token(req.rid, first, False)
        return lv

    def _continuation(self, lv: _Live) -> Request:
        """The PR-6 continuation: prompt + the good tokens so far,
        remaining budget, class/tenant preserved — greedy decode
        is deterministic, so the re-prefilled continuation emits
        exactly the tokens the original slot would have (token
        identity pinned in tests/test_serve_fire.py and
        tests/test_serve_slo.py)."""
        # graftcheck: disable=host-sync-in-loop -- builds the
        # continuation prompt from HOST token lists (no device
        # value involved); runs once per quarantine/preemption,
        # not per step
        cont = dataclasses.replace(
            lv.req,
            prompt=np.concatenate(
                [np.asarray(lv.req.prompt, np.int32),
                 np.asarray(lv.tokens, np.int32)])
            if lv.tokens else np.asarray(lv.req.prompt, np.int32),
            max_new_tokens=lv.req.max_new_tokens - len(lv.tokens))
        ladder = max(self.engine.buckets)
        if len(cont.prompt) > ladder:
            raise ValueError(
                f"request {lv.req.rid}: continuation prompt "
                f"{len(cont.prompt)} exceeds the largest bucket "
                f"{ladder} — re-admission needs the "
                f"ladder sized to prompt+new tokens (serve/run.py "
                f"does this when a fault plan, journal resume, or "
                f"policy=slo is armed; with --serve.buckets, "
                f"cover the full trajectory)")
        cont._base_tokens = lv.base + lv.tokens
        cont._waited = lv.queue_steps
        return cont

    def _quarantine(self, run: _Run, lv: _Live) -> None:
        """Contain one poisoned slot: free it, re-queue the
        request as a continuation at the head (prompt + good
        tokens, remaining budget)."""
        self._release(run, lv, retain=False)
        rid = lv.req.rid
        n = run.retries[rid] = run.retries.get(rid, 0) + 1
        if n > self.slot_retries:
            raise SlotRetryExhausted(
                f"request {rid} slot-quarantined {n} times "
                f"(budget {self.slot_retries}): repeated NaN on "
                f"the same request is a divergence, not a "
                f"transient — halting instead of hot-looping "
                f"re-prefills")
        run.total_retries += 1
        t = run.now()
        run.recovery_ts.append(t)
        if self.anomaly_hub is not None:
            # The engine's per-slot finiteness flag IS the
            # detection (already fetched with the step's tokens);
            # surface it as a critical anomaly beside the
            # containment's recovery record.
            self.anomaly_hub.note_slot_nonfinite(
                run.tally["steps"], slot=lv.slot, rid=rid)
        self._emit("recovery", kind="slot_quarantine", rid=rid,
                   slot=lv.slot, retry=n, t_s=round(t, 4))
        if self.tracer is not None:
            self.tracer.instant("slot_quarantine", rid=rid,
                                slot=lv.slot, retry=n)
            self.tracer.request_evicted(rid, "quarantine")
        # graftcheck: disable=host-sync-in-loop -- builds the
        # continuation prompt from HOST token lists (no device
        # value involved); runs once per quarantine, not per step
        run.queue.insert(0, self._continuation(lv))
        # Re-admit without waiting out the decode-priority clock:
        # the request was already being served.
        run.steps_since_admit = self.decode_priority

    def _preempt_one(self, run: _Run, lv: _Live) -> None:
        """SLO preemption: evict a live lower-class / over-quota
        request so the waiting higher-class one gets its slot.
        Same continuation machinery as quarantine (journal-
        compatible, token-identical), but no retry charge, no
        recovery event — this is policy, not failure."""
        # Retain: the victim's KV is valid, and its continuation
        # re-admits with this exact sequence as its prompt — on a
        # paged engine the preemption's re-prefill becomes a
        # prefix-cache hit instead of a full recompute.
        self._release(run, lv, retain=True)
        rid = lv.req.rid
        run.preempts[rid] = run.preempts.get(rid, 0) + 1
        run.total_preempts += 1
        cont = self._continuation(lv)
        # Mark the base as policy-only — UNLESS this request
        # already carried recovery base tokens (a prior quarantine
        # or journal replay): preemption must not erase that
        # provenance, or the completion would drop out of the
        # recovery-window population.
        if not lv.base or lv.req._policy_base:
            cont._policy_base = True
        run.queue.append(cont)     # class selection orders the queue
        self._emit("preempt", rid=rid, slot=lv.slot,
                   slo=lv.req.slo, tenant=lv.req.tenant,
                   served=len(lv.base) + len(lv.tokens),
                   t_s=round(run.now(), 4))
        if self.tracer is not None:
            self.tracer.instant("preempt", cat="policy", rid=rid,
                                slot=lv.slot, slo=lv.req.slo)
            self.tracer.request_evicted(rid, "preempt")

    def _cancel_rid(self, run: _Run, rid: int) -> None:
        """Fleet router moved this request elsewhere: drop it
        wherever it is (queue, pending, or a live slot — freed
        with retention, its KV is valid) without a completion; the
        new owner re-derives the stream (greedy determinism)."""
        run.marks.pop(rid, None)
        for i, r in enumerate(run.queue):
            if r.rid == rid:
                run.queue.pop(i)
                self._emit("serve_cancel", rid=rid, where="queue")
                return
        for i, r in enumerate(run.pending):
            if r.rid == rid:
                del run.pending[i]
                self._emit("serve_cancel", rid=rid, where="pending")
                return
        for slot, lv in list(run.live.items()):
            if lv.req.rid == rid:
                self._release(run, lv, retain=True)
                self._emit("serve_cancel", rid=rid, where="live",
                           slot=slot)
                return

    def _feed_cmd(self, run: _Run, cmd) -> None:
        kind = cmd.get("cmd")
        if kind == "drain":
            self.draining = True
        elif kind == "swap":
            self._swap(run)
        elif kind == "cancel":
            self._cancel_rid(run, int(cmd.get("rid", -1)))
        elif kind == "hold_export":
            self._export_hold_until = (
                self.clock() + float(cmd.get("secs", 0.0)))
        elif kind == "tune":
            self._apply_tune(cmd)

    def _feed_request(self, run: _Run, r: Request) -> None:
        # Unservable: draining, no budget, past the cache, or (a paged
        # engine) a reservation its pool can NEVER hold, which must be
        # rejected here — the idle-engine admission path raises, and
        # a replica must never crash on a bad dispatch.
        if (self.draining or r.max_new_tokens < 1
                or not self.engine.fits(len(r.prompt), r.max_new_tokens)
                or not self.engine.reservation_fits(
                    len(r.prompt), r.max_new_tokens)):
            self._emit("serve_reject", rid=r.rid,
                       prompt_len=len(r.prompt),
                       max_new=r.max_new_tokens,
                       draining=self.draining)
            if self.journal is not None:
                self.journal.reject(r.rid)
                self.journal.flush()
            return
        # A duplicate of an already-present rid SUPERSEDES it (a
        # router double-send must not interleave two token
        # streams into one journal entry).
        self._cancel_rid(run, r.rid)
        r.arrival_s = run.now()
        run.pending.append(r)
        if r.session:
            run.has_sessions = True

    def _poll_feed(self, run: _Run, commands: bool = True) -> None:
        """Streamed intake: new requests join ``pending`` due
        immediately; control commands act between decode steps.
        Items are processed in FILE ORDER — a stalled replica can
        read a dispatch, its cancel, and the re-dispatched
        continuation in ONE batch, and only line order makes that
        sequence mean what the router intended. An unservable
        request is REJECTED into the journal (the router sheds
        it) instead of crashing the replica. ``commands`` False
        (inside a step's wait): requests are taken up to the first
        command, which waits with what follows it for the loop's
        top, and the file is read every 2 ms at most."""
        fed = run.fed
        if not commands:
            if fed or self.clock() - run.fed_at < 2e-3:
                return
            run.fed_at = self.clock()
        fed.extend(self.feed.poll())
        while fed and (commands or not isinstance(fed[0], dict)):
            item = fed.popleft()
            if isinstance(item, dict):
                self._feed_cmd(run, item)
            else:
                self._feed_request(run, item)

    def _take_due(self, run: _Run, kind: str) -> None:
        """Open-loop arrivals: everything whose time has come goes
        from ``pending`` to ``queue``. It came due while an
        iteration of ``kind`` ran (or the engine slept): its
        lateness is that kind's."""
        pending = run.pending
        while pending and pending[0].arrival_s <= (t_poll := run.now()):
            req = pending.popleft()
            req._waited = 0
            run.marks[req.rid] = {"due": (
                run.clocks(), kind, t_poll - req.arrival_s)}
            run.queue.append(req)
            if self.tracer is not None:
                self.tracer.request_queued(
                    req.rid, slo=req.slo,
                    prompt_len=len(req.prompt),
                    tenant=req.tenant)

    def _pick_admission(self, run: _Run, in_flight: int) -> int:
        """The queued request that may be admitted now, or -1: one
        the policy picks, a slot free for it, under the live-slot
        cap, ``decode_priority`` decode iterations since the last
        admission (an idle engine admits at once). ``in_flight``: 1
        from inside the wait for a step, which was launched after
        the last admission and is one of those iterations though
        not retired yet; 0 at the loop's top."""
        eng, live = self.engine, run.live
        if not run.queue or (live and run.steps_since_admit + in_flight
                             < self.decode_priority):
            return -1
        if not eng.free_slots() or (
                self._slot_cap and len(live) >= self._slot_cap):
            return -1
        pick = self._pick_unblocked(run)
        if pick < 0:
            return -1
        # Page-pool pressure (paged engine only): the pick's
        # worst-case reservation must fit the pool after LRU
        # eviction of every reclaimable cached page. While live
        # slots hold the shortfall, keep decoding — they free pages
        # as they finish; an IDLE engine that still cannot admit
        # will never be able to, so fail loudly instead of
        # spinning.
        head = run.queue[pick]
        if eng.can_admit(len(head.prompt), head.max_new_tokens):
            return pick
        if not live:
            raise RuntimeError(
                f"request {head.rid}: page pool cannot hold its "
                f"reservation even with the engine idle and the "
                f"prefix cache fully evicted — raise "
                f"--serve.num-pages (or lower the request budget)")
        return -1

    def _on_wait(self) -> bool:
        """The engine's hook between the slices of its wait for the
        step in flight, with no successor queued behind it yet: do
        what the loop's top does, NOW. What came due joins the
        queue (its lateness a slice, not an iteration), and a
        request that may be admitted is: its prefill goes behind
        the RUNNING step (``_admit(behind=True)``), and True tells
        the engine to launch no successor."""
        run = self._state
        if self.feed is not None:
            self._poll_feed(run, commands=False)
        self._take_due(run, "step")
        pick = self._pick_admission(run, in_flight=1)
        if pick < 0:
            return False
        run.behind = self._admit(run, pick, behind=True)
        return True

    def _poll(self, run: _Run) -> Optional[int]:
        """``tfd.serve.poll``: everything between the last iteration's
        tail and this one's admission or engine dispatch — the
        admission itself (``tfd.serve.admit``) runs after it closes.
        Returns the queue index to admit, -1 to decode, or None where
        the iteration ends here: a preemption freed the slot the next
        one admits into, the idle engine slept, or the run is over."""
        eng, plan = self.engine, self.fault_plan
        with run.spans.span("serve.poll", queue=len(run.queue)):
            if self.feed is not None:
                self._poll_feed(run)
            self._take_due(run, run.last_iter)
            pick = self._pick_admission(run, in_flight=0)
            if pick >= 0:
                return pick
            if (self.policy == "slo" and self.preempt and run.queue
                    and run.live and not eng.free_slots()
                    and run.waited_since_admit >= self.decode_priority):
                pick = self._pick_unblocked(run)
                victim = None if pick < 0 else self._pick_victim(
                    run.live, run.queue[pick], run.tenant_tokens)
                if victim is not None:
                    self._preempt_one(run, victim)
                    return None
            if not run.live:
                run.last_iter = "other"    # the engine sleeps
                if run.pending:
                    # Nothing to decode, nothing admittable:
                    # sleep to the next arrival instead of
                    # spinning (bounded with a feed — new work
                    # or a command can land before the next
                    # synthetic arrival).
                    delay = max(0.0,
                                run.pending[0].arrival_s - run.now())
                    if self.feed is not None:
                        delay = min(delay, 0.02)
                    time.sleep(delay)
                elif self.feed is not None and not self.draining:
                    # Idle but open for business: keep the
                    # snapshot export fresh (the router's
                    # liveness signal) and poll again shortly.
                    self._maybe_export()
                    time.sleep(0.02)
                # else the run is over: the queue is empty too (free
                # slots exist), and the loop's condition ends it
                return None
            if plan:
                # The serve-phase fault points, on the
                # decode-step clock (resilience/faults.py):
                # poison, swap, signal. decode_stall is
                # consumed inside the engine's watched fetch.
                nstep = eng.decode_steps + 1
                bad_slot = plan.take_slot_nan(nstep)
                if bad_slot is not None:
                    if bad_slot not in run.live:
                        # The drill wants a SERVING slot: the
                        # named one is momentarily empty (freed
                        # last step, next insert pending —
                        # whose full-row overwrite would
                        # neutralize the poison), so redirect
                        # to the lowest live slot (one is live: the
                        # idle branch above has returned).
                        bad_slot = min(run.live)
                    eng.poison_slot(bad_slot)
                if plan.take_reload(nstep):
                    self._swap(run)
                plan.maybe_signal(nstep)
        return -1

    def _dispatch(self, run: _Run) -> tuple:
        """ONE program dispatch, one host fetch — speculative when
        armed, plain otherwise. Returns what :meth:`_retire` takes:
        when the dispatch began (for the decode-stall detector: just
        the engine dispatch + its watched token fetch, admission /
        prefill time excluded — a re-prefill is routine, not an
        incident), the verify plan ``fb`` (None = whole-batch plain
        step, [] = full verify, a slot list = MIXED dispatch: those
        slots take the plain path INSIDE the verify program,
        ``engine.verify_fallback_slots``), and the tokens: a plain
        step's [num_slots], or a verify's [num_slots, k + 1] with how
        many of each row count."""
        eng, spec, live = self.engine, self.speculator, run.live
        t_disp = self.clock() if self.anomaly_hub is not None else 0.0
        fb = eng.verify_fallback_slots() if spec is not None else None
        if fb is None:
            # (_on_wait may dispatch an admission under it)
            return t_disp, None, eng.step(), None
        with run.spans.span("serve.propose"):
            # Full per-slot histories are O(prompt + decoded)
            # host work per step — built only for proposers
            # that read them (the k-gram self-draft; a draft
            # MODEL's cache IS its history and ignores the
            # argument).
            hists = ({s: list(map(int, lv.req.prompt))
                      + lv.tokens for s, lv in live.items()}
                     if getattr(spec, "needs_histories", True)
                     else {s: () for s in live})
            props = spec.propose(hists)
            # graftcheck: disable=host-sync-in-loop -- builds
            # the fallback slots' HOST history tails (no
            # device value); only tight slots, only the rare
            # headroom-starved iterations
            tails = {s: list(map(int, live[s].req.prompt))
                     + live[s].tokens for s in fb}
        if fb:
            return (t_disp, fb, *eng.verify_step(props, tails=tails))
        return (t_disp, fb, *eng.verify_step(props))

    def _retire(self, run: _Run, t_disp: float, fb: Optional[list],
                toks, acc) -> None:
        """``tfd.serve.retire``: from the engine's return to the
        journal flush — what the host does with the tokens before it
        may think about the next dispatch. ``emitted`` maps slot ->
        the tokens the target model produced this dispatch, in
        order."""
        eng, spec, live = self.engine, self.speculator, run.live
        queue, tally = run.queue, run.tally
        with run.spans.span("serve.retire", live=len(live)):
            if fb is not None:
                fb_set = set(eng.last_verify_fallback)
                emitted = {s: [int(t) for t in toks[s, :acc[s]]]
                           for s in live}
                stats = run.spec_stats
                stats["verify_steps"] += 1
                spec_live = [s for s in live if s not in fb_set]
                stats["proposed"] += int(
                    eng.spec_tokens * len(spec_live))
                stats["accepted"] += int(
                    sum(acc[s] - 1 for s in spec_live))
                stats["fallback_slots"] += len(fb_set & set(live))
            else:
                # The engine runs one step ahead: a slot admitted
                # while this step was in flight (behind a successor
                # already queued) has no token in it yet
                # (engine.step_valid; None of a synchronous engine).
                # A slot admitted from inside
                # this step's wait is not live yet: ``run.behind``.
                valid = eng.step_valid
                emitted = {s: [int(toks[s])] for s in live
                           if valid is None or valid[s]}
            if spec is not None:
                spec.sync_from(eng)
            tally["occ_sum"] += eng.occupancy()
            tally["steps"] += 1
            if self.anomaly_hub is not None:
                self.anomaly_hub.observe_decode_step(
                    tally["steps"], queue_depth=len(queue),
                    step_wall_ms=1e3 * (self.clock() - t_disp))
            # The admission clock: EVERY decode iteration since
            # the last admission, whoever is or is not waiting —
            # an arrival on an engine that has decoded
            # decode_priority iterations since it last admitted
            # goes in at once, and a burst is still spaced that
            # many iterations apart.
            run.steps_since_admit += 1
            if queue and (eng.free_slots() or (
                    self.policy == "slo" and self.preempt)):
                # A decode iteration taken WHILE a queued request
                # waited with a free slot available: the request
                # the policy would admit endures at most
                # decode_priority of them (its queue_steps). Under
                # policy="slo" with preemption a request facing a
                # FULL engine accrues wait too, and the preemption
                # branch of the poll goes by that wait alone; FIFO (and
                # slo with preempt off) keeps capacity waits out.
                run.waited_since_admit += 1
                queue[self._pick_index(
                    queue, run.tenant_tokens)]._waited += 1
            # Containment BEFORE token retirement: a poisoned
            # slot's tokens are garbage — quarantine drops them
            # (never appended, never journaled) and the
            # continuation re-derives them.
            for slot in eng.take_bad_slots():
                if slot in live:
                    self._quarantine(run, live[slot])
            for slot in list(live):
                lv = live[slot]
                for tok in emitted.get(slot, ()):
                    lv.tokens.append(tok)
                    tally["decoded"] += 1
                    if self.journal is not None:
                        self.journal.token(lv.req.rid, tok, run.now())
                    self._count_token(run, lv.req)
                    if tok == lv.req.eos_id:
                        self._finish(run, lv, "eos")
                        break
                    if len(lv.tokens) >= lv.req.max_new_tokens:
                        self._finish(run, lv, "length")
                        break
                    if self.on_token is not None:
                        self.on_token(lv.req.rid, tok, False)
            if self.journal is not None:
                self.journal.flush()

    def _tail(self, run: _Run) -> None:
        """``tfd.serve.tail``: live observability, on the decode-step
        clock."""
        eng, spec, tally = self.engine, self.speculator, run.tally
        with run.spans.span("serve.tail"):
            run.rate_win.append((run.now(), tally["decoded"]))
            if spec is not None:
                run.spec_win.append((run.now(),
                                     run.spec_stats["accepted"],
                                     run.spec_stats["proposed"]))
            if self.tracer is not None:
                counters = {"slots": eng.occupancy(),
                            "queue": float(len(run.queue))}
                rate = self._window_rate()
                if rate is not None:
                    counters["tokens_per_s"] = round(rate, 2)
                if spec is not None and run.spec_stats["proposed"]:
                    counters["accept_rate"] = round(
                        run.spec_stats["accepted"]
                        / run.spec_stats["proposed"], 4)
                self.tracer.counters(**counters)
            if self.slo_monitor is not None:
                self.slo_monitor.on_step(tally["steps"])
            if (self.status_fn is not None
                    and self.status_every > 0
                    and tally["steps"] % self.status_every == 0):
                self.status_fn(self.status_line())
            if self.autopilot is not None:
                # The controller evaluates on its own cadence (the
                # off-cadence cost is one modulo — the snapshot is
                # only built on eval ticks) and its decisions
                # route through _feed_cmd like any fleet command:
                # applied HERE, between decode steps, where
                # continuation semantics + greedy determinism keep
                # every live stream token-identical.
                for tc in self.autopilot.maybe_step(
                        tally["steps"], self.metrics_snapshot):
                    self._feed_cmd(run, tc)
            self._maybe_export()

    def _summarize(self, run: _Run) -> None:
        """The run's ``serve_summary`` record (kept as ``summary``),
        the autopilot's rollup, the final snapshot and the journal's
        last flush."""
        eng, spec, tally = self.engine, self.speculator, run.tally
        done = run.done
        wall = run.now()
        at_end = run.clocks()
        total_new = sum(len(c.tokens) for c in done)
        # Throughput counts only tokens DECODED this leg: a resumed
        # leg's continuations deliver their journal-replayed base
        # tokens too (total_new_tokens — the user-facing count), but
        # those were the dead leg's work; dividing them by this leg's
        # wall would overstate tokens/s exactly when it matters.
        decoded = sum(c.decoded or len(c.tokens) for c in done)
        summary = {
            "requests": len(done),
            "total_new_tokens": total_new,
            "decoded_tokens": decoded,
            "wall_s": round(wall, 4),
            # Where the wall went, by host phase (self times; the
            # phases of an iteration tile it, so sum_ms adds to wall_s).
            "phase_ms": run.spans.totals.as_dict(),
            # The same wall by KIND of iteration (admissions, decode
            # iterations, the rest), and the admissions it held.
            "iter_ms": _parts_ms([0.0] * 3, at_end),
            "admissions": at_end[3],
            # Of them, those whose prefill found no decode step queued
            # behind the one running.
            "admits_first": eng.admits_first,
            "admitted_at_once": run.admitted_at_once,
            "tokens_per_sec": round(decoded / max(wall, 1e-9), 2),
            "mean_slot_occupancy": round(
                tally["occ_sum"] / max(1, tally["steps"]), 4),
            "decode_steps": tally["steps"],
            # How often the engine's one-step-ahead launch engaged, and
            # what it cost.
            "steps_ahead": eng.steps_ahead,
            "ahead_rows_dropped": eng.ahead_rows_dropped,
            "prefills": eng.prefills,
            "prefill_compiles": eng.prefill_compiles,
            "buckets": ",".join(str(b) for b in eng.buckets),
            "num_slots": eng.num_slots,
            "decode_priority": self.decode_priority,
            "policy": self.policy,
            "preemptions": run.total_preempts,
            "retries": run.total_retries,
            "swaps": eng.swaps,
            "swap_seconds": round(self._swap_seconds, 4),
            **self._capacity_fields(),
            **self.summary_extra,
        }
        if spec is not None:
            stats = run.spec_stats
            summary.update(
                spec_tokens=eng.spec_tokens,
                verify_steps=stats["verify_steps"],
                spec_proposed=stats["proposed"],
                spec_accepted=stats["accepted"],
                spec_fallback_slots=stats["fallback_slots"],
                accept_rate=round(
                    stats["accepted"] / max(1, stats["proposed"]), 4))
        if self.slo_monitor is not None:
            summary.update(self.slo_monitor.summary())
        if self.anomaly_hub is not None:
            summary["anomalies"] = self.anomaly_hub.count
        # What a family's decode program counted (routing load on
        # the experts held, the selection's keep share, cache bytes
        # by kind); empty for a family that counts nothing.
        summary.update(eng.model_stats())
        # Page-pool occupancy + prefix hit rate + evictions: the
        # capacity feed the fleet router polls, and the counts
        # behind prefill tokens saved.
        summary.update(eng.paging_stats())
        if self.autopilot is not None:
            summary["tune_actions"] = self._tunes
        self._emit("serve_summary", **summary)
        self.summary = summary
        if self.autopilot is not None:
            # Run-end rollup: the decision ledger plus the advisory
            # recommendations for the boot-time knobs (num_pages,
            # bucket ladder) sized from THIS run's observed peaks.
            self.autopilot.emit_summary(tally["steps"],
                                        self.metrics_snapshot())
        # One FINAL snapshot covering every completion, so the export
        # artifact's last point agrees exactly with the post-run
        # report's per-class percentiles
        # (tests/test_serve_observe.py holds it).
        if self.export_every or self.export_path:
            self._maybe_export(force=True)
        if self.journal is not None:
            self.journal.flush()

    # -- exportable rolling metrics ---------------------------------------

    def _window_rate(self) -> Optional[float]:
        """Decoded tokens/s over the rolling rate window (None until
        two samples exist)."""
        run = self._state
        if run is None or len(run.rate_win) < 2:
            return None
        (ta, da), (tb, db) = run.rate_win[0], run.rate_win[-1]
        if tb <= ta:
            return None
        return (db - da) / (tb - ta)

    def _window_accept(self) -> Optional[float]:
        """Accept rate over the rolling window — accepted/proposed
        deltas between the window's endpoints (None until speculation
        has proposed inside the window). The cumulative
        ``accept_rate`` stays beside it: a regime shift moves the
        window long before it moves the lifetime ratio."""
        run = self._state
        if run is None or len(run.spec_win) < 2:
            return None
        a, b = run.spec_win[0], run.spec_win[-1]
        dp = b[2] - a[2]
        if dp <= 0:
            return None
        return (b[1] - a[1]) / dp

    def _apply_tune(self, cmd: Dict[str, Any]) -> None:
        """One live knob change, between decode steps (the autopilot's
        actuation path — also reachable from a fleet inbox ``tune``
        command). Values are clamped, unknown knobs are ignored (a
        replica never crashes on a bad dispatch), and every applied
        change counts into ``tune_actions``."""
        knob = cmd.get("knob")
        value = cmd.get("value")
        if knob == "decode_priority":
            self.decode_priority = max(1, int(value))
        elif knob == "slot_cap":
            self._slot_cap = min(max(1, int(value)),
                                 int(self.engine.num_slots))
        elif knob == "preempt":
            self.preempt = bool(value)
        elif knob == "spec_k":
            k = max(1, int(value))
            if self.engine.set_spec_k is None:
                return
            self.engine.set_spec_k(k)
            sp_set = getattr(self.speculator, "set_k", None)
            if sp_set is not None:
                sp_set(k)
        else:
            return
        self._tunes += 1

    def _capacity_fields(self) -> Dict[str, Any]:
        """HBM-capacity facts for the fleet side, PER-DEVICE honest:
        a tensor-parallel replica's cache is head-sharded over its
        mesh, so each device holds 1/tp_width of the logical bytes —
        a router pre-checking headroom from the logical figure would
        overcount a TP replica's spend tp_width-fold. Rides both
        ``serve_summary`` and :meth:`metrics_snapshot`. (An engine
        that models no cache or mesh, a test's, leaves theirs out.)"""
        eng = self.engine
        out: Dict[str, Any] = {"tp_width": int(eng.tp_width)}
        bps = eng.cache_bytes_per_slot()
        if bps is not None:
            out["per_device_cache_bytes"] = int(bps * eng.num_slots)
        mesh = getattr(eng.model, "mesh", None)
        if mesh is not None:
            from tensorflow_distributed_tpu.parallel.mesh import (
                mesh_shape_dict)
            # "engine_mesh", not "mesh": every registry record already
            # carries the compact host "mesh" tag (observe/registry.py
            # host_tags), and fields override tags on emit.
            out["engine_mesh"] = mesh_shape_dict(mesh)
        return out

    def metrics_snapshot(self) -> Dict[str, Any]:
        """Atomic point-in-time view of the serving engine — the exact
        payload a router / fleet supervisor polls (``--observe.
        export-every`` dumps it; the fleet router reads these
        fields). Callable between
        decode steps and after :meth:`run` returns; everything is a
        plain JSON-able scalar. Per-class TTFT percentiles use the
        same nearest-rank formula as ``observe.report``, so the final
        snapshot agrees exactly with the post-run report."""
        run = self._state
        if run is None:
            raise RuntimeError(
                "metrics_snapshot() is available once run() has "
                "started")
        tally = run.tally
        now = run.now()
        self._snap_seq += 1
        snap: Dict[str, Any] = {
            # Liveness triplet: monotonic seq + wall-clock timestamp +
            # pid, so a poller (fleet/router.py) can tell a frozen
            # snapshot from a healthy idle replica — and a restarted
            # process from the one it replaced.
            "seq": self._snap_seq,
            "wall_ts": round(time.time(), 3),
            "pid": os.getpid(),
            "t_s": round(now, 4),
            "decode_steps": tally["steps"],
            "requests_done": len(run.done),
            "requests_live": len(run.live),
            "queue_depth": len(run.queue),
            "pending_arrivals": len(run.pending),
            "slot_occupancy": round(self.engine.occupancy(), 4),
            "mean_slot_occupancy": round(
                tally["occ_sum"] / max(1, tally["steps"]), 4),
            "decoded_tokens": tally["decoded"],
            "tokens_per_sec": round(
                tally["decoded"] / max(now, 1e-9), 2),
            "retries": sum(run.retries.values()),
            "preemptions": sum(run.preempts.values()),
            "swaps": self.engine.swaps,
            "policy": self.policy,
            # Capacity facts a router needs to pre-check dispatches
            # (engine limits are not otherwise visible fleet-side).
            "num_slots": self.engine.num_slots,
            "max_len": self.engine.max_len,
        }
        snap.update(self._capacity_fields())
        if self.served_ckpt_step is not None:
            # The fleet controller's model-staleness feed: which
            # trained step these weights came from.
            snap["ckpt_step"] = int(self.served_ckpt_step)
        if self.draining:
            snap["draining"] = True
        rate = self._window_rate()
        if rate is not None:
            snap["tokens_per_sec_window"] = round(rate, 2)
        spec_stats = run.spec_stats
        if self.speculator is not None and spec_stats["proposed"]:
            snap["accept_rate"] = round(
                spec_stats["accepted"] / spec_stats["proposed"], 4)
            snap["spec_tokens"] = int(self.engine.spec_tokens)
        aw = self._window_accept()
        if aw is not None:
            snap["accept_rate_window"] = round(aw, 4)
        if self.autopilot is not None:
            snap["tune_actions"] = self._tunes
        by_cls: Dict[str, List[float]] = {}
        for c in run.done:
            by_cls.setdefault(c.slo, []).append(1e3 * c.ttft_s)
        for cls, vals in sorted(by_cls.items()):
            vals.sort()
            snap[f"ttft_ms_p50_{cls}"] = round(percentile(vals, 50), 3)
            snap[f"ttft_ms_p95_{cls}"] = round(percentile(vals, 95), 3)
        snap.update(self.engine.paging_stats())
        lag_stats = getattr(self.feed, "lag_stats", None)
        if lag_stats is not None:
            # Inbox-poll lag (fleet replica mode): dispatch-file write
            # -> feed intake, from the router's enq_ts stamp — the
            # fleet latency decomposition's replica-side anchor and an
            # early warning for a wedged feed.
            snap.update(lag_stats())
        if self.slo_monitor is not None:
            snap["slo"] = self.slo_monitor.snapshot()
        if self.anomaly_hub is not None:
            # Live incident state (observe/anomaly.py): active
            # detectors, counts, last anomaly — so the export-path
            # pollers (the fleet router) see incident health, not
            # just throughput.
            snap["anomaly"] = self.anomaly_hub.snapshot()
        return snap

    def _maybe_export(self, force: bool = False) -> None:
        """On the export cadence (or forced at run end): emit one
        ``metrics_snapshot`` record through the registry (the durable
        history) and atomically rewrite ``export_path`` (tmp+rename —
        the single file a poller reads is always a complete
        point-in-time snapshot, never a torn write)."""
        if not force and not self.export_every:
            return
        now = self.clock()
        if not force and now - self._last_export < self.export_every:
            return
        if not force and now < self._export_hold_until:
            # The stale-snapshot drill (fleet "hold_export" command):
            # exports freeze, the file's seq stops advancing, and the
            # router must quarantine on staleness — exactly what this
            # window exists to prove.
            return
        self._last_export = now
        snap = self.metrics_snapshot()
        self._emit("metrics_snapshot", **snap)
        if self.export_path:
            atomic_write_json(self.export_path, snap)

    def status_line(self) -> str:
        """The periodic one-line live status: occupancy, queue depth,
        throughput, and (when the monitor is armed) per-target window
        percentiles + budget burn."""
        snap = self.metrics_snapshot()
        rate = snap.get("tokens_per_sec_window",
                        snap.get("tokens_per_sec", 0.0))
        line = (f"[serve] step={snap['decode_steps']} "
                f"occ={snap['slot_occupancy']:.2f} "
                f"queue={snap['queue_depth']} "
                f"done={snap['requests_done']} "
                f"tok/s={rate:.1f}")
        if self.slo_monitor is not None:
            line += " | " + self.slo_monitor.status_bits()
        return line

    def _swap(self, run: _Run) -> None:
        """One live weight swap: fetch fresh params via ``reload_fn``
        (integrity-verified, fallback-to-newest-verifiable —
        train.checkpoint.restore_params), hand them to the engine
        between decode steps, account the latency."""
        if self.reload_fn is None:
            raise ValueError(
                "fault plan requests a reload but no reload_fn is "
                "wired (mode=serve needs --checkpoint-dir for live "
                "weight swap)")
        t0 = self.clock()
        params, ckpt_step = self.reload_fn()
        self.engine.swap_params(params)
        dt = self.clock() - t0
        self._swap_seconds += dt
        self.served_ckpt_step = ckpt_step
        t = run.now()
        run.recovery_ts.append(t)
        self._emit("recovery", kind="weight_swap",
                   seconds=round(dt, 4), ckpt_step=ckpt_step,
                   t_s=round(t, 4))
        self._trace_instant("weight_swap", seconds=round(dt, 4),
                            ckpt_step=ckpt_step)
