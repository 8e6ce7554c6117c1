"""One decode step in flight (PR 29): ``SlotDecodeEngine.step`` launches
step N+1 from step N's tokens on the device before it fetches them.

What lags by one step must still be exact. The REAL engines at tiny
size (dense and paged, CPU) serve schedules that force every hazard of
a step in flight: an admission under it, a finish by length, a finish
by EOS (the slot computes one token more, dropped), a slot freed and
re-admitted inside one step in flight, a quarantined slot, a prefix
hit on the paged engine; and speculation with either drafter (PR 30),
whose verify never follows a step in flight. Every request's tokens
equal one-shot greedy ``generate()``. Beside that: at most one step is ever ahead, nothing is
in flight after ``Scheduler.run``, before ``swap_params``, a verify or
a poison drill, and the counters say how often it engaged.

WHEN the step ahead is launched (PR 45): late, when the step in flight
is about to end by the engine's own clock, with the scheduler's hook
looking for arrivals meanwhile. The real engines on a fake timeline
(:class:`_Timeline`: one clock for scheduler and engine, a device whose
decode step takes 10 ms of it): an arrival inside a step's wait is
admitted behind the RUNNING step, one inside the launch's margin behind
its successor as before, the admission clock keeps a burst
``decode_priority`` steps apart, a step nobody timed is followed at
once, the fakes' bare ``step()`` still serves, and the watchdog still
sees an injected stall.
"""

from __future__ import annotations

import importlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflow_distributed_tpu.resilience.faults import parse_fault_plan
from tensorflow_distributed_tpu.serve.scheduler import Request, Scheduler

BUCKETS = (8, 16, 32)
SLOTS = 2


@pytest.fixture(scope="module")
def lm():
    from tensorflow_distributed_tpu.models.transformer import gpt_lm

    model = gpt_lm(None, size="tiny", max_len=48, dropout_rate=0.0,
                   compute_dtype=jnp.float32)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _engine(kind, lm, slots=SLOTS, **kw):
    from tensorflow_distributed_tpu.serve.engine import SlotDecodeEngine
    from tensorflow_distributed_tpu.serve.paging.engine import (
        PagedSlotEngine)

    model, params = lm
    if kind == "paged":
        return PagedSlotEngine(model, params, slots, page_size=8,
                               buckets=BUCKETS, **kw)
    return SlotDecodeEngine(model, params, slots, buckets=BUCKETS, **kw)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 64, size=n).astype(np.int32)


_ONE_SHOT: dict = {}


def _one_shot(lm, prompt, n):
    """Greedy ``generate()`` of one request alone: the reference every
    served stream is held to."""
    from tensorflow_distributed_tpu.models.generate import generate

    key = (prompt.tobytes(), n)
    if key not in _ONE_SHOT:
        model, params = lm
        _ONE_SHOT[key] = [int(t) for t in np.asarray(generate(
            model, params, jnp.asarray(prompt[None, :]), n))[0]]
    return _ONE_SHOT[key]


def _watch(eng):
    """Record, at every launch, how many steps were already in flight
    (launched and not fetched): one for a launch from a step's device
    tokens, none for a launch from the host's. More cannot be: the
    engine holds one step at most, and it is the step a launch
    follows."""
    seen = []
    launch = eng._launch

    def counted_launch(prev):
        assert eng._ahead is None
        seen.append(0 if prev is None else 1)
        return launch(prev)

    eng._launch = counted_launch
    return seen


def _mixed(lm):
    """Five requests over two slots: admissions under a step in flight,
    and every freed slot re-admitted at once (the queue never empties
    before the last)."""
    lens = [5, 11, 3, 14, 7]
    news = [8, 4, 6, 3, 5]
    return [Request(rid=i, prompt=_prompt(n, seed=10 + i),
                    max_new_tokens=m)
            for i, (n, m) in enumerate(zip(lens, news))]


def _expect(lm, reqs):
    out = {}
    for r in reqs:
        toks = _one_shot(lm, np.asarray(r.prompt), r.max_new_tokens)
        if r.eos_id in toks:
            toks = toks[:toks.index(r.eos_id) + 1]
        out[r.rid] = toks
    return out


def _with_eos(lm, reqs):
    """Give every other request an EOS id that its own greedy stream
    emits before its budget ends (the last token of the stream's first
    half), so it finishes by EOS with a step already in flight."""
    out = []
    for r in reqs:
        toks = _one_shot(lm, np.asarray(r.prompt), r.max_new_tokens)
        eos = toks[len(toks) // 2] if r.rid % 2 == 0 else -1
        out.append(Request(rid=r.rid, prompt=r.prompt,
                           max_new_tokens=r.max_new_tokens, eos_id=eos))
    return out


def _shared_prefix(lm):
    """Requests that share a 16-token prefix (two pages of 8): served
    one after the other through one slot's worth of queue, the later
    ones attach the first one's pages."""
    head = _prompt(16, seed=99)
    return [Request(rid=i, prompt=np.concatenate(
        [head, _prompt(3 + i, seed=200 + i)]), max_new_tokens=5 + i)
        for i in range(4)]


HAZARDS = {
    # name -> (requests builder, fault plan, scheduler keywords)
    "admission_under_a_step": (_mixed, "", {}),
    "finish_by_length": (
        lambda lm: [Request(rid=i, prompt=_prompt(4 + i, seed=30 + i),
                            max_new_tokens=2 + i) for i in range(4)],
        "", {}),
    "finish_by_eos": (lambda lm: _with_eos(lm, _mixed(lm)), "", {}),
    "freed_and_readmitted_in_one_step": (
        # one long answer beside six of budget 2 (one decode step each):
        # every step in flight at a retire holds the row of a slot that
        # is re-admitted under it, next to a row that goes on
        lambda lm: [Request(rid=i, prompt=_prompt(3 + i % 5, seed=50 + i),
                            max_new_tokens=2 if i else 12)
                    for i in range(7)],
        "", {"decode_priority": 1}),
    "quarantined_slot": (_mixed, "slot_nan@2:0,slot_nan@5:1",
                         {"slot_retries": 3}),
    "shared_prefix": (_shared_prefix, "", {}),
    # speculation: a verify never follows a step in flight and leaves
    # none. The k-gram self-draft on fresh-init weights proposes almost
    # nothing right (the adversarial case for identity); a draft that IS
    # the target model is accepted whole
    "spec_self_draft": (_mixed, "", {"spec": "self"}),
    "spec_perfect_draft": (_mixed, "", {"spec": "model"}),
    # the autopilot deepens a fully-accepted draft from k=1 to SPEC_K
    # with slots live: engine and drafter rebind their programs between
    # two dispatches (``set_spec_k``), and the streams do not move
    "spec_k_retuned_mid_run": (
        lambda lm: [Request(rid=i, prompt=_prompt(5 + 6 * i, seed=70 + i),
                            max_new_tokens=24) for i in range(2)],
        "", {"spec": "model", "spec_k0": 1}),
}
SPEC_K = 3


def _speculator(how, lm, k):
    from tensorflow_distributed_tpu.serve.speculate import (
        DraftSpeculator, SelfDraft)

    if how == "self":
        return SelfDraft(2, k)
    return DraftSpeculator(*lm, SLOTS, BUCKETS, k)


@pytest.mark.parametrize("kind", ["dense", "paged"])
@pytest.mark.parametrize("hazard", sorted(HAZARDS))
def test_served_tokens_equal_one_shot_generate(hazard, kind, lm):
    build, plan_spec, sched_kw = HAZARDS[hazard]
    reqs = build(lm)
    plan = parse_fault_plan(plan_spec) if plan_spec else None
    kw = {"decode_priority": 2, **sched_kw}
    spec = kw.pop("spec", None)
    k0 = kw.pop("spec_k0", SPEC_K) if spec else 0
    retuned = bool(spec) and k0 != SPEC_K
    if retuned:
        from tensorflow_distributed_tpu.observe.autopilot import Autopilot

        kw["autopilot"] = Autopilot(every=3, confirm=1, cooldown=0,
                                    k_ladder=(k0, SPEC_K))
    eng = _engine(kind, lm, fault_plan=plan, spec_tokens=k0)
    in_flight = _watch(eng)
    sched = Scheduler(eng, fault_plan=plan,
                      speculator=_speculator(spec, lm, k0) if spec else None,
                      **kw)
    done = {c.rid: c for c in sched.run(reqs)}
    want = _expect(lm, reqs)
    assert {r: c.tokens for r, c in done.items()} == want
    for r in reqs:
        assert done[r.rid].finish == (
            "eos" if want[r.rid][-1] == r.eos_id else "length")
    s = sched.summary
    if spec:
        assert s["verify_steps"] > 0 and eng._ahead is None
        if spec == "model":
            assert s["accept_rate"] == 1.0
        if retuned:
            assert s["tune_actions"] == 1
            assert eng.spec_tokens == sched.speculator.k == SPEC_K
        return
    # it engaged, and never ran further than one step ahead
    assert in_flight and max(in_flight) == 1
    assert eng._ahead is None                # nothing left in flight
    # steps_ahead: of the steps retired, those launched from device
    # tokens (a step dropped whole was launched so, never retired)
    assert s["steps_ahead"] == eng.steps_ahead
    assert 0 < s["steps_ahead"] < s["decode_steps"]
    assert s["steps_ahead"] <= sum(in_flight)
    # every request left one row of a step in flight behind it (its
    # last step was launched before the host knew it had ended), a
    # quarantine another, a poison drill the whole step it drops
    assert s["ahead_rows_dropped"] == eng.ahead_rows_dropped
    assert s["ahead_rows_dropped"] >= len(reqs) - 1
    if hazard == "quarantined_slot":
        assert s["retries"] == 2
    if hazard == "shared_prefix" and kind == "paged":
        from tensorflow_distributed_tpu.serve.buckets import pick_bucket

        assert s["prefix_hits"] >= 1 and s["prefix_hit_tokens"] >= 16
        # what paging is for, as counts: fewer prefill tokens computed
        # than the dense engine's bucket-padded prompts, and a working
        # set of pages under the dense engine's reserved rows
        stats = eng.paging_stats()
        assert stats["prefill_tokens_computed"] < sum(
            pick_bucket(len(r.prompt), BUCKETS) for r in reqs)
        assert (stats["slot_pages_peak"] * stats["page_bytes"]
                < SLOTS * _engine("dense", lm).cache_bytes_per_slot())


@pytest.mark.parametrize("kind", ["dense", "paged"])
def test_a_step_ahead_is_dropped_before_swap_verify_and_drill(kind, lm):
    """``swap_params``, ``verify_step`` and ``poison_slot`` each find a
    step in flight, wait it out and drop it; the host's tokens and
    positions never moved for it, so the streams go on unchanged."""
    model, params = lm
    k = 2
    eng = _engine(kind, lm, spec_tokens=k)
    prompts = {0: _prompt(6, seed=1), 1: _prompt(9, seed=2)}
    got = {}
    for s, p in prompts.items():
        got[s] = [eng.prefill(p, s, max_new_tokens=16)
                  if kind == "paged" else eng.prefill(p, s)]

    def plain():
        nxt = eng.step()
        assert eng._ahead is not None and eng._ahead.rows.all()
        assert eng.step_valid.all()
        for s in prompts:
            got[s].append(int(nxt[s]))

    plain()
    plain()
    dropped = eng.ahead_rows_dropped
    eng.swap_params(jax.tree_util.tree_map(lambda p: p + 0, params))
    assert eng._ahead is None
    assert eng.ahead_rows_dropped == dropped + 2
    plain()
    # a verify: its positions come with the fetch, so it never follows
    # a step in flight, and leaves none
    toks, acc = eng.verify_step(np.zeros((SLOTS, k), np.int32))
    assert eng._ahead is None
    assert eng.ahead_rows_dropped == dropped + 4
    for s in prompts:
        got[s].extend(int(t) for t in toks[s, :acc[s]])
    plain()
    eng.poison_slot(1)
    assert eng._ahead is None
    eng.step()
    assert eng.take_bad_slots() == [1]       # the NEXT step retired
    for s, p in prompts.items():
        want = _one_shot(lm, p, 16)
        assert got[s] == want[:len(got[s])] and len(got[s]) >= 6


def test_a_changed_row_hands_on_neither_token_nor_flag(lm):
    """A slot quarantined at step N is freed while N+1 (NaN in that row
    too) is in flight, and re-admitted: N+1 marks the row invalid and
    does not flag the new owner."""
    eng = _engine("dense", lm)
    p0, p1 = _prompt(6, seed=1), _prompt(9, seed=2)
    eng.prefill(p0, 0)
    eng.prefill(p1, 1)
    eng.step()
    eng.poison_slot(1)
    eng.step()
    assert eng.take_bad_slots() == [1] and eng.step_valid.all()
    dropped = eng.ahead_rows_dropped
    eng.free(1)                              # N+1 is in flight
    assert eng.ahead_rows_dropped == dropped + 1
    assert not eng._ahead.rows[1] and eng._ahead.rows[0]
    redo = np.concatenate([p1, np.asarray(
        _one_shot(lm, p1, 8)[:1], np.int32)])
    tok = eng.prefill(redo, 1)
    assert tok == _one_shot(lm, p1, 8)[1]
    eng.step()                               # returns N+1
    assert list(eng.step_valid) == [True, False]
    assert eng.take_bad_slots() == []        # the stale NaN flag is not
    assert int(eng.tok[1]) == tok            # the new owner's; nor the
    assert int(eng.pos[1]) == len(redo)      # token
    nxt = eng.step()
    assert eng.step_valid.all()
    assert int(nxt[1]) == _one_shot(lm, p1, 8)[2]
    assert int(nxt[0]) == _one_shot(lm, p0, 8)[4]


def test_an_idle_step_in_flight_is_not_fetched(lm):
    """When every row of the step in flight changed hands, step() does
    not wait for it: the next launch starts from the host's tokens."""
    eng = _engine("dense", lm, slots=1)
    p = _prompt(5, seed=3)
    want = _one_shot(lm, p, 4)
    assert eng.prefill(p, 0) == want[0]
    assert int(eng.step()[0]) == want[1]
    eng.free(0)
    assert not eng._ahead.rows.any()
    q = _prompt(7, seed=4)
    wq = _one_shot(lm, q, 4)
    assert eng.prefill(q, 0) == wq[0]
    before = eng.decode_steps
    launched = _watch(eng)
    assert int(eng.step()[0]) == wq[1] and eng.step_valid[0]
    assert launched == [0, 1]                # from the host, then ahead
    assert eng.decode_steps == before + 1


def test_no_step_is_launched_past_max_len(lm):
    """A slot whose next position would be ``max_len`` gets no step
    ahead (its request must end with the step being fetched); the
    synchronous error for a slot that does not fit stays."""
    eng = _engine("dense", lm, slots=1)
    eng.buckets = (eng.max_len,)
    eng.prefill(_prompt(eng.max_len - 2, seed=5), 0)
    eng.step()                               # position max_len - 2
    assert eng._ahead is not None            # max_len - 1: the last row
    eng.step()
    assert eng._ahead is None                # nothing fits after it
    with pytest.raises(RuntimeError, match="max_len"):
        eng.step()


def _placed(params, how):
    """``params`` as a run may hold them: fresh from ``init`` (never
    placed), placed on a device (a restored checkpoint), or on a mesh of
    one device (what ``serve_run`` builds). jit keys an executable on
    each argument's placement, and a program's outputs inherit it."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    if how == "unplaced":
        return params
    dev = jax.devices()[0]
    if how == "device":
        return jax.device_put(params, dev)
    mesh = Mesh(np.asarray([dev]), ("data",))
    return jax.device_put(params, NamedSharding(mesh, PartitionSpec()))


@pytest.mark.parametrize("kind", ["dense", "paged"])
@pytest.mark.parametrize("how", ["unplaced", "device", "mesh"])
def test_one_decode_program_whatever_feeds_it(how, kind, lm):
    """The launch from the host's tokens (the placeholder) and the launch
    from the device's (a program output) run ONE executable, however the
    parameters were placed: no second trace or compile of the decode
    step at the first step launched ahead (on the chip that was an 8 s
    stall inside the serving window; PERF.md section 6)."""
    from tensorflow_distributed_tpu.serve import engine as engine_mod
    from tensorflow_distributed_tpu.serve.paging import (
        engine as paged_mod)

    model, params = lm
    caches = (engine_mod._compiled_step, paged_mod._compiled_step_paged)
    for c in caches:
        c.cache_clear()
    try:
        eng = _engine(kind, (model, _placed(params, how)))
        eng.warmup()
        jitted = eng._step_fn.__wrapped__
        assert jitted._cache_size() == 1
        eng.prefill(_prompt(5), 0)
        for _ in range(4):
            eng.step()
        eng.prefill(_prompt(6, seed=1), 1)   # a from_host row mid-run
        for _ in range(3):
            eng.step()
        assert eng.steps_ahead == 6 and eng.decode_steps == 7
        assert jitted._cache_size() == 1
    finally:
        for c in caches:
            c.cache_clear()


# --- when the step ahead is launched (PR 45) ------------------------------

class _Timeline:
    """One fake clock for the scheduler and the engine, and a device on
    it: the dispatch of a decode step takes the host ``LAUNCH_S``, the
    step ``STEP_S`` from the dispatch's end, or from the end of the step
    queued before it; a prefill nothing. It sits in the
    engine's own seams: its clock and sleep, the module's ``_is_ready``,
    and the watchdog's ``decode`` and ``drain``, where the host blocks
    (the clock jumps to the step's end there). ``log``: every dispatch, in
    order, with its time: ``step`` (from the host's tokens),
    ``step_ahead`` (from the step in flight's), ``prefill``."""

    STEP_S = 0.010
    LAUNCH_S = 0.0004
    sync_timeout_s = 1.0          # the watchdog surface step() asks for

    def __init__(self, eng, monkeypatch):
        from tensorflow_distributed_tpu.serve import engine as engine_mod
        from tensorflow_distributed_tpu.serve import scheduler as sched_mod

        self.t = self.busy_until = 0.0
        self.ends = {}            # step number -> when the device ends it
        self.flights = []         # (its token array, step number)
        self.log = []
        eng._clock, eng._sleep, eng._watchdog = self.now, self.advance, self
        monkeypatch.setattr(engine_mod, "_is_ready", self.is_ready)
        # an idle engine sleeps to the next arrival: on this clock
        monkeypatch.setattr(sched_mod, "time", types.SimpleNamespace(
            sleep=self.advance, time=lambda: 0.0))
        launch, dispatched, drain = (eng._launch, eng._dispatched,
                                     eng.drain)
        dispatch_step = eng._dispatch_step

        def slow_dispatch_step(prev, host):
            self.advance(self.LAUNCH_S)
            return dispatch_step(prev, host)

        def timed_launch(prev):
            self.log.append(
                ("step" if prev is None else "step_ahead", self.t))
            flight = launch(prev)
            self.busy_until = self.ends[flight.no] = (
                max(self.t, self.busy_until) + self.STEP_S)
            self.flights.append((flight.nxt, flight.no))
            return flight

        def logged_dispatched(*args):
            self.log.append(("prefill", self.t))
            return dispatched(*args)

        def timed_drain():
            if eng._ahead is not None:       # it waits the step out
                self.t = max(self.t, self.busy_until)
            drain()

        eng._launch, eng._dispatched = timed_launch, logged_dispatched
        eng.drain, eng._dispatch_step = timed_drain, slow_dispatch_step

    def now(self):
        return self.t

    def advance(self, seconds):
        self.t += max(seconds, 1e-5)         # a yield takes a moment too

    def is_ready(self, x):
        no = next(no for a, no in self.flights if a is x)
        return self.t >= self.ends[no]

    def decode(self, fetch, step):
        self.t = max(self.t, self.ends[step])
        return fetch()

    def whats(self):
        return [what for what, _ in self.log]


def _ms(log, what):
    return [round(1e3 * t, 1) for w, t in log if w == what]


def _served_on_a_timeline(kind, lm, monkeypatch, arrivals, news, **kw):
    eng = _engine(kind, lm, slots=3)
    tl = _Timeline(eng, monkeypatch)
    reqs = [Request(rid=i, prompt=_prompt(5 + 3 * i, seed=80 + i),
                    max_new_tokens=n, arrival_s=at)
            for i, (at, n) in enumerate(zip(arrivals, news))]
    sched = Scheduler(eng, clock=tl.now, **kw)
    done = {c.rid: c.tokens for c in sched.run(reqs)}
    assert done == _expect(lm, reqs)
    assert eng.on_wait is None and eng._ahead is None
    return eng, tl, sched.summary


@pytest.mark.parametrize("kind", ["dense", "paged"])
@pytest.mark.parametrize("due_ms, behind", [
    (34.0, "the_running_step"), (40.0, "its_successor")])
def test_an_arrival_in_a_steps_wait_goes_behind_the_running_step(
        due_ms, behind, kind, lm, monkeypatch):
    """Step 4 runs from 30.4 to 40.4 ms and the launch of its successor
    is begun 2.9 ms before its end (a launch of 0.4 ms and the 2.5 ms
    of lead the device needs).
    A request due at 34 ms is seen within a slice and its prefill
    dispatched at once, with NO step queued behind step 4; step 5
    follows from the host's tokens, the new row in it. One due at 40.0
    ms, inside the margin, finds step 5 queued and goes behind it, as
    before PR 45."""
    eng, tl, s = _served_on_a_timeline(
        kind, lm, monkeypatch, [0.0, 1e-3 * due_ms], [12, 4])
    whats = tl.whats()
    at = whats.index("prefill", 1)
    assert whats[:3] == ["prefill", "step", "step_ahead"]
    if behind == "the_running_step":
        assert whats[at - 1:at + 3] == ["step_ahead", "prefill", "step",
                                        "step_ahead"]
        assert _ms(tl.log, "prefill")[1] == pytest.approx(due_ms, abs=0.21)
        # step 4 was launched at 27.5, step 5 when step 4 had ended
        assert [t for w, t in tl.log[at - 1:at + 2]] == pytest.approx(
            [0.0275, 1e-3 * due_ms, 0.0404], abs=2.1e-4)
        assert eng.admits_first == s["admits_first"] == 2
        assert s["steps_ahead"] == s["decode_steps"] - 2
    else:
        assert whats[at - 1:at + 2] == ["step_ahead", "prefill",
                                        "step_ahead"]
        assert _ms(tl.log, "prefill")[1] == pytest.approx(40.4, abs=0.01)
        assert eng.admits_first == s["admits_first"] == 1
        assert s["steps_ahead"] == s["decode_steps"] - 1
    assert s["admissions"] == 2 and s["admitted_at_once"] == 2


def test_with_no_arrival_the_successor_is_launched_late_not_never(
        lm, monkeypatch):
    """No arrival: every step but the first is followed when it is about
    to end (2.9 ms before, here), before its fetch returns, and
    ``steps_ahead`` counts them all; the first, which nobody had timed,
    was followed at once."""
    eng, tl, s = _served_on_a_timeline("dense", lm, monkeypatch,
                                       [0.0], [8])
    assert tl.whats() == ["prefill", "step"] + ["step_ahead"] * 7
    assert _ms(tl.log, "step_ahead") == pytest.approx(
        [0.4] + [10.0 * n - 2.5 for n in range(2, 8)], abs=0.02)
    assert s["decode_steps"] == 7 and s["steps_ahead"] == 6
    assert s["ahead_rows_dropped"] == 1      # the step after the last


@pytest.mark.parametrize("stall_ms", [1.0, 5.0, 120.0])
def test_a_fetch_the_host_came_back_late_from_is_not_a_longer_step(
        stall_ms, lm, monkeypatch):
    """The host stalls inside step 3's fetch and sees its end
    ``stall_ms`` late: that reading says the step was longer and its
    successor began later than either did. One launch may come after
    its step's end for it (the device idles once); a launch that did
    forgets the reading, so the next step is followed at once and timed
    again, and every step after that is followed BEFORE it ends."""
    eng = _engine("dense", lm, slots=3)
    tl = _Timeline(eng, monkeypatch)
    decode = tl.decode

    def stalled(fetch, step):
        out = decode(fetch, step)
        if step == 3:
            tl.t += 1e-3 * stall_ms
        return out

    tl.decode = stalled
    reqs = [Request(rid=0, prompt=_prompt(5, seed=80), max_new_tokens=14,
                    arrival_s=0.0)]
    done = {c.rid: c.tokens for c in Scheduler(eng, clock=tl.now).run(reqs)}
    assert done == _expect(lm, reqs)
    # step n + 1 is launched from step n's tokens: step_ahead number
    # n - 1 of the log against the end of step n
    late = [n for n, at in enumerate(
                (t for w, t in tl.log if w == "step_ahead"), start=1)
            if at + tl.LAUNCH_S > tl.ends[n] + 1e-9]
    assert late in ([], [4])
    assert eng._step_s is None or eng._step_s < tl.STEP_S + 2.5e-3


@pytest.mark.parametrize("kind", ["dense", "paged"])
def test_a_step_no_longer_than_the_margin_is_followed_at_once(
        kind, lm, monkeypatch):
    """A step of 2 ms, under the margin (a launch of 0.4 ms and the
    2.5 ms of lead): the successor is due before the step began, so it
    is launched as each call comes in and the hook is never asked: an
    arrival during a step goes behind the step's successor, as before
    PR 45, and nothing waits for a launch that would come too late."""
    monkeypatch.setattr(_Timeline, "STEP_S", 0.002)
    eng, tl, s = _served_on_a_timeline(
        kind, lm, monkeypatch, [0.0, 0.0051], [12, 4])
    whats = tl.whats()
    at = whats.index("prefill", 1)
    assert whats[at - 1:at + 2] == ["step_ahead", "prefill", "step_ahead"]
    # each successor at the fetch's return: a launch after the step's end
    ahead = _ms(tl.log, "step_ahead")
    assert ahead[:3] == pytest.approx([0.4, 2.4, 4.4], abs=0.01)
    # the arrival was found at the loop's top, after step 3's fetch
    assert _ms(tl.log, "prefill")[1] == pytest.approx(6.4, abs=0.01)
    assert eng.admits_first == s["admits_first"] == 1
    assert s["steps_ahead"] == s["decode_steps"] - 1


@pytest.mark.parametrize("kind", ["dense", "paged"])
@pytest.mark.parametrize("decode_priority", [1, 2])
def test_a_burst_is_still_decode_priority_steps_apart(
        decode_priority, kind, lm, monkeypatch):
    """Two requests come due together inside step 4's wait. The first
    goes behind step 4; the step after it is the first decode iteration
    since that admission, so the second goes behind THAT step with
    ``decode_priority`` 1 and behind the one after with 2: never two
    prefills back to back, never one behind the first step after an
    admission when the clock asks for two."""
    eng, tl, s = _served_on_a_timeline(
        kind, lm, monkeypatch, [0.0, 0.034, 0.034], [16, 3, 3],
        decode_priority=decode_priority)
    whats = tl.whats()
    first = whats.index("prefill", 1)
    second = whats.index("prefill", first + 1)
    between = whats[first + 1:second]
    assert between == ["step", "step_ahead"][:decode_priority]
    assert whats[second + 1] == "step"       # it, too, found none queued
    assert eng.admits_first == 3
    assert _ms(tl.log, "prefill")[1:] == pytest.approx(
        [34.0, 40.8 + 10.0 * (decode_priority - 1)], abs=0.21)


def test_a_live_feed_is_read_inside_the_wait_at_a_bounded_cadence(
        lm, monkeypatch):
    """A feed's file holds, from 33.5 ms on, a request, a drain command
    and a second request. Inside step 4's wait the feed is polled every
    2 ms at most (not every 0.2 ms slice): the first request is taken
    there and goes behind the running step; the command and what
    follows it wait, in file order, for the loop's top, where the drain
    lands and the request after it is refused."""
    eng = _engine("dense", lm, slots=3)
    tl = _Timeline(eng, monkeypatch)

    first = Request(rid=0, prompt=_prompt(5, seed=80), max_new_tokens=12)
    second = Request(rid=1, prompt=_prompt(8, seed=81), max_new_tokens=4)

    class Feed:
        polls = []
        items = [second, {"cmd": "drain"},
                 Request(rid=2, prompt=_prompt(9, seed=82),
                         max_new_tokens=4)]

        def poll(self):
            self.polls.append(tl.t)
            if tl.t < 0.0335 or not self.items:
                return []
            out, self.items = self.items, []
            return out

    rejected = []
    reg = types.SimpleNamespace(emit=lambda event, **f: rejected.append(
        f["rid"]) if event == "serve_reject" else None)
    sched = Scheduler(eng, clock=tl.now, feed=Feed(), registry=reg)
    done = {c.rid: c.tokens for c in sched.run([first])}
    assert done == _expect(lm, [first, second])
    assert rejected == [2] and sched.draining
    whats = tl.whats()
    at = whats.index("prefill", 1)
    assert whats[at - 1:at + 2] == ["step_ahead", "prefill", "step"]
    assert 33.5 <= _ms(tl.log, "prefill")[1] <= 35.8
    # step 3's wait, 20.4 to 27.5 ms in 36 slices
    inside = [t for t in Feed.polls if 0.0205 < t < 0.0298]
    assert 3 <= len(inside) <= 5
    assert all(b - a >= 2e-3 - 1e-9 for a, b in zip(inside, inside[1:]))


@pytest.mark.parametrize("kind", ["dense", "paged"])
def test_a_step_nobody_timed_is_followed_at_once(kind, lm, monkeypatch):
    """By hand: the first step has no time to go by and is followed at
    once; so is the step after a ``drain``, and every step of an engine
    nobody gave a hook. A step launched onto the idle device after an
    admission began when it was launched: it IS followed late."""
    eng = _engine(kind, lm)
    tl = _Timeline(eng, monkeypatch)
    kw = {"max_new_tokens": 16} if kind == "paged" else {}
    p = _prompt(6, seed=7)
    want = _one_shot(lm, p, 12)
    got = [eng.prefill(p, 0, **kw)]

    def step():
        got.append(int(eng.step()[0]))
        assert eng.step_valid[0]

    step()
    step()
    # no hook: at once, as each call comes in (the last fetch's return)
    assert _ms(tl.log, "step_ahead") == [0.4, 10.4]
    eng.on_wait = lambda: False
    step()
    step()
    assert _ms(tl.log, "step_ahead")[2:] == pytest.approx(
        [27.5, 37.5], abs=0.02)
    eng.drain()
    assert eng._step_s is None
    at = tl.t
    assert at == pytest.approx(0.0504)
    step()                                   # from the host, and at once
    assert tl.log[-2:] == [("step", at),
                           ("step_ahead", pytest.approx(at + 0.0004))]
    step()                                   # timed again: late
    assert tl.log[-1][1] == pytest.approx(at + 0.0175, abs=2e-5)
    # an admission behind the running step, as the scheduler's hook
    # makes it: no successor, the next step from the host at the fetch
    q = _prompt(4, seed=8)
    eng.on_wait = lambda: eng.prefill(q, 1, fetch=False, **kw) is None
    step()
    assert eng._ahead is None and tl.whats()[-1] == "prefill"
    with pytest.raises(RuntimeError, match="first_token"):
        eng.step()
    assert eng.first_token() == _one_shot(lm, q, 4)[0]
    eng.on_wait = lambda: False
    at = tl.t
    step()
    assert tl.log[-2][0] == "step" and tl.log[-2][1] == at
    assert tl.log[-1] == ("step_ahead", pytest.approx(at + 0.0075,
                                                      abs=2e-5))
    assert int(eng.step()[1]) == _one_shot(lm, q, 4)[2]
    assert got == want[:len(got)] and len(got) == 9


FAKES = {
    # module -> its host-only engine with a bare step()
    "test_serve": "_FakeEngine", "test_autopilot": "_FakeEngine",
    "test_incident": "_FakeEngine", "test_paging": "_FakePagedEngine",
    "test_serve_observe": "_FakeEngine", "test_serve_slo": "_SLOFakeEngine",
    "test_fleet": "_fake_engine",
}


@pytest.mark.parametrize("module", sorted(FAKES))
def test_a_fake_engine_with_a_bare_step_still_serves(module):
    """The suites' fakes inherit ``on_wait`` from ``EngineSurface`` and
    are handed the hook, but their bare ``step()`` never calls it and
    their ``prefill`` is never asked for ``fetch=False``: they are
    driven as before, and the run takes its hook back."""
    make = getattr(importlib.import_module("tests." + module),
                   FAKES[module])
    eng = make() if module == "test_fleet" else make(num_slots=2)
    reqs = [Request(rid=i, prompt=np.asarray([i, 7, 7], np.int32),
                    max_new_tokens=5) for i in range(5)]
    sched = Scheduler(eng, decode_priority=1)
    done = sched.run(reqs)
    assert sorted(c.rid for c in done) == list(range(5))
    assert all(len(c.tokens) == 5 for c in done)
    assert eng.on_wait is None
    assert sched.summary["admits_first"] == 0
    assert sched.summary["admissions"] == 5


def test_the_watchdog_still_sees_an_injected_stall(lm):
    """``decode_stall`` sleeps inside the watched fetch, after the
    launch of the successor and whatever the hook did: past the
    deadline it is a StallError, not a hang."""
    from tensorflow_distributed_tpu.resilience.watchdog import (
        StallError, Watchdog)

    plan = parse_fault_plan("decode_stall@4:2s")
    eng = _engine("dense", lm, fault_plan=plan,
                  watchdog=Watchdog(sync_timeout_s=0.3))
    reqs = [Request(rid=0, prompt=_prompt(5), max_new_tokens=12)]
    with pytest.raises(StallError, match="decode step for step 4"):
        Scheduler(eng, fault_plan=plan).run(reqs)
    assert eng.on_wait is None               # the run took its hook back
