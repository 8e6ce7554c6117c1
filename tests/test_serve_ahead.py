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
"""

from __future__ import annotations

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
