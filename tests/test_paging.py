"""Paged KV cache + radix prefix reuse (serve/paging).

Fast tier (jax-free): page-pool allocator invariants (no double free,
refcount round-trip, FIFO determinism), radix/session lookup + COW
preconditions, LRU eviction-under-pressure determinism, scheduler
wiring on a fake paged engine (admission deferral, retention routing,
session turn ordering), config validation, truncated-journal session
replay, report folding. Slow tier: real-engine dense-vs-paged token
identity across radix hits / copy-on-write / session re-attach,
quarantine shared-page survival, and the int8 / speculative
compositions.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from tensorflow_distributed_tpu.serve.engine import EngineSurface
from tensorflow_distributed_tpu.serve.paging.pool import (
    GARBAGE_PAGE, PagePool, PoolExhausted)
from tensorflow_distributed_tpu.serve.paging.radix import RadixCache
from tensorflow_distributed_tpu.serve.scheduler import Request, Scheduler


# --- page pool (pure host) ---------------------------------------------

def test_pool_alloc_release_roundtrip():
    pool = PagePool(num_pages=6, page_size=8)
    assert pool.capacity == 5 and pool.free_count == 5
    a = pool.alloc(3)
    assert len(a) == 3 and GARBAGE_PAGE not in a
    assert pool.pages_in_use == 3 and pool.peak_in_use == 3
    pool.retain(a[:1])                      # a second holder
    assert pool.release(a) == 2             # a[0] still referenced
    assert pool.pages_in_use == 1
    assert pool.release([a[0]]) == 1
    assert pool.free_count == 5 and pool.pages_in_use == 0
    assert pool.peak_in_use == 3            # high-water survives


def test_pool_double_free_and_exhaustion_raise():
    pool = PagePool(num_pages=4, page_size=8)
    a = pool.alloc(3)
    with pytest.raises(PoolExhausted, match="raise --serve.num-pages"):
        pool.alloc(1)
    pool.release(a)
    with pytest.raises(RuntimeError, match="double free"):
        pool.release([a[0]])
    with pytest.raises(RuntimeError, match="retain of unreferenced"):
        pool.retain([a[0]])
    # The write-off page is never allocatable and releasing it is a
    # tolerated no-op (tables pad with it).
    assert pool.release([GARBAGE_PAGE]) == 0


def test_pool_allocation_deterministic_fifo():
    def run():
        pool = PagePool(num_pages=8, page_size=4)
        a = pool.alloc(3)
        pool.release(a[1:2])
        b = pool.alloc(2)
        pool.release(a[:1] + b)
        return a, b, pool.alloc(4)

    assert run() == run()


# --- radix cache --------------------------------------------------------

def _pool_and_cache(num_pages=32, ps=4):
    pool = PagePool(num_pages, ps)
    return pool, RadixCache(pool)


def test_radix_insert_lookup_full_blocks():
    pool, rc = _pool_and_cache()
    toks = list(range(10))                 # 2 full blocks of 4 + tail
    pages = pool.alloc(3)
    rc.insert(toks, pages)
    # The tree holds refs on the 2 full-block pages only.
    assert pool.ref[pages[0]] == 2 and pool.ref[pages[1]] == 2
    assert pool.ref[pages[2]] == 1
    pool.release(pages)                    # the "slot" lets go
    assert pool.ref[pages[0]] == 1 and pool.ref[pages[2]] == 0
    got, m, src = rc.lookup("", toks, cap=9)
    assert src == "radix" and m == 8 and got == pages[:2]
    assert pool.ref[pages[0]] == 2         # caller owns a ref now
    # A diverging prompt matches only the shared leading block.
    other = toks[:4] + [99] * 6
    got2, m2, _ = rc.lookup("", other, cap=9)
    assert m2 == 4 and got2 == pages[:1]
    pool.release(got + got2)


def test_radix_cap_clamps_mid_page_for_cow():
    """A fully-cached prompt matches cap = plen - 1 tokens MID-page —
    the engine's copy-on-write precondition (the returned partial page
    is shared with the tree, refcount > 1)."""
    pool, rc = _pool_and_cache()
    toks = list(range(8))                  # exactly 2 blocks
    pages = pool.alloc(2)
    rc.insert(toks, pages)
    pool.release(pages)
    got, m, _ = rc.lookup("", toks, cap=7)
    assert m == 7 and len(got) == 2        # partial page 1 included
    assert pool.ref[got[1]] == 2           # shared -> COW must fire
    pool.release(got)


def test_radix_duplicate_insert_keeps_existing():
    pool, rc = _pool_and_cache()
    toks = list(range(8))
    first = pool.alloc(2)
    rc.insert(toks, first)
    dup = pool.alloc(2)
    rc.insert(toks, dup)                   # same blocks, new pages
    pool.release(first)
    pool.release(dup)
    assert pool.ref[dup[0]] == 0           # duplicate NOT adopted
    got, m, _ = rc.lookup("", toks + [9], cap=9)
    assert got == first and m == 8         # the original stays
    pool.release(got)


def test_session_store_match_transfer_and_divergence():
    pool, rc = _pool_and_cache(ps=4)
    conv = list(range(10))                 # 2.5 pages
    pages = pool.alloc(3)
    rc.session_store("s1", conv, pages)
    assert rc.sessions_live == 1
    pool.release(pages)                    # slot lets go; session holds
    assert pool.ref[pages[2]] == 1
    # The follow-up turn extends the conversation: the session's refs
    # TRANSFER to the caller and the entry is consumed.
    got, m, src = rc.lookup("s1", conv + [77, 78], cap=11)
    assert src == "session" and m == 10 and got == pages
    assert rc.sessions_live == 0
    assert pool.ref[pages[0]] == 1         # one ref: the caller's
    pool.release(got)
    # A diverged prompt drops the stale session and frees its pages.
    pages2 = pool.alloc(2)
    rc.session_store("s2", conv[:8], pages2)
    pool.release(pages2)
    got2, m2, _ = rc.lookup("s2", [99] * 12, cap=11)
    assert got2 == [] and m2 == 0 and rc.sessions_live == 0
    assert pool.ref[pages2[0]] == 0        # freed, not leaked


def test_eviction_under_pressure_deterministic():
    def run():
        pool, rc = _pool_and_cache(num_pages=16, ps=4)
        order = []
        for i in range(3):
            toks = [i * 100 + j for j in range(8)]
            pages = pool.alloc(2)
            rc.insert(toks, pages)
            pool.release(pages)
        pages = pool.alloc(2)
        rc.session_store("s", [7] * 8, pages)
        pool.release(pages)
        while rc.evict_one():
            order.append((pool.free_count, rc.cached_pages,
                          rc.sessions_live))
        return order

    a, b = run(), run()
    assert a == b and a                    # deterministic + non-empty
    assert a[-1][1] == 0 and a[-1][2] == 0  # fully drained


def test_evict_prefers_entries_that_free_pages():
    pool, rc = _pool_and_cache(num_pages=16, ps=4)
    held = pool.alloc(2)                   # "live slot" holds these
    rc.insert(list(range(8)), held)        # cached AND slot-held
    free_young = pool.alloc(2)
    rc.insert([50 + j for j in range(8)], free_young)
    pool.release(free_young)               # cache-only -> freeable
    # The slot-held entry is OLDER (inserted first) but evicting it
    # frees nothing — the freeing entry must win despite its age.
    before = pool.free_count
    assert rc.evict_one()
    assert pool.free_count == before + 1
    assert pool.ref[held[0]] == 2          # older entry untouched
    assert rc.evict_one()                  # the chain's first block
    assert rc.reclaimable_pages == 0
    assert pool.free_count == before + 2


# --- scheduler wiring (fake paged engine) ------------------------------

class _FakePagedEngine(EngineSurface):
    """Host-only engine with the PAGED surface the scheduler keys on:
    ``paged``, ``can_admit``, ``release(tokens=, session=)``,
    kwargs-taking ``prefill``. Token stream rid*100 + step."""

    paged = True

    def __init__(self, num_slots=2, max_len=256, admit_ok=True):
        self.num_slots = num_slots
        self.max_len = max_len
        self.buckets = (32, 64)
        self.active = np.zeros((num_slots,), bool)
        self.slot_rid = {}
        self.counts = {}
        self.prefills = 0
        self.prefill_compiles = 0
        self.decode_steps = 0
        self.admit_ok = admit_ok
        self.admit_checks = 0
        self.released = []                 # (rid, retained?, session)
        self.admitted = []                 # (rid, max_new, session)

    def fits(self, plen, max_new):
        return plen + max_new <= self.max_len

    def free_slots(self):
        return [s for s in range(self.num_slots) if not self.active[s]]

    def occupancy(self):
        return float(self.active.sum()) / self.num_slots

    def can_admit(self, plen, max_new):
        self.admit_checks += 1
        return (self.admit_ok if isinstance(self.admit_ok, bool)
                else self.admit_ok(plen, max_new))

    def prefill(self, prompt, slot, max_new_tokens=0, session=""):
        rid = int(prompt[0])
        self.active[slot] = True
        self.slot_rid[slot] = rid
        self.counts.setdefault(rid, 0)
        self.prefills += 1
        self.admitted.append((rid, max_new_tokens, session))
        self.counts[rid] += 1
        return rid * 100 + self.counts[rid] - 1

    def step(self):
        out = np.zeros((self.num_slots,), np.int32)
        for s in range(self.num_slots):
            if self.active[s]:
                rid = self.slot_rid[s]
                out[s] = rid * 100 + self.counts[rid]
                self.counts[rid] += 1
        self.decode_steps += 1
        return out

    def release(self, slot, tokens=None, session=""):
        self.released.append((self.slot_rid.get(slot),
                              tokens is not None, session))
        self.active[slot] = False

    def free(self, slot):
        self.release(slot)

    def paging_stats(self):
        return {"pool_occupancy": 0.5, "prefix_hit_rate": 0.25,
                "prefix_hits": 1, "pages_peak": 7,
                "page_evictions": 2, "cow_copies": 1}


def test_scheduler_passes_admission_context_and_retains():
    eng = _FakePagedEngine()
    reqs = [Request(rid=i, prompt=np.asarray([i], np.int32),
                    max_new_tokens=4, session=f"conv{i}")
            for i in range(3)]
    done = Scheduler(eng, decode_priority=2).run(reqs)
    assert len(done) == 3
    # prefill saw the budget + session; finish retained with them.
    assert sorted(eng.admitted) == [(0, 4, "conv0"), (1, 4, "conv1"),
                                    (2, 4, "conv2")]
    assert sorted(eng.released) == [(0, True, "conv0"),
                                    (1, True, "conv1"),
                                    (2, True, "conv2")]
    # Summary folded the paging stats (the fleet router's feed).
    assert eng.admit_checks >= 3


def test_scheduler_summary_and_snapshot_carry_paging_stats():
    eng = _FakePagedEngine()
    sched = Scheduler(eng, decode_priority=2)
    sched.run([Request(rid=0, prompt=np.asarray([0], np.int32),
                       max_new_tokens=3)])
    assert sched.summary["prefix_hit_rate"] == 0.25
    assert sched.summary["page_evictions"] == 2
    snap = sched.metrics_snapshot()
    assert snap["pool_occupancy"] == 0.5 and snap["cow_copies"] == 1


def test_scheduler_defers_admission_under_pool_pressure():
    """can_admit False while slots are LIVE defers (decode continues,
    pages free as requests finish); False with an IDLE engine is a
    loud error, never a hang."""
    eng = _FakePagedEngine()
    # Pool "too tight for two": deny whenever a slot is live — each
    # admission must wait for the previous request to fully drain.
    eng.admit_ok = lambda plen, max_new: not eng.active.any()
    reqs = [Request(rid=i, prompt=np.asarray([i], np.int32),
                    max_new_tokens=3) for i in range(2)]
    done = Scheduler(eng, decode_priority=1).run(reqs)
    assert len(done) == 2                  # deferral, not loss
    # Serialized by the pool: rid 1's first token came after rid 0's
    # last (admissions never overlapped).
    assert eng.admitted == [(0, 3, ""), (1, 3, "")]
    eng2 = _FakePagedEngine(admit_ok=False)
    with pytest.raises(RuntimeError, match="raise --serve.num-pages"):
        Scheduler(eng2).run([Request(rid=0,
                                     prompt=np.asarray([0], np.int32),
                                     max_new_tokens=3)])


def test_scheduler_quarantine_releases_without_retention():
    class _Poisoning(_FakePagedEngine):
        def step(self):
            out = super().step()
            self._bad = [s for s in range(self.num_slots)
                         if self.active[s]
                         and self.slot_rid[s] == 1
                         and self.counts[1] == 2]
            return out

        def take_bad_slots(self):
            out = getattr(self, "_bad", [])
            self._bad = []
            return out

    eng = _Poisoning()
    reqs = [Request(rid=i, prompt=np.asarray([i], np.int32),
                    max_new_tokens=4, session=f"c{i}")
            for i in range(2)]
    done = Scheduler(eng, decode_priority=2, slot_retries=2).run(reqs)
    assert len(done) == 2
    # rid 1 was quarantined once: that release carried NO tokens (the
    # poisoned pages must never feed the prefix cache); the final
    # finishes retained.
    assert (1, False, "") in eng.released
    assert eng.released.count((1, True, "c1")) == 1
    by_rid = {c.rid: c for c in done}
    assert by_rid[1].retries == 1


def test_scheduler_session_turns_admit_in_order():
    """A session's turn j+1 never admits before turn j finishes (a
    client cannot send a follow-up before it has the reply) — even
    when both are queued with slots free."""
    eng = _FakePagedEngine(num_slots=2)
    reqs = [
        Request(rid=0, prompt=np.asarray([0], np.int32),
                max_new_tokens=6, session="conv"),
        Request(rid=1, prompt=np.asarray([1], np.int32),
                max_new_tokens=6, session="conv"),
        Request(rid=2, prompt=np.asarray([2], np.int32),
                max_new_tokens=6),
    ]
    done = Scheduler(eng, decode_priority=1).run(reqs)
    assert len(done) == 3
    admits = [rid for rid, _, _ in eng.admitted]
    # rid 2 (no session) may admit anytime; rid 1 strictly after rid 0
    # RELEASED (finished), not merely after it started.
    rel0 = eng.released.index((0, True, "conv"))
    adm1 = eng.admitted.index((1, 6, "conv"))
    assert admits.index(0) < admits.index(1)
    assert [r for r, _, _ in eng.released].index(0) is not None
    # turn 2's admission event happens after turn 1's release event:
    # reconstruct interleaving via counters — turn 1 ran its full
    # budget before turn 2's first token.
    assert eng.counts[0] >= 6
    assert rel0 is not None and adm1 is not None


# --- config surface -----------------------------------------------------

def test_paged_config_validation():
    from tensorflow_distributed_tpu.config import TrainConfig

    ok = TrainConfig(mode="serve", model="gpt_lm")
    ok.serve.paged = True
    ok.serve.page_size = 8
    ok.serve.num_pages = 64
    ok.serve.session_turns = 2
    ok.validate()
    for field, value, msg in [
            ("page_size", 8, "add --serve.paged"),
            ("num_pages", 64, "add --serve.paged"),
            ("radix", False, "add --serve.paged")]:
        bad = TrainConfig(mode="serve", model="gpt_lm")
        setattr(bad.serve, field, value)
        with pytest.raises(ValueError, match=msg):
            bad.validate()
    bad = TrainConfig(mode="serve", model="gpt_lm")
    bad.serve.paged = True
    bad.serve.page_size = 0
    with pytest.raises(ValueError, match="page_size"):
        bad.validate()
    bad = TrainConfig(mode="serve", model="gpt_lm")
    bad.serve.session_turns = 2
    bad.serve.requests = "reqs.jsonl"
    with pytest.raises(ValueError, match="session"):
        bad.validate()


# --- journal: sessions survive a kill ----------------------------------

def test_journal_session_roundtrip_and_truncated_replay(tmp_path):
    from tensorflow_distributed_tpu.serve import journal as jm

    path = str(tmp_path / "j.jsonl")
    j = jm.RequestJournal(path)
    j.admit(0, [5, 6], 4, -1, session="conv0")
    j.token(0, 50, 0.1)
    j.token(0, 51, 0.2)
    j.admit(1, [7], 4, -1)
    j.flush()
    j.close()
    # The admit record is self-describing (standalone reads keep the
    # conversation linkage).
    recs = [json.loads(ln) for ln in
            open(path).read().splitlines()]
    assert recs[0]["sess"] == "conv0" and "sess" not in recs[3]
    # Truncated tail (the SIGKILL lands mid-write): replay skips it.
    with open(path, "a") as f:
        f.write('{"e": "tok", "rid": 0, "t"')
    played = jm.replay(path)
    assert played[0]["tokens"] == [50, 51]
    reqs = [Request(rid=0, prompt=np.asarray([5, 6], np.int32),
                    max_new_tokens=4, session="conv0"),
            Request(rid=1, prompt=np.asarray([7], np.int32),
                    max_new_tokens=4, session="")]
    out = jm.apply_replay(reqs, played)
    cont = next(r for r in out if r.rid == 0)
    # The continuation keeps its session id (dataclasses.replace), so
    # the resumed leg re-links the conversation.
    assert cont.session == "conv0"
    assert list(cont.prompt) == [5, 6, 50, 51]
    assert cont.max_new_tokens == 2


# --- report folding -----------------------------------------------------

def test_report_folds_paging_fields(tmp_path):
    from tensorflow_distributed_tpu.observe.report import (
        load_records, summarize)

    path = tmp_path / "m.jsonl"
    rows = [
        {"event": "prefix_hit", "slot": 0, "prompt_len": 40,
         "hit_tokens": 32, "tail_bucket": 16},
        {"event": "prefix_hit", "slot": 1, "prompt_len": 40,
         "hit_tokens": 24, "tail_bucket": 16},
        {"event": "page_evict", "evicted": 3, "reason": "pressure",
         "pages_free": 2, "pages_in_use": 20},
        {"event": "serve_summary", "requests": 4, "wall_s": 1.0,
         "tokens_per_sec": 10.0, "mean_slot_occupancy": 0.5,
         "prefix_hit_rate": 0.7, "prefix_hits": 2,
         "pool_occupancy": 0.8, "pages_peak": 21,
         "slot_pages_peak": 12, "page_evictions": 3,
         "cow_copies": 1, "sessions": 2},
    ]
    path.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    out = summarize(load_records(str(path)))
    assert out["serve_prefix_hit_events"] == 2
    assert out["serve_prefix_hit_tokens"] == 56
    assert out["serve_page_evict_events"] == 1
    assert out["serve_pages_evicted"] == 3
    assert out["serve_prefix_hit_rate"] == 0.7
    assert out["serve_pool_occupancy"] == 0.8
    assert out["serve_cow_copies"] == 1
    # Plain (dense) summaries stay shape-stable: no paging keys.
    plain = tmp_path / "p.jsonl"
    plain.write_text(json.dumps(
        {"event": "serve_summary", "requests": 1, "wall_s": 1.0,
         "tokens_per_sec": 5.0}) + "\n")
    out2 = summarize(load_records(str(plain)))
    assert not any(k.startswith("serve_prefix")
                   or k.startswith("serve_page") for k in out2)


# --- real engine (slow tier) -------------------------------------------

@pytest.fixture(scope="module")
def tiny_lm():
    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.models.transformer import (
        CausalLM, tiny_config)

    cfg = tiny_config(causal=True, max_len=64)
    model = CausalLM(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _decode(eng, prompt, n, session=""):
    slot = eng.free_slots()[0]
    if getattr(eng, "paged", False):
        first = eng.prefill(prompt, slot, max_new_tokens=n,
                            session=session)
    else:
        first = eng.prefill(prompt, slot)
    toks = [first]
    while len(toks) < n:
        toks.append(int(eng.step()[slot]))
    if getattr(eng, "paged", False):
        eng.release(slot, tokens=list(prompt) + toks, session=session)
    else:
        eng.free(slot)
    return toks


@pytest.mark.slow
def test_paged_prefix_hit_token_identity(tiny_lm):
    """THE e2e contract: radix hits, copy-on-write, and session
    re-attach all produce exactly the dense engine's greedy stream."""
    from tensorflow_distributed_tpu.serve.engine import SlotDecodeEngine
    from tensorflow_distributed_tpu.serve.paging.engine import (
        PagedSlotEngine)

    model, params = tiny_lm
    rng = np.random.default_rng(3)
    prefix = rng.integers(0, 64, 24).astype(np.int32)
    reqs = [np.concatenate([prefix,
                            rng.integers(0, 64, 4 + i).astype(
                                np.int32)]) for i in range(3)]
    aligned = rng.integers(0, 64, 32).astype(np.int32)  # COW trigger

    dense = SlotDecodeEngine(model, params, 2)
    paged = PagedSlotEngine(model, params, 2, page_size=8)
    ref = [_decode(dense, r, 6) for r in reqs]
    got = [_decode(paged, r, 6) for r in reqs]
    assert got == ref
    assert paged.prefix_hits >= 2          # later requests hit
    # Identical aligned prompt twice: full match capped at plen-1
    # lands mid-page on a SHARED page -> COW, identity preserved, and
    # the cached copy survives for the third pass.
    refA = _decode(dense, aligned, 6)
    assert _decode(paged, aligned, 6) == refA
    assert _decode(paged, aligned, 6) == refA
    assert _decode(paged, aligned, 6) == refA
    assert paged.cow_copies >= 1
    # Session re-attach: the follow-up turn extends the conversation
    # (partial tail page included) and matches the dense recompute.
    conv = list(reqs[0]) + ref[0]
    turn2 = np.asarray(conv + [9, 8, 7], np.int32)
    ref2 = _decode(dense, turn2, 5)
    p2 = PagedSlotEngine(model, params, 2, page_size=8)
    _decode(p2, reqs[0], 6, session="sess")
    assert _decode(p2, turn2, 5, session="sess") == ref2
    assert p2.prefix_hits == 1 and p2.radix.sessions_live == 1


@pytest.mark.slow
def test_can_admit_reserves_the_cow_page(tiny_lm):
    """Review finding: attaching cached pages makes them un-evictable,
    and a mid-page match then needs one MORE page for copy-on-write —
    can_admit must count it, or a tight pool passes the check and
    PoolExhausted crashes inside prefill instead of deferring."""
    from tensorflow_distributed_tpu.serve.paging.engine import (
        PagedSlotEngine)

    model, params = tiny_lm                # max_len 64 -> 4 pages of 16
    rng = np.random.default_rng(6)
    cached = rng.integers(0, 64, 32).astype(np.int32)   # 2 full blocks
    eng = PagedSlotEngine(model, params, 2, page_size=16, num_pages=6)
    _decode(eng, cached, 4)                # radix now holds 2 pages
    # Occupy: a live slot pins 2 pages -> 1 free, 2 reclaimable.
    eng.prefill(rng.integers(0, 64, 16).astype(np.int32), 0,
                max_new_tokens=16)
    assert eng.pool.free_count == 1
    # need = 3 (33 tokens) + 1 COW: 1 free + 2 reclaimable cannot
    # cover it — the old check said yes and prefill then exhausted.
    assert not eng.can_admit(32, 1)
    eng.free(0)                            # the live slot drains
    assert eng.can_admit(32, 1)
    out = _decode(eng, cached, 4)          # now admits, COW fires
    assert eng.cow_copies == 1
    from tensorflow_distributed_tpu.serve.engine import SlotDecodeEngine
    ref = _decode(SlotDecodeEngine(model, params, 2), cached, 4)
    assert out == ref


@pytest.mark.slow
def test_paged_quarantine_scrubs_private_spares_shared(tiny_lm):
    """slot_nan drill on a paged slot: only PRIVATE pages poison (the
    flag fires), the quarantine release scrubs them before they re-
    enter the free list, and the SHARED prefix pages keep serving
    correct tokens."""
    from tensorflow_distributed_tpu.serve.engine import SlotDecodeEngine
    from tensorflow_distributed_tpu.serve.paging.engine import (
        PagedSlotEngine)

    model, params = tiny_lm
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, 64, 24).astype(np.int32)
    dense = SlotDecodeEngine(model, params, 2)
    ref = _decode(dense, prompt, 6)
    eng = PagedSlotEngine(model, params, 2, page_size=8)
    _decode(eng, prompt, 6)                # seeds the prefix cache
    slot = eng.free_slots()[0]
    eng.prefill(prompt, slot, max_new_tokens=6)
    assert eng.prefix_hits == 1            # shared pages attached
    eng.poison_slot(slot)
    eng.step()
    assert eng.take_bad_slots() == [slot]
    eng.free(slot)                         # quarantine: no retention
    # The shared pages survive — a fresh identical request still hits
    # AND still decodes the dense stream (nothing scrubbed them, no
    # NaN leaked through a recycled page).
    assert _decode(eng, prompt, 6) == ref
    assert eng.prefix_hits == 2
    # And the scrubbed pages are genuinely clean: fill the pool with
    # fresh admissions that reuse them.
    other = rng.integers(0, 64, 20).astype(np.int32)
    assert _decode(eng, other, 6) == _decode(dense, other, 6)


@pytest.mark.slow
def test_paged_composes_with_int8_and_speculation(tiny_lm):
    """kv_dtype=int8 and spec_tokens both ride the paged executables:
    int8-paged matches int8-dense bit-for-bit (same quantized math,
    relocated bytes), and paged speculation stays token-identical to
    plain paged decode."""
    import dataclasses as dc

    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.serve.engine import SlotDecodeEngine
    from tensorflow_distributed_tpu.serve.paging.engine import (
        PagedSlotEngine)

    model, params = tiny_lm
    q = type(model)(dc.replace(model.cfg, kv_cache_quant="int8"),
                    model.mesh)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 64, 12 + 3 * i).astype(np.int32)
               for i in range(3)]
    d8 = SlotDecodeEngine(q, params, 2)
    p8 = PagedSlotEngine(q, params, 2, page_size=8)
    for pr in prompts:
        assert _decode(p8, pr, 6) == _decode(d8, pr, 6)
    assert p8.page_bytes() < PagedSlotEngine(
        model, params, 2, page_size=8).page_bytes()
    # Speculation: k-gram self-draft over the paged verify program.
    from tensorflow_distributed_tpu.serve.speculate import SelfDraft

    plain = PagedSlotEngine(model, params, 2, page_size=8)
    ref = [_decode(plain, pr, 8) for pr in prompts]
    spec_eng = PagedSlotEngine(model, params, 2, page_size=8,
                               spec_tokens=2)
    sched = Scheduler(spec_eng, decode_priority=2,
                      speculator=SelfDraft(2, 2))
    done = sched.run([Request(rid=i, prompt=pr, max_new_tokens=8)
                      for i, pr in enumerate(prompts)])
    by_rid = {c.rid: c.tokens for c in done}
    assert [by_rid[i] for i in range(3)] == ref
    assert spec_eng.verify_steps > 0
