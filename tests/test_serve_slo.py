"""Fast-path serving suite: speculative decoding, int8 KV serving
knobs, and the SLO-aware scheduler.

Fast tier (jax-free, per the repo's tier rules): speculation host math
(accept_length, k-gram proposer, draft-config grammar), slo_mix
grammar, the new ServeConfig knob validation, the SLO policy against a
continuation-aware fake engine (priority inversion impossible, quota
exhaustion requeues instead of starving, preempted request's final
stream token-identical), speculative multi-token retirement semantics
(budget/EOS truncation mid-chain, accept telemetry), the journal's
class/tenant-tagged admits, and the report's new serve folding. Slow
tier (compiles the tiny GPT): real-engine self-draft token identity,
the perfect-draft accept-rate pin, int8 cache accounting on a real
engine, and a mode=serve e2e with speculation + SLO armed.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from tensorflow_distributed_tpu.serve.engine import EngineSurface
from tensorflow_distributed_tpu.serve.scheduler import (
    Request, Scheduler, parse_slo_mix)
from tensorflow_distributed_tpu.serve.speculate import (
    accept_length, kgram_propose, parse_draft_config)


# --- speculation host math ---------------------------------------------

def test_accept_length():
    # Full accept, partial, none; the bonus token is NOT counted here.
    assert accept_length([5, 6, 7], [5, 6, 7, 8]) == 3
    assert accept_length([5, 6, 9], [5, 6, 7, 8]) == 2
    assert accept_length([1, 2, 3], [9, 9, 9, 9]) == 0
    with pytest.raises(ValueError, match="k \\+ 1"):
        accept_length([1, 2], [1, 2])


def test_kgram_propose_periodic_history():
    # Period-4 history: the most recent earlier suffix occurrence is
    # one period back, so proposals continue the cycle exactly.
    hist = [1, 2, 3, 4] * 3
    assert kgram_propose(hist, k=4, g=3) == [1, 2, 3, 4]
    # Continuation shorter than k pads by repeating its final token.
    assert kgram_propose(hist, k=6, g=3) == [1, 2, 3, 4, 4, 4]


def test_kgram_propose_fallbacks():
    # No earlier occurrence -> repeat the last token (the degenerate
    # argmax-loop case); empty history -> zeros.
    assert kgram_propose([7, 8, 9], k=3, g=3) == [9, 9, 9]
    assert kgram_propose([], k=2) == [0, 0]
    # History shorter than the suffix still proposes.
    assert kgram_propose([4], k=2, g=3) == [4, 4]
    # Match whose continuation is shorter than k pads by extension.
    assert kgram_propose([5, 1, 2, 3, 5, 1, 2, 3], k=6, g=3)[:4] == [
        5, 1, 2, 3]


def test_parse_draft_config():
    assert parse_draft_config("tiny") == {"size": "tiny",
                                          "overrides": {}}
    parsed = parse_draft_config("size=tiny,n_layers=1,pos_emb=rope")
    assert parsed["size"] == "tiny"
    assert parsed["overrides"] == {"n_layers": 1, "pos_emb": "rope"}
    with pytest.raises(ValueError, match="key=value"):
        parse_draft_config("tiny,n_layers=1")
    with pytest.raises(ValueError, match="empty"):
        parse_draft_config("")


def test_parse_slo_mix():
    mix = parse_slo_mix("high:0.25,batch:0.25")
    assert mix == {"high": 0.25, "batch": 0.25, "standard": 0.5}
    assert parse_slo_mix("high:1")["standard"] == 0.0
    with pytest.raises(ValueError, match="unknown SLO class"):
        parse_slo_mix("gold:0.5")
    with pytest.raises(ValueError, match="class:fraction"):
        parse_slo_mix("high=0.5")
    with pytest.raises(ValueError, match="twice"):
        parse_slo_mix("high:0.2,high:0.2")
    with pytest.raises(ValueError, match="> 1"):
        parse_slo_mix("high:0.8,batch:0.4")


# --- config validation (the new serve knobs) ---------------------------

def _serve_cfg(**kw):
    from tensorflow_distributed_tpu.config import TrainConfig

    cfg = TrainConfig(mode="serve", model="gpt_lm")
    for k, v in kw.items():
        setattr(cfg.serve, k, v)
    return cfg


def test_serve_config_new_knobs_valid():
    _serve_cfg(spec_tokens=4).validate()
    _serve_cfg(spec_tokens=4, draft_config="tiny").validate()
    _serve_cfg(kv_dtype="int8").validate()
    _serve_cfg(policy="slo", tenant_quota=64, tenants=2,
               slo_mix="high:0.25").validate()
    # A request file carries its own tenant fields — quota without
    # --serve.tenants is meaningful there.
    _serve_cfg(policy="slo", tenant_quota=64,
               requests="r.jsonl").validate()


@pytest.mark.parametrize("kw,match", [
    (dict(spec_tokens=-1), "spec_tokens"),
    (dict(draft_config="tiny"), "spec-tokens"),
    (dict(spec_tokens=2, spec_kgram=0), "spec_kgram"),
    (dict(kv_dtype="fp8"), "kv_dtype"),
    (dict(policy="edf"), "policy"),
    (dict(tenant_quota=-1), "tenant_quota"),
    (dict(tenant_quota=5), "policy slo"),
    (dict(policy="slo", tenant_quota=5), "tenants to meter"),
    (dict(slo_mix="high:0.5"), "policy slo"),
    (dict(policy="slo", slo_mix="gold:0.5"), "unknown SLO class"),
    (dict(policy="slo", slo_mix="high:0.5", requests="r.jsonl"),
     "SYNTHETIC"),
    (dict(tenants=0), "tenants"),
])
def test_serve_config_new_knob_rejections(kw, match):
    with pytest.raises(ValueError, match=match):
        _serve_cfg(**kw).validate()


# --- fake engines (no jax; continuation-aware streams) ------------------

class _SLOFakeEngine(EngineSurface):
    """Host-only engine: token stream is a pure function of
    (rid, tokens-emitted-so-far) — prefill of a continuation prompt
    resumes the SAME stream, so token identity through preemption is
    checkable exactly. rid rides prompt[0]; emitted count =
    len(prompt) - 1 (base prompts are length 1)."""

    def __init__(self, num_slots=1, max_len=256):
        self.num_slots = num_slots
        self.max_len = max_len
        self.buckets = (64, 128)
        self.active = np.zeros((num_slots,), bool)
        self.slot_rid = {}
        self.counts = {}
        self.prefills = 0
        self.prefill_compiles = 0
        self.decode_steps = 0

    def fits(self, plen, max_new):
        return plen + max_new <= self.max_len

    def free_slots(self):
        return [s for s in range(self.num_slots) if not self.active[s]]

    def occupancy(self):
        return float(self.active.sum()) / self.num_slots

    def prefill(self, prompt, slot):
        rid = int(prompt[0])
        self.active[slot] = True
        self.slot_rid[slot] = rid
        self.counts[rid] = len(prompt) - 1   # continuation-aware
        self.prefills += 1
        return rid * 100 + self.counts[rid]

    def step(self):
        out = np.zeros((self.num_slots,), np.int32)
        for s in range(self.num_slots):
            if self.active[s]:
                rid = self.slot_rid[s]
                self.counts[rid] += 1
                out[s] = rid * 100 + self.counts[rid]
        self.decode_steps += 1
        return out

    def free(self, slot):
        self.active[slot] = False


class _SpecFakeEngine(_SLOFakeEngine):
    """Adds the speculative surface: every verify dispatch accepts
    ``accept`` proposals (+ the bonus), emitting the same deterministic
    stream in chunks."""

    def __init__(self, num_slots=1, max_len=256, spec_tokens=3,
                 accept=None):
        super().__init__(num_slots, max_len)
        self.spec_tokens = spec_tokens
        self.accept = (spec_tokens if accept is None else accept)
        self.verify_steps = 0

    def can_verify(self):
        return True

    def verify_step(self, props):
        k = self.spec_tokens
        assert np.asarray(props).shape == (self.num_slots, k)
        toks = np.zeros((self.num_slots, k + 1), np.int32)
        acc = np.zeros((self.num_slots,), np.int32)
        for s in range(self.num_slots):
            if not self.active[s]:
                continue
            rid = self.slot_rid[s]
            a = min(self.accept, k)
            for j in range(a + 1):
                self.counts[rid] += 1
                toks[s, j] = rid * 100 + self.counts[rid]
            acc[s] = a + 1
        self.decode_steps += 1
        self.verify_steps += 1
        return toks, acc


class _CountingSpeculator:
    """Records the scheduler's lifecycle calls; proposes zeros."""

    def __init__(self, num_slots, k):
        self.num_slots, self.k = num_slots, k
        self.admits = []
        self.frees = []
        self.syncs = 0

    def propose(self, histories):
        # Histories must cover exactly the live slots.
        assert all(len(h) > 0 for h in histories.values())
        return np.zeros((self.num_slots, self.k), np.int32)

    def observe_admit(self, slot, prompt, first_tok):
        self.admits.append((slot, int(first_tok)))

    def observe_free(self, slot):
        self.frees.append(slot)

    def sync_from(self, engine):
        self.syncs += 1


def _expected(rid, max_new, plen=1):
    return [rid * 100 + (plen - 1) + j for j in range(max_new)]


# --- SLO policy against the fake engine --------------------------------

def _admission_order(reqs, **kw):
    eng = _SLOFakeEngine(num_slots=1)
    seen = []
    sched = Scheduler(eng, decode_priority=2,
                      on_token=lambda rid, tok, fin: (
                          seen.append(rid) if rid not in seen else None),
                      **kw)
    done = sched.run(reqs)
    assert len(done) == len(reqs)
    return seen, done, sched


def test_slo_no_priority_inversion():
    """A high-class arrival never queues behind a lower class while a
    slot frees: with everything queued at t=0 on one slot, admission
    order is class order (then arrival), not arrival order."""
    reqs = [Request(rid=0, prompt=np.asarray([0], np.int32),
                    max_new_tokens=4, slo="standard"),
            Request(rid=1, prompt=np.asarray([1], np.int32),
                    max_new_tokens=4, slo="batch"),
            Request(rid=2, prompt=np.asarray([2], np.int32),
                    max_new_tokens=4, slo="standard"),
            Request(rid=3, prompt=np.asarray([3], np.int32),
                    max_new_tokens=4, slo="high"),
            Request(rid=4, prompt=np.asarray([4], np.int32),
                    max_new_tokens=4, slo="high")]
    fifo_order, _, _ = _admission_order(reqs, policy="fifo")
    assert fifo_order == [0, 1, 2, 3, 4]          # arrival order
    slo_order, done, _ = _admission_order(reqs, policy="slo")
    # The t=0 pick is already class-ordered: highs (arrival order
    # within the class), then standards, then batch LAST.
    assert slo_order == [3, 4, 0, 2, 1]
    # Streams are unaffected by admission order (identical per rid).
    for c in done:
        assert c.tokens == _expected(c.rid, 4)


def test_slo_quota_exhaustion_requeues_not_starves():
    """A tenant at its token quota is deferred while an under-quota
    tenant waits — and still served once nothing under-quota remains
    (work-conserving: exhaustion cannot starve)."""
    reqs = [Request(rid=0, prompt=np.asarray([0], np.int32),
                    max_new_tokens=6, tenant="a"),
            Request(rid=1, prompt=np.asarray([1], np.int32),
                    max_new_tokens=6, tenant="a"),
            Request(rid=2, prompt=np.asarray([2], np.int32),
                    max_new_tokens=6, tenant="b")]
    order, done, sched = _admission_order(reqs, policy="slo",
                                          tenant_quota=4)
    # rid0 exhausts tenant a's quota (6 tokens > 4): rid2 (tenant b,
    # under quota) jumps rid1 despite arriving later; rid1 still
    # completes with its full exact stream.
    assert order == [0, 2, 1]
    assert all(c.tokens == _expected(c.rid, 6) for c in done)
    # Without quotas, arrival order holds.
    order2, _, _ = _admission_order(
        [Request(rid=r.rid, prompt=r.prompt,
                 max_new_tokens=r.max_new_tokens, tenant=r.tenant)
         for r in reqs], policy="slo")
    assert order2 == [0, 1, 2]


def test_slo_preempt_token_identity():
    """Preempt-and-requeue: a late high-class arrival evicts the live
    batch request once it has waited out the decode-priority clock;
    the preempted request's FINAL stream is token-identical to the
    unpreempted (FIFO) run, and the preemption is accounted."""
    import itertools

    # A fake clock the test drives: arrivals keyed to decode steps.
    t = itertools.count()

    def reqs():
        return [Request(rid=0, prompt=np.asarray([0], np.int32),
                        max_new_tokens=12, slo="batch"),
                Request(rid=1, prompt=np.asarray([1], np.int32),
                        max_new_tokens=4, arrival_s=3.0, slo="high")]

    def run(policy):
        eng = _SLOFakeEngine(num_slots=1)
        sched = Scheduler(eng, decode_priority=2, policy=policy,
                          clock=lambda: float(next(t)))
        return {c.rid: c for c in sched.run(reqs())}, sched

    done_f, _ = run("fifo")
    t = itertools.count()
    done_s, sched = run("slo")
    assert sched.summary["preemptions"] == 1
    assert done_s[0].preempts == 1
    # The high request was served mid-batch-request, so it FINISHED
    # before the preempted one despite arriving later.
    assert done_s[1].decoded == 4
    # Token identity: the preemption continuation re-derives exactly
    # the stream the unpreempted run produced.
    for rid in (0, 1):
        assert done_s[rid].tokens == done_f[rid].tokens
        assert done_s[rid].tokens == _expected(rid, len(
            done_f[rid].tokens))


def test_slo_preempt_emits_event_not_recovery():
    from tensorflow_distributed_tpu.observe.registry import (
        MetricsRegistry)

    import itertools
    t = itertools.count()
    eng = _SLOFakeEngine(num_slots=1)
    reg = MetricsRegistry()
    sched = Scheduler(eng, decode_priority=2, policy="slo",
                      registry=reg, clock=lambda: float(next(t)))
    sched.run([Request(rid=0, prompt=np.asarray([0], np.int32),
                       max_new_tokens=12, slo="batch"),
               Request(rid=1, prompt=np.asarray([1], np.int32),
                       max_new_tokens=4, arrival_s=3.0, slo="high")])
    events = [r["event"] for r in reg.records]
    assert "preempt" in events
    assert "recovery" not in events   # policy, not failure
    req_recs = [r for r in reg.records if r["event"] == "serve_request"]
    assert {r["slo"] for r in req_recs} == {"high", "batch"}
    # Preemption continuations are NOT the recovery population.
    assert not any(r["recovery_window"] for r in req_recs)
    summary = [r for r in reg.records if r["event"] == "serve_summary"]
    assert summary[-1]["policy"] == "slo"
    assert summary[-1]["preemptions"] == 1


def test_preempt_skips_victim_outgrowing_ladder():
    """Preemption is ELECTIVE: a victim whose continuation prompt
    would exceed a user-pinned bucket ladder is skipped instead of
    crashing the run — the high request waits for a natural free."""
    import itertools

    t = itertools.count()
    eng = _SLOFakeEngine(num_slots=1)
    eng.buckets = (8,)                  # tight user-pinned ladder
    reqs = [Request(rid=0, prompt=np.asarray([0] * 7, np.int32),
                    max_new_tokens=10, slo="batch"),
            Request(rid=1, prompt=np.asarray([1], np.int32),
                    max_new_tokens=3, arrival_s=4.0, slo="high")]
    sched = Scheduler(eng, decode_priority=2, policy="slo",
                      clock=lambda: float(next(t)))
    done = {c.rid: c for c in sched.run(reqs)}
    assert sched.summary["preemptions"] == 0    # skipped, not crashed
    assert len(done[0].tokens) == 10 and len(done[1].tokens) == 3


def test_preempt_keeps_recovery_provenance():
    """A journal-replay continuation (recovery base tokens) that later
    gets preempted must STAY in the recovery-window population — the
    policy flag must not erase recovery provenance."""
    import itertools

    t = itertools.count()
    eng = _SLOFakeEngine(num_slots=1)
    cont = Request(rid=0, prompt=np.asarray([0, 100, 101], np.int32),
                   max_new_tokens=10, slo="batch")
    cont._base_tokens = [100, 101]     # replayed by a dead leg
    high = Request(rid=1, prompt=np.asarray([1], np.int32),
                   max_new_tokens=4, arrival_s=3.0, slo="high")
    sched = Scheduler(eng, decode_priority=2, policy="slo",
                      clock=lambda: float(next(t)))
    done = {c.rid: c for c in sched.run([cont, high])}
    assert sched.summary["preemptions"] == 1
    assert done[0].preempts == 1
    assert done[0].recovery_window     # provenance survived preemption
    # A preempted FRESH request stays out of the recovery population.
    assert not done[1].recovery_window


# --- speculative retirement semantics (fake engine) --------------------

def test_spec_multi_token_retirement_and_stats():
    """One verify dispatch retires accepted+1 tokens per slot in
    stream order; the summary carries the accept telemetry."""
    eng = _SpecFakeEngine(num_slots=2, spec_tokens=3)
    spec = _CountingSpeculator(2, 3)
    sched = Scheduler(eng, decode_priority=2, speculator=spec)
    done = {c.rid: c for c in sched.run(
        [Request(rid=i, prompt=np.asarray([i], np.int32),
                 max_new_tokens=9) for i in range(3)])}
    for rid, c in done.items():
        assert c.tokens == _expected(rid, 9)
    s = sched.summary
    assert s["verify_steps"] == eng.verify_steps > 0
    assert s["accept_rate"] == 1.0          # fake accepts everything
    assert s["spec_proposed"] >= s["spec_accepted"] > 0
    # Lifecycle hooks: every admission/free mirrored to the
    # speculator, one sync per decode iteration.
    assert len(spec.admits) == 3 and len(spec.frees) == 3
    assert spec.syncs == eng.decode_steps


def test_spec_budget_truncated_mid_chain():
    """A request whose budget lands mid-chain stops exactly at the
    budget — surplus accepted tokens are discarded, never streamed or
    journaled."""
    eng = _SpecFakeEngine(num_slots=1, spec_tokens=4)
    spec = _CountingSpeculator(1, 4)
    streamed = []
    sched = Scheduler(eng, decode_priority=2, speculator=spec,
                      on_token=lambda rid, tok, fin: streamed.append(
                          tok))
    done = sched.run([Request(rid=1, prompt=np.asarray([1], np.int32),
                              max_new_tokens=7)])   # 1 + 5 + trunc
    assert done[0].tokens == _expected(1, 7)
    assert done[0].finish == "length"
    assert len(done[0].tokens) == 7
    assert streamed == done[0].tokens   # nothing past the budget


def test_spec_eos_truncates_mid_chain():
    eos = 1 * 100 + 3                  # 4th emitted token of rid 1
    #                                    (prefill emits rid*100 + 0)
    eng = _SpecFakeEngine(num_slots=1, spec_tokens=4)
    sched = Scheduler(eng, decode_priority=2,
                      speculator=_CountingSpeculator(1, 4))
    done = sched.run([Request(rid=1, prompt=np.asarray([1], np.int32),
                              max_new_tokens=20, eos_id=eos)])
    assert done[0].finish == "eos"
    assert done[0].tokens == _expected(1, 4)
    assert done[0].tokens[-1] == eos


def test_spec_falls_back_without_headroom():
    """can_verify() False routes the iteration through the plain
    step — the stream is seamless across the mode switch."""

    class _Flaky(_SpecFakeEngine):
        def can_verify(self):
            return self.decode_steps % 2 == 0   # alternate modes

    eng = _Flaky(num_slots=1, spec_tokens=3)
    sched = Scheduler(eng, decode_priority=2,
                      speculator=_CountingSpeculator(1, 3))
    done = sched.run([Request(rid=2, prompt=np.asarray([2], np.int32),
                              max_new_tokens=10)])
    assert done[0].tokens == _expected(2, 10)
    assert 0 < eng.verify_steps < eng.decode_steps


# --- journal: class/tenant-tagged admits -------------------------------

def test_journal_admit_carries_slo_tenant(tmp_path):
    from tensorflow_distributed_tpu.serve import journal as journal_mod

    path = str(tmp_path / "j.jsonl")
    j = journal_mod.RequestJournal(path)
    j.admit(0, [5, 6], 8, -1, slo="high", tenant="acme")
    j.admit(1, [7], 8, -1)                 # defaults stay compact
    j.token(0, 9, 0.5)
    j.close()
    lines = [json.loads(ln) for ln in open(path)]
    assert lines[0]["slo"] == "high" and lines[0]["tenant"] == "acme"
    assert "slo" not in lines[1] and "tenant" not in lines[1]
    # Replay (the resume path) is untouched by the new fields.
    played = journal_mod.replay(path)
    assert played[0]["tokens"] == [9] and not played[0]["done"]


# --- report folding ----------------------------------------------------

def test_report_folds_slo_and_spec(tmp_path):
    from tensorflow_distributed_tpu.observe.report import (
        load_records, summarize)

    recs = ([{"event": "serve_request", "rid": i,
              "ttft_ms": 10.0 + 50.0 * (i % 2), "tok_ms": 2.0,
              "slo": ("high" if i % 2 == 0 else "batch")}
             for i in range(10)]
            + [{"event": "preempt", "rid": 3, "slot": 0},
               {"event": "serve_summary", "tokens_per_sec": 900.0,
                "policy": "slo", "preemptions": 1, "spec_tokens": 4,
                "verify_steps": 42, "accept_rate": 0.8}])
    path = tmp_path / "m.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in recs))
    out = summarize(load_records(str(path)))
    assert out["serve_policy"] == "slo"
    assert out["serve_preemptions"] == 1
    assert out["serve_preempt_events"] == 1
    assert out["serve_accept_rate"] == 0.8
    assert out["serve_spec_tokens"] == 4
    assert out["serve_ttft_ms_p95_high"] == pytest.approx(10.0)
    assert out["serve_ttft_ms_p95_batch"] == pytest.approx(60.0)


def test_report_plain_fifo_unchanged(tmp_path):
    """No classes beyond the default -> no per-class keys (plain
    reports keep their exact shape)."""
    from tensorflow_distributed_tpu.observe.report import (
        load_records, summarize)

    recs = [{"event": "serve_request", "rid": i, "ttft_ms": 5.0,
             "slo": "standard"} for i in range(4)]
    path = tmp_path / "m.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in recs))
    out = summarize(load_records(str(path)))
    assert not any(k.startswith("serve_ttft_ms_p95_") for k in out)


# --- real engine (slow tier) -------------------------------------------

def _tiny_serving_model(max_len=96, **overrides):
    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.models.transformer import gpt_lm

    model = gpt_lm(None, size="tiny", max_len=max_len,
                   dropout_rate=0.0, **overrides)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


@pytest.mark.slow
def test_spec_self_draft_token_identity_real_engine():
    """Speculation is token-identical to plain continuous decode on
    the REAL engine (fresh-init chains are chaotic — accept rate ~0 —
    which is exactly the adversarial case for identity)."""
    from tensorflow_distributed_tpu.serve.buckets import default_buckets
    from tensorflow_distributed_tpu.serve.engine import SlotDecodeEngine
    from tensorflow_distributed_tpu.serve.speculate import SelfDraft

    model, params = _tiny_serving_model()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, model.cfg.vocab_size,
                            size=int(n)).astype(np.int32)
               for n in rng.integers(4, 24, size=6)]
    buckets = default_buckets(32)

    def run(spec_tokens):
        eng = SlotDecodeEngine(model, params, 2, buckets=buckets,
                               spec_tokens=spec_tokens)
        spec = (SelfDraft(2, spec_tokens) if spec_tokens else None)
        sched = Scheduler(eng, decode_priority=3, speculator=spec)
        reqs = [Request(rid=i, prompt=p, max_new_tokens=24)
                for i, p in enumerate(prompts)]
        return {c.rid: c.tokens for c in sched.run(reqs)}, sched

    ref, _ = run(0)
    out, sched = run(4)
    assert all(ref[i] == out[i] for i in range(len(prompts)))
    assert sched.summary["verify_steps"] > 0
    assert 0.0 <= sched.summary["accept_rate"] <= 1.0


@pytest.mark.slow
def test_perfect_draft_accepts_everything_real_engine():
    """A DraftSpeculator whose draft IS the target model proposes the
    target's own argmax chain — every proposal accepted, accept_rate
    exactly 1.0, output still token-identical. Pins the draft-model
    mirror (prefill/insert/scan/sync) end to end."""
    from tensorflow_distributed_tpu.serve.buckets import default_buckets
    from tensorflow_distributed_tpu.serve.engine import SlotDecodeEngine
    from tensorflow_distributed_tpu.serve.speculate import (
        DraftSpeculator)

    model, params = _tiny_serving_model()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, model.cfg.vocab_size,
                            size=int(n)).astype(np.int32)
               for n in rng.integers(4, 16, size=4)]
    buckets = default_buckets(16)
    K = 3

    def run(spec):
        eng = SlotDecodeEngine(model, params, 2, buckets=buckets,
                               spec_tokens=K if spec else 0)
        drafter = (DraftSpeculator(model, params, 2, buckets, K)
                   if spec else None)
        sched = Scheduler(eng, decode_priority=3, speculator=drafter)
        reqs = [Request(rid=i, prompt=p, max_new_tokens=16)
                for i, p in enumerate(prompts)]
        return {c.rid: c.tokens for c in sched.run(reqs)}, sched

    ref, _ = run(False)
    out, sched = run(True)
    assert all(ref[i] == out[i] for i in range(len(prompts)))
    assert sched.summary["accept_rate"] == 1.0


@pytest.mark.parametrize("dh,floor", [(64, 1.8), (128, 1.9)])
def test_int8_cache_bytes_per_slot_ratio(dh, floor):
    """The count an int8 cache exists for, without serving anything:
    bytes a slot (scale leaves included) fall by 2*dh/(dh+4) against
    the bf16 cache at head dim ``dh``."""
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.serve.engine import SlotDecodeEngine

    kw = dict(d_model=dh, n_heads=1, d_ff=2 * dh, max_len=48,
              compute_dtype=jnp.bfloat16)
    model_b, params = _tiny_serving_model(**kw)
    model_q, _ = _tiny_serving_model(kv_cache_quant="int8", **kw)
    ratio = (SlotDecodeEngine(model_b, params, 2).cache_bytes_per_slot()
             / SlotDecodeEngine(model_q, params, 2).cache_bytes_per_slot())
    assert ratio == pytest.approx(2 * dh / (dh + 4))
    assert ratio >= floor


@pytest.mark.slow
def test_int8_engine_cache_accounting_and_serving():
    """kv_cache_quant=int8 really shrinks HBM per slot (scale leaves
    included) at head dim 64, and the quantized engine serves a
    workload end to end."""
    from tensorflow_distributed_tpu.serve.buckets import default_buckets
    from tensorflow_distributed_tpu.serve.engine import SlotDecodeEngine

    kw = dict(d_model=64, n_heads=1, d_ff=128, max_len=48)
    model_b, params = _tiny_serving_model(**kw)
    model_q, _ = _tiny_serving_model(kv_cache_quant="int8", **kw)
    buckets = default_buckets(16, cap=48)
    eng_b = SlotDecodeEngine(model_b, params, 2, buckets=buckets)
    eng_q = SlotDecodeEngine(model_q, params, 2, buckets=buckets)
    ratio = eng_b.cache_bytes_per_slot() / eng_q.cache_bytes_per_slot()
    assert ratio >= 1.8          # 2*dh/(dh+4) = 1.88 at dh=64
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, model_q.cfg.vocab_size,
                            size=8).astype(np.int32) for _ in range(3)]
    done = Scheduler(eng_q, decode_priority=3).run(
        [Request(rid=i, prompt=p, max_new_tokens=12)
         for i, p in enumerate(prompts)])
    assert all(len(c.tokens) == 12 for c in done)
    assert all(0 <= t < model_q.cfg.vocab_size
               for c in done for t in c.tokens)


@pytest.mark.slow
def test_serve_run_spec_slo_e2e(tmp_path):
    """mode=serve with speculation + the SLO scheduler armed: the
    summary carries accept telemetry and per-class p95s, and the
    JSONL folds through observe.report."""
    from tensorflow_distributed_tpu.config import TrainConfig
    from tensorflow_distributed_tpu.observe.report import (
        load_records, summarize)
    from tensorflow_distributed_tpu.serve.run import serve_run

    cfg = TrainConfig(mode="serve", model="gpt_lm", model_size="tiny",
                      seed=3)
    cfg.serve.num_requests = 6
    cfg.serve.num_slots = 2
    cfg.serve.max_new_tokens = 10
    cfg.serve.arrival_rate = 200.0
    cfg.serve.policy = "slo"
    cfg.serve.slo_mix = "high:0.3,batch:0.3"
    cfg.serve.spec_tokens = 3
    cfg.serve.kv_dtype = "int8"
    cfg.observe.metrics_jsonl = str(tmp_path / "m.jsonl")
    cfg.validate()
    summary = serve_run(cfg)
    assert summary["requests"] == 6
    assert summary["policy"] == "slo"
    assert "accept_rate" in summary
    assert any(k.startswith("ttft_ms_p95_") for k in summary)
    out = summarize(load_records(cfg.observe.metrics_jsonl))
    assert out["serve_policy"] == "slo"
    assert "serve_accept_rate" in out
