"""Serve-under-fire suite: fault-injected serving, proven not believed.

Fast tier (jax-free, per the repo's tier rules): serve-phase fault-plan
grammar + config phase validation, slot-retry policy against a fake
engine (token identity through quarantine, budgets, SlotRetryExhausted),
journal write/replay round-trips, supervisor serve-awareness, and the
report's recovery summary. Slow tier (compiles the tiny GPT): real-
engine slot-NaN containment token identity, live-swap token identity,
the mode=serve fire driver, serve exit codes, and the supervised
SIGKILL-with-journal-resume e2e.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from tensorflow_distributed_tpu.resilience.faults import parse_fault_plan
from tensorflow_distributed_tpu.serve import journal as journal_mod
from tensorflow_distributed_tpu.serve.engine import EngineSurface
from tensorflow_distributed_tpu.serve.scheduler import (
    Request, Scheduler, SlotRetryExhausted)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --- fault-plan grammar (serve kinds) -----------------------------------

def test_serve_fault_plan_grammar():
    plan = parse_fault_plan(
        "decode_stall@3:0.5s,slot_nan@5:1,reload@8,sigkill@12")
    assert plan.kinds() == {"decode_stall", "slot_nan", "reload",
                            "sigkill"}
    assert plan.take_slot_nan(4) is None
    assert plan.take_slot_nan(5) == 1
    assert plan.take_slot_nan(5) is None        # one-shot
    assert not plan.take_reload(7)
    assert plan.take_reload(8) and not plan.take_reload(8)
    # slot_nan default slot is 0.
    assert parse_fault_plan("slot_nan@2").take_slot_nan(2) == 0
    for bad in ("slot_nan@5:1.5", "reload@5:2", "decode_stall@5:0s",
                "slot_nan@0:1"):
        with pytest.raises(ValueError):
            parse_fault_plan(bad)


def test_fault_plan_phase_validation():
    from tensorflow_distributed_tpu.config import (
        ResilienceConfig, TrainConfig)

    ok = TrainConfig(mode="serve", model="gpt_lm",
                     checkpoint_dir="/tmp/x",
                     resilience=ResilienceConfig(
                         fault_plan="slot_nan@2:0,reload@4,sigkill@9"))
    ok.validate()
    with pytest.raises(ValueError, match="train-phase only"):
        TrainConfig(mode="serve", model="gpt_lm",
                    resilience=ResilienceConfig(
                        fault_plan="nan_grad@2")).validate()
    with pytest.raises(ValueError, match="serve-phase only"):
        TrainConfig(resilience=ResilienceConfig(
            fault_plan="slot_nan@2:0")).validate()
    with pytest.raises(ValueError, match="swap source"):
        TrainConfig(mode="serve", model="gpt_lm",
                    resilience=ResilienceConfig(
                        fault_plan="reload@4")).validate()
    with pytest.raises(ValueError, match="no injection points"):
        TrainConfig(mode="eval", model="gpt_lm", checkpoint_dir="/t",
                    resilience=ResilienceConfig(
                        fault_plan="sigterm@2")).validate()


def test_serve_fire_config_validation():
    from tensorflow_distributed_tpu.config import TrainConfig

    cfg = TrainConfig(mode="serve", model="gpt_lm")
    cfg.serve.trace = "bursty"
    with pytest.raises(ValueError, match="arrival_rate"):
        cfg.validate()
    cfg.serve.arrival_rate = 8.0
    cfg.validate()
    cfg.serve.trace = "lunar"
    with pytest.raises(ValueError, match="unknown serve.trace"):
        cfg.validate()
    cfg.serve.trace = ""
    cfg.serve.slot_retries = -1
    with pytest.raises(ValueError, match="slot_retries"):
        cfg.validate()
    cfg.serve.slot_retries = 2
    bad = TrainConfig(serve=cfg.serve)
    bad.serve.journal = "/tmp/j"
    with pytest.raises(ValueError, match="journal"):
        bad.validate()


# --- fake engine with fire surface (no jax) -----------------------------

class _FireFakeEngine(EngineSurface):
    """Host-only engine with the fire surface the scheduler drives.
    Token stream is a pure function of (rid, tokens-emitted-so-far):
    prefill of a continuation prompt resumes the SAME stream, so token
    identity through quarantine/retry is checkable exactly. The rid
    rides prompt[0]; tokens count as len(prompt) - 1 (base prompts
    are length 1)."""

    def __init__(self, num_slots=2, max_len=256, spec_tokens=0):
        self.num_slots = num_slots
        self.max_len = max_len
        self.buckets = (64, 128)
        self.active = np.zeros((num_slots,), bool)
        self.slot_rid = {}
        self.counts = {}
        self.prefills = 0
        self.prefill_compiles = 0
        self.decode_steps = 0
        self.verify_steps = 0
        self.spec_tokens = spec_tokens
        self.swaps = 0
        self.params = object()
        self._poisoned = set()

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
        self._poisoned.discard(slot)         # full-row overwrite
        return rid * 100 + self.counts[rid]

    def step(self):
        out = np.zeros((self.num_slots,), np.int32)
        self._bad = []
        for s in range(self.num_slots):
            if not self.active[s]:
                continue
            if s in self._poisoned:
                out[s] = 999_999             # garbage, must be dropped
                self._bad.append(s)
                continue
            rid = self.slot_rid[s]
            self.counts[rid] += 1
            out[s] = rid * 100 + self.counts[rid]
        self.decode_steps += 1
        return out

    def can_verify(self):
        return self.spec_tokens > 0

    def verify_step(self, props):
        """Verify dispatch mirroring the real contract: [S, k+1]
        tokens, per-slot accepted+1 counts, and the per-slot ok flag
        surfaced through take_bad_slots — a poisoned slot's whole row
        is garbage THIS dispatch, exactly like non-finite logits under
        the real verify program."""
        k = self.spec_tokens
        toks = np.zeros((self.num_slots, k + 1), np.int32)
        acc = np.zeros((self.num_slots,), np.int32)
        self._bad = []
        for s in range(self.num_slots):
            if not self.active[s]:
                continue
            if s in self._poisoned:
                toks[s, :] = 999_999         # garbage, must be dropped
                acc[s] = k + 1
                self._bad.append(s)
                continue
            rid = self.slot_rid[s]
            for j in range(k + 1):
                self.counts[rid] += 1
                toks[s, j] = rid * 100 + self.counts[rid]
            acc[s] = k + 1
        self.decode_steps += 1
        self.verify_steps += 1
        return toks, acc

    def take_bad_slots(self):
        bad, self._bad = getattr(self, "_bad", []), []
        return bad

    def poison_slot(self, slot):
        self._poisoned.add(slot)

    def swap_params(self, new_params):
        self.params = new_params
        self.swaps += 1

    def free(self, slot):
        self.active[slot] = False
        self._poisoned.discard(slot)


def _reqs(n, max_new=8):
    return [Request(rid=i, prompt=np.asarray([i], np.int32),
                    max_new_tokens=max_new) for i in range(n)]


def _expected(rid, max_new, plen=1):
    # First token continues the stream from the prompt's implied depth.
    return [rid * 100 + (plen - 1) + j for j in range(max_new)]


def test_slot_retry_token_identity_and_budget():
    from tensorflow_distributed_tpu.observe.registry import (
        MetricsRegistry)

    plan = parse_fault_plan("slot_nan@3:0,slot_nan@7:1")
    eng = _FireFakeEngine(num_slots=2)
    reg = MetricsRegistry()
    sched = Scheduler(eng, decode_priority=3, registry=reg,
                      fault_plan=plan, slot_retries=2)
    done = {c.rid: c for c in sched.run(_reqs(5))}
    assert len(done) == 5
    for rid, c in done.items():
        assert c.tokens == _expected(rid, 8), f"rid {rid} drifted"
    # Two quarantines happened, each charged to its request.
    assert sched.summary["retries"] == 2
    assert sum(c.retries for c in done.values()) == 2
    quars = [r for r in reg.records
             if r.get("kind") == "slot_quarantine"]
    assert len(quars) == 2 and all("t_s" in q for q in quars)
    # Retried requests flag the recovery window in their records.
    assert any(r.get("recovery_window")
               for r in reg.records if r["event"] == "serve_request")


class _FakeSpeculator:
    """Proposal source for the fake verify path. Content is ignored —
    the fake engine's verify_step derives truth from its own stream —
    so this only has to satisfy the scheduler's speculator surface."""

    needs_histories = False

    def __init__(self, num_slots, k):
        self.num_slots, self.k = num_slots, k

    def propose(self, histories):
        return np.zeros((self.num_slots, self.k), np.int32)

    def observe_admit(self, slot, prompt, first_tok):
        pass

    def observe_free(self, slot):
        pass

    def sync_from(self, engine):
        pass

    def warmup(self):
        pass


def test_mid_verify_slot_retry_token_identity():
    """slot_nan fired while speculation is armed lands INSIDE a verify
    dispatch: the dispatch's own per-slot ok flag (take_bad_slots)
    quarantines, the whole garbage row is dropped before retirement,
    and the requeued continuation resumes the exact stream."""
    plan = parse_fault_plan("slot_nan@2:0,slot_nan@3:1")
    eng = _FireFakeEngine(num_slots=2, spec_tokens=3)
    sched = Scheduler(eng, decode_priority=3, fault_plan=plan,
                      slot_retries=2, speculator=_FakeSpeculator(2, 3))
    done = {c.rid: c for c in sched.run(_reqs(5))}
    assert len(done) == 5
    for rid, c in done.items():
        assert c.tokens == _expected(rid, 8), f"rid {rid} drifted"
    assert sched.summary["retries"] == 2
    # Every dispatch this engine took was a verify dispatch, so both
    # containments necessarily rode the verify program's ok flag —
    # never a separate probe step.
    assert eng.verify_steps == eng.decode_steps >= 1
    assert sched.summary["verify_steps"] == eng.verify_steps


def test_slot_retry_budget_exhausted_is_diverged():
    # Poison the same slot every consultable step: the same request
    # re-poisons past its budget -> SlotRetryExhausted (exit 2 at the
    # CLI), never a hot loop.
    plan = parse_fault_plan("slot_nan@2:0,slot_nan@4:0,slot_nan@6:0")
    eng = _FireFakeEngine(num_slots=1)
    sched = Scheduler(eng, decode_priority=2, fault_plan=plan,
                      slot_retries=1)
    with pytest.raises(SlotRetryExhausted, match="quarantined 2"):
        sched.run(_reqs(1, max_new=12))


def test_scheduler_reload_swaps_params():
    from tensorflow_distributed_tpu.observe.registry import (
        MetricsRegistry)

    plan = parse_fault_plan("reload@4")
    eng = _FireFakeEngine(num_slots=2)
    fresh = object()
    reg = MetricsRegistry()
    sched = Scheduler(eng, decode_priority=3, registry=reg,
                      fault_plan=plan,
                      reload_fn=lambda: (fresh, 7))
    done = {c.rid: c for c in sched.run(_reqs(3))}
    assert eng.params is fresh and eng.swaps == 1
    assert sched.summary["swaps"] == 1
    assert sched.summary["swap_seconds"] >= 0
    swaps = [r for r in reg.records if r.get("kind") == "weight_swap"]
    assert len(swaps) == 1 and swaps[0]["ckpt_step"] == 7
    # Traffic unaffected: token streams identical to unfaulted.
    for rid, c in done.items():
        assert c.tokens == _expected(rid, 8)


def test_scheduler_reload_without_fn_is_clear_error():
    plan = parse_fault_plan("reload@2")
    sched = Scheduler(_FireFakeEngine(), fault_plan=plan)
    with pytest.raises(ValueError, match="no reload_fn"):
        sched.run(_reqs(1))


# --- journal -------------------------------------------------------------

def test_journal_write_replay_roundtrip(tmp_path):
    path = str(tmp_path / "j.jsonl")
    eng = _FireFakeEngine(num_slots=2)
    sched = Scheduler(eng, decode_priority=3,
                      journal=journal_mod.RequestJournal(path))
    done = {c.rid: c for c in sched.run(_reqs(4, max_new=5))}
    played = journal_mod.replay(path)
    assert set(played) == {0, 1, 2, 3}
    for rid, ent in played.items():
        assert ent["done"]
        assert ent["tokens"] == done[rid].tokens
        assert ent["req"]["prompt"] == [rid]
        assert ent["req"]["max_new"] == 5


def test_journal_replay_tolerates_truncated_tail(tmp_path):
    path = str(tmp_path / "j.jsonl")
    j = journal_mod.RequestJournal(path)
    j.admit(0, [0], 8, -1)
    j.token(0, 100, 0.1)
    j.close()
    with open(path, "a") as f:
        f.write('{"e": "tok", "rid": 0, "t": 1')   # the kill's tail
    played = journal_mod.replay(path)
    assert played[0]["tokens"] == [100] and not played[0]["done"]


def test_apply_replay_continuations_and_arrival_shift():
    import dataclasses

    reqs = [Request(rid=0, prompt=np.asarray([0], np.int32),
                    max_new_tokens=6),
            Request(rid=1, prompt=np.asarray([1], np.int32),
                    max_new_tokens=6),
            Request(rid=2, prompt=np.asarray([2], np.int32),
                    max_new_tokens=6, arrival_s=9.0),
            Request(rid=3, prompt=np.asarray([3], np.int32),
                    max_new_tokens=6, eos_id=305)]
    played = {
        0: {"req": None, "tokens": [100, 101, 102], "done": False,
            "last_s": 2.0},                      # in flight -> cont.
        1: {"req": None, "tokens": [100] * 6, "done": True,
            "last_s": 1.0},                      # finished -> drop
        3: {"req": None, "tokens": [303, 304, 305], "done": False,
            "last_s": 1.5},                      # eos tail -> drop
    }
    out = journal_mod.apply_replay(reqs, played)
    by_rid = {r.rid: r for r in out}
    assert set(by_rid) == {0, 2}
    cont = by_rid[0]
    assert list(cont.prompt) == [0, 100, 101, 102]
    assert cont.max_new_tokens == 3 and cont.arrival_s == 0.0
    assert cont._base_tokens == [100, 101, 102]
    # Untouched request's arrival shifts by the dead leg's elapsed
    # serving time (clients kept sending while the process was down).
    assert by_rid[2].arrival_s == pytest.approx(7.0)
    assert dataclasses.is_dataclass(cont)


def test_resumed_continuation_serves_to_token_identity(tmp_path):
    """The full resume path at the scheduler level: a journal says rid
    0 had 3 tokens in flight; the continuation re-enters and the FINAL
    completion reports the full, unfaulted token stream."""
    reqs = _reqs(2, max_new=7)
    played = {0: {"req": None, "tokens": _expected(0, 7)[:3],
                  "done": False, "last_s": 0.5}}
    narrowed = journal_mod.apply_replay(reqs, played)
    eng = _FireFakeEngine(num_slots=2)
    done = {c.rid: c for c in Scheduler(eng, decode_priority=2).run(
        narrowed)}
    assert done[0].tokens == _expected(0, 7)
    assert done[1].tokens == _expected(1, 7)
    assert done[0].prompt_len == 1      # base tokens excluded


# --- supervisor serve-awareness -----------------------------------------

def test_supervisor_leg_args_serve_vs_train():
    from tensorflow_distributed_tpu.resilience.supervisor import (
        build_leg_args)

    train_args = ["--checkpoint-dir", "/c", "--train-steps", "5"]
    assert "--resume" not in build_leg_args(train_args, 0)
    assert build_leg_args(train_args, 1)[-2:] == ["--resume", "true"]
    # Explicit user setting survives.
    explicit = train_args + ["--resume", "false"]
    assert build_leg_args(explicit, 2) == explicit
    # Serve children restart with the UNCHANGED command: continuity is
    # the journal, and --resume would even fail serve validation
    # without a checkpoint dir.
    serve_args = ["--mode", "serve", "--model", "gpt_lm",
                  "--serve.journal", "/tmp/j"]
    assert build_leg_args(serve_args, 3) == serve_args
    serve_ckpt = serve_args + ["--checkpoint-dir", "/c"]
    assert build_leg_args(serve_ckpt, 3) == serve_ckpt


# --- observe.report recovery summary ------------------------------------

def test_report_folds_recovery_into_serve_summary(tmp_path):
    from tensorflow_distributed_tpu.observe.report import (
        load_records, render, summarize)

    path = tmp_path / "m.jsonl"
    recs = (
        [{"event": "serve_request", "rid": i, "ttft_ms": 10.0 + i,
          "tok_ms": 2.0, "recovery_window": i < 3} for i in range(10)]
        + [{"event": "recovery", "kind": "slot_quarantine", "rid": 1,
            "slot": 0, "retry": 1, "t_s": 0.4},
           {"event": "recovery", "kind": "weight_swap",
            "seconds": 0.21, "ckpt_step": 2, "t_s": 0.9},
           {"event": "recovery", "kind": "weight_swap",
            "seconds": 0.14, "ckpt_step": 4, "t_s": 1.7},
           {"event": "recovery", "kind": "fault_injected",
            "fault": "decode_stall", "step": 3, "seconds": 0.5}]
        + [{"event": "serve_summary", "tokens_per_sec": 500.0,
            "total_new_tokens": 320, "retries": 1, "swaps": 2,
            "swap_seconds": 0.35, "seed": 7, "trace": "bursty"}])
    path.write_text("".join(json.dumps(r) + "\n" for r in recs))
    out = summarize(load_records(str(path)))
    assert out["recovery_counts"] == {"fault_injected": 1,
                                      "slot_quarantine": 1,
                                      "weight_swap": 2}
    assert out["swap_seconds_total"] == pytest.approx(0.35)
    assert out["serve_retries"] == 1 and out["serve_swaps"] == 2
    assert out["serve_seed"] == 7 and out["serve_trace"] == "bursty"
    assert out["serve_ttft_ms_p99"] == pytest.approx(19.0, abs=1.0)
    assert out["serve_recovery_requests"] == 3
    assert out["serve_ttft_ms_p99_recovery"] == pytest.approx(
        12.0, abs=1.0)
    text = render(out)
    assert "Recovery" in text and "slot_quarantine" in text


# --- the real engine under fire (slow tier) ------------------------------

def _tiny_lm():
    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.models.transformer import (
        CausalLM, tiny_config)

    model = CausalLM(tiny_config(causal=True,
                                 compute_dtype=jnp.float32))
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _mixed_requests(n=4, max_new=10):
    return [Request(rid=i,
                    prompt=np.random.default_rng(i).integers(
                        0, 64, size=L).astype(np.int32),
                    max_new_tokens=max_new)
            for i, L in enumerate([3, 9, 17, 5][:n])]


@pytest.mark.slow
def test_slot_nan_containment_token_identical():
    """A NaN-poisoned KV row is detected ON DEVICE, the slot
    quarantined and re-prefilled, and the final token streams are
    identical to the unfaulted run — one poisoned slot never costs an
    engine restart or a changed answer."""
    from tensorflow_distributed_tpu.serve.engine import SlotDecodeEngine

    model, params = _tiny_lm()
    base_eng = SlotDecodeEngine(model, params, num_slots=2)
    base = {c.rid: c.tokens
            for c in Scheduler(base_eng, decode_priority=3).run(
                _mixed_requests())}

    plan = parse_fault_plan("slot_nan@3:0,slot_nan@8:1")
    eng = SlotDecodeEngine(model, params, num_slots=2, fault_plan=plan)
    sched = Scheduler(eng, decode_priority=3, fault_plan=plan,
                      slot_retries=2)
    done = {c.rid: c for c in sched.run(_mixed_requests())}
    assert {r: c.tokens for r, c in done.items()} == base
    assert sched.summary["retries"] >= 1


@pytest.mark.slow
def test_spec_slot_nan_mid_verify_token_identical():
    """slot_nan under ARMED speculation: the poison is detected by the
    VERIFY program's per-slot finiteness flag (the same fetch that
    returns the verify tokens — no extra probe dispatch), the slot
    quarantined, and the final streams are identical to the plain
    greedy run. Containment composes with speculation, not around it."""
    from tensorflow_distributed_tpu.serve.engine import SlotDecodeEngine
    from tensorflow_distributed_tpu.serve.speculate import SelfDraft

    model, params = _tiny_lm()
    base_eng = SlotDecodeEngine(model, params, num_slots=2)
    base = {c.rid: c.tokens
            for c in Scheduler(base_eng, decode_priority=3).run(
                _mixed_requests())}

    k = 3
    plan = parse_fault_plan("slot_nan@2:0,slot_nan@4:1")
    eng = SlotDecodeEngine(model, params, num_slots=2, fault_plan=plan,
                           spec_tokens=k)
    sched = Scheduler(eng, decode_priority=3, fault_plan=plan,
                      slot_retries=2, speculator=SelfDraft(2, k))
    done = {c.rid: c for c in sched.run(_mixed_requests())}
    assert {r: c.tokens for r, c in done.items()} == base
    assert sched.summary["retries"] >= 1
    # Headroom never ran out at these lengths, so EVERY dispatch was a
    # verify dispatch — the quarantines came off the verify ok flag.
    assert eng.verify_steps == eng.decode_steps >= 1


def _tiny_state(max_len=64):
    """A gpt_lm-tiny TrainState (the factory defaults TP off at
    mesh.model==1, so create_train_state composes on one device) —
    the checkpointable twin of _tiny_lm for the swap tests."""
    import jax
    import optax

    from tensorflow_distributed_tpu.models.transformer import gpt_lm
    from tensorflow_distributed_tpu.parallel.mesh import (
        single_device_mesh)
    from tensorflow_distributed_tpu.train.state import create_train_state

    mesh = single_device_mesh(jax.devices()[0])
    model = gpt_lm(mesh, size="tiny", max_len=max_len,
                   dropout_rate=0.0)
    state = create_train_state(model, optax.identity(),
                               np.zeros((2, 16), np.int32), mesh,
                               seed=0)
    return model, state


@pytest.mark.slow
def test_live_swap_preserves_in_flight_tokens(tmp_path):
    """Live weight swap mid-traffic to the SAME checkpoint: slots stay
    live (no drain — prefill count unchanged, occupancy continuous)
    and every output is token-identical to the no-swap run; the swap
    is latency, never a correctness event."""
    from tensorflow_distributed_tpu.serve.engine import SlotDecodeEngine
    from tensorflow_distributed_tpu.train import checkpoint as ckpt

    model, state = _tiny_state()
    ckpt.save(str(tmp_path), state)
    params = state.params

    base_eng = SlotDecodeEngine(model, params, num_slots=2)
    base = {c.rid: c.tokens
            for c in Scheduler(base_eng, decode_priority=3).run(
                _mixed_requests())}

    plan = parse_fault_plan("reload@5")
    eng = SlotDecodeEngine(model, params, num_slots=2, fault_plan=plan)

    def reload_fn():
        return ckpt.restore_params(str(tmp_path), eng.params)

    sched = Scheduler(eng, decode_priority=3, fault_plan=plan,
                      reload_fn=reload_fn)
    done = {c.rid: c for c in sched.run(_mixed_requests())}
    assert eng.swaps == 1
    assert {r: c.tokens for r, c in done.items()} == base
    assert sched.summary["swaps"] == 1
    assert sched.summary["swap_seconds"] > 0
    # No drain: exactly one prefill per request — nobody was evicted
    # around the swap.
    assert eng.prefills == len(base)


@pytest.mark.slow
def test_swap_params_rejects_drift():
    import jax

    from tensorflow_distributed_tpu.serve.engine import SlotDecodeEngine

    model, params = _tiny_lm()
    eng = SlotDecodeEngine(model, params, num_slots=1)
    bad = jax.tree_util.tree_map(lambda x: x[..., :1], params)
    with pytest.raises(ValueError, match="shape/dtype drift"):
        eng.swap_params(bad)


@pytest.mark.slow
def test_restore_params_walks_back_past_nonfinite(tmp_path):
    """The swap source honors the integrity contract: a newest
    checkpoint with intact bytes but NaN params is skipped (recovery
    event, no quarantine) and the older finite step swaps in."""
    import jax
    from flax import serialization

    from tensorflow_distributed_tpu.train import checkpoint as ckpt

    _, state = _tiny_state()
    ckpt.save(str(tmp_path), state)                       # step 0
    ckpt.save(str(tmp_path), state.replace(step=state.step + 1))
    # NaN-poison step 1 in place with VALID bytes (checksum refreshed).
    import hashlib

    sd = os.path.join(str(tmp_path), "step_00000001")
    with open(os.path.join(sd, "state.msgpack"), "rb") as f:
        raw = serialization.msgpack_restore(f.read())
    raw["params"] = jax.tree_util.tree_map(
        lambda x: np.full_like(x, np.nan), raw["params"])
    blob = serialization.msgpack_serialize(raw)
    with open(os.path.join(sd, "state.msgpack"), "wb") as f:
        f.write(blob)
    with open(os.path.join(sd, "manifest.json")) as f:
        man = json.load(f)
    man["sha256"] = hashlib.sha256(blob).hexdigest()
    with open(os.path.join(sd, "manifest.json"), "w") as f:
        json.dump(man, f)

    new_params, step = ckpt.restore_params(str(tmp_path), state.params)
    assert step == 0
    leaf = jax.tree_util.tree_leaves(jax.device_get(new_params))[0]
    assert np.isfinite(leaf).all()
    # The skipped step was NOT quarantined (bytes are intact — a
    # training-side rewind may still want them for forensics).
    assert os.path.isdir(sd)


def _child_env():
    return {
        "PATH": os.environ["PATH"],
        "HOME": os.environ.get("HOME", "/tmp"),
        "PYTHONPATH": REPO,
        "JAX_PLATFORMS": "cpu",
        "JAX_COMPILATION_CACHE_DIR":
            os.environ.get("JAX_COMPILATION_CACHE_DIR", ""),
        "PYTHONUNBUFFERED": "1",
    }


_SERVE_ARGS = [
    "--mode", "serve", "--model", "gpt_lm", "--model-size", "tiny",
    "--seq-len", "48", "--compute-dtype", "float32",
    "--serve.num-slots", "2", "--serve.num-requests", "6",
    "--serve.prompt-len-min", "4", "--serve.prompt-len-max", "10",
    "--serve.max-new-tokens", "10",
]


@pytest.mark.slow
def test_serve_decode_stall_exits_3(tmp_path):
    """A decode stall past the watchdog deadline is a diagnosable
    StallError -> exit 3 (restart is the remedy), never a silent
    hang."""
    proc = subprocess.run(
        [sys.executable, "-m", "tensorflow_distributed_tpu.cli",
         *_SERVE_ARGS, "--resilience.sync-timeout-s", "0.5",
         "--resilience.fault-plan", "decode_stall@4:2s"],
        env=_child_env(), cwd=REPO, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode == 3, proc.stdout[-2000:] + proc.stderr[-1000:]
    assert "decode step" in proc.stderr


@pytest.mark.slow
def test_serve_slot_retry_exhausted_exits_2(tmp_path):
    """Repeated quarantine of the same request past its budget is
    serve's DIVERGED: exit 2, which the supervisor refuses to
    restart."""
    proc = subprocess.run(
        [sys.executable, "-m", "tensorflow_distributed_tpu.cli",
         *_SERVE_ARGS, "--serve.slot-retries", "0",
         "--resilience.fault-plan", "slot_nan@3:0"],
        env=_child_env(), cwd=REPO, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode == 2, proc.stdout[-2000:] + proc.stderr[-1000:]
    assert "slot-quarantined" in proc.stderr


@pytest.mark.slow
def test_supervisor_serve_sigkill_journal_resume(tmp_path):
    """The acceptance scenario: a serving process SIGKILLed
    mid-traffic is restarted by the supervisor; the restarted leg
    replays the journal, re-admits in-flight requests as
    continuations, and every request completes — zero lost."""
    journal = str(tmp_path / "serve.journal")
    jsonl = str(tmp_path / "m.jsonl")
    proc = subprocess.run(
        [sys.executable, "-m",
         "tensorflow_distributed_tpu.resilience.supervisor",
         "--max-restarts", "2", "--backoff-base-s", "0.2", "--",
         *_SERVE_ARGS, "--serve.max-new-tokens", "16",
         "--serve.journal", journal,
         "--observe.metrics-jsonl", jsonl,
         "--resilience.fault-plan", "sigkill@20"],
        env=_child_env(), cwd=REPO, capture_output=True, text=True,
        timeout=500)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    assert '"kind": "restart"' in proc.stdout
    played = journal_mod.replay(journal)
    assert len(played) == 6
    assert all(ent["done"] for ent in played.values())
    assert all(len(ent["tokens"]) == 16 for ent in played.values())
    recs = [json.loads(ln) for ln in open(jsonl)]
    sums = [r for r in recs if r["event"] == "serve_summary"]
    # The resumed leg's summary is tagged; both legs' request records
    # are in the ONE artifact (append-mode sink on resume).
    assert sums and sums[-1]["resumed"] is True
    req_rids = {r["rid"] for r in recs
                if r["event"] == "serve_request"}
    assert req_rids == set(range(6))
