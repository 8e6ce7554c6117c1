"""Autopilot suite: the online controller on fake clocks and engines.

Fast tier (jax-free, per the repo's tier rules — observe/autopilot.py
is pure stdlib and the scheduler runs against host-only fakes): ctor +
config validation matrices, confirm-count hysteresis (a noisy-but-
healthy stream never acts), per-knob cooldown rate limiting, the four
loops' trigger/actuate/back-off paths, pins, the streaming metrics
tail, run-end advisory recommendations, and the scheduler integration
— tune commands through the control path, token identity across
actuations, the rolling accept_rate_window, tune_actions in snapshot
and summary. The real-engine live-recompile path (set_spec_k mid-run)
is held to token identity in tests/test_serve_ahead.py
(``spec_k_retuned_mid_run``).
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from tensorflow_distributed_tpu.observe.autopilot import (
    ACCEPT_HI, ACCEPT_LO, KNOBS, POOL_HI, POOL_LO, Autopilot)
from tensorflow_distributed_tpu.serve.engine import EngineSurface
from tensorflow_distributed_tpu.serve.scheduler import (
    Request, Scheduler)


def _ap(**kw):
    recs = []
    ap = Autopilot(emit=lambda event, **f: recs.append(
        {"event": event, **f}), **kw)
    return ap, recs


def _alert(burn=3.0):
    return {"slo": {"ttft_p95": {"alerting": True, "burn_fast": burn}}}


def _calm():
    return {"slo": {"ttft_p95": {"alerting": False, "burn_fast": 0.0}}}


# --- ctor + config validation -------------------------------------------

@pytest.mark.parametrize("kw,match", [
    (dict(every=0), "every"),
    (dict(confirm=0), "confirm"),
    (dict(cooldown=-1), "cooldown"),
    (dict(drift_tol=0.0), "drift_tol"),
    (dict(pins=("decode_priority", "nope")), "unknown autopilot pin"),
    (dict(k_ladder=()), "k_ladder"),
    (dict(k_ladder=(0, 2)), "k_ladder"),
])
def test_ctor_rejections(kw, match):
    with pytest.raises(ValueError, match=match):
        Autopilot(**kw)


def _observe_cfg(**kw):
    from tensorflow_distributed_tpu.config import TrainConfig

    cfg = TrainConfig(mode="serve", model="gpt_lm")
    for k, v in kw.items():
        setattr(cfg.observe, k, v)
    return cfg


def test_observe_autopilot_config_valid():
    _observe_cfg(autopilot=True).validate()
    _observe_cfg(autopilot=True, autopilot_every=5,
                 autopilot_confirm=1, autopilot_cooldown=0,
                 autopilot_pin="spec_k,buckets",
                 autopilot_calibration="c.json").validate()


@pytest.mark.parametrize("kw,match", [
    (dict(autopilot=True, autopilot_every=0), "autopilot_every"),
    (dict(autopilot=True, autopilot_confirm=0), "autopilot_confirm"),
    (dict(autopilot=True, autopilot_cooldown=-1),
     "autopilot_cooldown"),
    (dict(autopilot=True, autopilot_drift_tol=0.0),
     "autopilot_drift_tol"),
    (dict(autopilot=True, autopilot_pin="gold"), "unknown knob"),
    # Every autopilot_* knob is inert without the master switch.
    (dict(autopilot_every=5), "no effect without"),
    (dict(autopilot_pin="spec_k"), "no effect without"),
    (dict(autopilot_calibration="c.json"), "no effect without"),
])
def test_observe_autopilot_config_rejections(kw, match):
    with pytest.raises(ValueError, match=match):
        _observe_cfg(**kw).validate()


# --- loop 4: admission (SLO burn -> decode_priority AIMD) ---------------

def test_admission_tighten_halves_then_relaxes_additively():
    ap, recs = _ap(every=1, confirm=2, cooldown=0)
    ap.bind_scheduler(num_slots=4, decode_priority=8)
    assert ap.evaluate(1, _alert()) == []          # confirm 1/2
    cmds = ap.evaluate(2, _alert())                # sustained -> halve
    assert cmds == [
        {"cmd": "tune", "knob": "decode_priority", "value": 4}]
    tune = [r for r in recs if r["event"] == "tune"][-1]
    assert tune["loop"] == "admission"
    assert tune["action"] == "tighten"
    assert tune["prev"] == 8 and tune["value"] == 4
    assert tune["signal"] == "slo_burn_fast"
    assert tune["observed"] == 3.0 and tune["threshold"] == 1.0
    assert tune["applied"] is True
    assert tune["evidence"]["alerting"] == ["ttft_p95"]
    # Calm: additive relax back toward the configured baseline — the
    # knob that burned is re-approached one step at a time, not
    # snapped back.
    assert ap.evaluate(3, _calm()) == []
    assert ap.evaluate(4, _calm()) == [
        {"cmd": "tune", "knob": "decode_priority", "value": 5}]
    relax = [r for r in recs if r["event"] == "tune"][-1]
    assert relax["action"] == "relax"
    # At the baseline the relax trigger itself goes quiet.
    for step in range(5, 12):
        ap.evaluate(step, _calm())
    values = [r["value"] for r in recs if r["event"] == "tune"]
    assert values == [4, 5, 6, 7, 8]
    assert ap.evaluate(20, _calm()) == []


def test_admission_floor_at_one():
    ap, _ = _ap(every=1, confirm=1, cooldown=0)
    ap.bind_scheduler(num_slots=4, decode_priority=2)
    assert ap.evaluate(1, _alert())[0]["value"] == 1
    assert ap.evaluate(2, _alert()) == []          # dp == 1: floor


def test_hysteresis_noisy_but_healthy_never_acts():
    # Alternating alert/calm (and pool occupancy wobbling around the
    # deadband) never satisfies a confirm count of 2 — zero decisions.
    ap, recs = _ap(every=1, confirm=2, cooldown=0)
    ap.bind_scheduler(num_slots=4, spec_k=2, has_spec=True,
                      decode_priority=8)
    for step in range(1, 41):
        snap = _alert() if step % 2 else _calm()
        snap["pool_occupancy"] = 0.95 if step % 2 else 0.70
        snap["accept_rate_window"] = 0.9 if step % 2 else 0.5
        assert ap.evaluate(step, snap) == []
    assert ap.actions == 0
    assert not [r for r in recs if r["event"] == "tune"]


def test_cooldown_rate_limit_counts_suppressed():
    ap, _ = _ap(every=1, confirm=1, cooldown=100)
    ap.bind_scheduler(num_slots=4, decode_priority=32)
    assert ap.evaluate(10, _alert())[0]["value"] == 16
    # Still alerting inside the cooldown window: triggered but held.
    assert ap.evaluate(20, _alert()) == []
    assert ap.evaluate(60, _alert()) == []
    assert ap.suppressed == 2
    assert ap.evaluate(110, _alert())[0]["value"] == 8


# --- loop 2: capacity (pool occupancy <-> slot cap) ---------------------

def test_capacity_shrink_and_grow_deadband():
    ap, recs = _ap(every=1, confirm=1, cooldown=0)
    ap.bind_scheduler(num_slots=4, decode_priority=8)
    assert ap.evaluate(1, {"pool_occupancy": POOL_HI}) == [
        {"cmd": "tune", "knob": "slot_cap", "value": 3}]
    assert ap.slot_cap == 3
    # Inside the deadband: quiet in both directions.
    assert ap.evaluate(2, {"pool_occupancy": 0.75}) == []
    # Headroom: grow back toward the allocated num_slots, capped.
    assert ap.evaluate(3, {"pool_occupancy": POOL_LO})[0]["value"] == 4
    assert ap.evaluate(4, {"pool_occupancy": 0.2}) == []
    tune = [r for r in recs if r["event"] == "tune"][0]
    assert tune["loop"] == "capacity"
    assert tune["signal"] == "pool_occupancy"


def test_capacity_needs_pool_signal_and_slots():
    ap, _ = _ap(every=1, confirm=1, cooldown=0)
    ap.bind_scheduler(num_slots=1, decode_priority=8)
    assert ap.evaluate(1, {"pool_occupancy": 0.99}) == []  # 1 slot
    ap2, _ = _ap(every=1, confirm=1, cooldown=0)
    ap2.bind_scheduler(num_slots=4, decode_priority=8)
    assert ap2.evaluate(1, {}) == []          # unpaged: no signal


# --- loop 3: speculation (accept rate -> k ladder) ----------------------

def test_speculation_walks_ladder_both_ways():
    ap, _ = _ap(every=1, confirm=1, cooldown=0, k_ladder=(1, 2, 4))
    ap.bind_scheduler(num_slots=4, spec_k=2, has_spec=True,
                      decode_priority=8)
    assert ap.evaluate(1, {"accept_rate_window": ACCEPT_HI})[0] == {
        "cmd": "tune", "knob": "spec_k", "value": 4}
    assert ap.evaluate(2, {"accept_rate_window": 0.99}) == []  # top
    assert ap.evaluate(3, {"accept_rate_window": ACCEPT_LO})[
        0]["value"] == 2
    assert ap.evaluate(4, {"accept_rate_window": 0.1})[0]["value"] == 1
    assert ap.evaluate(5, {"accept_rate_window": 0.1}) == []  # bottom
    # Mid-band: quiet.
    assert ap.evaluate(6, {"accept_rate_window": 0.5}) == []


def test_speculation_off_ladder_anchor_and_fallback_rate():
    ap, _ = _ap(every=1, confirm=1, cooldown=0, k_ladder=(1, 2, 4))
    ap.bind_scheduler(num_slots=4, spec_k=3, has_spec=True,
                      decode_priority=8)
    # k=3 anchors to the rung below (2) and deepens to 4; the
    # cumulative accept_rate is the fallback when no window exists.
    assert ap.evaluate(1, {"accept_rate": 0.9})[0]["value"] == 4


def test_speculation_inert_without_spec():
    ap, _ = _ap(every=1, confirm=1, cooldown=0)
    ap.bind_scheduler(num_slots=4, spec_k=0, has_spec=False,
                      decode_priority=8)
    assert ap.evaluate(1, {"accept_rate_window": 0.99}) == []


# --- loop 1: calibration refit ------------------------------------------

def _feed_drifting_join(ap, ratio=2.0, programs=("a", "b")):
    for i, prog in enumerate(programs):
        ap.observe_record("compile", {
            "program": prog, "flops": 1e9 * (i + 1),
            "bytes_accessed": 1e6 * (i + 1)})
        ap.observe_record("device_time", {
            "program": prog, "device_ms_per_call": ratio * (i + 1),
            "predicted_ms_per_call": 1.0 * (i + 1)})


def test_calibration_refit_writes_profile(tmp_path):
    path = str(tmp_path / "calib.json")
    replans = []
    ap, recs = _ap(every=1, confirm=1, cooldown=0, drift_tol=0.25,
                   calibration_path=path)
    ap.replan = replans.append
    ap.bind_scheduler(num_slots=4, decode_priority=8)
    _feed_drifting_join(ap, ratio=2.0)
    assert ap.evaluate(1, {}) == []     # a refit is a file write, not
    tune = [r for r in recs if r["event"] == "tune"]  # a sched cmd
    assert len(tune) == 1
    assert tune[0]["loop"] == "calibration"
    assert tune[0]["signal"] == "drift_ratio"
    assert tune[0]["observed"] == 2.0
    assert tune[0]["applied"] is True
    assert tune[0]["evidence"]["source"] == "device_time"
    profile = json.load(open(path))
    assert profile["calibration_id"] == tune[0]["value"]
    assert replans and replans[0]["calibration_id"] == tune[
        0]["value"]
    # Evidence-gated back-off: no NEW measurements -> no second refit.
    ap.evaluate(2, {})
    ap.evaluate(3, {})
    assert len([r for r in recs if r["event"] == "tune"]) == 1
    # New drift evidence re-arms the loop.
    ap.observe_record("device_time", {
        "program": "a", "device_ms_per_call": 3.0,
        "predicted_ms_per_call": 1.0})
    ap.evaluate(4, {})
    assert len([r for r in recs if r["event"] == "tune"]) == 2


def test_calibration_prefers_plan_drift_record():
    ap, recs = _ap(every=1, confirm=1, cooldown=0, drift_tol=0.25)
    ap.bind_scheduler(num_slots=4, decode_priority=8)
    _feed_drifting_join(ap, ratio=1.1)  # join alone: inside tolerance
    ap.observe_record("plan_drift", {
        "drift_ratio": 1.8, "predicted_step_ms": 10.0,
        "measured_step_ms_p50": 18.0, "calibration_id": "old"})
    ap.evaluate(1, {})
    tune = [r for r in recs if r["event"] == "tune"]
    assert len(tune) == 1
    assert tune[0]["evidence"]["source"] == "plan_drift"
    assert tune[0]["prev"] == "old"
    assert tune[0]["applied"] is False   # no calibration_path: advisory


def test_calibration_quiet_inside_tolerance():
    ap, recs = _ap(every=1, confirm=1, cooldown=0, drift_tol=0.25)
    ap.bind_scheduler(num_slots=4, decode_priority=8)
    _feed_drifting_join(ap, ratio=1.1)
    ap.evaluate(1, {})
    assert not [r for r in recs if r["event"] == "tune"]


# --- cross-loop rules ----------------------------------------------------

def test_one_applied_action_per_tick_protection_order():
    ap, _ = _ap(every=1, confirm=1, cooldown=0)
    ap.bind_scheduler(num_slots=4, decode_priority=2)
    snap = {**_alert(), "pool_occupancy": 0.99}
    # Admission (SLO protection) outranks capacity on the same tick.
    assert ap.evaluate(1, snap) == [
        {"cmd": "tune", "knob": "decode_priority", "value": 1}]
    # dp at floor: capacity gets the next tick.
    assert ap.evaluate(2, snap) == [
        {"cmd": "tune", "knob": "slot_cap", "value": 3}]


def test_pins_never_actuate():
    ap, recs = _ap(every=1, confirm=1, cooldown=0, drift_tol=0.25,
                   pins=KNOBS)
    ap.bind_scheduler(num_slots=4, spec_k=2, has_spec=True,
                      decode_priority=8)
    _feed_drifting_join(ap, ratio=2.0)
    snap = {**_alert(), "pool_occupancy": 0.99,
            "accept_rate_window": 0.99, "slot_pages_peak": 9}
    for step in range(1, 10):
        assert ap.evaluate(step, snap) == []
    ap.bind_paging(num_pages=100, recommend=lambda peak: (200, []))
    ap.bind_buckets((16, 32))
    ap.observe_prompt(100)
    ap.emit_summary(10, snap)
    assert ap.actions == 0 and ap.advisories == 0
    assert not [r for r in recs if r["event"] == "tune"]
    assert [r for r in recs if r["event"] == "tune_summary"][
        0]["quiet"] is True


def test_maybe_step_cadence_only_builds_snapshot_on_ticks():
    ap, _ = _ap(every=10, confirm=1, cooldown=0)
    ap.bind_scheduler(num_slots=4, decode_priority=8)
    calls = []

    def snap_fn():
        calls.append(1)
        return _calm()

    for step in range(1, 31):
        ap.maybe_step(step, snap_fn)
    assert len(calls) == 3 and ap.evals == 3


# --- streaming tail ------------------------------------------------------

def test_tail_reads_incrementally_and_skips_torn_lines(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    ap, _ = _ap(every=1, confirm=1, cooldown=0, metrics_path=path)
    ap.bind_scheduler(num_slots=4, decode_priority=8)
    with open(path, "w") as f:
        f.write(json.dumps({"event": "compile", "program": "a",
                            "flops": 1.0, "bytes_accessed": 1.0})
                + "\n")
        f.write('{"event": "device_time", "program": "a"')  # torn
    ap.evaluate(1, {})
    assert "a" in ap._costs and not ap._measured
    with open(path, "a") as f:                   # the write completes
        f.write(', "device_ms_per_call": 2.0}\n')
    ap.evaluate(2, {})
    assert ap._measured["a"]["device_ms_per_call"] == 2.0
    # Missing file: silently quiet (the run may not export JSONL).
    ap2, _ = _ap(metrics_path=str(tmp_path / "nope.jsonl"))
    ap2.bind_scheduler(num_slots=4, decode_priority=8)
    ap2.evaluate(1, {})


# --- run-end advisories --------------------------------------------------

def test_num_pages_and_bucket_recommendations():
    ap, recs = _ap(every=1, confirm=1, cooldown=0)
    ap.bind_scheduler(num_slots=4, decode_priority=8)
    ap.bind_paging(num_pages=100,
                   recommend=lambda peak: (160, [f"peak={peak}"]))
    ap.bind_buckets((16, 32))
    for n in [6] * 2 + [100] * 30:
        ap.observe_prompt(n)
    ap.emit_summary(50, {"slot_pages_peak": 40})
    tunes = {r["knob"]: r for r in recs if r["event"] == "tune"}
    assert tunes["num_pages"]["value"] == 160
    assert tunes["num_pages"]["applied"] is False
    assert tunes["num_pages"]["evidence"]["rationale"] == ["peak=40"]
    assert tunes["buckets"]["value"] == 128      # pow2 cover of p99
    assert tunes["buckets"]["applied"] is False
    summary = [r for r in recs if r["event"] == "tune_summary"][0]
    assert summary["actions"] == 0
    assert summary["advisories"] == 2
    assert summary["quiet"] is True              # advisories != actions


def test_num_pages_recommendation_inside_band_is_quiet():
    ap, recs = _ap()
    ap.bind_scheduler(num_slots=4, decode_priority=8)
    ap.bind_paging(num_pages=100, recommend=lambda peak: (110, []))
    ap.emit_summary(50, {"slot_pages_peak": 40})
    assert not [r for r in recs if r["event"] == "tune"]


# --- scheduler integration (host-only fake engine) ----------------------

class _FakeEngine(EngineSurface):
    """Deterministic host engine: token = rid * 1000 + count, so the
    stream is a pure function of (rid, emitted-count) and identity
    across actuations is exact."""

    def __init__(self, num_slots=2, max_len=256):
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
        return [s for s in range(self.num_slots)
                if not self.active[s]]

    def occupancy(self):
        return float(self.active.sum()) / self.num_slots

    def prefill(self, prompt, slot):
        rid = int(prompt[0])
        self.prefills += 1
        self.active[slot] = True
        self.slot_rid[slot] = rid
        self.counts[rid] = len(prompt) - 1
        return rid * 1000 + self.counts[rid]

    def step(self):
        out = np.zeros((self.num_slots,), np.int32)
        for s in range(self.num_slots):
            if self.active[s]:
                rid = self.slot_rid[s]
                self.counts[rid] += 1
                out[s] = rid * 1000 + self.counts[rid]
        return out

    def free(self, slot):
        self.active[slot] = False


class _FakeSpecEngine(_FakeEngine):
    """Speculative surface over the same stream; ``set_spec_k`` is the
    live-retune actuator the scheduler drives."""

    def __init__(self, num_slots=2, max_len=256, spec_tokens=2):
        super().__init__(num_slots, max_len)
        self.spec_tokens = spec_tokens
        self.set_k_calls = []

    def can_verify(self):
        return True

    def verify_step(self, props):
        k = self.spec_tokens
        toks = np.zeros((self.num_slots, k + 1), np.int32)
        acc = np.zeros((self.num_slots,), np.int32)
        for s in range(self.num_slots):
            if not self.active[s]:
                continue
            rid = self.slot_rid[s]
            for j in range(k + 1):               # full accept + bonus
                self.counts[rid] += 1
                toks[s, j] = rid * 1000 + self.counts[rid]
            acc[s] = k + 1
        return toks, acc

    def set_spec_k(self, k):
        self.set_k_calls.append(k)
        self.spec_tokens = k


class _FakeSpeculator:
    def __init__(self, num_slots, k):
        self.num_slots, self.k = num_slots, k

    def propose(self, histories):
        return np.zeros((self.num_slots, self.k), np.int32)

    def observe_admit(self, slot, prompt, first):
        pass

    def observe_free(self, slot):
        pass

    def sync_from(self, engine):
        pass

    def set_k(self, k):
        self.k = k


def _reqs(n=4, max_new=24):
    return [Request(rid=i, prompt=np.array([i], np.int32),
                    max_new_tokens=max_new) for i in range(n)]


def _tokens(comps):
    return {c.rid: list(c.tokens) for c in comps}


def test_scheduler_routes_tune_and_keeps_identity():
    from tensorflow_distributed_tpu.observe.slo import (
        SLOMonitor, parse_slo)

    ref = _tokens(Scheduler(_FakeEngine(), decode_priority=16).run(
        _reqs()))
    recs = []
    ap = Autopilot(emit=lambda event, **f: recs.append(
        {"event": event, **f}), every=5, confirm=1, cooldown=0)
    # An impossible TTFT target: every completion violates, the burn
    # alert fires, and the autopilot must walk decode_priority down
    # THROUGH the live control-command path.
    mon = SLOMonitor(parse_slo("ttft_p95=0.000001ms"), fast_window=4,
                     slow_window=8)
    sched = Scheduler(_FakeEngine(), decode_priority=16,
                      slo_monitor=mon, autopilot=ap)
    comps = sched.run(_reqs())
    assert _tokens(comps) == ref                 # identity across
    assert sched.decode_priority < 16            # every actuation
    tunes = [r for r in recs if r["event"] == "tune"]
    assert tunes and all(r["knob"] == "decode_priority"
                         for r in tunes)
    assert sched.summary["tune_actions"] == len(tunes) == ap.actions
    assert sched.metrics_snapshot()["tune_actions"] == len(tunes)
    summaries = [r for r in recs if r["event"] == "tune_summary"]
    assert len(summaries) == 1
    assert summaries[0]["actions"] == len(tunes)
    assert summaries[0]["quiet"] is False


def test_scheduler_quiet_without_alerts():
    recs = []
    ap = Autopilot(emit=lambda event, **f: recs.append(
        {"event": event, **f}), every=5, confirm=1, cooldown=0)
    sched = Scheduler(_FakeEngine(), decode_priority=4, autopilot=ap)
    sched.run(_reqs())
    assert sched.summary["tune_actions"] == 0
    assert [r for r in recs if r["event"] == "tune_summary"][
        0]["quiet"] is True


def test_scheduler_spec_retune_through_engine():
    eng = _FakeSpecEngine(spec_tokens=2)
    spec = _FakeSpeculator(2, 2)
    ap = Autopilot(every=5, confirm=1, cooldown=0, k_ladder=(1, 2, 4))
    sched = Scheduler(eng, decode_priority=4, speculator=spec,
                      autopilot=ap)
    comps = sched.run(_reqs(n=2, max_new=40))
    # Full-accept stream: the window rate is 1.0 and the controller
    # deepens k through engine.set_spec_k + speculator.set_k.
    assert eng.set_k_calls == [4]
    assert eng.spec_tokens == 4 and spec.k == 4
    assert sched.summary["tune_actions"] == 1
    ref = _tokens(Scheduler(_FakeEngine(), decode_priority=4).run(
        _reqs(n=2, max_new=40)))
    assert _tokens(comps) == ref                 # identity across the
    #                                              mid-stream retune


def test_snapshot_windowed_fields_beside_cumulative():
    eng = _FakeSpecEngine(spec_tokens=3)
    sched = Scheduler(eng, decode_priority=4,
                      speculator=_FakeSpeculator(2, 3))
    sched.run(_reqs(n=2, max_new=30))
    snap = sched.metrics_snapshot()
    assert snap["accept_rate"] == 1.0            # lifetime-cumulative
    assert snap["accept_rate_window"] == 1.0     # rolling window
    assert snap["spec_tokens"] == 3
    assert snap["tokens_per_sec_window"] >= 0.0
    assert "tune_actions" not in snap            # no autopilot armed
    assert "tune_actions" not in sched.summary


def test_apply_tune_clamps_and_ignores_unknown():
    sched = Scheduler(_FakeEngine(num_slots=4), decode_priority=8)
    sched._apply_tune({"cmd": "tune", "knob": "decode_priority",
                       "value": 0})
    assert sched.decode_priority == 1
    sched._apply_tune({"cmd": "tune", "knob": "slot_cap", "value": 99})
    assert sched._slot_cap == 4                  # clamped to num_slots
    sched._apply_tune({"cmd": "tune", "knob": "slot_cap", "value": 0})
    assert sched._slot_cap == 1                  # floor: can't wedge
    sched._apply_tune({"cmd": "tune", "knob": "warp_factor",
                       "value": 9})              # unknown: ignored,
    assert sched._tunes == 3                     # not counted
    # spec_k without an engine that can retune: ignored, not counted.
    sched._apply_tune({"cmd": "tune", "knob": "spec_k", "value": 4})
    assert sched._tunes == 3


def test_report_folds_tune_records():
    from tensorflow_distributed_tpu.observe.report import summarize

    recs = [
        {"event": "tune", "step": 10, "loop": "admission",
         "knob": "decode_priority", "action": "tighten", "value": 4,
         "prev": 8, "signal": "slo_burn_fast", "observed": 2.0,
         "threshold": 1.0, "applied": True, "evidence": {}},
        {"event": "tune_summary", "step": 50, "evals": 5, "actions": 1,
         "advisories": 0, "suppressed": 1,
         "by_knob": {"decode_priority": 1}, "quiet": False},
        {"event": "serve_summary", "requests": 4, "decode_steps": 50,
         "decoded_tokens": 96, "wall_s": 1.0, "tokens_per_sec": 96.0,
         "tune_actions": 1},
        {"event": "metrics_snapshot", "t_s": 1.0, "decode_steps": 50,
         "requests_done": 4, "queue_depth": 0, "slot_occupancy": 0.5,
         "tokens_per_sec": 96.0, "accept_rate_window": 0.5,
         "tune_actions": 1},
    ]
    summary = summarize(recs)
    assert summary["serve_tune_actions"] == 1
    assert summary["tune"]["actions"] == 1
    assert summary["tune"]["quiet"] is False
    assert summary["tune"]["decisions_by_loop"] == {"admission": 1}
    assert summary["snapshot_last"]["accept_rate_window"] == 0.5
    assert summary["snapshot_last"]["tune_actions"] == 1
