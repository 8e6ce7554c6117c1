"""Incident-observatory suite: anomaly detectors, crash flight
recorder, postmortem forensics.

Fast tier (jax-free except the one Observatory wiring test):
value-pinned detector units on canned streams (a spike fires at the
EXACT step, a clean stream stays silent), the hub's train/serve feeds
and snapshot state, ring-buffer overflow/flush semantics, bundle
round-trip with truncated-tail tolerance, postmortem CLI output shape
and likely-cause heuristics, scheduler snapshot/export wiring on a
fake engine, supervisor bundle collection, and the config knob
matrix.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import signal

import numpy as np
import pytest

from tensorflow_distributed_tpu.observe import flightrec, postmortem
from tensorflow_distributed_tpu.observe.anomaly import (
    AnomalyHub, MadSpikeDetector, NonFiniteDetector, PlateauDetector,
    QueueGrowthDetector, RatioCollapseDetector, RollingMedianSpike,
    SlopeDegradationDetector)
from tensorflow_distributed_tpu.serve.engine import EngineSurface

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --- detector units (value-pinned on canned streams) --------------------

def test_mad_spike_fires_at_exact_step():
    det = MadSpikeDetector("t", window=32, min_samples=8)
    for i in range(20):
        assert det.observe(10.0) is None, f"fired on clean sample {i}"
    f = det.observe(500.0)
    assert f is not None
    assert f["baseline"] == 10.0 and f["value"] == 500.0
    assert f["zscore"] > 8.0
    assert f["evidence"][-1] == 10.0


def test_mad_spike_needs_min_samples():
    det = MadSpikeDetector("t", min_samples=8)
    for _ in range(7):
        det.observe(10.0)
    assert det.observe(500.0) is None  # 7 samples < 8: still arming


def test_mad_spike_outlier_not_absorbed_and_cooldown():
    det = MadSpikeDetector("t", window=16, min_samples=4)
    for _ in range(8):
        det.observe(10.0)
    assert det.observe(500.0) is not None
    # Cooldown: the next min_samples high values absorb silently
    # (regime shift re-baselines instead of paging per step)...
    for _ in range(det.min_samples):
        assert det.observe(500.0) is None
    # ...and the spiking sample was NOT added at fire time: baseline
    # still reflects mostly-clean history.
    assert 10.0 in det._buf


def test_mad_spike_scale_guards():
    # Relative jitter on a small baseline: z is huge (constant
    # series, MAD 0) but the ratio/abs guards hold.
    det = MadSpikeDetector("t", min_samples=4, ratio_min=4.0)
    for _ in range(8):
        det.observe(1.0)
    assert det.observe(3.0) is None          # 3x < ratio_min 4x
    det2 = MadSpikeDetector("t", min_samples=4, abs_min=50.0)
    for _ in range(8):
        det2.observe(1.0)
    assert det2.observe(8.0) is None         # excess 7 < abs_min 50
    assert det2.observe(80.0) is not None    # both guards cleared


def test_rolling_median_spike_semantics():
    det = RollingMedianSpike(window=4, factor=3.0)
    for v in (1.0, 1.0, 1.0):
        assert det.observe(v) is None
    assert det.observe(10.0) is None         # window not yet full
    assert det.observe(10.0) == 1.0          # full -> spike, median 1
    # The spike was not absorbed: the window median is unchanged and
    # the same value re-flags.
    assert det.observe(10.0) == 1.0
    det.reset()
    assert det.observe(10.0) is None         # empty window re-arms


def test_policies_loss_spike_is_the_anomaly_core():
    from tensorflow_distributed_tpu.resilience.policies import (
        LossSpikeDetector)

    assert issubclass(LossSpikeDetector, RollingMedianSpike)
    # Exact decision parity with an inline reference implementation
    # over a mixed stream (the behavior the resilience suite pins).
    import collections
    import statistics
    rng = np.random.default_rng(0)
    stream = list(rng.uniform(0.5, 1.5, size=64)) + [9.0] + \
        list(rng.uniform(0.5, 1.5, size=16))
    det = LossSpikeDetector(window=8, factor=4.0)
    ref_win: collections.deque = collections.deque(maxlen=8)
    for v in stream:
        got = det.observe(float(v))
        want = None
        if len(ref_win) == 8:
            med = statistics.median(ref_win)
            if v > 4.0 * max(med, 1e-12):
                want = med
        if want is None:
            ref_win.append(v)
        assert got == want


def test_slope_degradation_fires_on_sustained_drop():
    det = SlopeDegradationDetector("t", window=8, drop=0.4)
    for v in [100.0] * 6 + [50.0] * 2:
        assert det.observe(v) is None
    f = det.observe(50.0)                    # window now 5x100 + 3x50
    assert f is not None and f["baseline"] == 100.0 and f["value"] == 50.0
    # Cleared on fire: silent until a fresh full window accumulates.
    assert all(det.observe(50.0) is None for _ in range(7))


def test_slope_degradation_silent_on_stable_and_improving():
    det = SlopeDegradationDetector("t", window=8, drop=0.4)
    assert all(det.observe(v) is None
               for v in list(range(100, 140)))  # improving
    det.reset()
    assert all(det.observe(100.0 + (i % 3)) is None
               for i in range(40))               # stable jitter


def test_plateau_detector():
    det = PlateauDetector("t", window=8, min_improve=0.01)
    # Improving halves: silent.
    for v in (4.0, 4.0, 4.0, 4.0, 2.0, 2.0, 2.0):
        assert det.observe(v) is None
    assert det.observe(2.0) is None
    det.reset()
    f = None
    for v in [3.0] * 8:
        f = det.observe(v)
    assert f is not None and f["value"] == 3.0


def test_nonfinite_detector():
    det = NonFiniteDetector("t")
    assert det.observe(1.0) is None
    assert det.observe(float("nan")) is not None
    assert det.observe(float("inf")) is not None
    assert det.observe(None) is None         # not a number: no claim


def test_ratio_collapse_fires_on_frozen_module():
    det = RatioCollapseDetector("t", window=8, factor=50.0)
    for _ in range(8):
        assert det.observe(1e-3) is None
    f = det.observe(1e-6)                    # 1000x under the median
    assert f is not None and f["baseline"] == 1e-3
    assert all(det.observe(1e-3) is None for _ in range(16))  # healthy


def test_queue_growth_fires_at_exact_step():
    det = QueueGrowthDetector("t", window=8, min_growth=5)
    fired_at = None
    for i in range(12):
        if det.observe(float(i)) is not None:
            fired_at = i
            break
    assert fired_at == 7                     # the step the window filled
    det.reset()
    # Oscillating (draining) backlog: net growth but not at the max.
    for i in range(40):
        assert det.observe(float(10 - (i % 5))) is None


# --- the hub ------------------------------------------------------------

def _hub(phase="train", **kw):
    recs = []
    hub = AnomalyHub(emit=lambda ev, **f: recs.append((ev, dict(f))),
                     phase=phase, **kw)
    return hub, recs


def test_hub_train_nan_and_step_spike():
    hub, recs = _hub()
    for s in range(1, 20):
        assert hub.observe_train_step(s, {"loss": 2.0},
                                      step_wall_ms=10.0) == []
    out = hub.observe_train_step(20, {"loss": float("nan")},
                                 step_wall_ms=900.0)
    assert {r["detector"] for r in out} == {"loss_nonfinite",
                                            "step_time_spike"}
    assert all(r["step"] == 20 for r in out)
    assert [ev for ev, _ in recs] == ["anomaly", "anomaly"]
    crit = next(r for r in out if r["detector"] == "loss_nonfinite")
    assert crit["severity"] == "critical"


def test_hub_train_throughput_slope():
    hub, _ = _hub(window=64)   # slope window = 16
    fired = []
    for s in range(1, 40):
        tput = 1000.0 if s < 20 else 100.0
        fired += hub.observe_train_step(
            s, {"loss": 1.0, "tokens_per_sec": tput})
    assert any(r["detector"] == "throughput_slope" for r in fired)


def test_hub_health_explosion_and_collapse():
    hub, _ = _hub()
    fired = []
    for s in range(1, 40):
        fired += hub.observe_health(s, "layer_1",
                                    {"grad_norm": 0.5,
                                     "update_ratio": 1e-3})
    assert fired == []
    f1 = hub.observe_health(40, "layer_1", {"grad_norm": 1e3,
                                            "update_ratio": 1e-3})
    assert [r["detector"] for r in f1] == ["grad_norm_spike/layer_1"]
    assert f1[0]["severity"] == "critical" and f1[0]["module"] == "layer_1"
    f2 = hub.observe_health(41, "layer_1", {"grad_norm": 0.5,
                                            "update_ratio": 1e-9})
    assert [r["detector"] for r in f2] == [
        "update_ratio_collapse/layer_1"]


def test_hub_serve_decode_spike_and_queue_growth():
    hub, _ = _hub(phase="serve", window=64)  # queue window = 32
    fired = []
    for s in range(1, 40):
        fired += hub.observe_decode_step(s, queue_depth=s,
                                         step_wall_ms=5.0)
    growth = [r for r in fired if r["detector"] == "queue_growth"]
    assert growth and growth[0]["step"] == 32
    f = hub.observe_decode_step(40, queue_depth=0, step_wall_ms=800.0)
    assert [r["detector"] for r in f] == ["decode_time_spike"]


def test_hub_serve_ttft_and_slot_nonfinite():
    hub, recs = _hub(phase="serve")
    for s in range(1, 12):
        assert hub.observe_completion(s, 20.0) == []
    f = hub.observe_completion(12, 900.0)
    assert [r["detector"] for r in f] == ["ttft_spike"]
    f = hub.note_slot_nonfinite(13, slot=1, rid=7)
    assert f[0]["detector"] == "slot_nonfinite"
    assert f[0]["severity"] == "critical"
    assert f[0]["slot"] == 1 and f[0]["rid"] == 7
    assert len(recs) == 2


def test_hub_snapshot_and_active_horizon():
    hub, _ = _hub(window=16)
    for s in range(1, 12):
        hub.observe_train_step(s, {"loss": 1.0})
    hub.observe_train_step(12, {"loss": float("nan")})
    snap = hub.snapshot()
    assert snap["anomalies"] == 1
    assert snap["active"] == ["loss_nonfinite"]
    assert snap["by_detector"] == {"loss_nonfinite": 1}
    assert snap["last"]["detector"] == "loss_nonfinite"
    assert snap["last"]["step"] == 12
    # Past the active horizon (window steps) the detector drops out of
    # "active" but stays in the counts.
    for s in range(13, 40):
        hub.observe_train_step(s, {"loss": 1.0})
    snap = hub.snapshot()
    assert snap["active"] == [] and snap["anomalies"] == 1


def test_hub_validation():
    with pytest.raises(ValueError, match="phase"):
        AnomalyHub(phase="eval")
    with pytest.raises(ValueError, match="window"):
        AnomalyHub(window=4)


# --- flight recorder ----------------------------------------------------

def test_ring_overflow_and_tails(tmp_path):
    rec = flightrec.FlightRecorder(str(tmp_path), ring=8,
                                   snapshot_every=1000)
    for i in range(20):
        rec.record({"event": "step", "step": i})
    rec.record({"event": "compile", "program": "train_step"})
    assert len(rec.ring) == 8                # bounded
    assert rec.ring[-1]["event"] == "compile"
    assert [r["step"] for r in rec.ring if r.get("event") == "step"] \
        == list(range(13, 20))               # oldest dropped
    assert len(rec._tails["compile"]) == 1   # kind tail survives churn


def test_snapshot_cadence_and_flush_on_anomaly(tmp_path):
    rec = flightrec.FlightRecorder(str(tmp_path), ring=32,
                                   snapshot_every=5)
    for i in range(4):
        rec.record({"event": "step", "step": i})
    assert not os.path.exists(rec.snapshot_path)   # cadence not hit
    rec.record({"event": "step", "step": 4})
    assert os.path.exists(rec.snapshot_path)       # 5th record
    os.remove(rec.snapshot_path)
    rec.record({"event": "anomaly", "detector": "x", "step": 5})
    assert os.path.exists(rec.snapshot_path)       # incident: immediate
    b = flightrec.load_bundle(rec.snapshot_path)
    assert b["meta"]["bundle"] == "snapshot"
    assert b["last"]["anomaly"][0]["detector"] == "x"


def test_bundle_round_trip_and_truncated_tail(tmp_path):
    rec = flightrec.FlightRecorder(str(tmp_path), ring=16,
                                   snapshot_every=1000,
                                   meta={"git_sha": "abc123",
                                         "config": {"model": "x"}})
    for i in range(10):
        rec.record({"event": "step", "step": i, "t": i * 0.1})
    rec.record({"event": "recovery", "kind": "fault_injected",
                "fault": "nan_grad", "step": 9})
    path = rec.dump("FloatingPointError: non-finite loss nan at step 10")
    b = flightrec.load_bundle(path)
    assert b["meta"]["reason"].startswith("FloatingPointError")
    assert b["meta"]["git_sha"] == "abc123"
    assert b["meta"]["config"] == {"model": "x"}
    assert len(b["records"]) == 11 and b["torn"] == 0
    assert b["last"]["recovery"][0]["fault"] == "nan_grad"
    assert b["tracebacks"]                    # thread stacks captured
    # First dump wins; later calls return the same path.
    assert rec.dump("other") == path
    # Torn tail (the death cut the final write): every complete line
    # still loads, the torn one is counted.
    with open(path, "ab") as f:
        f.write(b'{"kind": "record", "data": {"event": "ste')
    b2 = flightrec.load_bundle(path)
    assert b2["torn"] == 1
    assert len(b2["records"]) == len(b["records"])


def test_flightrec_sink_rides_registry(tmp_path):
    from tensorflow_distributed_tpu.observe.registry import (
        MetricsRegistry)

    rec = flightrec.FlightRecorder(str(tmp_path), snapshot_every=1000)
    reg = MetricsRegistry([flightrec.FlightRecorderSink(rec)],
                          tags={"process_index": 0})
    reg.emit("step", step=1, loss=2.0)
    reg.emit("anomaly", detector="loss_spike", step=1)
    assert rec.ring[0]["event"] == "step"
    assert rec.ring[0]["process_index"] == 0  # tags ride along
    assert os.path.exists(rec.snapshot_path)  # anomaly flushed
    reg.close()                               # sink close -> recorder close


def test_sigterm_hook_dumps_then_chains(tmp_path):
    rec = flightrec.FlightRecorder(str(tmp_path), snapshot_every=1000)
    rec.record({"event": "step", "step": 1})
    called = []
    rec._prev_sigterm = lambda signum, frame: called.append(signum)
    rec._on_sigterm(signal.SIGTERM, None)
    assert rec.dumped and os.path.exists(rec.dumped)
    assert called == [signal.SIGTERM]         # previous handler ran
    b = flightrec.load_bundle(rec.dumped)
    assert b["meta"]["reason"] == "sigterm"
    assert b["meta"]["signal"] == int(signal.SIGTERM)


def test_install_close_restores_sigterm(tmp_path):
    prev = signal.getsignal(signal.SIGTERM)
    rec = flightrec.FlightRecorder(str(tmp_path))
    rec.install()
    try:
        assert signal.getsignal(signal.SIGTERM) == rec._on_sigterm
    finally:
        rec.close()
    assert signal.getsignal(signal.SIGTERM) == prev
    assert os.path.exists(rec.snapshot_path)  # close left a snapshot


def test_newest_bundle_prefers_postmortem(tmp_path):
    rec = flightrec.FlightRecorder(str(tmp_path), snapshot_every=1000)
    rec.record({"event": "step", "step": 1})
    snap = rec.snapshot()
    assert flightrec.newest_bundle(str(tmp_path)) == snap
    dump = rec.dump("boom")
    os.utime(snap, None)                      # snapshot is NEWER...
    assert flightrec.newest_bundle(str(tmp_path)) == dump  # ...still
    assert flightrec.newest_bundle(str(tmp_path),
                                   since=os.path.getmtime(dump)
                                   + 3600) is None
    assert flightrec.newest_bundle(str(tmp_path / "missing")) is None


# --- postmortem CLI -----------------------------------------------------

def _canned_bundle(tmp_path, reason=None, kind="dump"):
    rec = flightrec.FlightRecorder(str(tmp_path), ring=32,
                                   snapshot_every=1000,
                                   meta={"git_sha": "abc123"})
    hub = AnomalyHub(emit=lambda ev, **f: rec.record(
        {"event": ev, **f}), phase="train")
    for s in range(1, 20):
        rec.record({"event": "step", "step": s, "t": s * 0.1,
                    "loss": 2.0})
        hub.observe_train_step(s, {"loss": 2.0}, step_wall_ms=10.0)
    hub.observe_health(38, "layer_1", {"grad_norm": 1.0})
    for s in range(21, 38):
        hub.observe_health(s, "layer_1", {"grad_norm": 1.0})
    fired = hub.observe_health(38, "layer_1", {"grad_norm": 1e4})
    assert fired
    rec.record({"event": "step", "step": 40, "t": 4.0,
                "loss": float("nan")})
    if kind == "dump":
        return rec.dump(reason or
                        "FloatingPointError: non-finite loss at 40")
    return rec.snapshot()


def test_postmortem_report_shape(tmp_path):
    path = _canned_bundle(tmp_path)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        assert postmortem.main([path]) == 0
    out = buf.getvalue()
    for section in ("== postmortem:", "Anomalies preceding death",
                    "Likely cause", "Timeline", "Last by kind",
                    "Tracebacks"):
        assert section in out, f"missing section {section!r}"
    assert "grad_norm_spike/layer_1" in out
    assert "git_sha=abc123" in out


def test_postmortem_likely_cause_nonfinite(tmp_path):
    b = flightrec.load_bundle(_canned_bundle(tmp_path))
    cause = postmortem.likely_cause(b)
    assert "grad-norm explosion in layer_1 at step 38" in cause
    assert "nonfinite halt at step 40" in cause


def test_postmortem_likely_cause_untrapped_kill(tmp_path):
    b = flightrec.load_bundle(_canned_bundle(tmp_path,
                                             kind="snapshot"))
    assert "untrapped process death" in postmortem.likely_cause(b)


def test_postmortem_likely_cause_no_anomalies(tmp_path):
    rec = flightrec.FlightRecorder(str(tmp_path), snapshot_every=1000)
    rec.record({"event": "step", "step": 3})
    b = flightrec.load_bundle(rec.dump("StallError: data stall"))
    cause = postmortem.likely_cause(b)
    assert cause.startswith("no anomalies preceded the stall halt")


def test_postmortem_json_and_bad_input(tmp_path):
    path = _canned_bundle(tmp_path)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        assert postmortem.main([path, "--json"]) == 0
    obj = json.loads(buf.getvalue())
    assert obj["likely_cause"]
    junk = tmp_path / "junk.jsonl"
    junk.write_text("not json\n")
    assert postmortem.main([str(junk)]) == 1


# --- scheduler / snapshot wiring (fake engine, jax-free) ----------------

class _FakeEngine(EngineSurface):
    """Deterministic stream: token = rid * 100 + count (the serve-slo
    suite's fake, trimmed)."""

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
        self.active[slot] = True
        self.slot_rid[slot] = rid
        self.counts[rid] = len(prompt) - 1
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


class _QuarantineOnceEngine(_FakeEngine):
    def __init__(self, **kw):
        super().__init__(**kw)
        self._fired = False

    def take_bad_slots(self):
        if not self._fired and self.decode_steps >= 1:
            self._fired = True
            return [0]
        return []


def _reqs(n, max_new=6):
    from tensorflow_distributed_tpu.serve.scheduler import Request
    return [Request(rid=i, prompt=np.asarray([i], np.int32),
                    max_new_tokens=max_new) for i in range(n)]


def test_scheduler_feeds_hub_and_snapshot_carries_anomaly_state():
    from tensorflow_distributed_tpu.serve.scheduler import Scheduler

    hub, recs = _hub(phase="serve", window=8)
    sched = Scheduler(_QuarantineOnceEngine(num_slots=2),
                      decode_priority=2, anomaly_hub=hub,
                      slot_retries=2)
    done = sched.run(_reqs(3))
    assert len(done) == 3
    # The quarantined slot surfaced as a critical anomaly...
    assert hub.by_detector.get("slot_nonfinite") == 1
    assert recs and recs[0][1]["detector"] == "slot_nonfinite"
    # ...and the export payload carries the incident state.
    snap = sched.metrics_snapshot()
    assert snap["anomaly"]["anomalies"] == 1
    assert "slot_nonfinite" in snap["anomaly"]["by_detector"]
    assert sched.summary["anomalies"] == 1


def test_scheduler_without_hub_shape_stable():
    from tensorflow_distributed_tpu.serve.scheduler import Scheduler

    sched = Scheduler(_FakeEngine(num_slots=2), decode_priority=2)
    sched.run(_reqs(2))
    assert "anomaly" not in sched.metrics_snapshot()
    assert "anomalies" not in sched.summary


def test_serve_observatory_arms_hub_and_flightrec(tmp_path):
    from tensorflow_distributed_tpu.config import ObserveConfig
    from tensorflow_distributed_tpu.observe.hub import ServeObservatory

    ocfg = ObserveConfig(
        metrics_jsonl=str(tmp_path / "m.jsonl"), anomaly=True,
        flightrec=str(tmp_path / "flight"))
    ocfg.validate()
    obs = ServeObservatory(ocfg, tags={"process_index": 0},
                           run_config={"serve": {"num_slots": 2}})
    try:
        kwargs = obs.scheduler_kwargs()
        assert kwargs["anomaly_hub"] is obs.anomalies
        assert obs.anomalies.phase == "serve"
        assert obs.flightrec is not None
        # Serve bundles carry the launch config like train bundles.
        assert obs.flightrec.meta["config"] == {
            "serve": {"num_slots": 2}}
        obs.registry.emit("anomaly", detector="x", step=1)
        assert obs.flightrec.ring[-1]["detector"] == "x"
    finally:
        obs.close()
    assert os.path.exists(obs.flightrec.snapshot_path)


# --- supervisor bundle collection ---------------------------------------

def test_supervisor_leg_bundle(tmp_path):
    from tensorflow_distributed_tpu.resilience.supervisor import (
        _leg_bundle)

    rec = flightrec.FlightRecorder(str(tmp_path), snapshot_every=1000)
    rec.record({"event": "step", "step": 1})
    snap = rec.snapshot()
    assert _leg_bundle(str(tmp_path), since=0.0) == snap
    assert _leg_bundle(None, since=0.0) is None
    assert _leg_bundle(str(tmp_path / "nope"), since=0.0) is None


# --- report folding -----------------------------------------------------

def test_report_folds_anomalies_and_postmortem():
    from tensorflow_distributed_tpu.observe.report import (
        render, summarize)

    records = [
        {"event": "step", "step": 1, "loss": 1.0},
        {"event": "anomaly", "detector": "loss_nonfinite",
         "severity": "critical", "step": 8},
        {"event": "anomaly", "detector": "step_time_spike",
         "severity": "warn", "step": 9},
        {"event": "anomaly", "detector": "step_time_spike",
         "severity": "warn", "step": 14},
        {"event": "postmortem", "bundle": "/tmp/p.jsonl",
         "reason": "boom"},
    ]
    out = summarize(records)
    assert out["anomalies"]["count"] == 3
    assert out["anomalies"]["by_detector"] == {
        "loss_nonfinite": 1, "step_time_spike": 2}
    assert out["anomalies"]["last"]["step"] == 14
    assert out["postmortem_bundles"] == ["/tmp/p.jsonl"]
    text = render(out)
    assert "Anomalies" in text and "Postmortem bundles" in text
    # Plain reports stay shape-stable.
    plain = summarize([{"event": "step", "step": 1, "loss": 1.0}])
    assert "anomalies" not in plain and "postmortem_bundles" not in plain


# --- config knobs -------------------------------------------------------

def test_observe_config_incident_validation():
    from tensorflow_distributed_tpu.config import ObserveConfig

    ObserveConfig(anomaly=True, anomaly_window=32).validate()
    ObserveConfig(flightrec="/tmp/f", flightrec_ring=64,
                  flightrec_snapshot_every=10).validate()
    with pytest.raises(ValueError, match="anomaly_window must be"):
        ObserveConfig(anomaly=True, anomaly_window=4).validate()
    with pytest.raises(ValueError, match="no effect without "
                                         "observe.anomaly"):
        ObserveConfig(anomaly_window=32).validate()
    with pytest.raises(ValueError, match="flightrec_ring must be"):
        ObserveConfig(flightrec="/tmp/f",
                      flightrec_ring=4).validate()
    with pytest.raises(ValueError, match="flightrec_snapshot_every"):
        ObserveConfig(flightrec="/tmp/f",
                      flightrec_snapshot_every=0).validate()
    with pytest.raises(ValueError, match="no effect without "
                                         "observe.flightrec"):
        ObserveConfig(flightrec_ring=64).validate()


# --- Observatory wiring (needs the observe hub's jax-adjacent deps) ----

def test_observatory_feeds_hub_and_dumps_on_exception(tmp_path):
    from tensorflow_distributed_tpu.config import ObserveConfig
    from tensorflow_distributed_tpu.observe.hub import Observatory

    ocfg = ObserveConfig(metrics_jsonl=str(tmp_path / "m.jsonl"),
                         anomaly=True,
                         flightrec=str(tmp_path / "flight"))
    ocfg.validate()
    clock = iter(np.arange(0.0, 100.0, 0.01))
    obs = Observatory(ocfg, tags={"process_index": 0},
                      clock=lambda: float(next(clock)),
                      run_config={"model": "unit"})
    try:
        assert obs.anomalies is not None and obs.flightrec is not None
        assert obs.flightrec.meta["config"] == {"model": "unit"}
        for s in range(1, 12):
            obs.log_step(s, {"loss": 2.0})
        obs.log_step(12, {"loss": float("nan")})
        # The health tee routes through emit().
        obs.emit("health", step=12, module="layer_0", grad_norm=0.5)
        assert obs.anomalies.by_detector.get("loss_nonfinite") == 1
        anoms = [r for r in obs.registry.records
                 if r["event"] == "anomaly"]
        assert anoms and anoms[0]["detector"] == "loss_nonfinite"
        try:
            raise FloatingPointError("non-finite loss nan at step 12")
        except FloatingPointError:
            obs.close()
        assert obs.flightrec.dumped
        post = [r for r in obs.registry.records
                if r["event"] == "postmortem"]
        assert post and post[0]["bundle"] == obs.flightrec.dumped
        b = flightrec.load_bundle(obs.flightrec.dumped)
        assert "FloatingPointError" in b["meta"]["reason"]
        assert b["last"]["anomaly"][-1]["detector"] == "loss_nonfinite"
    finally:
        obs.close()  # idempotent
