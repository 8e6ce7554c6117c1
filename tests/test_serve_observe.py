"""Serve observatory: per-request tracing, the live SLO burn-rate
monitor, and exportable rolling metrics (ISSUE 11).

Fast tier is jax-free: SLO grammar + burn-rate window math on the
deterministic decode-step clock, the ChromeTracer async primitives and
ServeTracer span trees (fake engines + fake clocks), the scheduler's
``metrics_snapshot()`` / export cadence / status line, report folding
(incl. the value-pinned recovery-window p99 — ISSUE satellite), the
per-slot verify fallback's scheduler accounting, and the
warmup-wall-exclusion audit. The slow tier pins the draft-model
warmup compile counter, the per-slot verify fallback's token identity
on the real engine, and a mode=serve e2e with the whole observatory
armed.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from tensorflow_distributed_tpu.observe.slo import (
    SLOMonitor, SLOTarget, parse_slo, parse_windows, percentile)
from tensorflow_distributed_tpu.observe.serve_trace import ServeTracer
from tensorflow_distributed_tpu.observe.trace import (
    ChromeTracer, load_trace, unbalanced_async)
from tensorflow_distributed_tpu.serve.engine import EngineSurface
from tensorflow_distributed_tpu.serve.scheduler import (
    Request, Scheduler)


# --- SLO grammar --------------------------------------------------------

def test_parse_slo_grammar():
    targets = parse_slo(
        "high:ttft_p95=100ms,tok_p50=30ms;standard:ttft_p95=0.5s;"
        "tok_p99=500us")
    assert [t.key for t in targets] == [
        "high:ttft_p95", "high:tok_p50", "standard:ttft_p95",
        "tok_p99"]
    assert targets[0].threshold_ms == 100.0
    assert targets[2].threshold_ms == 500.0      # 0.5s
    assert targets[3].threshold_ms == 0.5        # 500us
    assert targets[3].cls == ""                  # classless = all
    assert targets[0].budget == pytest.approx(0.05)
    assert targets[1].budget == pytest.approx(0.50)


@pytest.mark.parametrize("spec, match", [
    ("", "names no targets"),
    ("high:", "names no targets"),
    ("high:ttft=100ms", "not metric_pNN"),
    ("high:latency_p95=100ms", "unknown SLO metric"),
    ("ttft_p95=100", "unit suffix"),
    ("ttft_p0=100ms", "percentile"),
    ("ttft_p100=100ms", "percentile"),
    ("ttft_pxx=100ms", "not an integer"),
    ("ttft_p95=0ms", "must be > 0"),
    ("ttft_p95=100ms,ttft_p95=200ms", "declared twice"),
    ("high:ttft_p95", "not metric_pNN=value"),
])
def test_parse_slo_rejections(spec, match):
    with pytest.raises(ValueError, match=match):
        parse_slo(spec)


def test_parse_windows():
    assert parse_windows("60,600") == (60, 600)
    assert parse_windows(" 4 , 16 ") == (4, 16)
    for bad in ("60", "600,60", "0,10", "1,2,3"):
        with pytest.raises(ValueError):
            parse_windows(bad)


# --- burn-rate monitor (deterministic decode-step clock) ----------------

def _collect():
    events = []

    def emit(event, **fields):
        events.append({"event": event, **fields})

    return events, emit


def test_burn_rate_alert_fires_and_clears():
    """p95 target, windows 4/8, threshold 1: one violation in both
    windows burns 5x the budget -> alert; once both windows drain the
    violation, slo_ok. The whole trace is pinned — same inputs, same
    events, every run."""
    events, emit = _collect()
    mon = SLOMonitor(parse_slo("ttft_p95=100ms"), fast_window=4,
                     slow_window=8, burn_threshold=1.0, emit=emit)
    # Steps 1-2: compliant completions — no events.
    mon.observe("standard", 10.0, 1.0, step=1)
    assert mon.on_step(1) == []
    mon.observe("standard", 20.0, 1.0, step=2)
    assert mon.on_step(2) == []
    # Step 3: a violation. fast = 1/3 / 0.05 = 6.67x, slow the same ->
    # alert fires at step 3 exactly.
    mon.observe("standard", 500.0, 1.0, step=3)
    fired = mon.on_step(3)
    assert [e["event"] for e in fired] == ["slo_alert"]
    assert fired[0]["burn_fast"] == pytest.approx(1 / 3 / 0.05, rel=1e-3)
    assert fired[0]["budget_remaining"] == pytest.approx(
        1 - 1 / (0.05 * 3), abs=1e-3)
    assert mon.any_alerting()
    # Steps 4-7: quiet (still alerting, no transition). The violation
    # leaves the FAST window after step 3 + 4 -> slo_ok at step 7.
    cleared = []
    for s in range(4, 9):
        cleared += mon.on_step(s)
    assert [e["event"] for e in cleared] == ["slo_ok"]
    assert cleared[0]["step"] == 7
    assert not mon.any_alerting()
    assert events == fired + cleared          # emit mirrored returns
    assert mon.summary()["slo_alerts"] == 1


def test_budget_remaining_math():
    events, emit = _collect()
    mon = SLOMonitor(parse_slo("ttft_p95=100ms"), fast_window=2,
                     slow_window=20, emit=emit)
    for i in range(19):
        mon.observe("standard", 1.0, 1.0, step=1)
    mon.observe("standard", 999.0, 1.0, step=1)
    # 20 observed, 1 violation, budget 5% -> exactly spent.
    snap = mon.snapshot()["ttft_p95"]
    assert snap["budget_remaining"] == pytest.approx(0.0)
    mon.observe("standard", 999.0, 1.0, step=1)
    assert mon.snapshot()["ttft_p95"]["budget_remaining"] < 0


def test_monitor_class_filter_and_snapshot():
    mon = SLOMonitor(parse_slo("high:ttft_p95=100ms"), fast_window=2,
                     slow_window=4)
    mon.observe("standard", 9999.0, 1.0, step=1)   # wrong class
    mon.on_step(1)
    assert mon.snapshot()["high:ttft_p95"]["observed"] == 0
    mon.observe("high", 50.0, 1.0, step=2)
    mon.on_step(2)
    snap = mon.snapshot()["high:ttft_p95"]
    assert snap["observed"] == 1
    assert snap["window_value_ms"] == 50.0
    assert "high:ttft_p95" in mon.status_bits()


# --- tracer primitives --------------------------------------------------

def _tick_clock(step=0.001):
    t = [0.0]

    def clock():
        t[0] += step
        return t[0]

    return clock


def test_chrome_tracer_async_and_balance(tmp_path):
    path = str(tmp_path / "t.json")
    tr = ChromeTracer(path, clock=_tick_clock())
    tr.async_begin("request", 1, cat="serve", slo="high")
    tr.async_begin("queue", 1, cat="serve")
    tr.async_end("queue", 1, cat="serve")
    tr.async_begin("request", 2, cat="serve")
    tr.close()
    ev = load_trace(path)
    bs = [e for e in ev if e.get("ph") == "b"]
    assert {(e["name"], e["id"]) for e in bs} == {
        ("request", "1"), ("queue", "1"), ("request", "2")}
    stray = unbalanced_async(ev)
    assert [(e["name"], e["id"]) for e in stray] == [("request", "1"),
                                                     ("request", "2")]


def test_chrome_tracer_cap_preserves_async_balance(tmp_path):
    """The max_events cap must never unbalance async spans: an "e"
    whose "b" was recorded is appended even past the cap; an "e"
    whose "b" was dropped is dropped with it (no stray ends)."""
    path = str(tmp_path / "t.json")
    tr = ChromeTracer(path, clock=_tick_clock(), max_events=3)
    tr.async_begin("a", 1, cat="serve")
    tr.async_begin("b", 2, cat="serve")
    tr.instant("filler")                  # buffer now at the cap
    tr.async_begin("c", 3, cat="serve")   # dropped
    tr.async_end("c", 3, cat="serve")     # dropped with its begin
    tr.async_end("b", 2, cat="serve")     # forced past the cap
    tr.async_end("a", 1, cat="serve")     # forced past the cap
    tr.close()
    ev = load_trace(path)
    assert not unbalanced_async(ev)
    assert not any(e.get("name") == "c" for e in ev)
    assert tr.dropped >= 2                # c's begin + end accounted


def test_chrome_tracer_preload_offsets_clock(tmp_path):
    tr = ChromeTracer(str(tmp_path / "t.json"), clock=_tick_clock())
    tr.preload([{"ph": "X", "name": "old", "ts": 500.0, "dur": 100.0}])
    tr.instant("new")
    tr.close()
    ev = load_trace(str(tmp_path / "t.json"))
    new = [e for e in ev if e.get("name") == "new"][0]
    assert new["ts"] > 600.0              # after the preloaded span


def test_serve_tracer_request_tree(tmp_path):
    path = str(tmp_path / "serve.json")
    tr = ServeTracer(path, clock=_tick_clock())
    tr.request_queued(7, slo="high", prompt_len=5, tenant="t0")
    with tr.prefill(7, bucket=16, slot=0):
        pass
    tr.request_done(7, "eos", 12, 34.5)
    tr.close()
    ev = load_trace(path)
    assert not unbalanced_async(ev)
    names = [e["name"] for e in ev if e.get("ph") == "b"]
    assert names == ["request", "queue", "prefill", "decode"]


def test_serve_tracer_evict_reopens_queue(tmp_path):
    path = str(tmp_path / "serve.json")
    tr = ServeTracer(path, clock=_tick_clock())
    tr.request_queued(1)
    with tr.prefill(1, bucket=16, slot=0):
        pass
    tr.request_evicted(1, "quarantine")
    with tr.prefill(1, bucket=32, slot=1):
        pass
    tr.request_done(1, "length", 8, 10.0)
    tr.close()
    ev = load_trace(path)
    assert not unbalanced_async(ev)
    queues = [e for e in ev if e.get("name") == "queue"
              and e.get("ph") == "b"]
    assert len(queues) == 2               # original + post-eviction


def test_serve_tracer_resume_closes_dead_spans(tmp_path):
    """A killed leg leaves open spans in the flushed file; the resumed
    tracer closes them at the resume instant and continues the
    timeline — one balanced file across the restart."""
    path = str(tmp_path / "serve.json")
    dead = ServeTracer(path, clock=_tick_clock())
    dead.request_queued(1)
    with dead.prefill(1, bucket=16, slot=0):
        pass                               # decode left open = in flight
    dead.flush()                           # what a SIGKILL leaves behind
    assert unbalanced_async(load_trace(path))
    alive = ServeTracer(path, clock=_tick_clock(), resume=True)
    alive.request_queued(2)
    with alive.prefill(2, bucket=16, slot=0):
        pass
    alive.request_done(2, "eos", 4, 9.0)
    alive.close()
    ev = load_trace(path)
    assert not unbalanced_async(ev)
    assert any(e.get("name") == "journal_resume" for e in ev)
    death_ends = [e for e in ev if e.get("ph") == "e"
                  and (e.get("args") or {}).get("process_death")]
    assert {e["name"] for e in death_ends} == {"request", "decode"}


def test_serve_tracer_close_balances_open_requests(tmp_path):
    path = str(tmp_path / "serve.json")
    tr = ServeTracer(path, clock=_tick_clock())
    tr.request_queued(3)
    tr.close()
    assert not unbalanced_async(load_trace(path))


# --- fake engines (jax-free; mirror tests/test_serve_slo.py) ------------

class _FakeEngine(EngineSurface):
    """Deterministic stream: token = rid * 100 + count; continuation-
    aware (rid rides prompt[0], emitted count = len(prompt) - 1)."""

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
    """Flags slot 0 bad exactly once, on the first decode step."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self._fired = False

    def take_bad_slots(self):
        if not self._fired and self.decode_steps >= 1:
            self._fired = True
            return [0]
        return []


class _FallbackFakeEngine(_FakeEngine):
    """Speculative surface implementing the per-slot fallback
    contract: REQUEST 1 never has verify headroom (wherever it sits,
    verify_fallback_slots names its slot), so each verify dispatch
    must retire k+1 tokens for request 0's slot and exactly 1 for
    request 1's, with the scheduler excluding the latter from accept
    accounting."""

    def __init__(self, spec_tokens=3, **kw):
        super().__init__(num_slots=2, **kw)
        self.spec_tokens = spec_tokens
        self.verify_steps = 0
        self.seen_tails = []
        self.last_verify_fallback = []

    def verify_fallback_slots(self):
        return [s for s in range(self.num_slots)
                if self.active[s] and self.slot_rid.get(s) == 1]

    def verify_step(self, props, tails=None):
        k = self.spec_tokens
        assert np.asarray(props).shape == (2, k)
        fb = [s for s in (tails or {})]
        self.seen_tails.append(dict(tails or {}))
        toks = np.zeros((2, k + 1), np.int32)
        acc = np.zeros((2,), np.int32)
        for s in range(2):
            if not self.active[s]:
                continue
            rid = self.slot_rid[s]
            n = 1 if s in fb else k + 1
            for j in range(n):
                self.counts[rid] += 1
                toks[s, j] = rid * 100 + self.counts[rid]
            acc[s] = n
        self.decode_steps += 1
        self.verify_steps += 1
        self.last_verify_fallback = fb
        return toks, acc


class _NullSpec:
    needs_histories = True

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


class _FakeRegistry:
    def __init__(self):
        self.records = []

    def emit(self, event, **fields):
        self.records.append({"event": event, **fields})


def _reqs(n, max_new=6, slo=None):
    return [Request(rid=i, prompt=np.asarray([i], np.int32),
                    max_new_tokens=max_new,
                    slo=(slo[i] if slo else "standard"))
            for i in range(n)]


def _expected(rid, max_new, plen=1):
    return [rid * 100 + (plen - 1) + j for j in range(max_new)]


# --- scheduler wiring ----------------------------------------------------

def test_scheduler_traces_requests_fake_engine(tmp_path):
    path = str(tmp_path / "serve.json")
    tr = ServeTracer(path, clock=_tick_clock())
    sched = Scheduler(_FakeEngine(num_slots=2), decode_priority=2,
                      tracer=tr, clock=_tick_clock())
    done = sched.run(_reqs(4))
    assert len(done) == 4
    tr.close()
    ev = load_trace(path)
    assert not unbalanced_async(ev)
    reqs = [e for e in ev if e.get("ph") == "b"
            and e["name"] == "request"]
    assert {e["id"] for e in reqs} == {"0", "1", "2", "3"}
    assert {e["name"] for e in ev if e.get("ph") == "C"} >= {
        "slots", "queue", "tokens_per_s"}


def test_scheduler_quarantine_traced_and_balanced(tmp_path):
    path = str(tmp_path / "serve.json")
    tr = ServeTracer(path, clock=_tick_clock())
    reg = _FakeRegistry()
    sched = Scheduler(_QuarantineOnceEngine(num_slots=1),
                      decode_priority=2, tracer=tr, registry=reg,
                      clock=_tick_clock())
    done = sched.run(_reqs(1, max_new=5))
    tr.close()
    assert done[0].tokens == _expected(0, 5)     # identity through it
    ev = load_trace(path)
    assert not unbalanced_async(ev)
    assert any(e.get("name") == "slot_quarantine"
               and e.get("ph") == "i" for e in ev)
    # The request's track shows serve -> evict -> requeue -> serve.
    assert len([e for e in ev if e.get("ph") == "b"
                and e["name"] == "queue"]) == 2


def test_metrics_snapshot_fields_and_pinned_percentiles():
    reg = _FakeRegistry()
    sched = Scheduler(_FakeEngine(num_slots=2), decode_priority=2,
                      registry=reg, clock=_tick_clock(),
                      policy="slo",
                      slo_monitor=SLOMonitor(
                          parse_slo("ttft_p95=10000ms"),
                          fast_window=4, slow_window=8,
                          emit=reg.emit))
    slos = ["high", "standard", "standard", "batch"]
    done = sched.run(_reqs(4, slo=slos))
    snap = sched.metrics_snapshot()
    assert snap["requests_done"] == 4
    assert snap["requests_live"] == 0 and snap["queue_depth"] == 0
    assert snap["decoded_tokens"] == sum(len(c.tokens) for c in done)
    assert snap["decode_steps"] == sched.summary["decode_steps"]
    # Per-class p95 pinned to the report's nearest-rank formula over
    # the same completions.
    for cls in ("high", "standard", "batch"):
        vals = sorted(1e3 * c.ttft_s for c in done if c.slo == cls)
        assert snap[f"ttft_ms_p95_{cls}"] == round(
            percentile(vals, 95), 3)
    assert snap["slo"]["ttft_p95"]["observed"] == 4
    assert snap["slo"]["ttft_p95"]["alerting"] is False


def test_export_cadence_atomic_file_and_records(tmp_path):
    path = str(tmp_path / "snap.json")
    reg = _FakeRegistry()
    sched = Scheduler(_FakeEngine(num_slots=1), decode_priority=2,
                      registry=reg, clock=_tick_clock(0.01),
                      export_every=0.05, export_path=path)
    sched.run(_reqs(3, max_new=8))
    snaps = [r for r in reg.records
             if r["event"] == "metrics_snapshot"]
    assert len(snaps) >= 2                # cadence + forced final
    final = json.load(open(path))
    # The file is the LAST emitted snapshot, atomically replaced.
    assert final == {k: v for k, v in snaps[-1].items()
                     if k != "event"}
    assert final["requests_done"] == 3    # forced final covers all


def test_export_final_only_with_path(tmp_path):
    path = str(tmp_path / "snap.json")
    reg = _FakeRegistry()
    sched = Scheduler(_FakeEngine(num_slots=1), decode_priority=2,
                      registry=reg, clock=_tick_clock(),
                      export_every=0.0, export_path=path)
    sched.run(_reqs(2))
    snaps = [r for r in reg.records
             if r["event"] == "metrics_snapshot"]
    assert len(snaps) == 1                # only the forced final
    assert json.load(open(path))["requests_done"] == 2


def test_slo_events_flow_through_scheduler():
    reg = _FakeRegistry()
    mon = SLOMonitor(parse_slo("ttft_p95=0.000001ms"), fast_window=2,
                     slow_window=4, emit=reg.emit)
    sched = Scheduler(_FakeEngine(num_slots=1), decode_priority=2,
                      registry=reg, clock=_tick_clock(),
                      slo_monitor=mon)
    sched.run(_reqs(3))
    events = [r["event"] for r in reg.records]
    assert "slo_alert" in events
    summary = sched.summary
    assert summary["slo_alerts"] >= 1
    assert summary["slo_budget_remaining_min"] < 0
    assert summary["slo_targets"] == "ttft_p95"
    # A generous target on the same workload stays quiet.
    reg2 = _FakeRegistry()
    sched2 = Scheduler(_FakeEngine(num_slots=1), decode_priority=2,
                       registry=reg2, clock=_tick_clock(),
                       slo_monitor=SLOMonitor(
                           parse_slo("ttft_p95=1e9ms"), fast_window=2,
                           slow_window=4, emit=reg2.emit))
    sched2.run(_reqs(3))
    assert not any(r["event"] == "slo_alert" for r in reg2.records)
    assert sched2.summary["slo_alerts"] == 0


def test_status_line_cadence_and_content():
    lines = []
    sched = Scheduler(_FakeEngine(num_slots=1), decode_priority=2,
                      clock=_tick_clock(),
                      slo_monitor=SLOMonitor(
                          parse_slo("ttft_p95=100ms"), fast_window=2,
                          slow_window=4),
                      status_fn=lines.append, status_every=4)
    sched.run(_reqs(3, max_new=8))
    steps = sched.summary["decode_steps"]
    assert len(lines) == steps // 4
    assert "occ=" in lines[0] and "queue=" in lines[0]
    assert "ttft_p95" in lines[0]


def test_summary_wall_excludes_prerun_clock():
    """ISSUE satellite: serve_summary tokens/s is computed over the
    SERVING wall only — clock time spent before run() (warmup,
    compiles, restore) must not leak into wall_s."""
    t = [0.0]

    def clock():
        t[0] += 0.001
        return t[0]

    sched = Scheduler(_FakeEngine(num_slots=1), decode_priority=2,
                      clock=clock)
    t[0] += 1000.0                       # "warmup" before run()
    sched.run(_reqs(2, max_new=8))
    assert sched.summary["wall_s"] < 1.0
    assert sched.summary["tokens_per_sec"] > 0


# --- the span seam in the scheduler and the engine ----------------------

class _Annotations:
    """Stands in for jax.profiler.TraceAnnotation: the order in which
    the program opened and closed its tfd.* spans."""

    def __init__(self):
        self.log = []

    def __call__(self, name, **args):
        outer = self

        class _Ann:
            def __enter__(self):
                outer.log.append(("enter", name, args))

            def __exit__(self, *exc):
                outer.log.append(("exit", name, args))

        return _Ann()


class _ProgramClock:
    """A clock the fake programs move: a prefill and a decode dispatch
    take what the test says, every read a microsecond, a sleep what it
    was asked for. One instance is the scheduler's clock and the span
    seam's."""

    def __init__(self):
        self.t = 50.0

    def __call__(self):
        self.t += 1e-6
        return self.t

    def advance(self, seconds):
        self.t += seconds


def _span_engine(monkeypatch, num_slots=2, step_s=0.0, annotate=None,
                 clock=None, prefill_s=0.0):
    """The REAL SlotDecodeEngine.step/prefill (their spans are what is
    under test) over fake programs: no model, no compile. Token =
    rid * 100 + count, as the fakes above. With a ``clock`` (a
    :class:`_ProgramClock`) the programs move it by ``step_s`` and
    ``prefill_s`` where they would otherwise sleep."""
    import time

    from tensorflow_distributed_tpu.observe.trace import HostSpans
    from tensorflow_distributed_tpu.serve import engine as engine_mod

    class _Engine(engine_mod.SlotDecodeEngine):
        def __init__(self):
            self.num_slots, self.max_len = num_slots, 256
            self.buckets = (64, 128)
            self.model = self.params = self.cache = None
            self.tp_width, self.spec_tokens = 1, 0
            self.tok = np.zeros((num_slots,), np.int32)
            self.pos = np.zeros((num_slots,), np.int32)
            self.active = np.zeros((num_slots,), bool)
            self._buckets_used = set()
            self.prefills = self.decode_steps = self.swaps = 0
            self.steps_ahead = self.ahead_rows_dropped = 0
            self._ahead = self._no_prev = self._tokens_at = None
            self._plan = self._watchdog = self._last_ok = None
            self._check, self._declared_cache = False, None
            self._verify_fn = None
            self.spans = (HostSpans(annotate=annotate) if clock is None
                          else HostSpans(annotate=annotate, clock=clock))

        def _h2d(self, a):
            return np.array(a)

        def _dispatch_step(self, prev, host):
            # the "device" at work
            (time.sleep if clock is None else clock.advance)(step_s)
            tok = np.where(host[2] != 0, host[0], prev)   # step_inputs
            return None, tok + 1, np.ones((num_slots,), bool)

    def prefill_program(params, padded, plen):
        rid = int(np.asarray(padded)[0, 0])
        if clock is not None:
            clock.advance(prefill_s)
        return None, np.asarray([rid * 100 + int(plen) - 1], np.int32)

    monkeypatch.setattr(engine_mod, "lookup_program",
                        lambda *a: prefill_program)
    monkeypatch.setattr(engine_mod, "_insert_row", lambda c, r, s: c)
    return _Engine()


def _names(log, kind="enter"):
    return [n.replace("tfd.serve.", "") for k, n, _ in log if k == kind]


def test_one_iteration_emits_the_phases_in_order(monkeypatch):
    ann = _Annotations()
    eng = _span_engine(monkeypatch, num_slots=1, annotate=ann)
    sched = Scheduler(eng, decode_priority=2)
    assert sched.spans is eng.spans           # one seam, one vocabulary
    done = sched.run(_reqs(1, max_new=4))
    assert done[0].tokens == _expected(0, 4)
    names = _names(ann.log)
    # the admission: poll decides, admit covers launch and fetch
    assert names[:4] == ["poll", "admit", "prefill_launch",
                         "first_token_fetch"]
    # then every decode iteration, end to end: the next step is
    # launched INSIDE the wait for the one in flight (PR 45: when that
    # one is about to end; here, with no step timed, at once), the first
    # iteration launches the step it returns before it, and the step in
    # flight when the last request finished is waited out and dropped
    iteration = ["poll", "token_fetch", "step_upload", "step_dispatch",
                 "retire", "tail"]
    assert names[4:] == (["poll"] + iteration[2:4] + iteration[1:]
                         + iteration * 2 + ["drain"])
    ahead = [a["ahead"] for k, n, a in ann.log
             if k == "enter" and n == "tfd.serve.step_dispatch"]
    assert ahead == [0, 1, 1, 1]
    # no hole, no overlap: apart from admit and token_fetch with their
    # two children each the spans are flat, each closing before the
    # next opens
    depth, worst = 0, 0
    for kind, name, _ in ann.log:
        depth += 1 if kind == "enter" else -1
        worst = max(worst, depth)
        if kind == "enter" and depth == 2:
            assert name in ("tfd.serve.prefill_launch",
                            "tfd.serve.first_token_fetch",
                            "tfd.serve.step_upload",
                            "tfd.serve.step_dispatch")
    assert depth == 0 and worst == 2


def test_admission_span_carries_the_request(monkeypatch):
    ann = _Annotations()
    eng = _span_engine(monkeypatch, num_slots=2, annotate=ann)
    reg = _FakeRegistry()
    Scheduler(eng, decode_priority=2, registry=reg).run(
        _reqs(3, max_new=3))
    admits = [a for k, n, a in ann.log
              if k == "enter" and n == "tfd.serve.admit"]
    assert [a["rid"] for a in admits] == [0, 1, 2]
    assert all(a["bucket"] == 64 and a["prompt_len"] == 1
               and a["slot"] in (0, 1) for a in admits)
    steps = [a["step"] for k, n, a in ann.log
             if k == "enter" and n == "tfd.serve.token_fetch"]
    assert steps == list(range(1, len(steps) + 1))
    # every request's record holds the wall of its admission, and TTFT
    # is at least that
    reqs = [r for r in reg.records if r["event"] == "serve_request"]
    assert len(reqs) == 3
    for r in reqs:
        assert 0 < r["prefill_ms"] <= r["ttft_ms"] + 1e-6


def test_phase_ms_tiles_the_serving_wall(monkeypatch):
    eng = _span_engine(monkeypatch, num_slots=2, step_s=0.01)
    reg = _FakeRegistry()
    sched = Scheduler(eng, decode_priority=2, registry=reg)
    sched.run(_reqs(4, max_new=12))
    summary = [r for r in reg.records
               if r["event"] == "serve_summary"][0]
    phases = summary["phase_ms"]
    assert set(phases) == {
        "tfd.serve." + n for n in (
            "poll", "admit", "prefill_launch", "first_token_fetch",
            "step_upload", "step_dispatch", "token_fetch", "retire",
            "tail", "drain")}
    assert phases["tfd.serve.admit"]["count"] == 4
    assert phases["tfd.serve.drain"]["count"] == 1
    assert summary["steps_ahead"] == eng.steps_ahead > 0
    assert summary["ahead_rows_dropped"] == eng.ahead_rows_dropped > 0
    assert phases["tfd.serve.token_fetch"]["count"] == \
        summary["decode_steps"]
    total_ms = sum(p["sum_ms"] for p in phases.values())
    assert total_ms == pytest.approx(1e3 * summary["wall_s"], rel=0.02)
    # the worst dispatch is placed on the run's clocks
    worst = phases["tfd.serve.step_dispatch"]
    assert 1 <= worst["max_step"] <= summary["decode_steps"]
    assert 0 < worst["max_at_s"] <= summary["wall_s"]
    # a second run reports its own phases, not the engine's lifetime
    sched.run(_reqs(1, max_new=2))
    again = [r for r in reg.records
             if r["event"] == "serve_summary"][1]["phase_ms"]
    assert again["tfd.serve.admit"]["count"] == 1


def test_fake_engine_without_spans_still_tiles(tmp_path):
    """An engine that brings no seam (the fakes here) leaves the
    scheduler's own phases; with a tracer they land in its Chrome
    trace under the tfd.* names."""
    path = str(tmp_path / "serve.json")
    tr = ServeTracer(path)
    sched = Scheduler(_FakeEngine(num_slots=1), decode_priority=2,
                      tracer=tr)
    sched.run(_reqs(2, max_new=3))
    tr.close()
    assert set(sched.summary["phase_ms"]) == {
        "tfd.serve.poll", "tfd.serve.admit", "tfd.serve.retire",
        "tfd.serve.tail"}
    xs = {e["name"] for e in load_trace(path) if e.get("ph") == "X"}
    assert xs == set(sched.summary["phase_ms"])


def test_first_token_finish_retires_outside_admit(monkeypatch):
    """A request done at its first token (budget 1) is finished in a
    retire span after its admit span closed, so prefill_ms is known
    when its record is written."""
    ann = _Annotations()
    eng = _span_engine(monkeypatch, num_slots=1, annotate=ann)
    reg = _FakeRegistry()
    Scheduler(eng, decode_priority=2, registry=reg).run(
        _reqs(1, max_new=1))
    assert _names(ann.log) == ["poll", "admit", "prefill_launch",
                               "first_token_fetch", "retire"]
    rec = [r for r in reg.records if r["event"] == "serve_request"][0]
    assert rec["prefill_ms"] > 0 and rec["new_tokens"] == 1


# --- where a request's milliseconds went ---------------------------------

def _timed_run(monkeypatch, arrivals, max_new, num_slots=3,
               prefill_s=0.05, step_s=0.01, bad_once=False):
    """The real Scheduler over :func:`_span_engine` on a
    :class:`_ProgramClock`: request ``i`` is due at ``arrivals[i]`` and
    asks ``max_new[i]`` tokens; a prefill takes ``prefill_s``, a decode
    dispatch ``step_s``; ``decode_priority`` 1. ``bad_once``: slot 0 is
    flagged non-finite once, after the first decode step. Returns the
    ``serve_request`` records by rid, the summary, the admit spans'
    arguments and the completions by rid."""
    import types

    from tensorflow_distributed_tpu.serve import scheduler as sched_mod

    clock, ann = _ProgramClock(), _Annotations()
    eng = _span_engine(monkeypatch, num_slots=num_slots, step_s=step_s,
                       annotate=ann, clock=clock, prefill_s=prefill_s)
    # an idle engine sleeps to the next arrival: on this clock
    monkeypatch.setattr(sched_mod, "time", types.SimpleNamespace(
        sleep=clock.advance, time=lambda: 0.0))
    if bad_once:
        fired = []

        def take_bad_slots():
            if not fired and eng.decode_steps >= 1:
                fired.append(True)
                return [0]
            return []

        eng.take_bad_slots = take_bad_slots
    reqs = [Request(rid=i, prompt=np.asarray([i], np.int32),
                    max_new_tokens=n, arrival_s=at)
            for i, (at, n) in enumerate(zip(arrivals, max_new))]
    reg = _FakeRegistry()
    sched = Scheduler(eng, decode_priority=1, registry=reg, clock=clock)
    done = {c.rid: c for c in sched.run(reqs)}
    for c in done.values():
        assert c.tokens == _expected(c.rid, max_new[c.rid])
    recs = {r["rid"]: r for r in reg.records
            if r["event"] == "serve_request"}
    admits = [a for k, n, a in ann.log
              if k == "enter" and n == "tfd.serve.admit"]
    return recs, sched.summary, admits, done


def _close(got, want):
    """Within 0.5 ms or 1%, whichever is wider."""
    return abs(got - want) <= max(0.5, 0.01 * abs(want))


def test_request_parts_add_up_and_iterations_tile_the_wall(monkeypatch):
    """(a) wait_ms + prefill_ms is ttft_ms and decode_ms is tok_ms x
    (decoded - 1), request by request; (b) iter_ms tiles wall_s. The
    fifth request arrives into an idle engine that slept until it."""
    arrivals = [0.0, 0.0, 0.03, 0.12, 0.9, 0.93]
    max_new = [12, 5, 9, 1, 7, 4]
    recs, summary, _, _ = _timed_run(monkeypatch, arrivals, max_new,
                                     num_slots=2)
    assert sorted(recs) == list(range(6))
    for rid, r in recs.items():
        assert set(r["wait_ms"]) == set(r["decode_ms"]) == {
            "admit", "step", "other"}
        waited = sum(r["wait_ms"].values())
        assert _close(waited + r["prefill_ms"], r["ttft_ms"]), (rid, r)
        gaps = max(1, r["new_tokens"] - 1)
        assert _close(sum(r["decode_ms"].values()),
                      r["tok_ms"] * gaps), (rid, r)
    # request 0 met nothing on its way in; request 1 stood behind its
    # prefill and, a starvation clock of one, a decode iteration
    assert sum(recs[0]["wait_ms"].values()) < 0.5
    assert _close(recs[1]["wait_ms"]["admit"], recs[0]["prefill_ms"])
    assert recs[1]["wait_ms"]["step"] >= 10.0
    # the engine had run dry and slept until request 4 was due
    assert sum(recs[4]["wait_ms"].values()) < 0.5
    assert summary["admissions"] == 6
    assert set(summary["iter_ms"]) == {"admit", "step", "other"}
    assert sum(summary["iter_ms"].values()) == pytest.approx(
        1e3 * summary["wall_s"], rel=0.02)
    assert summary["iter_ms"]["admit"] == pytest.approx(6 * 50.0, rel=0.02)
    assert summary["iter_ms"]["other"] >= 1e3 * (0.9 - 0.5)   # the sleep
    assert sum(p["sum_ms"] for p in summary["phase_ms"].values()) == \
        pytest.approx(sum(summary["iter_ms"].values()), abs=0.01)


def test_a_decoding_request_endures_the_admissions_beside_it(monkeypatch):
    """(c) two requests are admitted while request 0 decodes: it reads
    both in admits_endured and their walls under decode_ms.admit; the
    rest of its gaps are decode iterations."""
    recs, _, _, _ = _timed_run(monkeypatch, [0.0, 0.1, 0.2], [24, 12, 3])
    first = recs[0]
    assert first["admits_endured"] == 2
    assert _close(first["decode_ms"]["admit"],
                  recs[1]["prefill_ms"] + recs[2]["prefill_ms"])
    assert first["decode_ms"]["step"] >= 23 * 10.0 - 0.5
    assert recs[1]["admits_endured"] == 1      # request 2's
    assert recs[2]["admits_endured"] == 0
    assert recs[2]["decode_ms"]["admit"] < 0.5


def test_due_in_the_middle_of_a_prefill_waits_under_admit(monkeypatch):
    """(d) request 1 comes due 40 ms into request 0's 100 ms prefill:
    the 60 ms left of it are wait_ms.admit, then one decode iteration
    (the starvation clock), then its own admission."""
    recs, _, _, _ = _timed_run(monkeypatch, [0.0, 0.04], [6, 3],
                               prefill_s=0.1)
    wait = recs[1]["wait_ms"]
    assert _close(wait["admit"], 60.0)
    assert wait["step"] >= 10.0 and wait["other"] < 0.5
    assert _close(sum(wait.values()) + recs[1]["prefill_ms"],
                  recs[1]["ttft_ms"])
    assert _close(recs[1]["prefill_ms"], 100.0)


def test_a_quarantined_requests_reprefill_is_decode_time(monkeypatch):
    """(e) slot 0's request is quarantined after its first decode step:
    the re-prefill of its continuation is time between two of its
    tokens (decode_ms.admit, one admission endured), its first-token
    split is the first admission's, and the parts still cover its
    decode time."""
    recs, _, _, done = _timed_run(monkeypatch, [0.0], [6], num_slots=1,
                                  bad_once=True)
    r = recs[0]
    assert r["retries"] == 1 and r["new_tokens"] == 6
    assert r["admits_endured"] == 1
    assert _close(r["decode_ms"]["admit"], 50.0)
    assert _close(sum(r["decode_ms"].values()), 1e3 * done[0].decode_s)
    assert _close(sum(r["wait_ms"].values()) + r["prefill_ms"],
                  r["ttft_ms"])


def test_the_admit_span_says_whom_it_stalls(monkeypatch):
    """(f) tfd.serve.admit carries the rows that hold a slot while the
    prefill runs and the requests still waiting behind it."""
    _, _, admits, _ = _timed_run(monkeypatch, [0.0, 0.0, 0.0, 0.5],
                                 [30, 30, 30, 2], num_slots=3)
    assert [a["rid"] for a in admits] == [0, 1, 2, 3]
    assert [a["live"] for a in admits] == [0, 1, 2, 0]
    assert [a["queue"] for a in admits] == [2, 1, 0, 0]
    assert all({"slot", "bucket", "prompt_len"} <= set(a) for a in admits)


def test_spec_fallback_scheduler_accounting():
    """Per-slot verify fallback (ISSUE satellite), scheduler side: the
    fallback slot retires exactly 1 token per dispatch, gets its
    history tail passed through, is EXCLUDED from accept accounting,
    and the streams stay identical to the plain run."""
    eng = _FallbackFakeEngine(spec_tokens=3)
    sched = Scheduler(eng, decode_priority=2,
                      speculator=_NullSpec(2, 3))
    done = {c.rid: c for c in sched.run(_reqs(2, max_new=7))}
    assert done[0].tokens == _expected(0, 7)
    assert done[1].tokens == _expected(1, 7)
    s = sched.summary
    assert s["verify_steps"] == eng.verify_steps > 0
    assert s["spec_fallback_slots"] > 0
    # Only the speculating slot counts toward proposals; the fake
    # accepts everything there, so accept_rate stays exactly 1.0 —
    # a fallback slot folded into the denominator would deflate it.
    assert s["accept_rate"] == 1.0
    # Tails were supplied for exactly the fallback slot and carry its
    # history stream (request 1's tokens are all >= 100).
    mixed = [t for t in eng.seen_tails if t]
    assert mixed
    for t in mixed:
        assert len(t) == 1
        (tail,) = t.values()
        assert tail[-1] >= 100


def test_fallback_engine_contract_matches_real_engine_guard():
    """verify_fallback_slots None (can_verify-only fakes) keeps the
    whole-batch fallback path: the scheduler must not call
    verify_step at all."""
    eng = _FakeEngine(num_slots=1)     # no verify surface
    sched = Scheduler(eng, decode_priority=2,
                      speculator=_NullSpec(1, 3))
    done = sched.run(_reqs(1, max_new=5))
    assert done[0].tokens == _expected(0, 5)
    assert "verify_steps" in sched.summary
    assert sched.summary["verify_steps"] == 0


# --- report folding ------------------------------------------------------

def _write_jsonl(path, recs):
    path.write_text("".join(json.dumps(r) + "\n" for r in recs))


def test_report_folds_slo_and_snapshots(tmp_path):
    from tensorflow_distributed_tpu.observe.report import (
        load_records, summarize)

    recs = [
        {"event": "slo_alert", "target": "high:ttft_p95",
         "burn_fast": 14.4, "burn_slow": 2.0,
         "budget_remaining": 0.61, "step": 40},
        {"event": "slo_ok", "target": "high:ttft_p95",
         "burn_fast": 0.2, "burn_slow": 0.9,
         "budget_remaining": 0.57, "step": 90},
        {"event": "metrics_snapshot", "t_s": 1.0, "decode_steps": 50,
         "requests_done": 4, "queue_depth": 1, "tokens_per_sec": 99.0,
         "ttft_ms_p95_high": 12.0},
        {"event": "metrics_snapshot", "t_s": 2.0, "decode_steps": 100,
         "requests_done": 9, "queue_depth": 0, "tokens_per_sec": 120.0,
         "ttft_ms_p95_high": 15.5},
        {"event": "serve_summary", "tokens_per_sec": 120.0,
         "slo_alerts": 1, "slo_budget_remaining_min": 0.57,
         "slo_targets": "high:ttft_p95"},
    ]
    path = tmp_path / "m.jsonl"
    _write_jsonl(path, recs)
    out = summarize(load_records(str(path)))
    assert out["slo"]["high:ttft_p95"] == {
        "alerts": 1, "clears": 1, "worst_burn_fast": 14.4,
        "budget_remaining": 0.57}
    assert out["snapshots"] == 2
    assert out["snapshot_last"]["requests_done"] == 9
    assert out["snapshot_last"]["ttft_ms_p95_high"] == 15.5
    assert out["serve_slo_alerts"] == 1
    from tensorflow_distributed_tpu.observe.report import render
    text = render(out)
    assert "SLO" in text and "Snapshot (final)" in text


def test_report_plain_serve_shape_unchanged(tmp_path):
    from tensorflow_distributed_tpu.observe.report import (
        load_records, summarize)

    recs = [{"event": "serve_request", "rid": 0, "ttft_ms": 5.0,
             "tok_ms": 1.0, "slo": "standard"},
            {"event": "serve_summary", "tokens_per_sec": 10.0}]
    path = tmp_path / "m.jsonl"
    _write_jsonl(path, recs)
    out = summarize(load_records(str(path)))
    assert "slo" not in out and "snapshots" not in out
    assert not any(k.startswith("serve_slo") for k in out)


def test_report_renders_serve_phases(tmp_path):
    """serve_summary.phase_ms has a reader: the report's phase table,
    largest first, with each phase's share of the serving wall and
    where its worst span fell."""
    from tensorflow_distributed_tpu.observe.report import (
        load_records, render, summarize)

    phases = {
        "tfd.serve.token_fetch": {"count": 100, "sum_ms": 1800.0,
                                  "max_ms": 3010.5, "max_step": 77,
                                  "max_at_s": 12.25},
        "tfd.serve.retire": {"count": 100, "sum_ms": 200.0,
                             "max_ms": 4.0, "max_step": 3,
                             "max_at_s": 0.1}}
    path = tmp_path / "m.jsonl"
    _write_jsonl(path, [{"event": "serve_summary", "wall_s": 2.0,
                         "tokens_per_sec": 10.0, "phase_ms": phases}])
    out = summarize(load_records(str(path)))
    assert out["phase_ms"] == phases and out["wall_s"] == 2.0
    text = render(out).splitlines()
    at = text.index("Serve host phases (self time; worst span at "
                    "step / run second)")
    assert "tfd.serve.token_fetch" in text[at + 1]
    assert " 90.0%" in text[at + 1]
    assert "max 3010.5 ms @ step 77 / 12.25s" in text[at + 1]
    assert "tfd.serve.retire" in text[at + 2]
    assert " 10.0%" in text[at + 2]


def test_report_renders_where_a_requests_time_went(tmp_path):
    """The operator's reader of serve_request.wait_ms / .decode_ms /
    .admits_endured and of serve_summary.iter_ms: one table of means
    and p95s, decode parts a token gap, and the wall by kind under the
    phase table; records from before the fields render as they did."""
    from tensorflow_distributed_tpu.observe.report import (
        load_records, render, summarize)

    def req(rid, wait, prefill, dec, new, endured):
        return {"event": "serve_request", "rid": rid, "ttft_ms":
                sum(wait) + prefill, "tok_ms": sum(dec) / (new - 1),
                "new_tokens": new, "prefill_ms": prefill,
                "wait_ms": dict(zip(("admit", "step", "other"), wait)),
                "decode_ms": dict(zip(("admit", "step", "other"), dec)),
                "admits_endured": endured}

    recs = [req(0, (0.0, 0.0, 0.1), 200.0, (400.0, 90.0, 10.0), 11, 2),
            req(1, (150.0, 10.0, 0.3), 100.0, (0.0, 40.0, 0.0), 5, 0),
            {"event": "serve_request", "rid": 2, "ttft_ms": 50.0,
             "tok_ms": 9.0, "new_tokens": 4, "prefill_ms": 20.0},
            {"event": "serve_summary", "wall_s": 2.0,
             "tokens_per_sec": 10.0, "admissions": 3,
             "phase_ms": {"tfd.serve.admit": {
                 "count": 3, "sum_ms": 1200.0, "max_ms": 500.0,
                 "max_step": 4, "max_at_s": 1.0}},
             "iter_ms": {"admit": 1200.0, "step": 700.0, "other": 100.0}}]
    path = tmp_path / "m.jsonl"
    _write_jsonl(path, recs)
    out = summarize(load_records(str(path)))
    parts = out["request_parts"]
    assert parts["wait.admit_ms"] == {"mean": 75.0, "p95": 150.0, "n": 2}
    assert parts["prefill_ms"] == {"mean": 150.0, "p95": 200.0, "n": 2}
    assert parts["decode.admit_ms_per_token"] == {
        "mean": 20.0, "p95": 40.0, "n": 2}
    assert parts["decode.step_ms_per_token"]["mean"] == 9.5
    assert parts["admits_endured"] == {"mean": 1.0, "p95": 2.0, "n": 2}
    text = render(out).splitlines()
    at = text.index("Where a request's time went (ms by kind of "
                    "scheduler iteration; mean / p95)")
    assert text[at + 1].split()[:4] == ["wait.admit_ms", "75.000", "/",
                                        "150.000"]
    kinds = next(ln for ln in text if "by kind of iteration" in ln)
    assert "admit 1200.0 ms (60.0%)" in kinds
    assert "step 700.0 ms (35.0%)" in kinds and "admissions=3" in kinds
    # the fields print once, in their tables
    assert not any(ln.strip().startswith(("iter_ms", "admissions",
                                          "request_parts"))
                   for ln in text)
    # records without the fields: no table, nothing else changed
    _write_jsonl(path, recs[2:3])
    old = summarize(load_records(str(path)))
    assert "request_parts" not in old
    assert "Where a request" not in render(old)


def test_report_recovery_window_p99_value_pinned(tmp_path):
    """ISSUE satellite: a synthetic JSONL with KNOWN recovery windows
    reproduces the exact nearest-rank p99-during-recovery value, not
    just its presence."""
    from tensorflow_distributed_tpu.observe.report import (
        load_records, summarize)

    recovery_ttfts = [10.0, 20.0, 30.0, 40.0, 50.0,
                      60.0, 70.0, 80.0, 90.0, 1000.0]
    recs = [{"event": "serve_request", "rid": i, "ttft_ms": t,
             "tok_ms": 1.0, "recovery_window": True}
            for i, t in enumerate(recovery_ttfts)]
    # Plenty of fast non-recovery requests that must NOT dilute the
    # recovery population.
    recs += [{"event": "serve_request", "rid": 100 + i,
              "ttft_ms": 1.0, "tok_ms": 1.0,
              "recovery_window": False} for i in range(30)]
    path = tmp_path / "m.jsonl"
    _write_jsonl(path, recs)
    out = summarize(load_records(str(path)))
    assert out["serve_recovery_requests"] == 10
    # Nearest-rank p99 over 10 sorted values: index round(.99*9) = 9.
    assert out["serve_ttft_ms_p99_recovery"] == 1000.0
    # And the overall p99 covers all 40: index round(.99*39) = 39 of
    # the merged sorted list -> the same 1000.0 outlier; p50 differs.
    assert out["serve_ttft_ms_p99"] == 1000.0
    assert out["serve_ttft_ms_p50"] == 1.0


# --- config plumbing -----------------------------------------------------

def _cfg(**kw):
    from tensorflow_distributed_tpu.config import TrainConfig
    cfg = TrainConfig(mode="serve", model="gpt_lm",
                      model_size="tiny")
    for k, v in kw.items():
        obj, _, field = k.rpartition(".")
        setattr(cfg.observe if obj == "observe" else cfg, field, v)
    return cfg


def test_config_serve_observatory_knobs_valid():
    cfg = _cfg(**{"observe.slo": "high:ttft_p95=100ms,tok_p50=30ms",
                  "observe.slo_windows": "30,300",
                  "observe.export_every": 2.0,
                  "observe.export_path": "/tmp/x.json"})
    cfg.validate()


@pytest.mark.parametrize("kw, match", [
    ({"observe.slo": "gold:ttft_p95=1ms"}, "unknown class"),
    ({"observe.slo": "ttft_p95=1"}, "unit suffix"),
    ({"observe.slo_windows": "600,60"}, "fast < slow"),
    ({"observe.slo_burn": 0.0}, "slo_burn"),
    ({"observe.slo_status_every": -1}, "slo_status_every"),
    ({"observe.export_every": -1.0}, "export_every"),
])
def test_config_serve_observatory_rejections(kw, match):
    with pytest.raises(ValueError, match=match):
        _cfg(**kw).validate()


def test_config_slo_and_export_are_serve_only():
    from tensorflow_distributed_tpu.config import TrainConfig
    cfg = TrainConfig()
    cfg.observe.slo = "ttft_p95=100ms"
    with pytest.raises(ValueError, match="mode=serve"):
        cfg.validate()
    cfg2 = TrainConfig()
    cfg2.observe.export_every = 1.0
    with pytest.raises(ValueError, match="mode=serve"):
        cfg2.validate()


# --- real engine (slow tier) --------------------------------------------

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
def test_draft_warmup_no_compiles_during_serving():
    """ISSUE satellite: engine.warmup(speculator) also dispatches the
    draft mirror's prefill/insert/scan — the serving loop then runs
    with ZERO compiled-program cache misses (the first speculative
    round pays compute, not compile)."""
    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.models.generate import (
        compile_cache_stats)
    from tensorflow_distributed_tpu.models.transformer import gpt_lm
    from tensorflow_distributed_tpu.serve.buckets import default_buckets
    from tensorflow_distributed_tpu.serve.engine import SlotDecodeEngine
    from tensorflow_distributed_tpu.serve.speculate import (
        DraftSpeculator)

    model, params = _tiny_serving_model()
    draft = gpt_lm(None, size="tiny", n_layers=1, max_len=96,
                   dropout_rate=0.0)
    dparams = draft.init(jax.random.key(1),
                         jnp.zeros((1, 8), jnp.int32))["params"]
    buckets = default_buckets(16)
    K = 3
    eng = SlotDecodeEngine(model, params, 2, buckets=buckets,
                           spec_tokens=K)
    drafter = DraftSpeculator(draft, dparams, 2, buckets, K)
    eng.warmup(drafter)
    before = compile_cache_stats()["misses"]
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, model.cfg.vocab_size,
                            size=int(n)).astype(np.int32)
               for n in rng.integers(4, 14, size=4)]
    sched = Scheduler(eng, decode_priority=3, speculator=drafter)
    done = sched.run([Request(rid=i, prompt=p, max_new_tokens=10)
                      for i, p in enumerate(prompts)])
    assert len(done) == 4
    assert sched.summary["verify_steps"] > 0
    assert compile_cache_stats()["misses"] == before


@pytest.mark.slow
def test_per_slot_verify_fallback_token_identity_real():
    """ISSUE satellite: one headroom-starved slot takes the plain path
    INSIDE the verify dispatch while the other slot keeps speculating
    — tokens identical to the non-speculative run, and the mixed
    dispatches really happened (spec_fallback_slots > 0)."""
    from tensorflow_distributed_tpu.serve.buckets import default_buckets
    from tensorflow_distributed_tpu.serve.engine import SlotDecodeEngine
    from tensorflow_distributed_tpu.serve.speculate import SelfDraft

    K = 4
    model, params = _tiny_serving_model(max_len=32)
    rng = np.random.default_rng(7)
    # Request 0 ends at pos 32 = max_len: its final decode rounds lack
    # pos + K + 1 headroom. Request 1 stays shallow throughout.
    prompts = [rng.integers(0, model.cfg.vocab_size,
                            size=20).astype(np.int32),
               rng.integers(0, model.cfg.vocab_size,
                            size=4).astype(np.int32)]
    buckets = default_buckets(32, cap=32)

    def run(spec_tokens):
        eng = SlotDecodeEngine(model, params, 2, buckets=buckets,
                               spec_tokens=spec_tokens)
        spec = (SelfDraft(2, spec_tokens) if spec_tokens else None)
        sched = Scheduler(eng, decode_priority=3, speculator=spec)
        reqs = [Request(rid=0, prompt=prompts[0], max_new_tokens=12),
                Request(rid=1, prompt=prompts[1], max_new_tokens=12)]
        return {c.rid: c.tokens for c in sched.run(reqs)}, sched

    ref, _ = run(0)
    out, sched = run(K)
    assert out[0] == ref[0] and out[1] == ref[1]
    assert sched.summary["verify_steps"] > 0
    assert sched.summary["spec_fallback_slots"] > 0


@pytest.mark.slow
def test_serve_run_observatory_e2e(tmp_path):
    """mode=serve with the full observatory armed: balanced trace,
    slo_alert fires on an absurd target, snapshots exported, report
    folds all of it."""
    from tensorflow_distributed_tpu.config import TrainConfig
    from tensorflow_distributed_tpu.observe.report import (
        load_records, summarize)
    from tensorflow_distributed_tpu.serve.run import serve_run

    cfg = TrainConfig(mode="serve", model="gpt_lm", model_size="tiny",
                      seed=11)
    cfg.serve.num_requests = 5
    cfg.serve.num_slots = 2
    cfg.serve.max_new_tokens = 8
    cfg.observe.metrics_jsonl = str(tmp_path / "m.jsonl")
    cfg.observe.trace = str(tmp_path / "serve.trace.json")
    cfg.observe.slo = "ttft_p95=0.0001ms"
    cfg.observe.slo_windows = "4,16"
    cfg.observe.export_every = 0.001
    cfg.observe.export_path = str(tmp_path / "snap.json")
    cfg.validate()
    summary = serve_run(cfg)
    assert summary["requests"] == 5
    assert summary["slo_alerts"] >= 1
    ev = load_trace(cfg.observe.trace)
    assert not unbalanced_async(ev)
    assert any(e.get("ph") == "C" for e in ev)
    snap = json.load(open(cfg.observe.export_path))
    out = summarize(load_records(cfg.observe.metrics_jsonl))
    assert out["snapshots"] >= 1
    # Final snapshot agrees with the report's per-class p95 exactly
    # (same nearest-rank formula over the same completions).
    assert (snap["ttft_ms_p95_standard"]
            == out["serve_ttft_ms_p95_standard"]
            if "serve_ttft_ms_p95_standard" in out
            else snap["requests_done"] == 5)
    assert out["serve_slo_alerts"] == summary["slo_alerts"]
