"""Autopilot: close the calibrate→plan→act loop on the run's own telemetry.

Every half of a self-tuning loop already exists as a separate artifact
in this repo — the AOT planner picks layouts (analysis/planner), the
calibration fitter turns measured steps into effective device rates
(planner/calibrate.py), anomaly detection watches live behavior
(observe/anomaly.py), the SLO monitor burns error budget on the
deterministic decode-step clock (observe/slo.py), and the scheduler
accepts live control commands between decode steps (serve/scheduler.py
``feed_cmd``). A human still read the telemetry and turned the knobs.
This module is the missing controller: it subscribes to the streams the
run already emits and closes four concrete loops against existing
actuators:

1. **Calibration** — join each run's ``compile`` × ``device_time``
   records (the same join ``calibrate.samples_from_metrics`` does on a
   finished artifact, here done streaming) and refit the effective-rate
   profile when the plan's predicted→measured drift leaves tolerance
   (``plan_drift.drift_ratio``, or the per-program measured/predicted
   ratios when no drift record exists). The refit writes an atomic
   ``calibration.json`` (``--observe.autopilot-calibration``) and an
   optional ``replan`` hook re-runs the planner against it.
2. **Capacity** — generalize the PR-15 one-shot ``auto_num_pages``
   sizer into a feedback rule: sustained page-pool pressure shrinks the
   scheduler's *effective slot cap* (fewer live slots pin fewer pages);
   sustained headroom grows it back toward ``num_slots``. The
   boot-time knobs it cannot change live (``--serve.num-pages``, the
   bucket ladder) get auditable *advisory* recommendations at run end,
   sized from the observed ``slot_pages_peak`` and the prompt-length
   distribution.
3. **Speculation** — walk the draft depth ``k`` along a bounded ladder
   from the rolling-window accept rate: a workload that accepts almost
   everything earns a deeper draft; one that rejects almost everything
   pays for k it never cashes. Verify programs compile lazily per
   (model, k), and greedy verify is token-identical at any k by
   construction, so the actuation is stream-safe.
4. **Admission** — drive the scheduler's admission threshold
   (``decode_priority``) from SLO burn: sustained alerting halves it
   (queued requests admit sooner — TTFT is what burns), sustained calm
   relaxes it back toward the configured baseline one step at a time
   (AIMD, so a knob that *caused* burn is re-approached slowly, not
   snapped back to).

Every actuation is a ``{"cmd": "tune", ...}`` command routed through
the scheduler's existing control-command path — the same path fleet
drain/swap/cancel commands take — so it applies between decode steps
and token identity is preserved by construction (greedy determinism +
continuation semantics; tests/test_autopilot.py holds the streams
identical across live actuations). Every decision emits one auditable
``tune`` record carrying machine-readable evidence: the signal, the
observed value, the threshold it crossed, and the triggering context.

Decisions are **hysteretic and rate-limited** so a well-tuned run stays
decision-quiet: a trigger must hold for ``confirm`` consecutive
evaluations (deadbands between the raise/lower thresholds absorb
noise), each knob then cools down for ``cooldown`` decode steps, and at
most one knob actuates per evaluation tick. Knobs named in
``--observe.autopilot-pin`` are never touched.

Pure stdlib on purpose: the controller must import (and unit-test) on
a box with no jax. The calibration fitter (already stdlib) is the only
repo import, done lazily at refit time.
"""

from __future__ import annotations

import json
import os
from typing import Any, Callable, Dict, List, Optional, Sequence

#: every knob the autopilot can touch — the valid ``autopilot_pin``
#: vocabulary (config.py validates against this).
KNOBS = ("calibration", "slot_cap", "spec_k", "decode_priority",
         "num_pages", "buckets")

#: loop-2 pool-occupancy deadband: shrink the slot cap above HI,
#: grow it back below LO, stay quiet in between.
POOL_HI, POOL_LO = 0.92, 0.60
#: loop-3 accept-rate deadband: deepen the draft above HI, shallow it
#: below LO.
ACCEPT_HI, ACCEPT_LO = 0.75, 0.35
#: loop-2 advisory band: recommend a different --serve.num-pages only
#: when the observed-peak sizing moves the pool by more than this.
PAGES_REL_TOL = 0.2


def _round(v):
    return round(v, 6) if isinstance(v, float) else v


class Autopilot:
    """The online controller. Owned by the serve observatory
    (observe/hub.py builds it from the ``--observe.autopilot*`` knobs)
    and driven by the scheduler on the decode-step clock:
    :meth:`maybe_step` returns the ``tune`` commands to route through
    ``feed_cmd``. None-safe like every other scheduler hook — a run
    without ``--observe.autopilot`` never constructs one."""

    def __init__(self, emit: Optional[Callable[..., None]] = None, *,
                 every: int = 50, confirm: int = 3, cooldown: int = 200,
                 drift_tol: float = 0.25,
                 pins: Sequence[str] = (),
                 metrics_path: str = "",
                 calibration_path: str = "",
                 k_ladder: Sequence[int] = (1, 2, 4, 8),
                 replan: Optional[Callable[[dict], None]] = None):
        if every < 1:
            raise ValueError(f"autopilot every must be >= 1, got {every}")
        if confirm < 1:
            raise ValueError(
                f"autopilot confirm must be >= 1, got {confirm}")
        if cooldown < 0:
            raise ValueError(
                f"autopilot cooldown must be >= 0, got {cooldown}")
        if drift_tol <= 0:
            raise ValueError(
                f"autopilot drift_tol must be > 0, got {drift_tol}")
        bad = sorted(set(pins) - set(KNOBS))
        if bad:
            raise ValueError(
                f"unknown autopilot pin knob(s) {', '.join(bad)} "
                f"(valid: {', '.join(KNOBS)})")
        self.emit = emit
        self.every = int(every)
        self.confirm = int(confirm)
        self.cooldown = int(cooldown)
        self.drift_tol = float(drift_tol)
        self.pins = frozenset(pins)
        self.metrics_path = metrics_path
        self.calibration_path = calibration_path
        self.k_ladder = tuple(sorted(set(int(k) for k in k_ladder)))
        if not self.k_ladder or self.k_ladder[0] < 1:
            raise ValueError(
                f"autopilot k_ladder must be positive ints, got "
                f"{k_ladder!r}")
        self.replan = replan
        # -- decision bookkeeping (the tune_summary rollup) ----------
        self.actions = 0          # applied knob changes
        self.advisories = 0       # applied=False recommendations
        self.evals = 0
        self.suppressed = 0       # triggered but cooling down
        self.by_knob: Dict[str, int] = {}
        self._confirm: Dict[str, int] = {}
        self._cool: Dict[str, int] = {}
        # -- bound run context (scheduler/run.py fill these in) ------
        self._num_slots = 0
        self._slot_cap = 0
        self._spec_k = 0
        self._has_spec = False
        self._dp0 = 0             # configured decode_priority baseline
        self._dp = 0
        self._num_pages = 0
        self._recommend_pages: Optional[Callable[[int], tuple]] = None
        self._buckets: tuple = ()
        self._prompt_lens: List[int] = []
        # -- loop-1 streaming state ----------------------------------
        self._tail_pos = 0
        self._costs: Dict[str, dict] = {}      # program -> compile rec
        self._measured: Dict[str, dict] = {}   # program -> device_time
        self._drift: Optional[dict] = None     # latest plan_drift rec
        self._drift_seen = 0      # drift-evidence records at last refit
        self._drift_new = 0       # drift-evidence records seen so far

    # -- run-context binding ---------------------------------------------

    def bind_scheduler(self, *, num_slots: int = 0, spec_k: int = 0,
                       decode_priority: int = 1,
                       has_spec: bool = False) -> None:
        """Called by the Scheduler ctor: the initial knob values the
        feedback rules move relative to."""
        self._num_slots = int(num_slots)
        self._slot_cap = int(num_slots)
        self._spec_k = int(spec_k)
        self._has_spec = bool(has_spec) and spec_k > 0
        self._dp0 = self._dp = int(decode_priority)

    def bind_paging(self, *, num_pages: int = 0,
                    recommend: Optional[Callable[[int], tuple]] = None
                    ) -> None:
        """serve/run.py hands over the boot-time sizing context: the
        pool it allocated and a closure over ``auto_num_pages`` (the
        PR-15 one-shot sizer) that re-sizes from an observed peak —
        the autopilot stays jax-free and never re-derives page bytes."""
        self._num_pages = int(num_pages)
        self._recommend_pages = recommend

    def bind_buckets(self, buckets: Sequence[int]) -> None:
        self._buckets = tuple(int(b) for b in buckets)

    def observe_prompt(self, prompt_len: int) -> None:
        """One host int per admission — the prompt-length distribution
        the bucket/num-pages recommendations are sized from."""
        self._prompt_lens.append(int(prompt_len))

    # -- record intake (loop 1) ------------------------------------------

    def observe_record(self, kind: str, rec: Dict[str, Any]) -> None:
        """Streamed telemetry intake: the compile × device_time join
        and the plan-drift signal. Fed by :meth:`_tail` from the run's
        own metrics JSONL (the streams the run already emits), or
        directly by tests."""
        if kind == "compile" and rec.get("program"):
            self._costs[rec["program"]] = rec
        elif kind == "device_time" and rec.get("program") and isinstance(
                rec.get("device_ms_per_call"), (int, float)):
            self._measured[rec["program"]] = rec
            self._drift_new += 1
        elif kind == "plan_drift" and isinstance(
                rec.get("drift_ratio"), (int, float)):
            self._drift = rec
            self._drift_new += 1

    def _tail(self) -> None:
        """Incrementally read NEW lines from the run's metrics JSONL
        (the registry's JSONL sink flushes per record). Count-and-skip
        on torn tails, same as observe.report."""
        if not self.metrics_path:
            return
        try:
            size = os.path.getsize(self.metrics_path)
        except OSError:
            return
        if size <= self._tail_pos:
            return
        try:
            with open(self.metrics_path) as f:
                f.seek(self._tail_pos)
                chunk = f.read()
        except OSError:
            return
        # Only consume complete lines; a mid-write tail stays for the
        # next tick.
        last_nl = chunk.rfind("\n")
        if last_nl < 0:
            return
        self._tail_pos += last_nl + 1
        for line in chunk[:last_nl].splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(rec, dict) and rec.get("event"):
                self.observe_record(str(rec["event"]), rec)

    # -- hysteresis helpers ----------------------------------------------

    def _sustained(self, key: str, cond: bool) -> bool:
        """Confirm-count hysteresis: a trigger must hold for
        ``confirm`` consecutive evaluations. Any tick off-trigger
        resets the count — a noisy-but-healthy stream never acts."""
        if cond:
            self._confirm[key] = self._confirm.get(key, 0) + 1
        else:
            self._confirm[key] = 0
        return self._confirm[key] >= self.confirm

    def _ready(self, knob: str, step: int) -> bool:
        last = self._cool.get(knob)
        return last is None or step - last >= self.cooldown

    def _fire(self, step: int, *, loop: str, knob: str, action: str,
              value, prev, signal: str, observed, threshold,
              applied: bool, evidence: Optional[dict] = None
              ) -> Optional[dict]:
        """Record one decision (auditable ``tune`` record with the
        triggering signal + threshold) and return the control command
        for an applied actuation (None for advisories)."""
        self._cool[knob] = step
        self._confirm.pop(f"{knob}:{action}", None)
        if applied:
            self.actions += 1
            self.by_knob[knob] = self.by_knob.get(knob, 0) + 1
        else:
            self.advisories += 1
        if self.emit is not None:
            self.emit("tune", step=int(step), loop=loop, knob=knob,
                      action=action, value=value, prev=prev,
                      signal=signal, observed=_round(observed),
                      threshold=_round(threshold), applied=applied,
                      evidence=evidence or {})
        if not applied:
            return None
        return {"cmd": "tune", "knob": knob, "value": value}

    # -- the four loops ---------------------------------------------------

    def _loop_admission(self, step: int, snap: Dict[str, Any]
                        ) -> Optional[dict]:
        """Loop 4: SLO burn drives the admission threshold. AIMD on
        ``decode_priority``: sustained alerting halves it (admit
        waiting requests sooner — queue time is what burns TTFT),
        sustained calm adds 1 back toward the configured baseline."""
        if "decode_priority" in self.pins:
            return None
        slo = snap.get("slo")
        if not isinstance(slo, dict):
            return None
        # SLOMonitor.snapshot() is keyed by target:
        # {"ttft_p95": {"alerting": ..., "burn_fast": ...}, ...}
        entries = {k: e for k, e in slo.items() if isinstance(e, dict)}
        firing = sorted(k for k, e in entries.items()
                        if e.get("alerting"))
        alerting = bool(firing)
        burns = {k: e.get("burn_fast") for k, e in entries.items()}
        worst = max((v for v in burns.values()
                     if isinstance(v, (int, float))), default=0.0)
        if self._sustained("decode_priority:tighten",
                           alerting) and self._dp > 1:
            if not self._ready("decode_priority", step):
                self.suppressed += 1
                return None
            prev, self._dp = self._dp, max(1, self._dp // 2)
            return self._fire(
                step, loop="admission", knob="decode_priority",
                action="tighten", value=self._dp, prev=prev,
                signal="slo_burn_fast", observed=worst, threshold=1.0,
                applied=True,
                evidence={"alerting": firing, "burn_fast": burns})
        if self._sustained("decode_priority:relax",
                           not alerting and self._dp < self._dp0):
            if not self._ready("decode_priority", step):
                self.suppressed += 1
                return None
            prev, self._dp = self._dp, min(self._dp0, self._dp + 1)
            return self._fire(
                step, loop="admission", knob="decode_priority",
                action="relax", value=self._dp, prev=prev,
                signal="slo_burn_fast", observed=worst, threshold=1.0,
                applied=True, evidence={"baseline": self._dp0})
        return None

    def _loop_capacity(self, step: int, snap: Dict[str, Any]
                       ) -> Optional[dict]:
        """Loop 2 (live half): page-pool pressure ⇄ effective slot
        cap. Fewer live slots pin fewer pages; headroom grows the cap
        back toward the allocated ``num_slots``."""
        if "slot_cap" in self.pins:
            return None
        occ = snap.get("pool_occupancy")
        if not isinstance(occ, (int, float)) or self._num_slots < 2:
            return None
        if self._sustained("slot_cap:shrink",
                           occ >= POOL_HI) and self._slot_cap > 1:
            if not self._ready("slot_cap", step):
                self.suppressed += 1
                return None
            prev, self._slot_cap = self._slot_cap, self._slot_cap - 1
            return self._fire(
                step, loop="capacity", knob="slot_cap",
                action="shrink", value=self._slot_cap, prev=prev,
                signal="pool_occupancy", observed=occ,
                threshold=POOL_HI, applied=True,
                evidence={"num_pages": snap.get("num_pages"),
                          "pages_in_use": snap.get("pages_in_use"),
                          "slot_pages_peak":
                              snap.get("slot_pages_peak")})
        if self._sustained(
                "slot_cap:grow",
                occ <= POOL_LO) and self._slot_cap < self._num_slots:
            if not self._ready("slot_cap", step):
                self.suppressed += 1
                return None
            prev, self._slot_cap = self._slot_cap, self._slot_cap + 1
            return self._fire(
                step, loop="capacity", knob="slot_cap", action="grow",
                value=self._slot_cap, prev=prev,
                signal="pool_occupancy", observed=occ,
                threshold=POOL_LO, applied=True,
                evidence={"num_slots": self._num_slots})
        return None

    def _loop_speculation(self, step: int, snap: Dict[str, Any]
                          ) -> Optional[dict]:
        """Loop 3: draft depth k from the rolling accept rate, one
        ladder rung at a time."""
        if "spec_k" in self.pins or not self._has_spec:
            return None
        ar = snap.get("accept_rate_window",
                      snap.get("accept_rate"))
        if not isinstance(ar, (int, float)):
            return None
        ladder = self.k_ladder
        try:
            i = ladder.index(self._spec_k)
        except ValueError:
            # Configured k off-ladder: adopt the nearest rung below
            # (or the bottom) as the anchor without actuating.
            i = max((j for j, k in enumerate(ladder)
                     if k <= self._spec_k), default=0)
        if self._sustained("spec_k:deepen",
                           ar >= ACCEPT_HI) and i + 1 < len(ladder):
            if not self._ready("spec_k", step):
                self.suppressed += 1
                return None
            prev, self._spec_k = self._spec_k, ladder[i + 1]
            return self._fire(
                step, loop="speculation", knob="spec_k",
                action="deepen", value=self._spec_k, prev=prev,
                signal="accept_rate_window", observed=ar,
                threshold=ACCEPT_HI, applied=True,
                evidence={"ladder": list(ladder)})
        if self._sustained("spec_k:shallow", ar <= ACCEPT_LO) and i > 0:
            if not self._ready("spec_k", step):
                self.suppressed += 1
                return None
            prev, self._spec_k = self._spec_k, ladder[i - 1]
            return self._fire(
                step, loop="speculation", knob="spec_k",
                action="shallow", value=self._spec_k, prev=prev,
                signal="accept_rate_window", observed=ar,
                threshold=ACCEPT_LO, applied=True,
                evidence={"ladder": list(ladder)})
        return None

    def _drift_evidence(self) -> Optional[dict]:
        """The trigger signal for a refit: the run's own ``plan_drift``
        record when one landed, else the median measured/predicted
        ratio across the device_time attributions."""
        if self._drift is not None:
            return {"source": "plan_drift",
                    "drift_ratio": float(self._drift["drift_ratio"]),
                    "record": {k: self._drift.get(k) for k in
                               ("predicted_step_ms",
                                "measured_step_ms_p50",
                                "drift_ratio", "calibration_id")}}
        ratios = []
        for prog, rec in self._measured.items():
            m = rec.get("device_ms_per_call")
            p = rec.get("predicted_ms_per_call")
            if isinstance(m, (int, float)) and isinstance(
                    p, (int, float)) and p > 0:
                ratios.append(m / p)
        if not ratios:
            return None
        ratios.sort()
        med = ratios[len(ratios) // 2]
        return {"source": "device_time", "drift_ratio": med,
                "programs": len(ratios)}

    def _loop_calibration(self, step: int) -> Optional[dict]:
        """Loop 1: refit the effective-rate profile from the streaming
        compile × device_time join when drift leaves tolerance. No
        confirm count — the drift signal is already an aggregate over
        a measurement window, not per-step noise — but evidence-gated:
        a refit consumes the records that justified it, and the loop
        stays quiet until NEW measurements land."""
        if "calibration" in self.pins:
            return None
        if self._drift_new <= self._drift_seen:
            return None
        ev = self._drift_evidence()
        if ev is None or abs(ev["drift_ratio"] - 1.0) <= self.drift_tol:
            return None
        samples = [
            {"flops": c.get("flops"),
             "bytes_accessed": c.get("bytes_accessed"),
             "collective_bytes": 0.0,
             "measured_ms": self._measured[p].get("device_ms_per_call"),
             "key": p}
            for p, c in self._costs.items() if p in self._measured]
        if len(samples) < 2:
            return None
        if not self._ready("calibration", step):
            self.suppressed += 1
            return None
        from tensorflow_distributed_tpu.analysis.planner import (
            calibrate)
        try:
            fit = calibrate.fit_rates(samples)
        except ValueError:
            return None
        self._drift_seen = self._drift_new
        profile = calibrate.make_profile(
            fit, platform="autopilot", device_kind="measured",
            source=f"autopilot:{os.path.basename(self.metrics_path)}"
                   if self.metrics_path else "autopilot:stream")
        applied = bool(self.calibration_path)
        if applied:
            calibrate.write_calibration(profile,
                                        self.calibration_path)
        if self.replan is not None:
            self.replan(profile)
        self._fire(
            step, loop="calibration", knob="calibration",
            action="refit", value=profile["calibration_id"],
            prev=ev.get("record", {}).get("calibration_id"),
            signal="drift_ratio", observed=ev["drift_ratio"],
            threshold=1.0 + self.drift_tol, applied=applied,
            evidence={**ev, "samples": fit["samples"],
                      "median_abs_rel_err":
                          fit["median_abs_rel_err"],
                      "path": self.calibration_path or None})
        # A calibration refit is a file write + optional replan, not a
        # scheduler knob — nothing to route through feed_cmd.
        return None

    # -- the scheduler-facing hook ----------------------------------------

    def maybe_step(self, step: int,
                   snap_fn: Callable[[], Dict[str, Any]]
                   ) -> List[dict]:
        """Called by the scheduler every decode step; evaluates on the
        ``every`` cadence (``snap_fn`` is only invoked then — the off-
        cadence cost is one modulo). Returns the ``tune`` commands to
        route through ``feed_cmd``."""
        if step % self.every != 0:
            return []
        return self.evaluate(step, snap_fn())

    def evaluate(self, step: int, snap: Dict[str, Any]) -> List[dict]:
        """One evaluation tick over a metrics snapshot. At most ONE
        applied actuation per tick (the rate limit on top of per-knob
        cooldowns): loops are consulted in protection order —
        admission (SLO), capacity, speculation — and calibration
        (a file write, not a scheduler command) runs independently."""
        self.evals += 1
        self._tail()
        cmds: List[dict] = []
        for loop in (self._loop_admission, self._loop_capacity,
                     self._loop_speculation):
            cmd = loop(step, snap)
            if cmd is not None:
                cmds.append(cmd)
                break
        self._loop_calibration(step)
        return cmds

    # -- run-end rollup ----------------------------------------------------

    def _recommendations(self, snap: Dict[str, Any], step: int) -> None:
        """The boot-time knobs (advisory half of loop 2): re-run the
        one-shot sizer against the MEASURED peak, and size the bucket
        ladder's top to the observed prompt distribution."""
        peak = snap.get("slot_pages_peak")
        if ("num_pages" not in self.pins and self._num_pages
                and self._recommend_pages is not None
                and isinstance(peak, (int, float)) and peak > 0):
            rec_pages, lines = self._recommend_pages(int(peak))
            if (abs(rec_pages - self._num_pages)
                    > PAGES_REL_TOL * self._num_pages):
                self._fire(
                    step, loop="capacity", knob="num_pages",
                    action="recommend", value=int(rec_pages),
                    prev=self._num_pages, signal="slot_pages_peak",
                    observed=peak,
                    threshold=PAGES_REL_TOL, applied=False,
                    evidence={"rationale": list(lines)})
        if ("buckets" not in self.pins and self._buckets
                and self._prompt_lens):
            lens = sorted(self._prompt_lens)
            p99 = lens[min(len(lens) - 1,
                           int(0.99 * (len(lens) - 1)))]
            top = max(self._buckets)
            need = 1
            while need < p99:
                need *= 2
            if need != top:
                self._fire(
                    step, loop="capacity", knob="buckets",
                    action="recommend", value=int(need), prev=top,
                    signal="prompt_len_p99", observed=p99,
                    threshold=float(top), applied=False,
                    evidence={"prompts": len(lens),
                              "buckets": list(self._buckets)})

    def emit_summary(self, step: int,
                     snap: Optional[Dict[str, Any]] = None) -> None:
        """One ``tune_summary`` at run end: the decision ledger rollup
        plus the advisory recommendations (quiet == zero applied
        actions, what a well-tuned control run shows)."""
        if snap is not None:
            self._recommendations(snap, step)
        if self.emit is not None:
            self.emit("tune_summary", step=int(step),
                      evals=self.evals, actions=self.actions,
                      advisories=self.advisories,
                      suppressed=self.suppressed,
                      by_knob=dict(sorted(self.by_knob.items())),
                      quiet=self.actions == 0)

    # -- state the scheduler reads -----------------------------------------

    @property
    def slot_cap(self) -> int:
        return self._slot_cap
