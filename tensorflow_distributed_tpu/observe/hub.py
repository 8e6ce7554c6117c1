"""The Observatory: one object wiring the observe/ instruments into a run.

The training loop (train/loop.py) drives it at four well-defined
points per step — data fetch, async dispatch, blocking on the oldest
in-flight step, cadence host work — and at the phase boundaries (eval,
checkpoint, restore, preemption drain). Everything else (registry
fan-out, Chrome-trace spans, rolling step-time stats, throughput/MFU
windows, goodput ledger) happens here so the loop body stays thin.

Fully inert when no sink, trace path, or CSV is configured: every
method returns a null context or no-ops, so the loop calls them
unconditionally.
"""

from __future__ import annotations

import contextlib
import sys
import time
from typing import Any, Dict, Iterator, Optional

from tensorflow_distributed_tpu.observe import device as device_mod
from tensorflow_distributed_tpu.observe import goodput as goodput_mod
from tensorflow_distributed_tpu.observe import mfu as mfu_mod
from tensorflow_distributed_tpu.observe.anomaly import AnomalyHub
from tensorflow_distributed_tpu.observe.flightrec import (
    FlightRecorder, FlightRecorderSink)
from tensorflow_distributed_tpu.observe.goodput import GoodputCounter
from tensorflow_distributed_tpu.observe import registry as registry_mod
from tensorflow_distributed_tpu.observe.registry import (
    CsvSink, JsonlSink, MetricsRegistry, host_tags)
from tensorflow_distributed_tpu.observe.steptime import StepTimeBreakdown
from tensorflow_distributed_tpu.observe.trace import (
    ChromeTracer, HostSpans)


def _build_flightrec(ocfg, tags: Optional[Dict[str, Any]],
                     run_config: Any = None) -> FlightRecorder:
    """The crash flight recorder both observatories arm the same way:
    bundle-dir ring + snapshot cadence from the config, provenance
    (git sha, calibration id, host tags, the run config) in the
    bundle meta, signal hooks installed."""
    meta: Dict[str, Any] = {
        **registry_mod.artifact_stamp(
            registry_mod.default_calibration_path()),
        **(tags or {}),
    }
    if run_config is not None:
        import dataclasses

        meta["config"] = (dataclasses.asdict(run_config)
                          if dataclasses.is_dataclass(run_config)
                          and not isinstance(run_config, type)
                          else run_config)
    rec = FlightRecorder(ocfg.flightrec, ring=ocfg.flightrec_ring,
                         snapshot_every=ocfg.flightrec_snapshot_every,
                         meta=meta)
    rec.install()
    return rec


def _crash_dump(flightrec: Optional[FlightRecorder],
                registry: MetricsRegistry) -> None:
    """Called from the observatories' close(): when an exception is in
    flight (non-finite halt, recovery-budget exhaustion, stall — every
    fatal path funnels through the run's ``finally: obs.close()``),
    dump the postmortem bundle and leave one ``postmortem`` record in
    the JSONL (flushed per record, so it survives)."""
    if flightrec is None or flightrec.dumped is not None:
        return
    exc = sys.exc_info()[1]
    if exc is None:
        return
    reason = f"{type(exc).__name__}: {exc}"
    path = flightrec.dump(reason=reason)
    if path:
        registry.emit("postmortem", bundle=path, reason=reason)


def _emit_device_time(registry: MetricsRegistry, profile_dir: str,
                      calibration: str = "") -> list:
    """Parse the profiler capture under ``profile_dir``
    (observe/xprof.py), join each attributed program with the roofline
    prediction from its registered compile costs (at the calibration
    profile when one is given), and emit one ``device_time`` record
    per program through ``registry``. The measured-vs-predicted pair
    observe.report's "Device time" section renders. Never raises —
    xprof degrades to explicit-null records, and anything past that is
    swallowed (telemetry must not take down a finished run)."""
    try:
        from tensorflow_distributed_tpu.observe import xprof

        costs = {r["program"]: r for r in device_mod.programs()
                 if r.get("program")}
        recs = xprof.device_time_records(profile_dir,
                                         programs=list(costs))
        cal = None
        if calibration:
            try:
                from tensorflow_distributed_tpu.analysis.planner \
                    .calibrate import load_calibration
                cal = load_calibration(calibration)
            except Exception as e:
                # A mis-pointed profile must not die silently: the
                # run finishes, but the user is told the device-time
                # predictions fell back to the static tables.
                import sys

                print(f"observe: --plan-calibration {calibration}: "
                      f"{e} — device-time predictions use the static "
                      f"tables", file=sys.stderr)
        hw = None
        try:
            from tensorflow_distributed_tpu.analysis.planner.score \
                import detect_hardware
            hw = detect_hardware(calibration=cal)
        except Exception:
            pass  # no backend — measured-only records
        recs = xprof.with_predictions(recs, costs, hw)
        for rec in recs:
            registry.emit("device_time", **rec)
        return recs
    except Exception:
        return []


class ServeObservatory:
    """mode=serve's observability bundle: the metrics registry (JSONL
    sink, appended on a journal resume), the per-request
    :class:`~..serve_trace.ServeTracer` (resumed too — one trace file
    spans a supervised restart), the :class:`~..slo.SLOMonitor` built
    from ``--observe.slo``, and the rolling-snapshot export knobs —
    everything serve/run.py hands the scheduler and engine. Owns the
    process-level installs (active registry for library-level events,
    compiled-program registration) and tears them down in
    :meth:`close`, mirroring the training Observatory."""

    def __init__(self, ocfg, *, chief: bool = True,
                 tags: Optional[Dict[str, Any]] = None,
                 process_index: int = 0, resumed: bool = False,
                 run_config: Any = None):
        from tensorflow_distributed_tpu.observe.serve_trace import (
            ServeTracer)
        from tensorflow_distributed_tpu.observe.slo import (
            SLOMonitor, parse_slo, parse_windows)

        sinks = []
        if ocfg.metrics_jsonl:
            # A journal-resumed leg APPENDS: the dead leg's records
            # are part of the same serving story (the train-side
            # --resume convention).
            sinks.append(JsonlSink(ocfg.metrics_jsonl, append=resumed))
        self.flightrec = None
        if ocfg.flightrec:
            # Crash flight recorder (observe/flightrec.py): the ring
            # rides the registry as a sink; a SIGKILL'd leg leaves its
            # last fsync'd snapshot as the postmortem bundle.
            self.flightrec = _build_flightrec(ocfg, tags, run_config)
            sinks.append(FlightRecorderSink(self.flightrec))
        self.registry = MetricsRegistry(
            sinks, enabled=chief, tags=tags or {},
            max_records=ocfg.max_records,
            validate=bool(getattr(run_config, "check", False)))
        # Online anomaly detection on the decode-step clock
        # (observe/anomaly.py): the scheduler feeds TTFT / decode-wall
        # / queue-depth samples it already has on host; "anomaly"
        # records flow to the same sinks and the live incident state
        # rides metrics_snapshot() for the export-path pollers.
        self.anomalies = None
        if ocfg.anomaly:
            self.anomalies = AnomalyHub(emit=self.registry.emit,
                                        window=ocfg.anomaly_window,
                                        phase="serve")
        self.tracer = None
        if ocfg.trace:
            self.tracer = ServeTracer(ocfg.trace, enabled=chief,
                                      pid=process_index,
                                      resume=resumed,
                                      durable=getattr(
                                          ocfg, "trace_durable", False))
        self.slo_monitor = None
        self.status_every = 0
        fast, _slow = parse_windows(ocfg.slo_windows)
        if ocfg.slo:
            self.slo_monitor = SLOMonitor(
                parse_slo(ocfg.slo), fast_window=fast,
                slow_window=_slow, burn_threshold=ocfg.slo_burn,
                emit=self.registry.emit, tracer=self.tracer)
            # The live status line defaults to the fast window's
            # cadence when the monitor is armed.
            self.status_every = ocfg.slo_status_every or fast
        elif ocfg.slo_status_every:
            self.status_every = ocfg.slo_status_every
        self.export_every = ocfg.export_every
        self.export_path = ocfg.export_path
        # The online controller (observe/autopilot.py): tune records
        # flow to the same sinks; actuation happens scheduler-side
        # through the control-command path. The metrics JSONL this
        # bundle itself writes is the stream loop 1 tails for the
        # compile × device_time join.
        self.autopilot = None
        if getattr(ocfg, "autopilot", False):
            from tensorflow_distributed_tpu.observe.autopilot import (
                Autopilot)
            pins = tuple(
                p.strip() for p in ocfg.autopilot_pin.split(",")
                if p.strip())
            self.autopilot = Autopilot(
                emit=self.registry.emit,
                every=ocfg.autopilot_every,
                confirm=ocfg.autopilot_confirm,
                cooldown=ocfg.autopilot_cooldown,
                drift_tol=ocfg.autopilot_drift_tol,
                pins=pins,
                metrics_path=ocfg.metrics_jsonl,
                calibration_path=ocfg.autopilot_calibration)
        # Library-level events (engine program registrations,
        # generate's compile-cache misses) land in this run's JSONL;
        # the program registry arms under the same sink-configured
        # condition the training Observatory uses.
        registry_mod.set_active(self.registry)
        self.programs_armed = bool(sinks) and bool(ocfg.programs)
        if self.programs_armed:
            device_mod.set_enabled(True)

    def scheduler_kwargs(self) -> Dict[str, Any]:
        """The scheduler-facing slice of this bundle (serve/run.py
        splats it into the Scheduler ctor)."""
        return {
            "registry": self.registry, "tracer": self.tracer,
            "slo_monitor": self.slo_monitor,
            "anomaly_hub": self.anomalies,
            "autopilot": self.autopilot,
            "export_every": self.export_every,
            "export_path": self.export_path,
            "status_every": self.status_every,
        }

    def emit_device_time(self, profile_dir: str,
                         calibration: str = "") -> list:
        """Device-time attribution for a serve capture (see
        :func:`_emit_device_time`) — call before :meth:`close`."""
        return _emit_device_time(self.registry, profile_dir,
                                 calibration)

    def close(self) -> None:
        # A fatal exception funneling through serve_run's finally
        # (SlotRetryExhausted, StallError, ...) dumps the postmortem
        # bundle before the sinks close.
        _crash_dump(self.flightrec, self.registry)
        if self.programs_armed:
            device_mod.set_enabled(False)
        if registry_mod.get_active() is self.registry:
            registry_mod.set_active(None)
        if self.tracer is not None:
            self.tracer.close()
        self.registry.close()


class Observatory:
    """Run-scoped observability hub; build with :meth:`for_training`."""

    def __init__(self, ocfg=None, *, chief: bool = True,
                 tags: Optional[Dict[str, Any]] = None,
                 accountant: Optional[mfu_mod.ThroughputAccountant] = None,
                 items_per_step: float = 0.0,
                 process_index: int = 0,
                 append: bool = False,
                 clock=time.perf_counter,
                 run_config: Any = None):
        sinks = []
        window, max_records, trace_path = 200, 100_000, ""
        self.flightrec = None
        if ocfg is not None:
            if ocfg.metrics_jsonl:
                sinks.append(JsonlSink(ocfg.metrics_jsonl,
                                       append=append))
            if ocfg.metrics_csv:
                sinks.append(CsvSink(ocfg.metrics_csv,
                                     max_rows=ocfg.max_records))
            if getattr(ocfg, "flightrec", ""):
                # Crash flight recorder (observe/flightrec.py): rides
                # the registry as a sink; periodic fsync'd snapshots +
                # a postmortem dump on trappable deaths (see close()).
                self.flightrec = _build_flightrec(ocfg, tags,
                                                  run_config)
                sinks.append(FlightRecorderSink(self.flightrec))
            window, max_records = ocfg.window, ocfg.max_records
            trace_path = ocfg.trace
        self.registry = MetricsRegistry(
            sinks, enabled=chief, tags=tags or {},
            max_records=max_records,
            # --check arms per-record schema validation: every emit is
            # checked against observe/schemas.py and a violation
            # raises instead of landing in the artifact.
            validate=bool(getattr(run_config, "check", False)))
        # Online anomaly detection (observe/anomaly.py): fed from
        # log_step / health records below — values the loop already
        # fetched; zero new host transfers.
        self.anomalies = None
        if ocfg is not None and getattr(ocfg, "anomaly", False):
            self.anomalies = AnomalyHub(emit=self.registry.emit,
                                        window=ocfg.anomaly_window,
                                        phase="train")
        self.tracer = ChromeTracer(trace_path, pid=process_index,
                                   enabled=chief,
                                   process_name="tfd-train-host",
                                   clock=clock)
        # The span seam (observe/trace.py): the loop's phases as
        # tfd.train.* spans — always on the profiler's clock, in the
        # Chrome trace when --observe.trace configured one.
        self.spans = HostSpans(chrome=self.tracer, clock=clock)
        # Active only when something consumes the output — the loop
        # calls every hook unconditionally and relies on this gate.
        self.active = bool(sinks) or self.tracer.enabled
        self.steptime = StepTimeBreakdown(window=window, clock=clock)
        self.goodput = GoodputCounter(clock=clock)
        self.accountant = accountant or mfu_mod.ThroughputAccountant()
        self.items_per_step = items_per_step
        self._clock = clock
        self._last_log: Optional[tuple] = None  # (step, clock)
        # Compiled-program registration (observe/device.py) arms only
        # for runs with a SINK: the AOT pass costs one extra trace per
        # program, which is worth paying exactly when a sink will
        # carry the compile records (a trace-only run has nowhere
        # durable for them — serve/run.py gates on the same
        # condition).
        self._programs = bool(sinks) and bool(
            getattr(ocfg, "programs", True) if ocfg is not None
            else True)
        if self.active:
            goodput_mod.set_active(self.goodput)
            # Library-level recovery events (checkpoint retries,
            # quarantines, watchdog stalls) flow to the same sinks.
            registry_mod.set_active(self.registry)
        if self._programs:
            device_mod.set_enabled(True)

    # -- construction -----------------------------------------------------
    @classmethod
    def for_training(cls, cfg, mesh, task=None, model=None, params=None,
                     chief: bool = True) -> "Observatory":
        """Build from a TrainConfig + live mesh/task/model/params."""
        import jax

        seq = None
        if task is not None and task.seq_axis is not None:
            seq = int(task.sample_input.shape[task.seq_axis])
        model_cfg = getattr(model, "cfg", None)
        fpi, unit = mfu_mod.flops_per_item(cfg.model, params, model_cfg,
                                           seq_len=seq)
        peak_dev = (cfg.observe.peak_tflops * 1e12
                    if cfg.observe.peak_tflops > 0
                    else mfu_mod.device_peak_flops())
        peak_total = peak_dev * len(jax.devices()) if peak_dev else None
        accountant = mfu_mod.ThroughputAccountant(
            flops_per_item=fpi, unit=unit, peak_flops_total=peak_total)
        # A resumed (preempt-restart) run APPENDS to the prior leg's
        # JSONL instead of truncating it — the pre-preemption records
        # are the artifact's point. Keyed to an ACTUAL restore (the
        # same condition train.loop restores under), not the flag
        # alone: schedulers pass --resume on every leg, and the first
        # leg of a fresh run must still replace a stale file.
        append = False
        if cfg.resume and cfg.checkpoint_dir:
            from tensorflow_distributed_tpu.train.checkpoint import (
                latest_step)
            append = latest_step(cfg.checkpoint_dir) is not None
        obs = cls(cfg.observe, chief=chief,
                  tags=host_tags(mesh, cfg), accountant=accountant,
                  items_per_step=float(cfg.batch_size) * (seq or 1),
                  process_index=jax.process_index(), append=append,
                  run_config=cfg)
        obs.seq_len = seq
        return obs

    def note_grad_sync(self, comm_bytes_per_step: float,
                       plan: Optional[Dict[str, Any]] = None) -> None:
        """Arm the per-step collective-exposed-vs-hidden estimate
        (grad_sync=overlap): ``comm_bytes_per_step`` is the overlap
        plan's per-device traffic (parallel.overlap.comm_bytes_per_
        step). Step records then carry ``comm_ms_est`` (traffic over
        the device kind's ICI bandwidth — the planner's tables:
        generic ratios off-TPU, an error on a TPU kind they lack)
        and, when the accountant
        knows the model FLOPs AND the chip peak, ``comm_exposed_ms_
        est``/``comm_hidden_ms_est``: the slice of the comm estimate
        NOT covered by the measured p50 step time's compute headroom.
        An estimate by construction: the measured figure is the dp4
        cell's ``train.exposed_collective_ms`` (PERF.md)."""
        if not self.active:
            return
        self._comm_bytes = float(comm_bytes_per_step)
        # Lazy: analysis.planner.score is import-light, but hub must
        # not pull it (or jax device queries) for runs that never arm
        # this.
        import jax

        from tensorflow_distributed_tpu.analysis.planner.score import (
            table_peaks)
        dev = jax.devices()[0]
        self._ici_bw = table_peaks(
            dev.platform, getattr(dev, "device_kind", "unknown"))[2]
        if plan:
            self.emit("grad_sync", comm_bytes_per_step=self._comm_bytes,
                      ici_bw=self._ici_bw, **plan)

    def _comm_fields(self, step_ms: Optional[float]) -> Dict[str, Any]:
        """The exposed-vs-hidden split for one step-time sample."""
        comm_bytes = getattr(self, "_comm_bytes", 0.0)
        if not comm_bytes:
            return {}
        comm_ms = 1e3 * comm_bytes / self._ici_bw
        out = {"comm_ms_est": round(comm_ms, 4)}
        acc = self.accountant
        if (acc.flops_per_item and acc.peak_flops_total
                and self.items_per_step and step_ms is not None):
            compute_ms = (1e3 * acc.flops_per_item * self.items_per_step
                          / acc.peak_flops_total)
            exposed = min(comm_ms, max(0.0, step_ms - compute_ms))
            out["comm_exposed_ms_est"] = round(exposed, 4)
            out["comm_hidden_ms_est"] = round(comm_ms - exposed, 4)
        return out

    def note_step_fn(self, step_fn, params=None, model_cfg=None) -> None:
        """Inspect the built step function for observability metadata:
        a 1F1B step whose ``observe_hw_recompute`` attribute is set
        (train.pipeline_step) executes ~4x-forward for the block stack,
        so hw-MFU is reported alongside model MFU."""
        if (getattr(step_fn, "observe_hw_recompute", False)
                and self.accountant.flops_per_item
                and params is not None and "blocks" in params):
            self.accountant.hw_flops_per_item = (
                mfu_mod.pipelined_hw_flops_per_token(
                    params, model_cfg,
                    seq_len=getattr(self, "seq_len", None)))

    # -- per-step phase hooks (the loop's hot path) -----------------------
    # Each is one tfd.train.* span (written whether or not a sink makes
    # this Observatory active) plus, when active, the step-time mark
    # that closes the phase.
    @contextlib.contextmanager
    def _step_phase(self, name: str, mark) -> Iterator[None]:
        with self.spans.span(name):
            yield
        if self.active:
            mark()

    def data(self):
        if self.active:
            self.steptime.data_start()
        return self._step_phase("train.data", self.steptime.data_end)

    def dispatch(self):
        return self._step_phase("train.dispatch",
                                self.steptime.dispatch_end)

    def device_wait(self):
        return self._step_phase("train.device_wait",
                                self.steptime.device_end)

    def cadence(self):
        """The loop's per-step host work after the dispatch: loss
        fetch on the log cadence, eval, checkpoint (those two nest
        their own :meth:`phase` spans inside)."""
        return self.spans.span("train.cadence")

    def step_end(self) -> None:
        if self.active:
            self.steptime.step_end()

    # -- phase spans ------------------------------------------------------
    def phase(self, name: str):
        """``tfd.train.<name>`` span + goodput charge for non-step
        phases the loop enters (eval, checkpoint, restore, drain).
        Goodput's nested-suppression keeps the inner train.checkpoint
        hooks from double-charging."""
        span = self.spans.span("train." + name)
        if not self.active:
            return span
        stack = contextlib.ExitStack()
        stack.enter_context(span)
        stack.enter_context(self.goodput.account(name))
        return stack

    def instant(self, name: str, **args: Any) -> None:
        self.tracer.instant(name, **args)

    # -- emission ---------------------------------------------------------
    def emit(self, event: str, **fields: Any) -> None:
        if not self.active:
            return
        if self.anomalies is not None and event == "health":
            # Per-module vitals tee into the anomaly hub (grad-norm
            # explosion / update-ratio collapse) — the values were
            # already fetched on the health cadence; anomaly records
            # flow out through the hub's own registry emit.
            self.anomalies.observe_health(
                int(fields.get("step", 0)),
                str(fields.get("module", "")), fields)
        self.registry.emit(event, **fields)

    def log_step(self, step: int, metrics: Dict[str, float]) -> None:
        """Per-cadence record: task metrics + rolling step-time
        breakdown + throughput/MFU over the window since the previous
        cadence log."""
        if not self.active:
            return
        now = self._clock()
        prev_log = self._last_log
        fields: Dict[str, Any] = {"step": step}
        fields.update({k: float(v) for k, v in metrics.items()})
        fields.update(self.steptime.summary())
        fields.update(self._comm_fields(fields.get("step_ms_p50")))
        if prev_log is not None:
            last_step, last_t = prev_log
            rates = self.accountant.rates(
                (step - last_step) * self.items_per_step, now - last_t)
            fields.update(rates)
            if "mfu" in rates:
                self.tracer.counter("mfu", mfu=rates["mfu"])
            key = f"{self.accountant.unit}s_per_sec"
            if key in rates:
                self.tracer.counter("throughput", **{key: rates[key]})
        self._last_log = (step, now)
        self.registry.emit("step", **fields)
        if self.anomalies is not None:
            # Detectors consume exactly what this cadence already
            # fetched: the task metrics (loss, grad_norm), the window
            # throughput, and the cadence-derived per-step wall.
            wall_ms = None
            if prev_log is not None and step > prev_log[0]:
                wall_ms = 1e3 * (now - prev_log[1]) / (step
                                                       - prev_log[0])
            self.anomalies.observe_train_step(step, fields,
                                              step_wall_ms=wall_ms)

    def summarize(self, total_seconds: Optional[float] = None,
                  **fields: Any) -> None:
        """Final 'summary' record: rolling stats + goodput ledger +
        caller-supplied run totals."""
        if not self.active:
            return
        # Process-level HBM budget rollup over the registered compiled
        # programs — the "how much must stay resident" companion to
        # the per-program compile records.
        if self._programs:
            budget = device_mod.hbm_budget()
            if budget:
                self.registry.emit("hbm_budget", **budget)
        # Plain dict merge (caller fields win): the goodput ledger may
        # carry categories whose "<cat>_seconds" keys the caller also
        # reports (e.g. compile_seconds from the loop's Timer).
        steps = self.steptime.summary()
        rec = {**steps, **self._comm_fields(steps.get("step_ms_p50")),
               **self.goodput.summary(total_seconds), **fields}
        self.registry.emit("summary", **rec)

    def emit_device_time(self, profile_dir: str,
                         calibration: str = "") -> list:
        """Device-time attribution after a profiler window closed
        (train/loop.py calls this once the StepProfiler stopped):
        parse the capture, join roofline predictions, emit
        ``device_time`` records (see :func:`_emit_device_time`)."""
        if not self.active:
            return []
        return _emit_device_time(self.registry, profile_dir,
                                 calibration)

    # -- lifecycle --------------------------------------------------------
    def flush(self) -> None:
        """Durable partial artifacts (the loop's exception path)."""
        if self.active:
            self.tracer.flush()

    def close(self) -> None:
        # Fatal exceptions (non-finite halt, recovery-budget
        # exhaustion, stall) all funnel through the loop's
        # ``finally: obs.close()`` — dump the postmortem bundle while
        # the exception is still in flight, before the sinks close.
        _crash_dump(self.flightrec, self.registry)
        if self._programs:
            device_mod.set_enabled(False)
        if goodput_mod.get_active() is self.goodput:
            goodput_mod.set_active(None)
        if registry_mod.get_active() is self.registry:
            registry_mod.set_active(None)
        self.tracer.close()
        self.registry.close()
