"""Declarative telemetry record schemas — the cross-process contract.

Every ``event=`` record kind the framework emits (through
``observe.registry`` or the stdout run log) is declared here once:
field name, type, required/optional, explicit-null allowed. Three
things consume the table:

* ``analysis/schema.py`` — the static pass that checks literal dict
  keys at every emit site (producers) and every field read in the
  report/fleetview/router consumers against these schemas.
* ``MetricsRegistry(validate=True)`` — runtime validation, armed by
  ``--check``: an emit whose record violates its schema raises
  immediately instead of poisoning the JSONL stream.
* ``RECORDS.md`` — regenerated verbatim from this registry
  (``python -m tensorflow_distributed_tpu.analysis.schema --update``),
  so the doc can never drift from the declared contract.

Pure stdlib on purpose: the lint tier and the supervisor import this
without jax present.

Conventions
-----------
* ``required`` fields must be present on every record of the kind.
* ``nullable`` fields may be explicitly ``null`` (never absent when
  the producer promises shape stability — see RECORDS.md preamble).
* ``patterns`` declare open field FAMILIES (``val_<metric>``,
  ``coll_<family>_ms``, per-class ``ttft_ms_p95_<class>``) that a
  closed field list cannot enumerate.
* ``open_fields=True`` marks rollup kinds (``step`` task metrics,
  ``serve_summary``, ``metrics_snapshot``, …) whose producers splat
  computed dicts; producers may add fields beyond the table, but
  consumers may still only read DECLARED fields — one-sided openness
  keeps the reader contract checkable.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Tuple

__all__ = [
    "Field", "Schema", "COMMON_TAGS", "SCHEMAS", "NESTED",
    "RECOVERY_KINDS", "schema_for", "allowed_fields",
    "consumer_universe", "validate_record", "render_records_md",
]


@dataclasses.dataclass(frozen=True)
class Field:
    """One declared record field."""

    name: str
    type: str = "any"        # int|float|num|str|bool|dict|list|any
    required: bool = False
    nullable: bool = False
    doc: str = ""


def F(name: str, type: str = "any", required: bool = False,
      nullable: bool = False, doc: str = "") -> Field:
    return Field(name, type, required, nullable, doc)


@dataclasses.dataclass(frozen=True)
class Schema:
    """Contract for one record kind."""

    kind: str
    doc: str
    fields: Tuple[Field, ...]
    patterns: Tuple[str, ...] = ()
    open_fields: bool = False
    section: str = ""
    registry: bool = True    # False: stdout run-log only (no tags)

    def field_names(self) -> Tuple[str, ...]:
        return tuple(f.name for f in self.fields)


# Tags the registry stamps on every record (observe/registry.py emit).
COMMON_TAGS: Tuple[Field, ...] = (
    F("event", "str", required=True, doc="the record kind (sections below)"),
    F("t", "num", required=True,
      doc="seconds since the registry was built (run-relative)"),
    F("process_index", "int",
      doc="emitting host's `jax.process_index()` — the per-host grouping "
          "key `observe.report` splits sections on"),
    F("mesh", "str", doc="compact mesh shape, e.g. `\"data=8\"`"),
    F("config_hash", "str",
      doc="10-hex sha of the run config (`registry.config_hash`) — "
          "compare two streams run-to-run"),
)

# Keys observe.registry.artifact_stamp puts in a flight-recorder
# bundle's meta (not live registry events) — consumers may read them.
ARTIFACT_STAMP_FIELDS: Tuple[str, ...] = ("git_sha", "calibration_id")

# recovery.kind discriminator values (static pass checks literal kinds).
RECOVERY_KINDS: Tuple[str, ...] = (
    "fault_injected", "ckpt_retry", "quarantine", "rewind", "stall",
    "slot_quarantine", "weight_swap", "swap_skip", "restart",
    "mesh_change", "mesh_exhausted", "diverged_no_restart",
    "restart_budget_exhausted", "reshard_restore", "loss_spike",
    "nonfinite",
)

_SECTIONS: Tuple[Tuple[str, str], ...] = (
    ("Training", ""),
    ("Device telemetry (observe/device.py, observe/xprof.py)", ""),
    ("Planner (analysis/planner)", ""),
    ("Resilience", ""),
    ("Incident observatory (observe/anomaly.py, observe/flightrec.py)", ""),
    ("Serving", ""),
    ("Autopilot (observe/autopilot.py)",
     "The online controller's decision ledger (`--observe.autopilot`): "
     "every knob move (and every advisory it could not apply live) is "
     "one auditable `tune` record carrying the triggering signal, the "
     "observed value, and the threshold it crossed; one `tune_summary` "
     "rolls up the run (`quiet=true` is the well-tuned-run contract)."),
    ("Fleet serving (fleet/router.py, fleet/controller.py)",
     "Emitted by the FRONT-END process (fleet/run.py's registry), not "
     "the replicas; `observe.report` folds them into the Fleet section."),
    ("Fleet observatory (observe/fleet_trace.py, fleet/run.py)",
     "Front-end records arming `--fleet.trace` / `--fleet.slo` / "
     "`--fleet.export-path`; `observe.report` folds them into the "
     "Fleet section's `slo`/`decomposition` entries."),
    ("Run log (stdout only)",
     "Written by `utils.logging.MetricLogger.log_json` to the human "
     "stdout stream only — never through the registry, so no common "
     "tags. Declared here so the same schema pass covers them."),
)

_SLO_FIELDS: Tuple[Field, ...] = (
    F("target", "str", required=True,
      doc="SLO target id, `<class>:<metric>:p<pct>`"),
    F("slo_class", "str", doc="request class the target scores"),
    F("metric", "str", doc="latency metric (`ttft_ms` / `tok_ms`)"),
    F("pct", "num", doc="target percentile"),
    F("threshold_ms", "num", doc="latency threshold for the percentile"),
    F("burn_fast", "num", doc="fast-window error-budget burn rate"),
    F("burn_slow", "num", doc="slow-window error-budget burn rate"),
    F("window_fast", "int", doc="fast window length (decode steps)"),
    F("window_slow", "int", doc="slow window length (decode steps)"),
    F("budget_remaining", "num", doc="error budget remaining, 0..1"),
    F("step", "int", doc="decode-step clock at the transition"),
)

SCHEMAS: Tuple[Schema, ...] = (
    # ---------------------------------------------------------- Training
    Schema(
        "start", section="Training",
        doc="One per run (a serve run's names the task `serve`).",
        fields=(
            F("model", "str", required=True, doc="model name from config"),
            F("task", "str", required=True, doc="task name"),
            F("params", "int", required=True, doc="parameter count"),
            F("global_batch", "int", doc="global batch size"),
            F("start_step", "int", doc="first step of this leg (0 fresh)"),
            F("mesh", "dict",
              doc="mesh axes as a dict (stdout log only; the registry "
                  "copy rides the compact `mesh` tag instead)"),
            F("flash_plan", "dict",
              doc="`ops.flash_attention.flash_plan` for the step's "
                  "attention calls: `block_q`/`block_k` (grid level), "
                  "`tile_q`/`tile_k` (in-kernel loops), `tiles_total`/"
                  "`tiles_computed`/`tiles_masked`, `computed_share` "
                  "(tiles the loops visit of the score square) and "
                  "`masked_share` (of those, tiles the diagonal or the "
                  "window's edge crosses). Absent where the step does "
                  "not reach the kernel"),
            F("prefill_attend_plan", "dict",
              doc="a serve run of a family with an expanded prefill "
                  "attend (`ops.latent_attention.prefill_attend`): by "
                  "prefill bucket, `form` (`kernel`: the fused "
                  "`%mla_prefill_attend` call; `xla`: the blocked "
                  "loop) as the bucket's program traced it, its "
                  "`block_q`/`block_k`, and `tiles_computed` of "
                  "`tiles_total` score tiles (`computed_share`; the "
                  "rest lie past the diagonal and are neither fetched "
                  "nor computed). exaone_moe: by bucket `full` and "
                  "`window`, the same of a full layer's attend and of a "
                  "window layer's, the latter with `window` and "
                  "`keys_per_query` (the keys the computed tiles hold a "
                  "query)"),
            F("serving_params", "dict",
              doc="a serve run: what the engine holds of the trained "
                  "tree (`serve.params.serving_tree`). `leaves_cast`: "
                  "leaves made by casting a trained leaf to the compute "
                  "dtype once, because the model's programs only read "
                  "it through that cast (`model.serving_params`; a tied "
                  "head's copy of the table counts); `bytes_trained`: "
                  "the tree as built or restored; `bytes_held`: the "
                  "engine's. 0 and equal bytes where the family "
                  "declares nothing or the declaration changes nothing "
                  "(float32 compute, bfloat16 parameters). `params` "
                  "counts the model's parameters either way"),
        )),
    Schema(
        "step", section="Training", open_fields=True,
        doc="Per log cadence (`--log-every`). Open record: task metrics "
            "(`loss`, …) ride along beyond this table.",
        fields=(
            F("step", "int", required=True, doc="global step"),
            F("loss", "num", doc="task loss (task metrics are open)"),
            F("step_ms_p50", "num", doc="rolling step-time median"),
            F("step_ms_p95", "num", doc="rolling step-time p95"),
            F("data_ms", "num", doc="phase breakdown: host data wait"),
            F("dispatch_ms", "num", doc="phase breakdown: dispatch"),
            F("device_ms", "num", doc="phase breakdown: device wall"),
            F("tokens_per_sec", "num", doc="window throughput (LM tasks)"),
            F("images_per_sec", "num", doc="window throughput (vision)"),
            F("items_per_sec", "num", doc="window throughput (generic)"),
            F("model_tflops", "num", doc="model FLOP rate"),
            F("mfu", "num", doc="model FLOPs utilization"),
            F("hw_mfu", "num",
              doc="hardware MFU (counts recompute FLOPs, 1F1B)"),
            F("comm_ms_est", "num",
              doc="estimated collective traffic per step "
                  "(`--grad-sync overlap` only)"),
            F("comm_exposed_ms_est", "num",
              doc="estimated NON-overlapped collective wall"),
            F("comm_hidden_ms_est", "num",
              doc="estimated overlapped collective wall"),
        )),
    Schema(
        "eval", section="Training",
        doc="Cadence/final eval.",
        fields=(
            F("step", "int", required=True, doc="global step"),
            F("eval_seconds", "num", doc="eval wall seconds"),
        ),
        patterns=(r"val_\w+",)),
    Schema(
        "summary", section="Training", open_fields=True,
        doc="One per run: final rolling stats, goodput ledger, "
            "steady-state throughput. Open record: rolling stats and "
            "throughput rates ride along beyond this table.",
        fields=(
            F("steps", "int", doc="final global step"),
            F("preempted", "bool", doc="run ended on a preemption signal"),
            F("goodput", "num", doc="productive fraction of wall time"),
            F("train_seconds", "num", doc="total train wall"),
            F("compile_seconds", "num", doc="compile wall"),
            F("steps_per_sec", "num", doc="steady-state step rate"),
        ),
        patterns=(r"val_\w+", r"\w+_seconds")),
    Schema(
        "preempted", section="Training",
        doc="Lifecycle marker: the run checkpointed and exited on a "
            "preemption signal.",
        fields=(F("step", "int", required=True, doc="step at exit"),)),
    Schema(
        "resumed", section="Training",
        doc="Lifecycle marker: the run restored from a checkpoint; a "
            "resharded resume carries the mesh transition.",
        fields=(
            F("step", "int", required=True, doc="restored step"),
            F("from_mesh", "any",
              doc="mesh dict the checkpoint was saved on"),
            F("to_mesh", "any", doc="mesh dict restored onto"),
            F("reshard_seconds", "num", doc="reshard wall seconds"),
            F("per_device_batch", "int", doc="batch per device after"),
        )),
    Schema(
        "rewound", section="Training", registry=False,
        doc="Lifecycle marker (stdout): the loop rewound to an earlier "
            "checkpoint (the registry twin is `recovery` kind=`rewind`).",
        fields=(F("step", "int", required=True, doc="step rewound to"),)),
    # ------------------------------------------------- Device telemetry
    Schema(
        "compile", section="Device telemetry (observe/device.py, observe/xprof.py)",
        doc="One per instrumented program registration.",
        fields=(
            F("program", "str", required=True, doc="instrumented program name"),
            F("flops", "num", nullable=True, doc="cost analysis: FLOPs"),
            F("bytes_accessed", "num", nullable=True,
              doc="cost analysis: bytes accessed"),
            F("argument_bytes", "int", nullable=True,
              doc="memory analysis: argument bytes"),
            F("output_bytes", "int", nullable=True,
              doc="memory analysis: output bytes"),
            F("temp_bytes", "int", nullable=True,
              doc="memory analysis: temp bytes"),
            F("generated_code_bytes", "int", nullable=True,
              doc="memory analysis: generated code bytes"),
            F("donated_bytes", "int", nullable=True,
              doc="bytes of donated (aliased) arguments"),
            F("peak_hbm_bytes", "int", nullable=True,
              doc="peak HBM estimate for the program"),
            F("lower_s", "num", doc="lowering wall seconds"),
            F("compile_s", "num", doc="compile wall seconds"),
            F("error", "str",
              doc="only on degraded registration: why costs are missing"),
        )),
    Schema(
        "compile_cache",
        section="Device telemetry (observe/device.py, observe/xprof.py)",
        open_fields=True,
        doc="A compiled-program cache MISS in `models/generate.py`'s "
            "sampler factories. Open record: per-program miss counters "
            "ride along.",
        fields=(
            F("program", "str", required=True, doc="program family name"),
            F("result", "str", doc="cache outcome (`miss`, …)"),
        )),
    Schema(
        "hbm_budget",
        section="Device telemetry (observe/device.py, observe/xprof.py)",
        doc="Process rollup over registered programs.",
        fields=(
            F("programs", "int", required=True, doc="registered programs"),
            F("peak_hbm_bytes_max", "int", nullable=True,
              doc="max single-program peak"),
            F("peak_hbm_bytes_sum", "int", nullable=True,
              doc="all-resident worst case"),
        )),
    Schema(
        "device_time",
        section="Device telemetry (observe/device.py, observe/xprof.py)",
        doc="Ground-truth device wall per program, parsed from the "
            "profiler's Perfetto export after a `--profile-dir` window "
            "closes (`observe/xprof.py`).",
        fields=(
            F("program", "str", nullable=True,
              doc="instrumented program name (`null` for unmatched modules)"),
            F("module", "str", nullable=True,
              doc="XLA module the ops carried (`jit_<program>`)"),
            F("device_ms", "num", nullable=True,
              doc="union of op intervals over the window (concurrent "
                  "lanes counted once)"),
            F("device_ms_per_call", "num", nullable=True,
              doc="`device_ms / calls`"),
            F("op_ms", "num", nullable=True, doc="plain sum of op durations"),
            F("calls", "int", nullable=True,
              doc="estimated invocations in the window (modal per-op "
                  "occurrence count)"),
            F("collective_ms", "num", nullable=True,
              doc="union of collective-op intervals"),
            F("exposed_collective_ms", "num", nullable=True,
              doc="collective wall NOT overlapped by same-module compute "
                  "— the measured counterpart of `comm_exposed_ms_est`"),
            F("coarse", "bool",
              doc="true when the trace had no `/device:` timeline "
                  "(CPU: host-threadpool walls)"),
            F("predicted_ms_per_call", "num", nullable=True,
              doc="roofline prediction from the program's `compile` "
                  "costs (when joinable)"),
            F("calibration_id", "str", nullable=True,
              doc="profile that predicted (null = static tables)"),
            F("reason", "str",
              doc="only on explicit-null records: why nothing was "
                  "attributable"),
        ),
        patterns=(r"coll_\w+_ms",)),
    Schema(
        "health",
        section="Device telemetry (observe/device.py, observe/xprof.py)",
        doc="Per-module on-device vitals on the health cadence.",
        fields=(
            F("module", "str", required=True, doc="instrumented module"),
            F("step", "int", required=True, doc="global step"),
            F("grad_norm", "num", doc="gradient norm"),
            F("update_ratio", "num", doc="update/param RMS ratio"),
            F("param_rms", "num", doc="parameter RMS"),
            F("act_rms", "num", doc="activation RMS (when instrumented)"),
        )),
    # ------------------------------------------------------------ Planner
    Schema(
        "plan", section="Planner (analysis/planner)", open_fields=True,
        doc="The `--plan auto` choice. Open record: planner diagnostics "
            "ride along.",
        fields=(
            F("family", "str", doc="model family planned for"),
            F("size", "str", doc="model size"),
            F("devices", "int", doc="device count planned for"),
            F("batch_size", "int", doc="global batch planned for"),
            F("mesh", "str", doc="chosen mesh"),
            F("strategy", "str", doc="chosen strategy"),
            F("partition", "str", doc="chosen partition"),
            F("predicted_step_ms", "num", doc="cost-model step prediction"),
            F("predicted_peak_hbm_bytes", "int", doc="cost-model HBM peak"),
            F("candidates", "int", doc="layouts scored"),
            F("feasible", "int", doc="layouts under the HBM budget"),
            F("infeasible", "int", doc="layouts over the HBM budget"),
            F("pruned", "int", doc="layouts pruned before scoring"),
            F("calibration_id", "str", nullable=True,
              doc="calibration profile used (null = static tables)"),
        )),
    Schema(
        "plan_drift", section="Planner (analysis/planner)",
        doc="Emitted at run end when a plan record exists and a "
            "steady-state p50 was measured — the cost model's error on "
            "this very run, the signal a calibration refit "
            "(`analysis/planner/calibrate.py`) consumes.",
        fields=(
            F("predicted_step_ms", "num", required=True,
              doc="the plan's prediction"),
            F("measured_step_ms_p50", "num", required=True,
              doc="measured steady-state p50"),
            F("drift_ratio", "num", required=True, doc="measured/predicted"),
            F("calibration_id", "str", nullable=True,
              doc="profile that predicted (null = static tables)"),
        )),
    Schema(
        "grad_sync", section="Planner (analysis/planner)", open_fields=True,
        doc="The overlap bucket plan at startup. Open record: "
            "bucket-plan fields ride along.",
        fields=(
            F("comm_bytes_per_step", "int", required=True,
              doc="estimated collective bytes per step"),
            F("ici_bw", "num", doc="assumed interconnect bandwidth"),
            F("axis_size", "int", doc="data-axis size"),
            F("bucket_bytes", "int", doc="bucket size"),
            F("scatter_buckets", "int", doc="reduce-scatter buckets"),
            F("replicated_buckets", "int", doc="all-reduce buckets"),
            F("scatter_bytes", "int", doc="reduce-scatter bytes"),
            F("replicated_bytes", "int", doc="all-reduce bytes"),
            F("leaves", "int", doc="gradient leaves bucketed"),
        )),
    # --------------------------------------------------------- Resilience
    Schema(
        "recovery", section="Resilience",
        doc="Every fault/containment action, discriminated by `kind`: "
            + ", ".join(f"`{k}`" for k in RECOVERY_KINDS)
            + ". Kind-specific fields ride along (table below is the "
              "union across kinds).",
        fields=(
            F("kind", "str", required=True, doc="the discriminator"),
            F("step", "int", doc="global/decode step at the action"),
            F("fault", "str", doc="fault_injected: injected fault id"),
            F("slot", "int", doc="slot index (slot faults/quarantine)"),
            F("rid", "str", doc="request id (slot_quarantine)"),
            F("retry", "int", doc="slot_quarantine: retry count"),
            F("seconds", "num",
              doc="wall seconds (stalls, weight_swap, reshard_restore)"),
            F("t_s", "num", doc="serve clock seconds"),
            F("attempt", "int", doc="ckpt_retry: attempt number"),
            F("budget", "int", doc="retry/skip budget"),
            F("error", "str", doc="ckpt_retry: exception text"),
            F("backoff_s", "num", doc="backoff before the retry/restart"),
            F("reason", "str", doc="why (quarantine, swap_skip, nonfinite)"),
            F("mesh", "str", doc="quarantine: mesh after masking"),
            F("from_step", "int", doc="rewind: step rewound from"),
            F("to_step", "int", doc="rewind: step rewound to"),
            F("from_mesh", "any", doc="mesh before (mesh_change/reshard)"),
            F("to_mesh", "any", doc="mesh after (mesh_change/reshard)"),
            F("resharded", "bool",
              doc="reshard_restore: topology actually changed"),
            F("what", "str", doc="stall: watched phase (data/sync)"),
            F("timeout_s", "num", doc="stall: the tripped timeout"),
            F("multihost", "bool", doc="stall: multihost run"),
            F("loss", "num", doc="loss_spike/nonfinite: offending loss"),
            F("window_median", "num", doc="loss_spike: rolling median"),
            F("action", "str", doc="nonfinite: policy action taken"),
            F("used", "int", doc="nonfinite: budget used"),
            F("ckpt_step", "int", doc="weight_swap: step swapped in"),
            F("leg", "int", doc="supervisor: leg number"),
            F("rc", "int", doc="supervisor: dead leg's return code"),
            F("restarts", "int", doc="supervisor: restarts so far"),
            F("alive", "int", doc="supervisor: alive device count"),
            F("masked", "int", doc="supervisor: masked device count"),
            F("bundle", "str",
              doc="supervisor: dead leg's postmortem bundle path"),
            F("resume", "bool", doc="supervisor: next leg resumes"),
            F("lost", "int", doc="fault_injected device_loss: lost count"),
            F("mask_file", "str",
              doc="fault_injected device_loss: device-mask path"),
            F("failures", "int", doc="fault_injected ckpt_io_fail: count"),
        )),
    # ------------------------------------------------ Incident observatory
    Schema(
        "anomaly",
        section="Incident observatory (observe/anomaly.py, observe/flightrec.py)",
        doc="One per detection, emitted the moment a streaming detector "
            "leaves its envelope (`--observe.anomaly`; fed from values "
            "already fetched on the log cadence — train — or the "
            "decode-step clock — serve). The live rollup (total count, "
            "per-detector counts, currently-`active` detectors, `last` "
            "anomaly) rides `metrics_snapshot` records and the "
            "`--observe.export-path` payload under the `anomaly` key.",
        fields=(
            F("detector", "str", required=True,
              doc="detector id: `loss_nonfinite`, `loss_spike`, "
                  "`loss_plateau`, `step_time_spike`, `throughput_slope`, "
                  "`grad_norm_spike[/module]`, "
                  "`update_ratio_collapse/<module>`, `ttft_spike`, "
                  "`decode_time_spike`, `queue_growth`, `slot_nonfinite`"),
            F("severity", "str", required=True,
              doc="`warn` (degradation) or `critical` (active damage: "
                  "non-finite values, explosions)"),
            F("step", "int",
              doc="the phase's clock at detection (train step / decode "
                  "step)"),
            F("value", "num", nullable=True, doc="the offending sample"),
            F("baseline", "num", nullable=True,
              doc="rolling baseline (median) it broke from"),
            F("zscore", "num", nullable=True,
              doc="robust MAD z-score (spike detectors)"),
            F("evidence", "list",
              doc="the last few window samples behind the baseline"),
            F("module", "str", nullable=True,
              doc="module context (per-module detectors)"),
            F("slot", "int", nullable=True,
              doc="slot context (per-slot detectors)"),
            F("rid", "str", nullable=True,
              doc="request context (per-slot detectors)"),
        )),
    Schema(
        "postmortem",
        section="Incident observatory (observe/anomaly.py, observe/flightrec.py)",
        doc="Emitted when a fatal exception funnels through the run's "
            "``finally`` (non-finite halt, recovery-budget exhaustion, "
            "stall) and the flight recorder dumps its bundle. Signal "
            "deaths leave no registry record — a SIGTERM writes the "
            "same bundle FILE from its handler before the process dies, "
            "a SIGKILL leaves only the last fsync'd "
            "`flight-<pid>.jsonl` snapshot — and the supervisor's "
            "`restart` recovery event carries the dead leg's bundle "
            "path as `bundle` either way. Render any flavor with "
            "`python -m tensorflow_distributed_tpu.observe.postmortem "
            "<bundle>`.",
        fields=(
            F("bundle", "str", required=True,
              doc="the `postmortem-<pid>.jsonl` path"),
            F("reason", "str", required=True,
              doc="exception class + message"),
        )),
    # ------------------------------------------------------------ Serving
    Schema(
        "serve_request", section="Serving",
        doc="One per completed request.",
        fields=(
            F("rid", "str", required=True, doc="request id"),
            F("prompt_len", "int", doc="prompt tokens"),
            F("new_tokens", "int", doc="generated tokens"),
            F("finish", "str", doc="`eos` or `budget`"),
            F("ttft_ms", "num", nullable=True, doc="time to first token"),
            F("tok_ms", "num", nullable=True, doc="mean inter-token ms"),
            F("queue_steps", "int", doc="decode steps spent queued"),
            F("prefill_ms", "num", nullable=True,
              doc="wall of the request's first admission, from the "
                  "prefill's dispatch to its first token (its "
                  "`tfd.serve.admit` span: prefill launch, first-token "
                  "fetch, bookkeeping; where the prefill was dispatched "
                  "behind the running decode step, from inside its "
                  "`tfd.serve.token_fetch`, the rest of that step's "
                  "fetch and its retire too: the span then opens at "
                  "the first-token fetch) — `ttft_ms` is `wait_ms` "
                  "plus this"),
            F("wait_ms", "dict", nullable=True,
              doc="where the wait for its first admission went, by "
                  "KIND of scheduler iteration (see `NESTED` "
                  "`iter_ms`): from the time it was due to the start "
                  "of its first `tfd.serve.admit`, read off the span "
                  "seam's running self-time totals. `admit`: other "
                  "requests' admissions (a prefill holds every live "
                  "row and every waiting request); `step`: decode "
                  "iterations (the starvation clock, or no free "
                  "slot); `other`: the rest (poll, tail, a sleeping "
                  "engine). The lateness between due time and the "
                  "iteration that took it from `pending` counts "
                  "under the kind of the iteration that was running. "
                  "The three plus `prefill_ms` add up to `ttft_ms`"),
            F("decode_ms", "dict",
              doc="the same three kinds from its first token to its "
                  "last: they add up to `tok_ms` x (tokens decoded - "
                  "1). `admit` is time a token gap spent behind "
                  "admissions — other requests', and its own "
                  "re-prefill after a quarantine or a preemption; "
                  "`step` is decode iterations. Host-clock walls: a "
                  "token is the client's when `tfd.serve.retire` "
                  "hands it to `on_token`"),
            F("admits_endured", "int",
              doc="`tfd.serve.admit` spans that closed between its "
                  "first token and its last, its own first admission "
                  "not counted (a re-prefill of its continuation is)"),
            F("retries", "int", doc="intake retries"),
            F("preempts", "int", doc="times preempted by the scheduler"),
            F("slo", "str", doc="SLO class"),
            F("tenant", "str", nullable=True, doc="tenant id"),
            F("recovery_window", "bool",
              doc="arrival→first-token overlapped a recovery event"),
            F("arrival_s", "num", doc="serve-clock arrival stamp"),
            F("t_first_s", "num", nullable=True,
              doc="serve-clock first-token stamp"),
        )),
    Schema(
        "serve_summary", section="Serving", open_fields=True,
        doc="One per serve run. Open record: speculation fields "
            "(`spec_tokens`, `verify_steps`, `accept_rate`, "
            "`spec_fallback_slots`), the SLO monitor rollup "
            "(`slo_alerts`, `slo_budget_remaining_min`, `slo_targets`) "
            "and — on a paged run (`--serve.paged`) — the paging rollup "
            "(`page_size`, `num_pages`, `page_bytes`, "
            "`pages_per_max_len`, `pages_in_use`, `pages_peak`, "
            "`slot_pages_peak`, `pool_occupancy`, `prefix_hits`, "
            "`prefix_hit_tokens`, `prefix_hit_rate`, `prompt_tokens`, "
            "`prefill_tokens_computed`, `prefill_tokens_dense`, "
            "`cow_copies`, `page_evictions`, `cached_pages`, "
            "`sessions`) ride along.",
        fields=(
            F("requests", "int", doc="completed requests"),
            F("total_new_tokens", "int", doc="tokens generated"),
            F("wall_s", "num", doc="serve wall seconds"),
            F("phase_ms", "dict",
              doc="where the wall went, by host phase (see `NESTED`): "
                  "`{span name: "
                  "{count, sum_ms, max_ms, max_step, max_at_s}}` for "
                  "every `tfd.serve.*` span of the run (self times — a "
                  "parent excludes its children — so the `sum_ms` add "
                  "up to `wall_s`); `max_step`/`max_at_s` place the "
                  "worst single span on the decode-step and run clocks"),
            F("iter_ms", "dict",
              doc="the same wall by KIND of scheduler iteration (see "
                  "`NESTED`): `phase_ms` summed into `admit`, `step` "
                  "and `other`, so the three add up to `wall_s` too; "
                  "`admit` over `wall_s` is the share of the run in "
                  "which no live row could advance"),
            F("admissions", "int",
              doc="`tfd.serve.admit` spans of the run (first "
                  "admissions and re-prefills of continuations)"),
            F("admits_first", "int",
              doc="of those, the admissions whose prefill was "
                  "dispatched with NO decode step queued behind the one "
                  "running (an idle engine's too): the prefill was the "
                  "next thing the device did. The engine launches the "
                  "step ahead only when the step in flight is about to "
                  "end and looks for arrivals until then; the rest came "
                  "due after that launch (the last margin of a step, a "
                  "slot a step's retire had just freed) and stood "
                  "behind one whole step more"),
            F("admitted_at_once", "int",
              doc="of `admissions`, those whose request had "
                  "`queue_steps` 0: it came due (or was re-queued) on "
                  "an engine that had decoded `decode_priority` "
                  "iterations since its last admission, with a slot "
                  "free, and went in without enduring one more; the "
                  "rest were spaced by the admission clock or waited "
                  "for a slot under `policy=slo`"),
            F("tokens_per_sec", "num", doc="decode throughput"),
            F("decode_steps", "int",
              doc="decode steps retired this run (a verify counts as "
                  "one): each handed its tokens to the host"),
            F("steps_ahead", "int",
              doc="of `decode_steps`, those the engine had launched from "
                  "the previous step's tokens ON THE DEVICE, before "
                  "fetching them (one step in flight; the `ahead` "
                  "argument of their `tfd.serve.step_dispatch` span is "
                  "1): the device went into them without waiting for "
                  "the host. The rest started from the host's tokens: "
                  "the first, the one after an idle engine, an admission "
                  "dispatched behind the running step, a verify, a swap "
                  "or a drill"),
            F("ahead_rows_dropped", "int",
              doc="row-steps computed ahead for a slot that had changed "
                  "hands by the fetch (its request ended, was "
                  "quarantined, preempted or cancelled one step "
                  "earlier) plus the rows of steps dropped whole "
                  "(`drain`): computed, never served"),
            F("mean_slot_occupancy", "num", doc="mean live-slot fraction"),
            F("prefill_compiles", "int", doc="prefill bucket compiles"),
            F("buckets", "list", doc="prefill bucket sizes"),
            F("retries", "int", doc="intake retries"),
            F("swaps", "int", doc="weight swaps absorbed"),
            F("swap_seconds", "num", doc="wall spent swapping"),
            F("seed", "int", doc="sampler seed"),
            F("trace", "str", nullable=True, doc="Perfetto trace path"),
            F("resumed", "int", doc="requests resumed from the journal"),
            F("policy", "str", doc="scheduler policy"),
            F("preemptions", "int", doc="scheduler preemptions"),
            F("anomalies", "int",
              doc="total anomaly-record count (when `--observe.anomaly` "
                  "is armed)"),
            F("tune_actions", "int",
              doc="applied autopilot knob changes this run (when "
                  "`--observe.autopilot` is armed; 0 on a well-tuned "
                  "run — the quiet-control contract)"),
            F("tp_width", "int",
              doc="tensor-parallel width (`--serve.mesh-model`, 1 when "
                  "unsharded)"),
            F("per_device_cache_bytes", "int",
              doc="slot cache's PER-DEVICE resident bytes — already "
                  "divided by the TP width, so a router summing replicas "
                  "never counts one sharded cache N times"),
            F("engine_mesh", "dict",
              doc="engine's mesh shape as a dict, e.g. "
                  "`{\"data\": 1, \"model\": 2}` — distinct from the "
                  "registry's compact `mesh` host tag"),
            # What a family's decode program counts (its own
            # summarize_stats through SlotDecodeEngine.model_stats:
            # glm_moe_dsa, minicpm_sala, granitemoehybrid, nemotron_h,
            # exaone_moe); absent for the others.
            F("cache_bytes_per_slot_by_kind", "dict",
              doc="the slot cache's bytes a slot by KIND of leaf "
                  "(`latent`, `index_keys`; `latent` alone for a model "
                  "without indexer layers; `kv`, `pooled_keys`, `state` "
                  "and its `state_pos` stamp for a hybrid of block-sparse "
                  "and linear layers; `kv`, `state`, `state_pos` and "
                  "`conv`, the ring of a convolution's last inputs by "
                  "position modulo its taps, for a model of state-space "
                  "and attention layers; `kv` and `kv_ring`, the ring of "
                  "the last `sliding_window` rows of K and V by position "
                  "modulo the window, for a model of full and window "
                  "attention layers), built from the model's per-layer "
                  "list"),
            F("decode_live_rows", "int",
              doc="live slots summed over the decode steps (a step "
                  "computes every slot; only these need its result)"),
            F("select_keys_available", "int",
              doc="keys the sparse selection could choose from, summed "
                  "over live slots and decode steps (each slot's depth; "
                  "exaone_moe: over every attention layer too, what full "
                  "attention everywhere would attend)"),
            F("select_keys_kept", "int",
              doc="keys it kept (`min(depth, index_topk)`; a model of "
                  "dense latent layers keeps them all; a selection by "
                  "blocks keeps `topk` blocks' causal positions, a "
                  "key-value group's; exaone_moe: the depth on a full "
                  "layer, at most `sliding_window` on a window layer), "
                  "same sum"),
            F("sparse_blocks_kept", "int",
              doc="a selection by blocks only: blocks kept, every "
                  "key-value group's, summed over live slots and decode "
                  "steps (one sparse layer's)"),
            F("sparse_rows_dense", "int",
              doc="a selection by blocks only: live row-steps whose "
                  "context was at most `dense_len`, which attend all of "
                  "it and select nothing"),
            F("state_rows_stepped", "int",
              doc="a model with recurrent layers only: slot-rows whose "
                  "state a decode step read and moved, summed over those "
                  "layers and the decode steps (the state step's own trip "
                  "count: one a LIVE slot a layer)"),
            F("state_bytes_per_slot", "int",
              doc="the same model: bytes of recurrent state a slot "
                  "holds, whatever its depth"),
            F("state_rows_folded", "int",
              doc="a model of state-space layers: of "
                  "`state_rows_stepped`, the slot-rows that FOLDED their "
                  "token into the state (the step's position was the "
                  "state's `state_pos` stamp)"),
            F("state_rows_reread", "int",
              doc="the same model: the rest, whose state already held "
                  "the token and was only read (a step the engine "
                  "dropped and computed again)"),
            F("conv_bytes_per_slot", "int",
              doc="the same model: bytes of the convolution rings a slot "
                  "holds (`mamba_d_conv` rows a state-space layer)"),
            F("attend_keys", "int",
              doc="the same model: cached positions its attention "
                  "layers' live rows attend (each row's depth), summed "
                  "over live slots and decode steps, one layer's"),
            F("attend_positions_visited", "int",
              doc="dense latent layers, and exaone_moe: cached positions "
                  "the attends' blocks covered, over ALL slots and decode "
                  "steps, as the kernel's grid visits them (a live row's "
                  "blocks up to its depth, none of a free slot's; "
                  "exaone_moe adds every slot's whole ring a window "
                  "layer, which its slot-blind attend reads; "
                  "granitemoehybrid and nemotron_h: their attention "
                  "layers' `gqa_dense_attend`, with `select_keys_kept` "
                  "the live rows' depths over those layers)"),
            F("full_attend_keys", "int",
              doc="exaone_moe, and jamba (every attention layer of which "
                  "is a full one): cached positions the full-attention "
                  "layers' live rows attend (each row's depth), summed "
                  "over live slots, full layers and decode steps"),
            F("s6_scan_positions", "int",
              doc="jamba: positions the state-space layers' prefill scans "
                  "were handed, summed over those layers and the run's "
                  "prefills (each prefill's bucket: the prompt and its "
                  "padding)"),
            F("s6_scan_positions_live", "int",
              doc="jamba: of `s6_scan_positions`, those before the "
                  "prompt's `true_len` (what a scan that stops at the "
                  "prompt's end computes; the rest is a bucket's padding, "
                  "which the kernel skips by whole chunks)"),
            F("kv_attend_positions_visited", "int",
              doc="the dense slot engine over `[slots, max_len, heads, "
                  "head_dim]` key and value leaves (neither paged, "
                  "windowed nor int8): cached positions the decode "
                  "attends' blocks covered, summed over the rows at a "
                  "position past 0, the layers and the decode launches, "
                  "as `ops/kv_attend.py`'s grid visits them (a live "
                  "row's blocks up to its depth, none of a free slot's; "
                  "a leaf the kernel does not take, `max_len` not whole "
                  "lane tiles: every slot's whole row); counted on the "
                  "host, in the kernel's blocks on every backend"),
            F("kv_attend_positions_seen", "int",
              doc="the same engine: cached positions those rows' queries "
                  "could see (each row's position + 1), same sum"),
            F("select_rows_gathered", "int",
              doc="a model with a selection only: latent cache rows the "
                  "decode steps' gathers moved, summed over the layers "
                  "with a selection and the decode steps (`index_topk` a "
                  "LIVE slot a layer; a free slot's rows are not "
                  "gathered)"),
            F("index_keep_share", "num",
              doc="`select_keys_kept / select_keys_available`"),
            F("moe_layers", "int", doc="expert layers counted"),
            F("moe_held_pairs", "int",
              doc="routed (token, expert) pairs that landed on the "
                  "experts HELD here, over live slots, expert layers and "
                  "decode steps"),
            F("moe_held_pairs_by_expert", "list",
              doc="the same by held expert"),
            F("moe_pairs_routed", "int",
              doc="nemotron_h, exaone_moe: the (token, expert) pairs the "
                  "live rows' routers picked over ALL published experts "
                  "(live rows x `num_experts_per_tok` x expert layers, "
                  "summed over "
                  "decode steps), of which `moe_held_pairs` landed here"),
            F("moe_pairs_spread", "num",
              doc="max / mean of `moe_held_pairs_by_expert`"),
            F("moe_pairs_per_expert_step", "num",
              doc="held pairs per held expert, expert layer and decode "
                  "step"),
            F("moe_experts_hit", "int",
              doc="held experts a live row's pair reached, summed over "
                  "expert layers and decode steps: the grouped matmul "
                  "skips the others, so only these experts' weights are "
                  "read"),
            F("moe_plan", "dict",
              doc="a family with routed experts held as a share: how "
                  "`ops.latent_attention.held_experts` takes the pairs "
                  "of the decode step (`decode`) and of each prefill "
                  "bucket, static by shape (`moe_plan`): `form` "
                  "(`one_hot` or `gather`), `block_rows`, "
                  "`expected_trips` at the configuration's share of "
                  "held over routed experts, `max_trips` if every pair "
                  "landed here, the grouped matmuls' tiles (tm, tk, tn) "
                  "`tiles_in` (gate and up) and `tiles_out` (down), and "
                  "`combine_tile`, the lanes of the `[tokens, D]` "
                  "result that `moe_combine_held` holds while a block's "
                  "held rows pass"),
        ),
        patterns=(r"ttft_ms_p\d+(_\w+)?",)),
    Schema(
        "prefix_hit", section="Serving",
        doc="One per paged admission whose prompt matched cached pages "
            "(serve/paging).",
        fields=(
            F("slot", "int", required=True, doc="admitted slot"),
            F("prompt_len", "int", doc="prompt tokens"),
            F("hit_tokens", "int",
              doc="matched prefix length — prefill ran only on the rest"),
            F("tail_bucket", "int",
              doc="the bucket the tail actually computed"),
            F("session", "str", nullable=True,
              doc="conversation id on a session re-attach, else null"),
        )),
    Schema(
        "page_evict", section="Serving",
        doc="LRU eviction under pool pressure (an admission needed more "
            "pages than were free).",
        fields=(
            F("evicted", "int", required=True,
              doc="entries released this acquire"),
            F("reason", "str", doc="eviction reason"),
            F("pages_free", "int", doc="free pages after"),
            F("pages_in_use", "int", doc="in-use pages after"),
        )),
    Schema(
        "slo_alert", section="Serving",
        doc="Burn-rate alert transition on the decode-step clock "
            "(`observe/slo.py`).",
        fields=_SLO_FIELDS),
    Schema(
        "slo_ok", section="Serving",
        doc="Burn-rate recovery transition (the alert cleared).",
        fields=_SLO_FIELDS),
    Schema(
        "metrics_snapshot", section="Serving", open_fields=True,
        doc="Rolling point-in-time export (`--observe.export-every`; "
            "also atomically rewritten at `--observe.export-path`). "
            "Open record: the SLO state and — on a paged run — the "
            "paged rollup (same fields as `serve_summary`'s) ride "
            "along. `ckpt_step` (when serving restored weights) is the "
            "trained step the live params came from — the fleet "
            "controller's model-staleness feed; `draining` appears once "
            "a drain command landed.",
        fields=(
            F("seq", "int", required=True,
              doc="monotonic snapshot sequence — liveness triplet for "
                  "pollers (fleet/router.py): a frozen file is "
                  "distinguishable from a healthy idle replica"),
            F("wall_ts", "num", required=True,
              doc="liveness triplet: time.time() at the write"),
            F("pid", "int", doc="liveness triplet: emitting pid"),
            F("t_s", "num", doc="serve clock seconds"),
            F("decode_steps", "int", doc="decode steps so far"),
            F("requests_done", "int", doc="completed requests"),
            F("requests_live", "int", doc="live requests"),
            F("queue_depth", "int", doc="queued requests"),
            F("slot_occupancy", "num", doc="live-slot fraction"),
            F("tokens_per_sec", "num", doc="cumulative throughput"),
            F("tokens_per_sec_window", "num",
              doc="throughput over the rolling window — beside the "
                  "cumulative rate, so a regime shift is visible to a "
                  "controller (the autopilot reads this one)"),
            F("accept_rate", "num", nullable=True,
              doc="speculation accept rate, lifetime-cumulative"),
            F("accept_rate_window", "num",
              doc="accept rate over the rolling window "
                  "(accepted/proposed deltas between the window "
                  "endpoints) — the autopilot's loop-3 signal"),
            F("spec_tokens", "int",
              doc="CURRENT speculation depth k — moves live under "
                  "autopilot loop 3"),
            F("tune_actions", "int",
              doc="applied autopilot knob changes so far "
                  "(`--observe.autopilot`)"),
            F("retries", "int", doc="intake retries"),
            F("preemptions", "int", doc="scheduler preemptions"),
            F("swaps", "int", doc="weight swaps absorbed"),
            F("num_slots", "int", doc="capacity: decode slots"),
            F("max_len", "int", doc="capacity: max sequence length"),
            F("tp_width", "int", doc="capacity: tensor-parallel width"),
            F("per_device_cache_bytes", "int",
              doc="capacity: per-device cache bytes (see `serve_summary`)"),
            F("engine_mesh", "dict", doc="engine mesh dict"),
            F("ckpt_step", "int",
              doc="trained step the live params came from"),
            F("draining", "bool", doc="a drain command landed"),
            F("inbox_poll_lag_ms", "num",
              doc="intake-minus-`enq_ts` stamp over recent requests — "
                  "the decomposition's replica-side anchor and an early "
                  "warning for a wedged feed"),
            F("inbox_poll_lag_ms_p95", "num", doc="p95 of the same"),
            F("anomaly", "dict", doc="live anomaly rollup (see `anomaly`)"),
            F("slo", "dict", doc="live SLO state (see `NESTED`)"),
        ),
        patterns=(r"ttft_ms_p\d+(_\w+)?",)),
    Schema(
        "serve_cancel", section="Serving",
        doc="Fleet-replica intake outcome (`--serve.inbox`): the router "
            "moved the request elsewhere, dropped without a completion.",
        fields=(
            F("rid", "str", required=True, doc="request id"),
            F("where", "str", required=True,
              doc="`queue` | `pending` | `live`"),
            F("slot", "int", doc="slot freed (live cancels)"),
        )),
    Schema(
        "serve_reject", section="Serving",
        doc="Fleet-replica intake outcome (`--serve.inbox`): the "
            "request cannot be served here (does not fit, or arrived "
            "while draining); a matching `reject` line lands in the "
            "journal so the router sheds instead of waiting.",
        fields=(
            F("rid", "str", required=True, doc="request id"),
            F("prompt_len", "int", doc="prompt tokens"),
            F("max_new", "int", doc="requested generation budget"),
            F("draining", "bool", doc="rejected because draining"),
        )),
    Schema(
        "preempt", section="Serving",
        doc="SLO scheduler preempt-and-requeue (policy, NOT a recovery).",
        fields=(
            F("rid", "str", required=True, doc="victim request id"),
            F("slot", "int", doc="slot released"),
            F("slo", "str", doc="victim's SLO class"),
            F("tenant", "str", nullable=True, doc="victim's tenant"),
            F("served", "int", doc="tokens served before preemption"),
            F("t_s", "num", doc="serve clock seconds"),
        )),
    # ---------------------------------------------------------- Autopilot
    Schema(
        "tune", section="Autopilot (observe/autopilot.py)",
        doc="One autopilot decision: a live knob actuation "
            "(`applied=true` — routed through the scheduler's "
            "control-command path between decode steps, so the token "
            "streams are identical by construction) or an advisory "
            "recommendation for a boot-time knob it cannot change live "
            "(`applied=false`: `num_pages`, `buckets`, or a calibration "
            "refit with no `--observe.autopilot-calibration` path). The "
            "`signal`/`observed`/`threshold` triple plus `evidence` is "
            "the machine-readable audit trail.",
        fields=(
            F("step", "int", required=True,
              doc="decode-step clock at the decision"),
            F("loop", "str", required=True,
              doc="`admission` | `capacity` | `speculation` | "
                  "`calibration`"),
            F("knob", "str", required=True,
              doc="`decode_priority` | `slot_cap` | `spec_k` | "
                  "`calibration` | `num_pages` | `buckets`"),
            F("action", "str", required=True,
              doc="what moved: `tighten`/`relax` (admission), "
                  "`shrink`/`grow` (slot cap), `deepen`/`shallow` "
                  "(spec k), `refit` (calibration), `recommend` "
                  "(advisories)"),
            F("value", "any", required=True,
              doc="the new knob value (calibration: the refit "
                  "profile's `calibration_id`)"),
            F("prev", "any", nullable=True, doc="the value it replaced"),
            F("signal", "str", required=True,
              doc="the telemetry stream that triggered: "
                  "`slo_burn_fast` | `pool_occupancy` | "
                  "`accept_rate_window` | `drift_ratio` | "
                  "`slot_pages_peak` | `prompt_len_p99`"),
            F("observed", "num", doc="the signal's observed value"),
            F("threshold", "num", doc="the threshold it crossed"),
            F("applied", "bool", required=True,
              doc="true = actuated live through the control-command "
                  "path; false = advisory only"),
            F("evidence", "dict",
              doc="the triggering context (e.g. the `plan_drift` "
                  "record, burn rates per target, the sizer's "
                  "rationale lines)"),
        )),
    Schema(
        "tune_summary", section="Autopilot (observe/autopilot.py)",
        doc="One per autopilot-armed run: the decision-ledger rollup. "
            "`quiet=true` (zero applied actions) is the well-tuned-run "
            "contract; `suppressed` counts triggers absorbed by per-knob "
            "cooldowns (the rate limiter working, not a bug).",
        fields=(
            F("step", "int", required=True,
              doc="decode-step clock at run end"),
            F("evals", "int", doc="evaluation ticks"),
            F("actions", "int", doc="applied knob changes"),
            F("advisories", "int",
              doc="applied=false recommendations emitted"),
            F("suppressed", "int",
              doc="triggers absorbed by a cooling-down knob"),
            F("by_knob", "dict", doc="applied changes per knob"),
            F("quiet", "bool", required=True,
              doc="zero applied actions (the control-run gate)"),
        )),
    # ------------------------------------------------------ Fleet serving
    Schema(
        "fleet_dispatch",
        section="Fleet serving (fleet/router.py, fleet/controller.py)",
        doc="One request handed to one replica.",
        fields=(
            F("rid", "str", required=True, doc="request id"),
            F("replica", "int", required=True, doc="target replica"),
            F("kind", "str", doc="`fresh` | `redispatch`"),
            F("retry", "int", doc="re-dispatches so far"),
            F("slo", "str", doc="SLO class"),
            F("base_tokens", "int", doc="continuation length"),
            F("t_s", "num", doc="router clock seconds"),
        )),
    Schema(
        "fleet_shed",
        section="Fleet serving (fleet/router.py, fleet/controller.py)",
        doc="Load shedding / retry exhaustion (shed, never hang).",
        fields=(
            F("rid", "str", required=True, doc="request id"),
            F("slo", "str", doc="SLO class"),
            F("reason", "str",
              doc="`saturated` | `retry_budget` | `rejected`"),
            F("retries", "int", doc="re-dispatches before the shed"),
            F("t_s", "num", doc="router clock seconds"),
        )),
    Schema(
        "fleet_replica",
        section="Fleet serving (fleet/router.py, fleet/controller.py)",
        doc="Replica lifecycle transition.",
        fields=(
            F("replica", "int", required=True, doc="replica index"),
            F("state", "str", required=True,
              doc="`spawned` | `up` | `quarantined` | `rejoined` | "
                  "`dead` | `restarted` | `exited` | "
                  "`diverged_no_restart` | `restart_budget_exhausted`"),
            F("reason", "str",
              doc="quarantine: `stale_snapshot` | `anomaly:<detector>`"),
            F("epoch", "int", doc="replica epoch (restarts bump it)"),
            F("rc", "int", doc="exit code (exited)"),
            F("inflight", "int", doc="requests in flight at the event"),
            F("restarts", "int", doc="restart count (budget exhaustion)"),
            F("t_s", "num", doc="controller/router clock seconds"),
        )),
    Schema(
        "fleet_swap",
        section="Fleet serving (fleet/router.py, fleet/controller.py)",
        doc="Rolling weight swap, per replica acknowledgement "
            "(`state: timeout` when one never acked).",
        fields=(
            F("replica", "int", required=True, doc="replica index"),
            F("ckpt_step", "int", doc="step swapped in"),
            F("state", "str", doc="`timeout` when the ack never came"),
            F("t_s", "num", doc="controller clock seconds"),
        )),
    Schema(
        "fleet_roll",
        section="Fleet serving (fleet/router.py, fleet/controller.py)",
        doc="Fleet-wide rollout lifecycle (`done_partial`: a replica "
            "timed out — NOT counted as a rolling swap).",
        fields=(
            F("state", "str", required=True,
              doc="`begin` | `done` | `done_partial` | `drain`"),
            F("ckpt_step", "int", doc="step rolled out"),
            F("replicas", "int", doc="replicas targeted (begin)"),
            F("timeouts", "int", doc="replicas that never acked"),
            F("t_s", "num", doc="controller clock seconds"),
        )),
    Schema(
        "fleet_summary",
        section="Fleet serving (fleet/router.py, fleet/controller.py)",
        open_fields=True,
        doc="One per fleet run: request totals, availability counters, "
            "TTFT percentiles, train→serve loop state. Open record: "
            "`shed_by_class`/`shed_reasons`/`dispatch_retry_hist` "
            "dicts, the fleet SLO rollup (`fleet_slo_alerts`, "
            "`fleet_slo_budget_remaining_min`, `fleet_slo_targets`), "
            "stitch stats (`stitch_sources`, `stitch_skipped`, "
            "`stitch_balanced`, `stitch_closed_at_death`, `fleet_trace` "
            "path) and decomposition coverage (`decomp_requests`, "
            "`decomp_residual_frac_mean`) ride along.",
        fields=(
            F("requests", "int", doc="requests accepted"),
            F("requests_done", "int", doc="requests completed"),
            F("requests_shed", "int", doc="requests shed"),
            F("requests_lost", "int", doc="requests lost (should be 0)"),
            F("dispatches", "int", doc="dispatch count"),
            F("redispatches", "int", doc="re-dispatch count"),
            F("quarantines", "int", doc="replica quarantines"),
            F("rejoins", "int", doc="replica rejoins"),
            F("deaths", "int", doc="replica deaths"),
            F("restarts", "int", doc="replica restarts"),
            F("recovery_requests", "int",
              doc="requests whose arrival→first-token window overlapped "
                  "a death/quarantine/timeout, or that were "
                  "re-dispatched"),
            F("rolling_swaps", "int", doc="fully-acked rollouts only"),
            F("partial_rolls", "int", doc="rollouts with a timeout"),
            F("swap_timeouts", "int", doc="per-replica ack timeouts"),
            F("rolled_step", "int", nullable=True,
              doc="last step rolled out"),
            F("staleness_max_steps", "int", nullable=True,
              doc="max model staleness observed (steps)"),
            F("replica_swaps", "int", doc="per-replica swap count"),
            F("replica_staleness_max", "int", nullable=True,
              doc="max per-replica staleness"),
            F("tokens_per_sec", "num", doc="fleet decode throughput"),
            F("wall_s", "num", doc="fleet wall seconds"),
            F("drained_clean", "bool", doc="drain completed cleanly"),
            F("timed_out", "bool", doc="run hit its wall-clock limit"),
            F("shed_by_class", "dict", doc="sheds per SLO class"),
            F("shed_reasons", "dict", doc="sheds per reason"),
            F("dispatch_retry_hist", "dict",
              doc="dispatch-count histogram per request"),
            F("fleet_trace", "str", nullable=True,
              doc="merged Perfetto file path (`--fleet.trace`)"),
            F("decomp_requests", "int",
              doc="requests the decomposition covered"),
            F("decomp_residual_frac_mean", "num", nullable=True,
              doc="mean residual fraction of the decomposition"),
        ),
        patterns=(r"ttft_ms_p\d+(_\w+)?",)),
    # -------------------------------------------------- Fleet observatory
    Schema(
        "fleet_request",
        section="Fleet observatory (observe/fleet_trace.py, fleet/run.py)",
        doc="One per COMPLETED client request, the fleet-level twin of "
            "`serve_request` scored on client-perceived latency. This "
            "population drives the per-class summary percentiles, the "
            "exported snapshot, and the fleet SLO monitor — all three "
            "agree exactly.",
        fields=(
            F("rid", "str", required=True, doc="request id"),
            F("slo", "str", doc="SLO class"),
            F("tenant", "str", nullable=True, doc="tenant id"),
            F("ttft_ms", "num", nullable=True,
              doc="arrival→first token, across retries/failovers"),
            F("e2e_ms", "num", doc="arrival→last token absorbed"),
            F("tok_ms", "num", nullable=True, doc="mean inter-token ms"),
            F("tokens", "int", doc="tokens generated"),
            F("retries", "int", doc="re-dispatches"),
            F("redispatched", "bool", doc="request moved replicas"),
            F("t_s", "num", doc="router clock seconds"),
        )),
    Schema(
        "fleet_slo_alert",
        section="Fleet observatory (observe/fleet_trace.py, fleet/run.py)",
        doc="Fleet-level SLO burn-rate alert (same machinery and fields "
            "as the per-replica `slo_alert`, namespaced by the router's "
            "`event_prefix=\"fleet_\"`).",
        fields=_SLO_FIELDS),
    Schema(
        "fleet_slo_ok",
        section="Fleet observatory (observe/fleet_trace.py, fleet/run.py)",
        doc="Fleet-level SLO recovery transition.",
        fields=_SLO_FIELDS),
    Schema(
        "fleet_stitch",
        section="Fleet observatory (observe/fleet_trace.py, fleet/run.py)",
        doc="One per run end when `--fleet.trace` is armed (the merged "
            "Perfetto file's path rides `fleet_summary.fleet_trace`).",
        fields=(
            F("stitch_sources", "int",
              doc="router + one per replica epoch"),
            F("stitch_skipped", "int", doc="torn/missing files"),
            F("stitch_balanced", "bool", doc="all spans closed"),
            F("stitch_closed_at_death", "int",
              doc="dead-leg spans the stitcher closed at the redispatch "
                  "instant"),
            F("stitch_error", "str", doc="only when the stitch failed"),
            F("events", "int", doc="events in the merged timeline"),
        )),
    Schema(
        "fleet_decomp",
        section="Fleet observatory (observe/fleet_trace.py, fleet/run.py)",
        doc="Per-request latency decomposition read back from the "
            "merged timeline (`residual_ms` = e2e − sum of parts).",
        fields=(
            F("rid", "str", required=True, doc="request id"),
            F("gens", "list", doc="wire ids, one per dispatch leg"),
            F("e2e_ms", "num", doc="arrival→last token absorbed"),
            F("router_queue_ms", "num", doc="router arrival → dispatch"),
            F("inbox_lag_ms", "num", doc="dispatch write → feed intake"),
            F("replica_queue_ms", "num", doc="intake → admission"),
            F("prefill_ms", "num", doc="admission → first token"),
            F("decode_ms", "num", doc="first → last token"),
            F("absorb_ms", "num",
              doc="replica done → router journal-poll absorb"),
            F("residual_ms", "num", doc="e2e − sum of parts"),
        )),
    Schema(
        "fleet_snapshot",
        section="Fleet observatory (observe/fleet_trace.py, fleet/run.py)",
        open_fields=True,
        doc="The control-plane feed payload, mirrored into the JSONL "
            "whenever the `--fleet.export-path` file is atomically "
            "rewritten. Open record: per-class percentiles (EXACTLY the "
            "summary's numbers — same population, same nearest-rank "
            "percentile) ride along.",
        fields=(
            F("slots", "int", doc="aggregate decode slots"),
            F("slots_live", "int", doc="aggregate live slots"),
            F("queue_depth", "int", doc="router queue depth"),
            F("waiting", "int", doc="requests waiting"),
            F("inflight", "int", doc="requests in flight"),
            F("requests", "int", doc="requests accepted"),
            F("requests_done", "int", doc="requests completed"),
            F("requests_shed", "int", doc="requests shed"),
            F("quarantined", "int", doc="replicas quarantined now"),
            F("deaths", "int", doc="replica deaths so far"),
            F("slo", "dict", doc="SLO state (see `NESTED`)"),
            F("slo_budget_remaining_min", "num", nullable=True,
              doc="min error budget across targets"),
            F("slo_alerting", "list", doc="targets currently alerting"),
            F("replicas", "dict",
              doc="per-replica health map (see `NESTED`)"),
        ),
        patterns=(r"ttft_ms_p\d+(_\w+)?",)),
    # --------------------------------------------------- Run log (stdout)
    Schema(
        "generate", section="Run log (stdout only)", registry=False,
        doc="mode=generate output record.",
        fields=(
            F("step", "int", required=True, doc="checkpoint step sampled"),
            F("prompt", "str", doc="the prompt"),
            F("new_tokens", "list", doc="generated token ids"),
            F("beam_score", "num", doc="beam search score (beam runs)"),
            F("text", "str", doc="decoded text (when a decoder exists)"),
        )),
    Schema(
        "done", section="Run log (stdout only)", registry=False,
        doc="End-of-run stdout rollup (the registry twin is `summary`).",
        fields=(
            F("steps", "int", doc="final global step"),
            F("train_seconds", "num", doc="total train wall"),
            F("compile_seconds", "num", doc="compile wall"),
            F("steps_per_sec", "num", doc="steady-state step rate"),
            F("images_per_sec", "num", doc="steady-state item rate"),
        ),
        patterns=(r"val_\w+",)),
)

# Nested structures consumers traverse inside records and the exported
# snapshot payloads. Keyed by context name; the static consumer pass
# unions these into the readable-field universe, and RECORDS.md renders
# them so pollers know the sub-shapes too.
NESTED: Dict[str, Tuple[Field, ...]] = {
    "slo": (
        F("alerting", "list", doc="targets currently alerting"),
        F("alerts", "int", doc="alert transitions so far"),
        F("burn_fast", "dict", doc="per-target fast-window burn"),
        F("burn_slow", "dict", doc="per-target slow-window burn"),
        F("budget_remaining", "dict", doc="per-target budget remaining"),
        F("threshold_ms", "dict", doc="per-target thresholds"),
        F("targets", "list", doc="declared targets"),
    ),
    "phase_ms": (
        F("count", "int", doc="spans of this name in the run"),
        F("sum_ms", "num", doc="their self time in sum"),
        F("max_ms", "num", doc="the worst single span's self time"),
        F("max_step", "int",
          doc="decode step (this run's count) the worst span fell in"),
        F("max_at_s", "num",
          doc="seconds into the run at which the worst span ended"),
    ),
    # serve_summary.iter_ms, and serve_request.wait_ms / .decode_ms
    # (serve/scheduler.py::_ITER_KIND names each span's kind).
    "iter_ms": (
        F("admit", "num",
          doc="ms inside `tfd.serve.admit` and its children "
              "(`.prefill_launch`, `.first_token_fetch`)"),
        F("step", "num",
          doc="ms inside a decode iteration's spans: `.step_upload`, "
              "`.step_dispatch`, `.token_fetch`, `.retire`, `.drain`, "
              "and the speculative `.propose`, `.verify_*`"),
        F("other", "num",
          doc="ms inside every other span (`.poll`, which holds an "
              "idle engine's sleep, and `.tail`)"),
    ),
    "anomaly": (
        F("total", "int", doc="anomaly records so far"),
        F("counts", "dict", doc="per-detector counts"),
        F("active", "list", doc="detectors currently out of envelope"),
        F("anomalies", "int", doc="alias of total in snapshot payloads"),
        F("last", "dict", doc="most recent anomaly record"),
    ),
    "replicas": (
        F("health", "str", doc="`up` | `down` | `quarantined` | …"),
        F("epoch", "int", doc="replica epoch"),
        F("load", "num", doc="occupancy-based load score"),
        F("inflight", "int", doc="requests in flight"),
        F("done", "int", doc="requests completed"),
        F("stale_s", "num", nullable=True, doc="snapshot staleness"),
        F("reason", "str", nullable=True, doc="quarantine reason"),
        F("ckpt_step", "int", nullable=True, doc="model staleness feed"),
        F("tp_width", "int", doc="tensor-parallel width"),
        F("per_device_cache_bytes", "int", doc="per-device cache bytes"),
        F("tune_actions", "int",
          doc="autopilot knob changes on this replica — a replica "
              "self-tuning hard is one whose workload shifted"),
    ),
    # The serve journal's line records (serve/journal.py) — the
    # replay/crash-recovery contract the fleet router also tails.
    "journal-line": (
        F("e", "str", required=True,
          doc="`admit` | `tok` | `done` | `reject`"),
        F("rid", "int", required=True, doc="wire request id"),
        F("prompt", "list", doc="admit: prompt token ids"),
        F("max_new", "int", doc="admit: generation budget"),
        F("eos", "int", doc="admit: eos token id (-1 = none)"),
        F("slo", "str", doc="admit: SLO class"),
        F("tenant", "str", doc="admit: tenant id"),
        F("sess", "str", doc="admit: session id"),
        F("t", "int", doc="tok: the token id"),
        F("s", "num", doc="serve clock seconds of the write"),
    ),
    # serve.journal.fold_record's replay accumulator entries —
    # {rid: {...}} as returned by replay()/read_journal().
    "journal-replay": (
        F("req", "dict", nullable=True,
          doc="admitted request (`prompt`/`max_new`/`eos`)"),
        F("tokens", "list", doc="tokens journaled so far"),
        F("done", "bool", doc="completion record seen"),
        F("reject", "bool", doc="reject record seen"),
        F("last_s", "num", doc="serve clock of the last record"),
    ),
    # The workload file fed to serve/fleet runs (one request per
    # line; fleet/router.submit's intake contract).
    "workload": (
        F("rid", "int", required=True, doc="request id"),
        F("prompt", "list", required=True, doc="prompt token ids"),
        F("max_new", "int", doc="generation budget"),
        F("eos", "int", doc="eos token id (-1 = none)"),
        F("arrival_s", "num", doc="arrival offset from run begin"),
        F("slo", "str", doc="SLO class"),
        F("tenant", "str", doc="tenant id"),
        F("session", "str", doc="conversation id (paged prefix reuse)"),
    ),
    # Perfetto trace-file events (observe/trace.py writers;
    # fleetview/fleet_trace read them back).
    "perfetto": (
        F("traceEvents", "list", doc="top-level event array"),
        F("name", "str", doc="event/metadata name"),
        F("ph", "str", doc="phase (`X`/`b`/`e`/`i`/`M`)"),
        F("ts", "num", doc="microsecond timestamp"),
        F("dur", "num", doc="duration (complete events)"),
        F("pid", "int", doc="process lane"),
        F("tid", "int", doc="thread lane"),
        F("cat", "str", doc="category"),
        F("args", "dict", doc="event payload"),
        F("id", "any", doc="async span id"),
        F("process_death", "bool",
          doc="args flag: span closed by the stitcher at process death"),
    ),
    # observe/report.py's OWN summary document: the section keys its
    # renderer (and the bench tests) read back from summarize().
    "report": (
        F("hosts", "int", doc="hosts folded into the report"),
        F("records", "int", doc="records folded"),
        F("plan", "dict", doc="Planner section"),
        F("device_time", "list", doc="Device-time section rows"),
        F("device_time_null_records", "int", doc="unattributable rows"),
        F("recovery_counts", "dict", doc="recovery events by kind"),
        F("swap_seconds_total", "num", doc="weight-swap wall total"),
        F("mesh_changes", "int", doc="supervisor mesh changes"),
        F("mesh_change_path", "list", doc="mesh transition chain"),
        F("reshard_seconds_total", "num", doc="reshard wall total"),
        F("fleet", "dict", doc="Fleet section"),
        F("decomposition", "dict", doc="fleet decomposition rollup"),
        F("e2e_ms_p95", "num", doc="fleet e2e p95"),
        F("e2e_ms_mean", "num", doc="decomposition mean e2e"),
        F("router_queue_ms_mean", "num", doc="decomposition component"),
        F("inbox_lag_ms_mean", "num", doc="decomposition component"),
        F("replica_queue_ms_mean", "num", doc="decomposition component"),
        F("prefill_ms_mean", "num", doc="decomposition component"),
        F("decode_ms_mean", "num", doc="decomposition component"),
        F("absorb_ms_mean", "num", doc="decomposition component"),
        F("residual_ms_mean", "num", doc="decomposition residual"),
        F("residual_frac_mean", "num", doc="residual fraction"),
        F("oks", "int", doc="SLO clears"),
        F("alerts_by_target", "dict", doc="SLO alerts per target"),
        F("budget_remaining_min", "num", nullable=True,
          doc="min SLO budget remaining"),
        F("worst_burn_fast", "num", doc="worst fast-window burn"),
        F("snapshot_last", "dict", doc="last metrics_snapshot folded"),
        F("request_parts", "dict",
          doc="\"Where a request's time went\": by part of "
              "`serve_request.wait_ms`, `prefill_ms`, `decode_ms` a "
              "token gap and `admits_endured`, `{mean, p95, n}`"),
        F("mean", "num", doc="request_parts: mean over the requests"),
        F("p95", "num", doc="request_parts: nearest-rank p95"),
        F("n", "int", doc="request_parts: requests that carry the part"),
        F("tune", "dict",
          doc="Autopilot section: the run's `tune_summary` rollup plus "
              "the decision records folded per loop"),
        F("by_detector", "dict", doc="anomaly counts per detector"),
        F("postmortem_bundles", "list", doc="bundle paths seen"),
        F("worst_update_ratio", "num", doc="health: worst update ratio"),
        F("worst_update_ratio_step", "int", doc="…and its step"),
        F("grad_norm_first", "num", doc="health: first grad norm"),
        F("grad_norm_last", "num", doc="health: last grad norm"),
    ),
}

_BY_KIND: Dict[str, Schema] = {s.kind: s for s in SCHEMAS}
_TAG_NAMES = frozenset(f.name for f in COMMON_TAGS)

_TYPES = {
    "int": (int,),
    "float": (int, float),
    "num": (int, float),
    "str": (str,),
    "bool": (bool, int),
    "dict": (dict,),
    "list": (list, tuple),
    "any": (object,),
}


def schema_for(kind: str) -> Optional[Schema]:
    return _BY_KIND.get(kind)


def allowed_fields(kind: str) -> Optional[frozenset]:
    """Declared field names + common tags for ``kind`` (None if the
    kind itself is undeclared). Pattern families are NOT expanded here
    — callers match them via :func:`matches_pattern`."""
    s = _BY_KIND.get(kind)
    if s is None:
        return None
    return frozenset(s.field_names()) | _TAG_NAMES


def matches_pattern(kind: str, name: str) -> bool:
    s = _BY_KIND.get(kind)
    if s is None:
        return False
    return any(re.fullmatch(p, name) for p in s.patterns)


def consumer_universe() -> frozenset:
    """Every field name a consumer may read by literal key: all
    declared fields across kinds, the common tags, the nested
    sub-shapes, and the artifact stamp."""
    names = set(_TAG_NAMES) | set(ARTIFACT_STAMP_FIELDS) | {"kind"}
    for s in SCHEMAS:
        names.add(s.kind)  # consumers bucket counts by kind name
        names.update(s.field_names())
    for fields in NESTED.values():
        names.update(f.name for f in fields)
    return frozenset(names)


def consumer_patterns() -> Tuple[str, ...]:
    pats: List[str] = []
    for s in SCHEMAS:
        for p in s.patterns:
            if p not in pats:
                pats.append(p)
    return tuple(pats)


def validate_record(event: str, rec: dict) -> List[str]:
    """Runtime half of the contract (``MetricsRegistry(validate=True)``,
    armed under ``--check``): return a list of violations for one
    assembled record (empty = clean)."""
    s = _BY_KIND.get(event)
    if s is None:
        return [f"undeclared record kind {event!r}"]
    errors: List[str] = []
    by_name = {f.name: f for f in s.fields}
    for f in s.fields:
        if f.required and f.name not in rec and f.name not in _TAG_NAMES:
            errors.append(f"{event}: missing required field {f.name!r}")
    for name, value in rec.items():
        if name in _TAG_NAMES:
            continue
        fld = by_name.get(name)
        if fld is None:
            if matches_pattern(event, name) or s.open_fields:
                continue
            errors.append(f"{event}: undeclared field {name!r}")
            continue
        if value is None:
            if not fld.nullable:
                errors.append(
                    f"{event}: field {name!r} is null but not declared "
                    f"nullable")
            continue
        want = _TYPES.get(fld.type, (object,))
        if not isinstance(value, want) and not hasattr(value, "item"):
            errors.append(
                f"{event}: field {name!r} expected {fld.type}, got "
                f"{type(value).__name__}")
    return errors


# --------------------------------------------------------------------
# RECORDS.md rendering — the doc is generated, never hand-edited.
# --------------------------------------------------------------------

_PREAMBLE = """\
# RECORDS.md — the observe JSONL record schema

> Generated from `observe/schemas.py` — edit the schema registry, then
> run `python -m tensorflow_distributed_tpu.analysis.schema --update`.
> The schema pass (`scripts/lint.sh`) fails on drift.

Every run event flows through ONE registry (`observe/registry.py`) as
a flat JSON object per line. This file enumerates every `event=` kind
the framework emits, with field tables — the contract `observe.report`,
the calibration fitter, and any external poller read against. Summarize any stream with
`python -m tensorflow_distributed_tpu.observe.report <metrics.jsonl> [more.jsonl ...]`.

**Common tags on every record** (added by the registry):

| field | meaning |
|---|---|
"""

_CONVENTIONS = """\

Null-field convention: telemetry fields a backend cannot supply are
**explicitly `null`**, never absent — record SHAPE is stable across
platforms. Fields marked *null ok* below follow it; a `null` in any
other declared field is a producer bug (`--check` arms runtime
validation of exactly these tables via `MetricsRegistry(validate=True)`).

Open records (marked below) splat computed rollups, so producers may
add fields beyond the table — but consumers may still only read
DECLARED fields; the one-sided openness keeps the reader contract
statically checkable (`analysis/schema.py`).
"""

_EPILOGUE = """\
## Nested payload shapes

Sub-objects consumers traverse inside `metrics_snapshot` /
`fleet_snapshot` records and the `--observe.export-path` /
`--fleet.export-path` payloads:

"""

_PROVENANCE = """\
## Provenance stamp (not a registry event)

A flight-recorder bundle's meta line carries `git_sha` and
`calibration_id` (`observe.registry.artifact_stamp`): the tree and the
calibration profile the dead run was built with, which
`observe.postmortem` prints.
"""


def _md_escape(text: str) -> str:
    return text.replace("|", "\\|")


def _render_field_table(fields: Iterable[Field]) -> List[str]:
    out = ["| field | type | required | null ok | meaning |",
           "|---|---|---|---|---|"]
    for f in fields:
        out.append(
            f"| `{f.name}` | {f.type} | {'yes' if f.required else ''} | "
            f"{'yes' if f.nullable else ''} | {_md_escape(f.doc)} |")
    return out


def render_records_md() -> str:
    lines: List[str] = [_PREAMBLE.rstrip("\n")]
    for f in COMMON_TAGS:
        lines.append(f"| `{f.name}` | {_md_escape(f.doc)} |")
    lines.append(_CONVENTIONS.rstrip("\n"))
    for section, intro in _SECTIONS:
        lines.append("")
        lines.append(f"## {section}")
        if intro:
            lines.append("")
            lines.append(intro)
        for s in SCHEMAS:
            if s.section != section:
                continue
            lines.append("")
            lines.append(f"### `{s.kind}`")
            lines.append("")
            flags = []
            if s.open_fields:
                flags.append("open record")
            if not s.registry:
                flags.append("stdout only")
            if flags:
                lines.append(f"*({', '.join(flags)})* {s.doc}")
            else:
                lines.append(s.doc)
            lines.append("")
            lines.extend(_render_field_table(s.fields))
            if s.patterns:
                pats = ", ".join(f"`{p}`" for p in s.patterns)
                lines.append("")
                lines.append(f"Open field families (regex): {pats}.")
    lines.append("")
    lines.append(_EPILOGUE.rstrip("\n"))
    for name in sorted(NESTED):
        lines.append("")
        lines.append(f"### `{name}`")
        lines.append("")
        lines.extend(_render_field_table(NESTED[name]))
    lines.append("")
    lines.append(_PROVENANCE.rstrip("\n"))
    lines.append("")
    return "\n".join(lines)
