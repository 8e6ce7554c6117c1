"""Single configuration surface for the framework.

Replaces the reference's ``tf.app.flags`` block (mnist_python_m.py:49-87,
full surface in SURVEY.md Appendix A) and its role-by-editing-defaults
scheme (the only difference between mnist_python_m.py / _w1.py / _w2.py is
the default of ``job_name``/``task_index``). Here there are no roles:
every process runs the same program; multi-host identity comes from
``jax.distributed`` environment bootstrap, not from flags.

Flag mapping (reference -> here):
    data_dir                -> data_dir
    download_only           -> (dropped; zero-egress environments load
                               from disk or use --dataset=synthetic)
    task_index/job_name     -> (dropped; no ps/worker roles exist)
    ps_hosts/worker_hosts   -> coordinator/num_processes/process_id env
                               (see parallel.mesh.bootstrap)
    existing_servers        -> (dropped; no user-visible server object)
    num_gpus                -> (dropped; devices come from jax.devices())
    replicas_to_aggregate   -> mesh data-axis size (sync quorum == mesh,
                               by construction; mnist_python_m.py:62-65)
    hidden_units            -> (dead flag in the reference; dropped)
    train_steps             -> train_steps
    batch_size              -> batch_size (GLOBAL batch; the reference's
                               was per-worker, mnist_python_m.py:70,291)
    learning_rate           -> learning_rate
    sync_replicas           -> (sync is the only SPMD mode; async ps is a
                               documented non-goal, SURVEY.md N6. The
                               ps-style sync path survives only as the
                               benchmark baseline in parallel.collectives)
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
from typing import Optional, Sequence

# The latent-attention / routed-expert family (models/glm_moe_dsa.py)
# under the source ``model_type``s it is registered as: one module, one
# set of refusals, whichever name the command line gives.
LATENT_MOE_MODELS = ("glm_moe_dsa", "axk1")
# Every family that takes its sizes from ``--model-config`` (a JSON of the
# source's own config.json keys) and no preset, and is served only: name ->
# (the family as its messages call it, why it is not trained, what its
# cache is and what therefore is not implemented over it).
SOURCE_CONFIG_FAMILIES = {
    **{name: (
        "the glm_moe_dsa family",
        "latent attention and dropless routing are served, not trained: "
        "ROADMAP B",
        "glm_moe_dsa serves through the dense slot engine with its own "
        "bfloat16 latent cache: --serve.paged, --serve.spec-tokens, "
        "--serve.mesh-model and an int8 KV cache are not implemented for "
        "it") for name in LATENT_MOE_MODELS},
    "minicpm_sala": (
        "the minicpm_sala family",
        "the lightning scan has no backward here and the block selection "
        "is not trained: ROADMAP B2",
        "minicpm_sala serves through the dense slot engine with a "
        "float32 recurrent state a slot beside its bfloat16 K, V and "
        "pooled keys: --serve.paged (no paging over a state), "
        "--serve.spec-tokens (a verify cannot roll a state back without "
        "a snapshot), --serve.mesh-model and an int8 KV cache are not "
        "implemented for it"),
    "granitemoehybrid": (
        "the granitemoehybrid family",
        "the state-space scan has no backward here and dropless routing "
        "is not trained: ROADMAP B2",
        "granitemoehybrid serves through the dense slot engine with a "
        "float32 state-space state and a convolution ring a slot beside "
        "its bfloat16 K and V: --serve.paged (no paging over a state or "
        "a ring), --serve.spec-tokens (a verify cannot roll a state back "
        "without a snapshot), --serve.mesh-model and an int8 KV cache are "
        "not implemented for it"),
    "nemotron_h": (
        "the nemotron_h family",
        "the state-space scan has no backward here and dropless routing "
        "in a latent is not trained: ROADMAP B2",
        "nemotron_h serves through the dense slot engine with a float32 "
        "state-space state and a convolution ring a slot beside its "
        "bfloat16 K and V (its expert layers keep nothing): --serve.paged "
        "(no paging over a state or a ring), --serve.spec-tokens (a "
        "verify cannot roll a state back without a snapshot, and the "
        "source's next-token layers are not served), --serve.mesh-model "
        "(no exchange of routed pairs between chips that share a layer) "
        "and an int8 KV cache are not implemented for it"),
    "exaone_moe": (
        "the exaone_moe family",
        "a ring of K and V has no backward here and dropless routing is "
        "not trained: ROADMAP B",
        "exaone_moe serves through the dense slot engine with a ring of "
        "the last sliding_window rows of bfloat16 K and V a slot on its "
        "window layers beside the whole rows of its full layers: "
        "--serve.paged (no paging over a ring), --serve.spec-tokens (a "
        "verify that rejects drafted tokens cannot take them back out of a "
        "ring that has already overwritten what they displaced, and the "
        "source's next-token layer is not served), --serve.mesh-model (no "
        "exchange of routed pairs between chips that share a layer) and an "
        "int8 KV cache are not implemented for it"),
    "jamba": (
        "the jamba family",
        "the selective scan has no backward here: ROADMAP B2",
        "jamba serves through the dense slot engine with a float32 "
        "per-channel state and a convolution ring a slot beside the "
        "bfloat16 K and V of its attention layers: --serve.paged (no "
        "paging over a state or a ring), --serve.spec-tokens (a verify "
        "cannot roll a state back without a snapshot), --serve.mesh-model "
        "(the model is served whole on one chip) and an int8 KV cache are "
        "not implemented for it"),
}
SOURCE_CONFIG_MODELS = tuple(SOURCE_CONFIG_FAMILIES)


@dataclasses.dataclass
class MeshConfig:
    """Logical device-mesh shape.

    ``data`` is the data-parallel axis (the reference's worker replicas,
    mnist_python_m.py:62-65); ``model`` is tensor parallelism; ``seq`` is
    sequence/context parallelism (ring attention); ``pipe`` is pipeline
    parallelism (GPipe microbatch schedule over stage-sharded layers);
    ``expert`` is a dedicated expert-parallel axis for MoE (experts
    alias the "model" axis when it is 1 — see models/moe.py).
    A value of -1 for ``data`` means "all remaining devices".
    """

    data: int = -1
    model: int = 1
    seq: int = 1
    pipe: int = 1
    expert: int = 1

    def validate(self) -> None:
        for name in ("model", "seq", "pipe", "expert"):
            v = getattr(self, name)
            if v < 1:
                raise ValueError(f"mesh.{name} must be >= 1, got {v}")
        if self.data == 0 or self.data < -1:
            raise ValueError(f"mesh.data must be -1 or >= 1, got {self.data}")


@dataclasses.dataclass
class ObserveConfig:
    """The observe/ subsystem's knobs (see observe package docs and the
    README "Observability" section). All off by default — the loop pays
    nothing unless a sink or trace path is configured."""

    # JSONL metrics sink: one JSON object per event (step records carry
    # the step-time breakdown and throughput/MFU fields). The durable
    # artifact format; summarize with
    # ``python -m tensorflow_distributed_tpu.observe.report <path>``.
    metrics_jsonl: str = ""
    # CSV sink: step records only, buffered and written on exit with a
    # union-of-keys header (late columns like mfu still get a column).
    # Convenience format — JSONL is the lossless, crash-durable one.
    metrics_csv: str = ""
    # Chrome-trace (Perfetto-compatible) JSON of HOST phases — data
    # wait, dispatch, device wait, eval, checkpoint, restore, drain.
    # Pure Python: works even when jax.profiler is unavailable.
    # Open at https://ui.perfetto.dev or chrome://tracing.
    trace: str = ""
    # Durable trace flushing (mode=serve): rewrite the trace file at
    # every request-lifecycle edge (admission/completion/eviction)
    # instead of only on the 5s cadence, so a SIGKILLed fleet replica
    # leaves its in-flight requests' spans on disk for the stitcher
    # (observe/fleet_trace.py). The controller sets this on replicas;
    # don't arm it for a high-rate standalone serve — each flush
    # rewrites the whole buffer.
    trace_durable: bool = False
    # Per-device peak TFLOP/s for MFU. 0 = auto-detect for known TPU
    # generations (observe.mfu.PEAK_BF16_FLOPS); unknown devices omit
    # MFU rather than invent a number.
    peak_tflops: float = 0.0
    # Rolling window (steps) for the p50/p95 step-time stats.
    window: int = 200
    # In-memory record ring-buffer cap (registry + MetricLogger) so
    # multi-million-step runs don't grow host memory unboundedly.
    max_records: int = 100_000
    # Compiled-program registry (observe/device.py): every jit call
    # site registers its program's cost_analysis/memory_analysis
    # (flops, bytes accessed, peak-HBM estimate, donated bytes) plus
    # lower/compile wall time, emitted as one "compile" record per
    # program. Default on, but armed only when a sink is configured
    # (the registration pass costs one extra trace + a persistent-
    # cache-absorbed compile per program).
    programs: bool = True
    # On-device model-health telemetry (observe/health.py): per-top-
    # level-module grad norm, update-to-param ratio, and param RMS
    # computed INSIDE the jitted step, cadence-gated on device so
    # off-cadence steps pay neither the norm reductions nor any extra
    # host transfer. Emitted as per-module "health" records on the
    # log cadence.
    health: bool = False
    # Health cadence in steps. 0 = ride log_every (the usual choice:
    # the scalars travel in the metrics fetch the logger already
    # does). A nonzero value must be a multiple of log_every — the
    # host only LOOKS on the log cadence.
    health_every: int = 0
    # Optional activation-RMS taps: each transformer block sows the
    # f32 RMS of its output (TransformerConfig.health_taps) into the
    # same per-layer health records. Transformer families except
    # pipelined_lm (its stages run inside a manual shard_map).
    health_taps: bool = False
    # --- serve observatory (mode=serve; README "Serve tracing & SLO
    # monitoring"). With mode=serve, --observe.trace writes the
    # PER-REQUEST Perfetto trace (observe/serve_trace.py: one async
    # span tree per request, recovery instants, counter tracks)
    # instead of the training host-phase trace. -------------------------
    # Declared SLO targets (observe/slo.py grammar):
    # "high:ttft_p95=100ms,tok_p50=30ms;standard:ttft_p95=500ms" —
    # ";"-separated class groups, an entry with no class prefix
    # applies to every request. Arms the live burn-rate monitor:
    # slo_alert/slo_ok JSONL events + error-budget accounting.
    slo: str = ""
    # Burn-rate windows in DECODE STEPS, "fast,slow" (the 1m/10m
    # multi-window shape at ~1 step/s, on the deterministic
    # decode-step clock).
    slo_windows: str = "60,600"
    # Burn-rate alert threshold: alert when BOTH windows burn error
    # budget faster than this multiple of the sustainable rate.
    slo_burn: float = 1.0
    # Periodic one-line live status print cadence in decode steps
    # (occupancy, queue, tokens/s, per-target window percentile +
    # budget burn). 0 = the fast window's length when slo is armed,
    # off otherwise.
    slo_status_every: int = 0
    # Rolling-metrics snapshot cadence in seconds (scheduler clock):
    # each snapshot is one "metrics_snapshot" JSONL record — the
    # payload a router/fleet supervisor polls. 0 = one final snapshot
    # only when export_path is set, nothing otherwise.
    export_every: float = 0.0
    # Atomic snapshot file (tmp+rename per dump): the single file a
    # poller reads. "" = snapshots ride the JSONL sink only.
    export_path: str = ""
    # --- incident observatory (observe/anomaly.py + observe/
    # flightrec.py; README "Incident observatory") -------------------
    # Online anomaly detection: streaming detectors over the values
    # the run already fetches on its log cadence (step-time /
    # grad-norm spikes, throughput-slope degradation, loss spike /
    # plateau / non-finite; serve: TTFT spike, decode-step-time
    # spike, queue growth, slot non-finite) emitting "anomaly" JSONL
    # records with severity + evidence window. Zero new host fetches.
    anomaly: bool = False
    # Rolling-window length (in the phase's step clock) for the spike
    # detectors; also the "active" horizon the exported incident
    # state uses.
    anomaly_window: int = 64
    # Crash flight recorder: a directory for the bounded in-memory
    # ring of recent records, periodically fsync'd as an atomic
    # snapshot bundle (flight-<pid>.jsonl — what a SIGKILL leaves
    # behind) and dumped in full (postmortem-<pid>.jsonl, with thread
    # stacks) on SIGTERM / fatal exceptions; faulthandler tracebacks
    # land beside them. Render with
    # ``python -m ...observe.postmortem <bundle>``. "" = off.
    flightrec: str = ""
    # Ring capacity (records) of the flight recorder.
    flightrec_ring: int = 256
    # Snapshot cadence in records (anomaly/recovery records always
    # snapshot immediately).
    flightrec_snapshot_every: int = 50
    # --- autopilot (observe/autopilot.py; README "Autopilot") -------
    # The online controller: closes the calibrate→plan→act loop on
    # the run's own telemetry (SLO burn → admission, page-pool
    # pressure → slot cap, rolling accept rate → speculation depth,
    # plan drift → calibration refit). Every decision is an auditable
    # "tune" record; every actuation rides the scheduler's control-
    # command path between decode steps (token-identical).
    autopilot: bool = False
    # Evaluation cadence in decode steps.
    autopilot_every: int = 50
    # Consecutive on-trigger evaluations before a knob moves (the
    # confirm half of the hysteresis; deadbands are built into each
    # loop's thresholds).
    autopilot_confirm: int = 3
    # Per-knob cooldown in decode steps after an actuation.
    autopilot_cooldown: int = 200
    # Relative plan-drift tolerance before a calibration refit
    # (|drift_ratio - 1| > tol triggers loop 1).
    autopilot_drift_tol: float = 0.25
    # Comma-separated knobs the autopilot must NEVER touch:
    # calibration,slot_cap,spec_k,decode_priority,num_pages,buckets.
    autopilot_pin: str = ""
    # Where loop 1 writes the refit calibration profile (atomic JSON,
    # planner-loadable). "" = refits become advisory tune records
    # only (applied=false).
    autopilot_calibration: str = ""

    def validate(self) -> None:
        if self.health_every < 0:
            raise ValueError(
                f"observe.health_every must be >= 0, "
                f"got {self.health_every}")
        if self.health_every and not self.health:
            raise ValueError(
                "observe.health_every has no effect without "
                "observe.health; add --observe.health true")
        if self.health_taps and not self.health:
            raise ValueError(
                "observe.health_taps has no effect without "
                "observe.health; add --observe.health true")
        if self.window < 1:
            raise ValueError(
                f"observe.window must be >= 1, got {self.window}")
        if self.max_records < 1:
            raise ValueError(
                f"observe.max_records must be >= 1, "
                f"got {self.max_records}")
        if self.peak_tflops < 0:
            raise ValueError(
                f"observe.peak_tflops must be >= 0, "
                f"got {self.peak_tflops}")
        if self.trace_durable and not self.trace:
            raise ValueError(
                "observe.trace_durable has no effect without "
                "observe.trace; set a trace path (--observe.trace)")
        if self.slo:
            from tensorflow_distributed_tpu.observe.slo import (
                parse_slo)
            parse_slo(self.slo)  # grammar at config time
        from tensorflow_distributed_tpu.observe.slo import parse_windows
        parse_windows(self.slo_windows)
        if self.slo_burn <= 0:
            raise ValueError(
                f"observe.slo_burn must be > 0, got {self.slo_burn}")
        if not self.slo:
            # The burn-rate shape knobs only matter once targets are
            # declared — accepting them alone would be a silent no-op.
            if self.slo_windows != "60,600":
                raise ValueError(
                    "observe.slo_windows has no effect without "
                    "observe.slo; declare targets (--observe.slo)")
            if self.slo_burn != 1.0:
                raise ValueError(
                    "observe.slo_burn has no effect without "
                    "observe.slo; declare targets (--observe.slo)")
        if self.slo_status_every < 0:
            raise ValueError(
                f"observe.slo_status_every must be >= 0, "
                f"got {self.slo_status_every}")
        if self.export_every < 0:
            raise ValueError(
                f"observe.export_every must be >= 0, "
                f"got {self.export_every}")
        if self.anomaly_window < 8:
            raise ValueError(
                f"observe.anomaly_window must be >= 8, "
                f"got {self.anomaly_window}")
        if self.anomaly_window != 64 and not self.anomaly:
            raise ValueError(
                "observe.anomaly_window has no effect without "
                "observe.anomaly; add --observe.anomaly true")
        if self.flightrec_ring < 8:
            raise ValueError(
                f"observe.flightrec_ring must be >= 8, "
                f"got {self.flightrec_ring}")
        if self.flightrec_snapshot_every < 1:
            raise ValueError(
                f"observe.flightrec_snapshot_every must be >= 1, "
                f"got {self.flightrec_snapshot_every}")
        if not self.flightrec and (
                self.flightrec_ring != 256
                or self.flightrec_snapshot_every != 50):
            raise ValueError(
                "observe.flightrec_ring/flightrec_snapshot_every have "
                "no effect without observe.flightrec; set a bundle "
                "directory (--observe.flightrec DIR)")
        if self.autopilot_every < 1:
            raise ValueError(
                f"observe.autopilot_every must be >= 1, "
                f"got {self.autopilot_every}")
        if self.autopilot_confirm < 1:
            raise ValueError(
                f"observe.autopilot_confirm must be >= 1, "
                f"got {self.autopilot_confirm}")
        if self.autopilot_cooldown < 0:
            raise ValueError(
                f"observe.autopilot_cooldown must be >= 0, "
                f"got {self.autopilot_cooldown}")
        if self.autopilot_drift_tol <= 0:
            raise ValueError(
                f"observe.autopilot_drift_tol must be > 0, "
                f"got {self.autopilot_drift_tol}")
        if self.autopilot_pin:
            from tensorflow_distributed_tpu.observe.autopilot import (
                KNOBS)
            bad = sorted(
                {p.strip() for p in self.autopilot_pin.split(",")
                 if p.strip()} - set(KNOBS))
            if bad:
                raise ValueError(
                    f"observe.autopilot_pin: unknown knob(s) "
                    f"{', '.join(bad)} (valid: {', '.join(KNOBS)})")
        if not self.autopilot and (
                self.autopilot_every != 50
                or self.autopilot_confirm != 3
                or self.autopilot_cooldown != 200
                or self.autopilot_drift_tol != 0.25
                or self.autopilot_pin
                or self.autopilot_calibration):
            raise ValueError(
                "observe.autopilot_* knobs have no effect without "
                "observe.autopilot; add --observe.autopilot true")


@dataclasses.dataclass
class ServeConfig:
    """The serve/ subsystem's knobs (continuous-batching inference —
    see the serve package docs and the README "Serving" section).
    Active only under ``mode=serve``."""

    # Decode batch width: concurrent requests in flight. The decode
    # step is ONE compiled program over [num_slots, max_len] for the
    # life of the process; requests join/leave between steps.
    num_slots: int = 8
    # Default per-request generation budget (a request file may
    # override per request).
    max_new_tokens: int = 64
    # Prefill bucket ladder, e.g. "32,64,128" (prompts pad up to the
    # next bucket; compiled prefill programs are bounded by the ladder
    # size). "" = power-of-two ladder covering the workload's longest
    # prompt (serve/buckets.py).
    buckets: str = ""
    # Starvation bound for the decode-priority interleave: at least
    # this many decode iterations between two admissions, counted from
    # the last admission whoever waits. A request that comes due on an
    # engine that has decoded that many since it last admitted, with a
    # slot free, is admitted at once; a burst is spaced this far apart.
    decode_priority: int = 1
    # EOS token id terminating a request early (-1 = run every request
    # to its full budget).
    eos_id: int = -1
    # Request file (JSONL: {"prompt": [ids...], "max_new_tokens": n,
    # "eos_id": e, "arrival_s": t} — "text" instead of "prompt" with
    # --dataset text). "" = synthetic workload below.
    requests: str = ""
    # Synthetic workload: request count, mixed prompt lengths
    # (uniform in [min, max], seeded by --seed), open-loop arrival
    # rate in req/s (0 = the whole batch queued at t=0).
    num_requests: int = 16
    prompt_len_min: int = 8
    prompt_len_max: int = 64
    arrival_rate: float = 0.0
    # Arrival-trace shape for the synthetic workload (serve/run.py):
    # "" = uniformly spaced at arrival_rate, "poisson" = exponential
    # interarrivals, "bursty" = whole bursts land at once, "diurnal" =
    # sinusoidally modulated rate, or a .jsonl file of per-request
    # {"arrival_s": t} offsets. Non-"" shapes (except a file) need
    # arrival_rate > 0.
    trace: str = ""
    # Request journal path (serve/journal.py): admits/tokens/
    # completions append here, flushed per decode step, so a killed
    # serving process resumes at token granularity — a non-empty
    # journal at startup means RESUME (finished requests skip,
    # in-flight ones re-admit as continuations). The supervisor's
    # serve-mode restart story; "" = off.
    journal: str = ""
    # Per-request slot-retry budget: how many times one request may be
    # quarantined (NaN logits -> free the slot, re-prefill prompt +
    # good tokens) before the run halts with SlotRetryExhausted (exit
    # 2 — serve's DIVERGED equivalent; the supervisor won't hot-loop).
    slot_retries: int = 2
    # Print each streamed token as it retires (chief only).
    stream: bool = False
    # --- speculative decoding (serve/speculate.py) -----------------
    # Tokens PROPOSED per decode step (0 = off). With speculation on,
    # each step runs ONE jitted verify program that scores all
    # spec_tokens proposals against the target model in a single
    # forward over the slot's KV cache and accepts the longest
    # greedy-consistent prefix — output stays token-identical to
    # non-speculative greedy decode; the win is (accepted + 1) tokens
    # per program dispatch instead of 1.
    spec_tokens: int = 0
    # Draft model spec, e.g. "tiny" or "size=tiny,n_layers=1" — a
    # smaller model of the same transformer family proposing the
    # spec_tokens. "" = k-gram SELF-draft: proposals come from the
    # request's own token history (prompt-lookup; no second model, no
    # extra device work), which is what repetitive greedy tails make
    # cheap to predict.
    draft_config: str = ""
    # Suffix length the k-gram self-draft matches on (history lookups
    # key on the last this-many tokens).
    spec_kgram: int = 3
    # --- KV-cache storage ------------------------------------------
    # "bf16": cache rows stored in the model's compute dtype (the
    # default). "int8": per-(token, head) absmax-quantized rows with
    # f32 scales stored beside the cache (models/transformer.py's
    # kv_cache_quant path) — roughly halves HBM per slot at real head
    # dims, so num_slots can grow at a fixed budget; greedy output may
    # diverge from the bf16 cache's.
    kv_dtype: str = "bf16"  # bf16 | int8
    # --- paged KV cache + radix prefix reuse (serve/paging) --------
    # Replace the dense per-slot [max_len] KV rows with a refcounted
    # page pool + host page tables, and arm the radix prefix cache:
    # shared system prompts / few-shot headers / multi-turn sessions
    # attach cached pages instead of re-prefilling, and a slot holds
    # pages for its ACTUAL trajectory instead of reserving max_len.
    # Default OFF — the dense engine path is byte-identical to the
    # pre-paging tree (tests/test_serve_ahead.py holds dense and
    # paged streams alike to one-shot generate(), and the paged
    # engine to fewer prefill tokens on a shared-prefix trace).
    paged: bool = False
    # Tokens per page (must divide the cache length; serve/run.py
    # rounds an auto-sized --seq-len up to a multiple).
    page_size: int = 16
    # Physical pages in the pool (0 = auto: twice the dense worst
    # case — half serving, half prefix cache). Sizing it below
    # num_slots * max_len/page_size is how you trade cache headroom
    # for slots under a fixed HBM budget; admission defers under
    # pressure after LRU-evicting cached pages.
    num_pages: int = 0
    # Radix prefix cache + sessions (paged only). Off = pure paged
    # allocation with no reuse — an A/B diagnostic.
    radix: bool = True
    # Synthetic-workload multi-turn sessions: group consecutive
    # requests into conversations of this many turns — each turn's
    # prompt EXTENDS the previous turn's prompt (the client re-sends
    # the conversation so far), tagged with a shared session id.
    # Request files carry their own per-request "session" field.
    # Works on the dense engine too (turns just recompute).
    session_turns: int = 1
    # --- SLO-aware scheduling --------------------------------------
    # "fifo": arrival-order admission (the original policy). "slo":
    # class-priority admission (high > standard > batch), per-tenant
    # token quotas, and preempt-and-requeue of over-budget requests
    # (the PR-6 continuation machinery: prompt + tokens-so-far
    # re-admit, journal-compatible, token-identical by greedy
    # determinism).
    policy: str = "fifo"  # fifo | slo
    # Per-tenant decoded-token quota for policy=slo (0 = off): a
    # tenant at/over its quota is DEFERRED while an under-quota
    # request waits — requeued behind, never dropped, and still
    # served when nothing under-quota is waiting (work-conserving).
    tenant_quota: int = 0
    # Allow policy=slo to preempt a live lower-class (or over-quota)
    # request when a higher-class one has waited decode_priority
    # decode iterations with no free slot.
    preempt: bool = True
    # Synthetic-workload SLO class mix, e.g. "high:0.25,batch:0.25"
    # (remainder "standard"); "" = all standard. Request files carry
    # their own per-request "slo" field instead.
    slo_mix: str = ""
    # Synthetic-workload tenant count (requests assigned round-robin);
    # request files carry their own "tenant" field.
    tenants: int = 1
    # --- tensor-parallel serving (README "Tensor-parallel serving") -
    # Shard the replica ITSELF over a model axis: the engine's
    # programs (prefill/insert/decode/verify) build over a
    # [data=1, model=N] mesh with tp_partitioning on — attention
    # heads and MLP width shard over the axis, the slot KV cache's
    # head dim shards with them (per-device cache bytes shrink by N),
    # and GSPMD inserts the block psums. Output stays token-identical
    # to the single-device engine (greedy determinism;
    # tests/test_serve_tp.py holds it). Needs n_heads (and n_kv_heads under GQA)
    # divisible by N and N local devices — validated in serve/run.py
    # where both are known. 1 = the single-device engine, unchanged.
    # NOTE: this is deliberately NOT --mesh.model — the train mesh
    # flags keep their pure-data-mesh contract under mode=serve; the
    # serve mesh is the engine's own.
    mesh_model: int = 1
    # --- fleet serving (fleet/; README "Fleet serving") ------------
    # Inbox file this replica TAILS for requests and control commands
    # (fleet/replica.py line protocol): with an inbox the scheduler
    # serves an OPEN-ENDED stream — no synthetic workload, requests
    # appended by the fleet router, swap/drain/cancel commands from
    # the controller — until a drain lands and the engine runs dry.
    # Requires an explicit --seq-len (no workload to auto-size from)
    # and --serve.journal (the journal is the router's data plane).
    inbox: str = ""
    # HBM budget (GiB) the paged auto-sizing caps --serve.num-pages
    # against (0 = uncapped): pages = (budget - params - programs) /
    # page_bytes. Only meaningful with --serve.paged and num_pages=0.
    hbm_budget_gb: float = 0.0

    def validate(self) -> None:
        if self.num_slots < 1:
            raise ValueError(
                f"serve.num_slots must be >= 1, got {self.num_slots}")
        if self.max_new_tokens < 1:
            raise ValueError(
                f"serve.max_new_tokens must be >= 1, "
                f"got {self.max_new_tokens}")
        if self.decode_priority < 1:
            raise ValueError(
                f"serve.decode_priority must be >= 1, "
                f"got {self.decode_priority}")
        if self.buckets:
            from tensorflow_distributed_tpu.serve.buckets import (
                parse_buckets)
            parse_buckets(self.buckets)  # syntax at config time
        if not self.requests:
            if self.num_requests < 1:
                raise ValueError(
                    f"serve.num_requests must be >= 1, "
                    f"got {self.num_requests}")
            if not 1 <= self.prompt_len_min <= self.prompt_len_max:
                raise ValueError(
                    f"serve prompt length range [{self.prompt_len_min},"
                    f" {self.prompt_len_max}] must satisfy 1 <= min "
                    f"<= max")
        if self.arrival_rate < 0:
            raise ValueError(
                f"serve.arrival_rate must be >= 0, "
                f"got {self.arrival_rate}")
        if self.slot_retries < 0:
            raise ValueError(
                f"serve.slot_retries must be >= 0, "
                f"got {self.slot_retries}")
        if self.trace and not self.trace.endswith(".jsonl"):
            if self.trace not in ("poisson", "bursty", "diurnal"):
                raise ValueError(
                    f"unknown serve.trace {self.trace!r}; have "
                    f"('poisson', 'bursty', 'diurnal') or a .jsonl "
                    f"file of arrival offsets")
            if not self.arrival_rate:
                raise ValueError(
                    f"serve.trace={self.trace!r} shapes the arrival "
                    f"process around serve.arrival_rate — set a rate "
                    f"> 0")
        if self.trace and self.requests:
            raise ValueError(
                "serve.trace shapes the SYNTHETIC workload's "
                "arrivals; a request file carries its own arrival_s "
                "— drop one of the flags")
        if self.spec_tokens < 0:
            raise ValueError(
                f"serve.spec_tokens must be >= 0, "
                f"got {self.spec_tokens}")
        if self.draft_config and not self.spec_tokens:
            raise ValueError(
                "serve.draft_config proposes serve.spec_tokens tokens "
                "per step; add --serve.spec-tokens > 0")
        if self.spec_kgram < 1:
            raise ValueError(
                f"serve.spec_kgram must be >= 1, "
                f"got {self.spec_kgram}")
        if self.kv_dtype not in ("bf16", "int8"):
            raise ValueError(
                f"unknown serve.kv_dtype {self.kv_dtype!r}; have "
                f"('bf16', 'int8')")
        if self.page_size < 1:
            raise ValueError(
                f"serve.page_size must be >= 1, got {self.page_size}")
        if self.num_pages < 0:
            raise ValueError(
                f"serve.num_pages must be >= 0, got {self.num_pages}")
        if not self.paged:
            # The paged knobs silently doing nothing would be a trap —
            # reject them without their parent (the repo-wide
            # no-effect-without-parent rule).
            if self.page_size != 16:
                raise ValueError(
                    "serve.page_size shapes the paged KV cache; add "
                    "--serve.paged")
            if self.num_pages:
                raise ValueError(
                    "serve.num_pages sizes the paged KV pool; add "
                    "--serve.paged")
            if not self.radix:
                raise ValueError(
                    "serve.radix toggles the paged engine's prefix "
                    "cache; add --serve.paged")
        if self.session_turns < 1:
            raise ValueError(
                f"serve.session_turns must be >= 1, "
                f"got {self.session_turns}")
        if self.session_turns > 1 and self.requests:
            raise ValueError(
                "serve.session_turns shapes the SYNTHETIC workload; a "
                "request file carries its own per-request session "
                "field — drop one of the flags")
        if self.policy not in ("fifo", "slo"):
            raise ValueError(
                f"unknown serve.policy {self.policy!r}; have "
                f"('fifo', 'slo')")
        if self.tenant_quota < 0:
            raise ValueError(
                f"serve.tenant_quota must be >= 0, "
                f"got {self.tenant_quota}")
        if self.tenant_quota and self.policy != "slo":
            raise ValueError(
                "serve.tenant_quota is enforced by the SLO scheduler; "
                "add --serve.policy slo")
        if (self.tenant_quota and not self.requests
                and self.tenants <= 1):
            raise ValueError(
                "serve.tenant_quota needs tenants to meter: the "
                "synthetic workload assigns tenants only when "
                "--serve.tenants > 1 (request files carry their own "
                "per-request tenant fields) — without them the quota "
                "silently never fires")
        if self.slo_mix:
            if self.policy != "slo":
                raise ValueError(
                    "serve.slo_mix assigns classes the SLO scheduler "
                    "acts on; add --serve.policy slo")
            if self.requests:
                raise ValueError(
                    "serve.slo_mix shapes the SYNTHETIC workload; a "
                    "request file carries its own per-request slo "
                    "field — drop one of the flags")
            from tensorflow_distributed_tpu.serve.scheduler import (
                parse_slo_mix)
            parse_slo_mix(self.slo_mix)  # syntax at config time
        if self.hbm_budget_gb < 0:
            raise ValueError(
                f"serve.hbm_budget_gb must be >= 0, "
                f"got {self.hbm_budget_gb}")
        if self.hbm_budget_gb and not self.paged:
            raise ValueError(
                "serve.hbm_budget_gb caps the paged KV pool's "
                "auto-sizing; add --serve.paged")
        if self.hbm_budget_gb and self.num_pages:
            raise ValueError(
                "serve.hbm_budget_gb sizes num_pages automatically; "
                "an explicit --serve.num-pages already pins the pool "
                "— drop one of the flags")
        if self.inbox:
            # Inbox mode replaces the workload entirely — knobs that
            # shape a synthetic/file workload would silently do
            # nothing (the repo-wide no-effect rule).
            if self.requests:
                raise ValueError(
                    "serve.inbox streams requests from the fleet "
                    "router; a request file is a fixed workload — "
                    "drop one of the flags")
            if self.trace or self.slo_mix or self.session_turns > 1:
                raise ValueError(
                    "serve.trace/slo_mix/session_turns shape the "
                    "SYNTHETIC workload; with serve.inbox the router "
                    "owns arrivals, classes, and sessions — drop "
                    "them")
            if not self.journal:
                raise ValueError(
                    "serve.inbox needs --serve.journal: the journal "
                    "is how the fleet router reads tokens back and "
                    "re-dispatches after a replica death")
        if self.mesh_model < 1:
            raise ValueError(
                f"serve.mesh_model must be >= 1, "
                f"got {self.mesh_model}")
        if self.tenants < 1:
            raise ValueError(
                f"serve.tenants must be >= 1, got {self.tenants}")


@dataclasses.dataclass
class ResilienceConfig:
    """The resilience/ subsystem's knobs (see resilience package docs
    and the README "Fault tolerance" section). All off by default —
    the loop's hot path pays nothing unless a policy, watchdog, or
    fault plan is configured. Checkpoint-save retries are the one
    always-on piece (they cost nothing until a save actually fails)."""

    # Deterministic fault-injection plan, e.g.
    # "nan_grad@40,ckpt_io_fail@80,data_stall@120:5s,sigterm@200" —
    # comma-separated kind@step[:arg] events (resilience/faults.py).
    # Kinds: nan_grad (NaN-poison that step's batch -> genuinely
    # non-finite loss AND gradients), ckpt_io_fail (:N failures,
    # default 1, injected into the next checkpoint save's write path),
    # data_stall (:duration, e.g. 5s, slept inside the batch fetch so
    # the watchdog sees it), sigterm / sigkill (self-signal when the
    # step is dispatched; first-leg only, so a supervised restart
    # terminates), device_loss (:N lost chips, default 1 — writes the
    # device-mask file under checkpoint_dir and hard-kills the
    # process; under resilience.supervisor --elastic the restart
    # degrades onto the best mesh that fits the survivors and
    # CONTINUES via the resharded restore). Under mode=serve the step
    # key counts DECODE steps
    # and the kinds are decode_stall (:duration, slept inside the
    # decode watchdog's window), slot_nan (:slot, NaN-poisons one
    # slot's KV row -> quarantine + re-prefill of only that slot),
    # reload (live weight swap from --checkpoint-dir), plus sigterm/
    # sigkill. Test/drill harness — empty in production runs.
    fault_plan: str = ""
    # Non-finite-loss policy, checked per step on the metrics the loop
    # already retires: "off" (legacy: train on, unless the separate
    # halt_on_nonfinite cadence check fires), "halt" (flush saves,
    # raise), "skip_batch" (the jitted step discards that batch's
    # update on device — params/opt state/EMA keep their pre-step
    # values, the step counter still advances — and the host charges
    # the skip budget), "rewind" (restore the newest verifiable
    # checkpoint in-process and re-enter the loop from there).
    nonfinite: str = "off"  # off | halt | skip_batch | rewind
    # Recovery budgets: exceeding either halts with a clear error —
    # unbounded skipping/rewinding would loop forever on a truly
    # diverged run.
    max_skips: int = 3
    max_rewinds: int = 1
    # Loss-spike detection over a rolling window: a FINITE loss >
    # spike_factor x the window median counts as a divergence event
    # (emitted always; under nonfinite=rewind it also triggers a
    # budgeted rewind — a skip can't help, the update already
    # applied). 0 = off.
    spike_window: int = 0
    spike_factor: float = 10.0
    # Watchdog timeouts (seconds; 0 = off): next-batch fetch and
    # device sync. A breach raises StallError — a diagnosable failure
    # instead of a silent hang. Multi-host caveat: always raise, never
    # unilaterally skip (an uncoordinated skip desyncs the SPMD
    # programs; resilience/watchdog.py).
    data_timeout_s: float = 0.0
    sync_timeout_s: float = 0.0
    # Capped-exponential-backoff retries around checkpoint save I/O
    # (train/checkpoint.py::set_io_policy): transient FS errors retry
    # instead of killing the run.
    save_retries: int = 2
    save_retry_backoff_s: float = 0.05

    def validate(self) -> None:
        if self.nonfinite not in ("off", "halt", "skip_batch",
                                  "rewind"):
            raise ValueError(
                f"unknown resilience.nonfinite {self.nonfinite!r}; "
                f"have ('off', 'halt', 'skip_batch', 'rewind')")
        if self.max_skips < 0 or self.max_rewinds < 0:
            raise ValueError(
                "resilience.max_skips/max_rewinds must be >= 0")
        if self.spike_window < 0:
            raise ValueError(
                f"resilience.spike_window must be >= 0, "
                f"got {self.spike_window}")
        if self.spike_window and self.spike_factor <= 1.0:
            raise ValueError(
                f"resilience.spike_factor must be > 1, "
                f"got {self.spike_factor}")
        if self.data_timeout_s < 0 or self.sync_timeout_s < 0:
            raise ValueError(
                "resilience timeouts must be >= 0 (0 disables)")
        if self.save_retries < 0 or self.save_retry_backoff_s < 0:
            raise ValueError(
                "resilience.save_retries/save_retry_backoff_s must "
                "be >= 0")
        if self.fault_plan:
            # Parse for syntax errors at config time, not mid-run.
            from tensorflow_distributed_tpu.resilience.faults import (
                parse_fault_plan)
            parse_fault_plan(self.fault_plan)


@dataclasses.dataclass
class TrainConfig:
    """Everything needed to run one training job, any model, any mesh."""

    # --- model -----------------------------------------------------------
    model: str = "mnist_cnn"  # mnist_cnn | resnet20 | resnet50 | bert_mlm
    # "reference" reproduces tf.random_normal stddev-1.0 init
    # (mnist_python_m.py:185-196); "improved" (default) uses He/Glorot and
    # is what reaches the >=99% target the reference never hits
    # (performance:6 tops out at 95.75%).
    init_scheme: str = "improved"  # improved | reference
    # Transformer-family size preset ("base"/"small"/"tiny"); empty =
    # the family's default. Ignored by models without presets.
    model_size: str = ""
    # A JSON file of the SOURCE's own config.json keys (hidden_size,
    # kv_lora_rank, mlp_layer_types, ... plus the experts and layers
    # held here), optionally ``path#dotted.key`` for an object nested in
    # it. The one place the sizes of a SOURCE_CONFIG_MODELS family come
    # in (models/glm_moe_dsa.py, models/minicpm_sala.py,
    # models/granitemoehybrid.py, models/nemotron_h.py,
    # models/exaone_moe.py and models/jamba.py, seven names over six
    # modules, build their per-layer lists from it);
    # other families take presets and flags.
    model_config: str = ""
    # Position encoding for the transformer families (pipelined_lm
    # included): "learned" (additive table, GPT-2/BERT) or "rope"
    # (rotary — relative positions, composes with flash/ring attention
    # and the pipeline schedules). Ignored by the vision models.
    pos_emb: str = "learned"  # learned | rope
    # RoPE base frequency; raising it (e.g. 500000, the Llama-3 value)
    # slows the rotation so longer contexts stay resolvable — the knob
    # context-window extension actually turns.
    rope_theta: float = 10000.0
    # Share the input embedding as the LM output projection (GPT-2
    # style weight tying). Transformer families only.
    tie_embeddings: bool = False
    # Grouped-query attention: K/V head count (0 = same as n_heads,
    # standard MHA; 1 = MQA). Shrinks the decode KV cache by
    # n_heads/n_kv_heads. Transformer families only.
    n_kv_heads: int = 0
    # Sliding-window attention (Mistral-style): attend to the last
    # W positions only (0 = full causal). Causal LM families; rides
    # the flash kernel's block-skip (O(L*W) compute) and masks the
    # decode cache to the window. Requires mesh.seq == 1 (the ring
    # schedule is not windowed; at W << L the window replaces it).
    attn_window: int = 0
    # Decode KV-cache storage: "none" or "int8" (per-(token, head)
    # absmax quantization, exact scale-adjusted int8 attend —
    # models/transformer.py). Generation/eval path only.
    kv_cache_quant: str = "none"
    # MLP nonlinearity for the transformer families: "gelu" (GPT-2/
    # BERT) or "swiglu" (gated, Llama-style).
    mlp_variant: str = "gelu"  # gelu | swiglu
    # Megatron vocab-parallel embedding: shard the token table's vocab
    # dim (and the tied logits) over mesh.model. Worth it at real
    # vocabs (50257 x 768 + Adam slots ~ 460 MB/replica); pointless at
    # mesh.model == 1. Not available for pipelined_lm (its shell params
    # carry no TP metadata).
    shard_vocab: bool = False
    # Fused (vocab-chunked) head+loss for the LM families: > 0 runs the
    # lm_head matmul INSIDE the training loss, ``ce_chunk`` vocab
    # columns at a time with online-softmax statistics, so the full
    # [B, L, V] logits (~825 MB bf16 at GPT-2-small train shapes) are
    # never materialized in forward or backward (ops/fused_ce.py).
    # 0 = dense path. Train-side only (eval keeps dense logits).
    # Composes with pipelined_lm (the 1F1B last stage runs the fused
    # loss inside its scheduled head vjp, train/pipeline_step.py) and
    # with tensor parallelism / shard_vocab (at mesh.model > 1 the
    # scan impl switches to the Megatron vocab-parallel form: each TP
    # rank scans its own head shard, stats combine with pmax/psum).
    # 8192 is a good first value at vocab 50257.
    ce_chunk: int = 0
    # Fused-loss formulation when ce_chunk > 0: "scan" (lax.scan over
    # vocab chunks — all shapes, SPMD-transparent) or "kernel" (the
    # Pallas flash-CE triple, ops/fused_ce_kernel.py — logits blocks
    # live only in VMEM; per-device token count and d_model must be
    # multiples of 8, tokens must divide the 256 block when above it —
    # kernel_supported() is the authority).
    ce_impl: str = "scan"  # scan | kernel
    # Block normalization: "layernorm" or "rmsnorm" (scale-only,
    # Llama-style). Transformer families only.
    norm: str = "layernorm"  # layernorm | rmsnorm
    dropout_rate: float = 0.25  # reference keep_prob 0.75 fed as literal
    # (mnist_python_m.py:292, mnist_single.py:112)

    # --- data ------------------------------------------------------------
    # mnist | synthetic | cifar10 | cifar10_synthetic | imagenet_synthetic
    # (see data.load_dataset dispatch). The LM families
    # (bert_mlm/gpt_lm/moe_lm/pipelined_lm) default to synthetic token
    # data regardless of this field, EXCEPT dataset="text": byte-level
    # causal LM over the local file named by --data-dir (vocab = the
    # 256 byte values; no tokenizer, no egress).
    dataset: str = "mnist"
    data_dir: str = "/tmp/mnist-data"  # reference default, mnist_python_m.py:50
    # Rows carved off the head of the real train split for validation
    # (the reference hardcodes 5000, mnist_python_m.py via
    # input_data.read_data_sets). Small local datasets (e.g. the
    # committed idx fixture) need a smaller split. mnist/cifar10 only.
    validation_size: int = 5000
    # Sequence length for the LM families: the data stream's window AND
    # the model's max_len. 0 = the family default (128). This is the
    # long-context knob: --seq-len 8192 --mesh.seq 8 trains with ring
    # attention over the seq axis (pair with --remat dots and
    # --pos-emb rope --rope-theta 500000 at real length). Ignored by
    # the vision models.
    seq_len: int = 0
    # Vocabulary of the SYNTHETIC LM token streams (and the model built
    # over them). 0 = the default (64). dataset="text" ignores it (the
    # byte corpus pins vocab to 256).
    synthetic_vocab: int = 0
    # dataset='text' tokenization: "byte" (vocab = the 256 byte
    # values, works on any file) or "bpe" (byte-level BPE trained ON
    # the corpus — no downloads; cached next to the file). The model
    # vocab follows the tokenizer (data/lm.py::text_clm).
    text_tokenizer: str = "byte"  # byte | bpe
    # Target merge count for text_tokenizer='bpe' (uint16 storage
    # caps it at 65536; tiny corpora may train fewer).
    bpe_vocab_size: int = 8192
    # Global batch. Reference: 128 per worker x 2 workers = 256 global
    # (mnist_python_m.py:70, replicas_to_aggregate :62-65).
    batch_size: int = 256
    shuffle_seed: int = 0
    # "u8_native": keep images as uint8 and gather batches with the C++
    # threaded gather (data/u8.py; falls back to numpy without a
    # toolchain). Same deterministic sample stream either way; "numpy"
    # stays the default so results don't depend on the host toolchain.
    data_backend: str = "numpy"  # numpy | u8_native

    # --- optimization ----------------------------------------------------
    # adam (reference: AdamOptimizer, mnist_python_m.py:208; becomes
    # adamw when weight_decay > 0) | sgd | adafactor (factored second
    # moments — O(rows+cols) state for the big-model families)
    optimizer: str = "adam"
    learning_rate: float = 1e-3
    lr_schedule: str = "constant"  # constant | cosine | warmup_cosine
    warmup_steps: int = 0
    weight_decay: float = 0.0
    grad_clip_norm: Optional[float] = None
    # Standard (1-eps) one-hot + eps/V uniform target mixture, applied
    # to every family's cross-entropy (including through the 1F1B
    # pipeline's loss head). 0 = off.
    label_smoothing: float = 0.0
    # Polyak/EMA weight averaging: eval (and mode=eval) runs on the
    # exponential moving average of the params, updated every step
    # with this decay. 0 = off. Costs one extra param-sized buffer
    # (sharded like the params — 1/data per device under FSDP).
    ema_decay: float = 0.0
    # > 1: split each global batch into this many microbatches and
    # accumulate the mean gradient before the (single) optimizer update
    # — 1/A the activation memory, same math (train.step).
    grad_accum_steps: int = 1
    train_steps: int = 500
    # bfloat16 matmuls keep the MXU fed; params/optimizer stay f32.
    compute_dtype: str = "bfloat16"  # bfloat16 | float32

    # --- MoE (transformer families only) ---------------------------------
    # > 0 overrides the family's expert count (moe_lm defaults to 4;
    # gpt_lm/bert_mlm/pipelined_lm default dense). Any transformer
    # family with experts trains with the MoE objective.
    moe_experts: int = 0
    # Switch-Transformer-style load-balancing coefficient.
    moe_aux_weight: float = 0.01
    # ST-MoE router z-loss coefficient (0 = off).
    moe_zloss_weight: float = 0.0
    # Experts each token routes to (1 = Switch-style, 2 = GShard-style).
    moe_top_k: int = 2
    # Per-expert buffer slack over the perfectly-balanced load; each
    # expert holds ceil(capacity_factor * top_k * tokens / experts)
    # slots (models/moe.py) and assignments past that are dropped (the
    # dropped fraction is a train metric).
    moe_capacity_factor: float = 1.25
    # Routing-group length for MoE layers: 0 routes the whole
    # sequence as one group; S' > 0 routes independent contiguous
    # chunks of S' tokens, bounding the dense dispatch tensors to
    # O(S'^2) per chunk (models/moe.py scale envelope).
    moe_group_len: int = 0
    # MoE token movement: "dense" one-hot dispatch/combine einsums
    # (GShard; the EP-proven layout) or "scatter" slot scatter/
    # gather (no one-hot tensors, no O(E*C)-per-token dispatch
    # FLOPs; models/moe.py).
    moe_dispatch: str = "dense"

    # --- mesh / parallelism ---------------------------------------------
    # "auto": run the cost-model auto-layout planner (analysis/planner)
    # before the mesh is built — every valid mesh factorization x
    # parallelism strategy for this model/device-count/batch is scored
    # by AOT-compiling the REAL train step (no execution), and the
    # winner's --mesh.* axes + --param-partition (+ pipelined
    # microbatches) replace the defaults. The choice is emitted as a
    # "plan" JSONL record through observe so it is auditable. "" =
    # the explicit mesh below (the default).
    plan: str = ""  # "" | auto
    # Per-device HBM budget (GB) the planner marks candidates
    # infeasible against. 0 = the device's own memory_stats limit
    # when it reports one (TPUs do; CPU hosts don't -> no budget).
    plan_hbm_budget_gb: float = 0.0
    # Calibration profile path (analysis/planner/calibrate.py writes
    # it; platform/device-kind tagged, git-sha stamped): its MEASURED
    # effective rates replace the GENERIC_HW/TPU-table peaks in the
    # planner roofline (--plan auto) and in the device-time
    # predicted-vs-measured join (--profile-dir). "" = table rates.
    plan_calibration: str = ""
    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)
    # "fsdp": ZeRO-3-style sharding of params + optimizer slots over
    # the data axis (parallel.sharding.param_sharding) — memory per
    # device drops ~1/data for the large tensors; GSPMD inserts the
    # all-gather/reduce-scatter pair. "zero1": params stay replicated
    # (no per-use gathers), only the optimizer slots shard — the usual
    # best deal when params fit but Adam doubles don't. Both compose
    # with tensor/expert annotations (only still-unsharded dims are
    # taken). "replicated" (default) matches the reference's
    # every-worker-has-all-weights layout, minus its per-step ps
    # pull/push.
    param_partition: str = "replicated"  # replicated | zero1 | fsdp
    # Gradient-sync formulation (parallel/overlap.py; README
    # "Gradient-sync overlap"). "implicit" (default): GSPMD inserts
    # the allreduce — the serial psum tail. "overlap": the grad tree
    # is bucketed, each bucket reduce-scattered over the data axis as
    # its backward contribution completes, the ZeRO-1 sharded
    # optimizer update runs per bucket on each device's shard, and
    # updated params are all-gathered bucketed — XLA's latency-hiding
    # scheduler interleaves the explicit collectives with remaining
    # compute instead of paying them serially. Requires
    # param_partition=zero1 (the sharded update runs against zero1's
    # slot layout), a pure-data mesh with data > 1, an elementwise
    # optimizer (adam/sgd), and a non-pipelined family. "serial" is
    # the explicit monolithic-psum baseline overlap is held
    # bit-identical to (tests/test_overlap.py; requires
    # param_partition=replicated).
    grad_sync: str = "implicit"  # implicit | serial | overlap
    # Bucket bound (MiB) for grad_sync=overlap: leaves pack into
    # dtype-keyed buckets of at most this size, one fused
    # reduce-scatter + one fused all-gather per bucket. None = the
    # path's default (parallel.overlap.DEFAULT_BUCKET_BYTES, 4 MiB);
    # a sentinel rather than the literal so ANY explicit value without
    # --grad-sync overlap is rejected, not just non-default ones.
    grad_sync_bucket_mb: Optional[float] = None
    # Remat (jax.checkpoint) policy for big models: none | full | dots
    remat: str = "none"
    # Pipeline schedule for model=pipelined_lm: "1f1b" (default —
    # hand-scheduled backward interleaved with forward: per-stage
    # state O(S) AND lax.cond-skipped bubbles, measured 2.1x faster
    # than gpipe at S=4/M=4; train.pipeline_step) or "gpipe" (AD
    # through the forward schedule; per-stage residuals grow O(M);
    # composes with grad_accum_steps, which 1f1b subsumes).
    pipeline_schedule: str = "1f1b"
    # Microbatches per pipeline step (M): batch_size % M == 0 and
    # M >= mesh.pipe. More microbatches shrink the bubble,
    # (S-1)/(M+S-1) for gpipe (parallel.pipeline.bubble_fraction).
    pipeline_microbatches: int = 4
    # 1F1B backward strategy: "recompute" (stash stage inputs, re-run
    # the stage forward at the backward tick — minimal memory) or
    # "stash" (stash vjp residuals at the forward tick — no recompute,
    # ~4/3 fewer stage FLOPs; costs D=min(2*pipe, M) residual copies
    # per stage). parallel.pipeline.pipeline_value_and_grad.
    pipeline_backward: str = "recompute"
    # Interleaved (virtual-stage) layout, V > 1: each device owns V
    # depth chunks of n_layers/(pipe*V) layers (Megatron's interleaved
    # assignment, [S, V, lps] stacking). Correctness-complete for both
    # schedules (1f1b: the single-scan interleaved schedule; gpipe/
    # eval: V chained pipeline passes); the uniform-tick bubble math
    # is analyzed in parallel.pipeline.bubble_fraction. recompute
    # backward only.
    pipeline_virtual_stages: int = 1

    # The runnable async-family mode (reference: sync_replicas=False,
    # mnist_python_m.py:208,247-253; SURVEY N6): 1 = synchronous data
    # parallelism (default — psum every step). H > 1 = local SGD:
    # each data replica takes H optimizer steps on its own shard
    # with NO gradient sync, then replicas pmean their params — the
    # divergence-for-communication trade async-ps actually makes,
    # expressed SPMD-native (train/local_sgd.py; exact sync-DP
    # equivalence at H=1+SGD is a test). Pure-DP meshes, no EMA/
    # grad-accum/ZeRO, models without mutable extra state.
    param_sync_every: int = 1

    # --- eval / logging --------------------------------------------------
    eval_every: int = 100
    eval_batch_size: int = 1000  # reference validates 5x1000
    # (mnist_python_m.py:309-320)
    log_every: int = 10  # reference logs loss every 10 steps
    # (mnist_single.py:113-116)
    # Report the pre-clip global gradient norm as a per-step metric
    # (one fused on-device reduction; the standard divergence signal).
    log_grad_norm: bool = False
    # Raise at the next log point whose loss is NaN/inf instead of
    # silently training on garbage (checked host-side on the metrics
    # fetch the logger already does — zero extra device syncs).
    halt_on_nonfinite: bool = False

    # --- checkpoint ------------------------------------------------------
    # Unlike the reference, which checkpoints to a throwaway
    # tempfile.mkdtemp() making resume impossible (mnist_python_m.py:236),
    # this is a durable path; empty string disables checkpointing.
    checkpoint_dir: str = ""
    checkpoint_every: int = 200
    resume: bool = False
    keep_checkpoints: int = 3
    # Background-thread serialization/writes (the reference Supervisor's
    # background saver, mnist_python_m.py:245): the device->host
    # snapshot stays in-loop, the disk work overlaps training. The
    # loop flushes the writer (ckpt.wait) before returning.
    checkpoint_async: bool = False
    # "native" (flax msgpack, chief-only atomic writes after a
    # collective host fetch) or "orbax" (sharded OCDBT saves: every
    # process writes/reads ITS OWN shards, no allgather — the scale
    # path train/checkpoint.py's docstring documents). --resume
    # auto-detects the on-disk format either way.
    checkpoint_backend: str = "native"

    # --- profiling -------------------------------------------------------
    # Non-empty: the chief captures a jax.profiler trace of steps
    # [profile_start_step, profile_start_step + profile_num_steps) into
    # this dir (TensorBoard/Perfetto XPlane). The reference's only
    # "profiler" was wall-clock prints (SURVEY.md §5).
    profile_dir: str = ""
    profile_start_step: int = 10
    profile_num_steps: int = 5

    # --- observability ---------------------------------------------------
    # Structured metrics/trace/goodput (observe/ package). CLI flags:
    # --observe.metrics-jsonl, --observe.trace, --observe.peak-tflops...
    observe: ObserveConfig = dataclasses.field(
        default_factory=ObserveConfig)

    # --- resilience ------------------------------------------------------
    # Fault-tolerance policies and drills (resilience/ package). CLI
    # flags: --resilience.nonfinite, --resilience.fault-plan,
    # --resilience.data-timeout-s...
    resilience: ResilienceConfig = dataclasses.field(
        default_factory=ResilienceConfig)

    # --- serving ---------------------------------------------------------
    # Continuous-batching inference (serve/ package; active under
    # mode=serve). CLI flags: --serve.num-slots, --serve.buckets,
    # --serve.decode-priority, --serve.requests...
    serve: ServeConfig = dataclasses.field(default_factory=ServeConfig)

    # --- static analysis / runtime checks --------------------------------
    # graftcheck's runtime mode (analysis/runtime.py): the inner train/
    # decode loops run under jax.transfer_guard("disallow") — any
    # IMPLICIT host<->device transfer raises at its source line instead
    # of silently serializing the pipeline every step — and the
    # sharding contract (layouts declared at state/cache creation vs
    # actual leaf shardings) is asserted after the first step. The
    # static layers are the CLI cousins:
    #   python -m tensorflow_distributed_tpu.analysis.lint
    #   python -m tensorflow_distributed_tpu.analysis.jaxprcheck
    # Costs nothing when off.
    check: bool = False

    # --- misc ------------------------------------------------------------
    seed: int = 0
    # "eval": restore the latest checkpoint from checkpoint_dir and run
    # only the validation pass (train.loop.evaluate_only) — the
    # reference's validation loop without its mandatory training
    # prelude; "generate" restores a checkpoint and continues a prompt
    # (causal LM families; train/loop.py::generate_only); "serve"
    # drives the continuous-batching inference engine over a request
    # workload (serve/run.py; checkpoint optional — fresh-init params
    # serve as a load-testing mode). "train" (default) is the full
    # loop.
    mode: str = "train"  # train | eval | generate | serve

    # --- mode=generate ---------------------------------------------------
    # The prompt: for dataset=text, a string run through the SAME
    # tokenizer as training (data/lm.py::text_codec); otherwise
    # comma-separated token ids (synthetic-stream models have no
    # text vocabulary).
    prompt: str = ""
    max_new_tokens: int = 64
    # 0 = greedy; > 0 samples (optionally truncated by gen_top_k /
    # nucleus gen_top_p — models/generate.py).
    gen_temperature: float = 0.0
    gen_top_k: int = 0
    gen_top_p: float = 1.0
    # > 1: beam search (deterministic; excludes gen_temperature > 0).
    num_beams: int = 1

    def _explicit_sync_knob_conflict(self) -> Optional[str]:
        """First training knob the explicit grad-sync step (serial or
        overlap; parallel/overlap.py) cannot compose with, as the
        message validate raises — None when compatible."""
        if self.grad_accum_steps > 1:
            return ("grad_sync != implicit has no microbatch scan; "
                    "drop grad_accum_steps or use the implicit step")
        if self.param_sync_every > 1:
            return ("grad_sync != implicit does not compose with "
                    "param_sync_every > 1 (local SGD has its own sync "
                    "protocol)")
        # grad_clip_norm COMPOSES: both explicit modes clip by the
        # SAME psum-reconstructed global norm (block sums-of-squares,
        # one scalar psum) before the elementwise update — the optax
        # chain clip is omitted for explicit runs (train/optim.py),
        # since inside the shard_map tx sees grad BLOCKS and a chain
        # clip would use each device's local norm. Serial+clip vs
        # overlap+clip bit-identity is pinned in tests/test_overlap.py.
        if self.ce_chunk:
            return ("ce_chunk's fused loss applies its own sharding "
                    "constraints, which cannot run inside the explicit "
                    "step's shard_map; drop one of the flags")
        if self.shard_vocab:
            return ("shard_vocab annotates params over the model axis; "
                    "the explicit grad-sync step needs plain pure-data "
                    "params — drop one of the flags")
        return None

    def overlap_grad_sync_conflict(self) -> Optional[str]:
        """Why grad_sync=overlap cannot run with this config's TRAINING
        knobs (mesh shape / partition / family aside) — None when
        compatible. The SAME checks validate raises for an explicit
        --grad-sync overlap; --plan auto consults this so the planner
        never picks an overlap layout the launch would then reject
        (analysis/planner/plan.apply_auto)."""
        if self.optimizer not in ("adam", "sgd"):
            return (f"grad_sync=overlap needs an ELEMENTWISE "
                    f"optimizer (adam/sgd; adamw via "
                    f"weight_decay): a device's block must compute "
                    f"exactly the full update's slice, which "
                    f"{self.optimizer!r}'s factored statistics "
                    f"break")
        return self._explicit_sync_knob_conflict()

    def validate(self) -> None:
        if self.batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {self.batch_size}")
        if self.train_steps < 0:
            raise ValueError(f"train_steps must be >= 0, got {self.train_steps}")
        if not 0.0 <= self.dropout_rate < 1.0:
            raise ValueError(f"dropout_rate must be in [0,1), got {self.dropout_rate}")
        if self.init_scheme not in ("improved", "reference"):
            raise ValueError(f"unknown init_scheme {self.init_scheme!r}")
        if self.compute_dtype not in ("bfloat16", "float32"):
            raise ValueError(f"unknown compute_dtype {self.compute_dtype!r}")
        if self.data_backend not in ("numpy", "u8_native"):
            raise ValueError(f"unknown data_backend {self.data_backend!r}")
        if self.remat not in ("none", "full", "dots"):
            raise ValueError(f"unknown remat {self.remat!r}")
        if self.checkpoint_backend not in ("native", "orbax"):
            raise ValueError(
                f"unknown checkpoint_backend "
                f"{self.checkpoint_backend!r}")
        if self.pipeline_schedule not in ("gpipe", "1f1b"):
            raise ValueError(
                f"unknown pipeline_schedule {self.pipeline_schedule!r}")
        if self.pipeline_backward not in ("recompute", "stash"):
            raise ValueError(
                f"unknown pipeline_backward {self.pipeline_backward!r}")
        if (self.pipeline_backward != "recompute"
                and not (self.model == "pipelined_lm"
                         and self.pipeline_schedule == "1f1b")):
            # Same convention as the 1f1b/grad_accum exclusion below:
            # reject knobs that would be silently ignored. The backward
            # strategy only exists in the hand-scheduled 1F1B step;
            # GPipe's backward comes from AD and the other families
            # have no pipeline at all.
            raise ValueError(
                "pipeline_backward applies only to model=pipelined_lm "
                "with pipeline_schedule=1f1b")
        if (self.model == "pipelined_lm"
                and self.pipeline_schedule == "1f1b"
                and self.grad_accum_steps > 1):
            # Deliberate exclusion, not a gap: 1F1B's microbatch loop IS
            # gradient accumulation (per-microbatch grads accumulate in
            # the schedule's dp_acc before the single optimizer update,
            # with O(S) activation state). To cut activation memory
            # further, raise pipeline_microbatches — same math, smaller
            # microbatches — instead of wrapping a second accumulation
            # loop around the pipeline.
            raise ValueError(
                "pipeline_schedule=1f1b already accumulates per-"
                "microbatch gradients; raise pipeline_microbatches "
                "instead of grad_accum_steps")
        if self.param_partition not in ("replicated", "zero1", "fsdp"):
            raise ValueError(
                f"unknown param_partition {self.param_partition!r}")
        if (self.param_partition == "fsdp"
                and self.model == "pipelined_lm"):
            # FSDP only: pipelined stage PARAMS carry the "pipe" axis
            # and are consumed stage-sliced inside a manual shard_map —
            # a second data-axis shard would have to be gathered inside
            # the schedule by hand, not by GSPMD. ZeRO-1 composes:
            # optimizer slots are consumed in tx.update OUTSIDE the
            # pipe shard_map (train/pipeline_step.py), so sharding
            # them over "data" never touches the schedule — at
            # GPT-2-xl replicated Adam slots are ~19 GB f32, the first
            # OOM the size ladder hits (round-4 review item 2).
            raise ValueError(
                "param_partition=fsdp does not compose with "
                "model=pipelined_lm (stage params are shard_map-"
                "managed); use param_partition=zero1 for optimizer-"
                "slot memory, mesh.pipe/mesh.model for param memory")
        if self.grad_sync not in ("implicit", "serial", "overlap"):
            raise ValueError(
                f"unknown grad_sync {self.grad_sync!r}; have "
                f"('implicit', 'serial', 'overlap')")
        if self.grad_sync_bucket_mb is not None:
            if self.grad_sync_bucket_mb <= 0:
                raise ValueError(
                    f"grad_sync_bucket_mb must be > 0, "
                    f"got {self.grad_sync_bucket_mb}")
            if self.grad_sync != "overlap":
                raise ValueError(
                    "grad_sync_bucket_mb sizes the overlap path's "
                    "collective buckets; it has no effect without "
                    "--grad-sync overlap — drop the flag")
        if self.grad_sync != "implicit":
            # The explicit-collective step (parallel/overlap.py) is a
            # shard_map over a pure data mesh; every exclusion below is
            # a knob the explicit formulation would silently ignore or
            # silently get wrong — rejected loudly, repo policy.
            if self.mode != "train":
                raise ValueError(
                    f"grad_sync={self.grad_sync!r} shapes the TRAIN "
                    f"step's gradient sync; it has no effect under "
                    f"mode={self.mode!r} — drop the flag")
            if self.model == "pipelined_lm":
                raise ValueError(
                    "grad_sync applies to the standard jitted step; "
                    "the hand-scheduled pipeline step owns its own "
                    "collective schedule (use mesh.pipe for that "
                    "family)")
            bad = [a for a in ("model", "seq", "pipe", "expert")
                   if getattr(self.mesh, a) > 1]
            if bad:
                raise ValueError(
                    f"grad_sync={self.grad_sync!r} needs a pure "
                    f"data-parallel mesh; axes {bad} > 1")
            if self.mesh.data == 1:
                raise ValueError(
                    "grad_sync with mesh.data=1 has nothing to "
                    "synchronize; use the implicit step")
            if self.grad_sync == "overlap":
                if self.param_partition != "zero1":
                    raise ValueError(
                        "grad_sync=overlap IS weight-update sharding: "
                        "the per-bucket update runs against zero1's "
                        "sharded optimizer slots — add "
                        "--param-partition zero1")
            elif self.param_partition != "replicated":
                raise ValueError(
                    "grad_sync=serial replicates the full-tree update "
                    "on every device; it requires "
                    "param_partition=replicated (overlap is the mode "
                    "that composes with zero1)")
            conflict = (self.overlap_grad_sync_conflict()
                        if self.grad_sync == "overlap"
                        else self._explicit_sync_knob_conflict())
            if conflict:
                raise ValueError(conflict)
        if self.pipeline_microbatches < 1:
            raise ValueError(
                f"pipeline_microbatches must be >= 1, "
                f"got {self.pipeline_microbatches}")
        if self.pipeline_virtual_stages < 1:
            raise ValueError(
                f"pipeline_virtual_stages must be >= 1, "
                f"got {self.pipeline_virtual_stages}")
        if self.pipeline_virtual_stages > 1:
            if self.model != "pipelined_lm":
                raise ValueError(
                    "pipeline_virtual_stages > 1 applies only to "
                    "model=pipelined_lm")
            if self.pipeline_backward != "recompute":
                raise ValueError(
                    "pipeline_virtual_stages > 1 supports "
                    "pipeline_backward='recompute' only (the stash "
                    "variant's per-chunk residual treedefs are a "
                    "follow-up; parallel.pipeline."
                    "interleaved_pipeline_value_and_grad)")
            if (self.pipeline_schedule == "1f1b"
                    and self.pipeline_microbatches
                    < self.mesh.pipe * self.pipeline_virtual_stages):
                raise ValueError(
                    f"pipeline_microbatches "
                    f"{self.pipeline_microbatches} < mesh.pipe x "
                    f"virtual stages ({self.mesh.pipe} x "
                    f"{self.pipeline_virtual_stages}): every virtual "
                    f"stage needs a microbatch in flight")
        if (self.model == "pipelined_lm"
                and self.batch_size % self.pipeline_microbatches):
            raise ValueError(
                f"batch_size {self.batch_size} not divisible by "
                f"pipeline_microbatches {self.pipeline_microbatches}")
        if (self.model == "pipelined_lm"
                and self.pipeline_microbatches < self.mesh.pipe):
            raise ValueError(
                f"pipeline_microbatches {self.pipeline_microbatches} "
                f"< mesh.pipe {self.mesh.pipe}: every stage needs at "
                f"least one microbatch in flight")
        if not 0.0 <= self.label_smoothing < 1.0:
            raise ValueError(
                f"label_smoothing must be in [0, 1), "
                f"got {self.label_smoothing}")
        if self.param_sync_every < 1:
            raise ValueError(
                f"param_sync_every must be >= 1, "
                f"got {self.param_sync_every}")
        if self.param_sync_every > 1:
            bad = [a for a in ("model", "seq", "pipe", "expert")
                   if getattr(self.mesh, a) > 1]
            if bad:
                raise ValueError(
                    "param_sync_every > 1 (local SGD) needs a pure "
                    f"data-parallel mesh; axes {bad} > 1")
            if self.param_partition != "replicated":
                raise ValueError(
                    "param_sync_every > 1 needs "
                    "param_partition=replicated (each replica owns "
                    "its full diverged copy)")
            if self.grad_accum_steps > 1:
                raise ValueError(
                    "param_sync_every > 1 does not compose with "
                    "grad_accum_steps; raise batch_size instead")
            if self.ema_decay:
                raise ValueError(
                    "param_sync_every > 1 does not compose with "
                    "ema_decay (average-of-averages ambiguity)")
            from tensorflow_distributed_tpu.models import (
                MUTABLE_EXTRA_MODELS)
            if self.model in MUTABLE_EXTRA_MODELS:
                raise ValueError(
                    "param_sync_every > 1 needs models without "
                    "mutable extra state (BN statistics diverge "
                    "with no principled average); "
                    f"{self.model} carries them")
            if self.model == "pipelined_lm":
                raise ValueError(
                    "param_sync_every > 1 is a pure-DP mode; "
                    "pipelined_lm is not supported")
        if not 0.0 <= self.ema_decay < 1.0:
            raise ValueError(
                f"ema_decay must be in [0, 1), got {self.ema_decay}")
        if self.grad_accum_steps < 1:
            raise ValueError(
                f"grad_accum_steps must be >= 1, got {self.grad_accum_steps}")
        if self.moe_experts < 0:
            raise ValueError(
                f"moe_experts must be >= 0, got {self.moe_experts}")
        if self.kv_cache_quant not in ("none", "int8"):
            raise ValueError(
                f"unknown kv_cache_quant {self.kv_cache_quant!r}")
        if self.attn_window < 0:
            raise ValueError(
                f"attn_window must be >= 0, got {self.attn_window}")
        if self.attn_window:
            if self.model not in ("gpt_lm", "moe_lm", "pipelined_lm"):
                raise ValueError(
                    "attn_window needs a causal LM family "
                    "(gpt_lm | moe_lm | pipelined_lm)")
            if self.mesh.seq > 1:
                raise ValueError(
                    "attn_window with mesh.seq > 1 is not "
                    "implemented; at W << L the window replaces "
                    "ring attention — use mesh.seq == 1")
        if self.moe_experts > 0 and self.model not in (
                "bert_mlm", "gpt_lm", "moe_lm", "pipelined_lm"):
            raise ValueError(
                f"moe_experts > 0 needs a transformer family, "
                f"got model={self.model!r}")
        if self.moe_aux_weight < 0 or self.moe_zloss_weight < 0:
            raise ValueError("moe_aux_weight/moe_zloss_weight must be >= 0")
        if self.moe_top_k < 1:
            raise ValueError(f"moe_top_k must be >= 1, got {self.moe_top_k}")
        if 0 < self.moe_experts < self.moe_top_k:
            # The router would argmax over an exhausted mask and route
            # the same token to expert 0 repeatedly — silent
            # degradation, not an error, so reject it here.
            raise ValueError(
                f"moe_top_k {self.moe_top_k} > moe_experts "
                f"{self.moe_experts}")
        if self.moe_capacity_factor <= 0:
            raise ValueError(
                f"moe_capacity_factor must be > 0, "
                f"got {self.moe_capacity_factor}")
        if self.text_tokenizer not in ("byte", "bpe"):
            raise ValueError(
                f"unknown text_tokenizer {self.text_tokenizer!r}")
        if self.text_tokenizer == "bpe" and not (
                2 <= self.bpe_vocab_size <= 65536):
            raise ValueError(
                f"bpe_vocab_size must be in [2, 65536], "
                f"got {self.bpe_vocab_size}")
        if self.moe_dispatch not in ("dense", "scatter"):
            raise ValueError(
                f"unknown moe_dispatch {self.moe_dispatch!r}")
        if self.moe_group_len < 0:
            raise ValueError(
                f"moe_group_len must be >= 0, got {self.moe_group_len}")
        if (self.moe_group_len and self.seq_len > self.moe_group_len
                and self.seq_len % self.moe_group_len):
            # seq_len <= moe_group_len is fine: MoeMlp routes such
            # sequences as one group (the decode/short-prefill path).
            raise ValueError(
                f"seq_len {self.seq_len} not divisible by "
                f"moe_group_len {self.moe_group_len}")
        if self.batch_size % self.grad_accum_steps:
            raise ValueError(
                f"batch_size {self.batch_size} not divisible by "
                f"grad_accum_steps {self.grad_accum_steps}")
        if self.resume and not self.checkpoint_dir:
            raise ValueError("resume=True requires checkpoint_dir")
        if self.mode not in ("train", "eval", "generate", "serve"):
            raise ValueError(f"unknown mode {self.mode!r}")
        if self.model_config and self.model not in SOURCE_CONFIG_MODELS:
            raise ValueError(
                "model_config (a JSON of the source's config.json keys) "
                "is how the glm_moe_dsa family (also --model axk1), "
                "minicpm_sala, granitemoehybrid, nemotron_h, exaone_moe and "
                f"jamba take their sizes; model={self.model!r} takes presets and "
                "flags")
        if self.model in SOURCE_CONFIG_MODELS:
            family, untrained, cache = SOURCE_CONFIG_FAMILIES[self.model]
            if not self.model_config or self.model_size:
                raise ValueError(
                    f"{family} takes its sizes from "
                    "--model-config <json of the source's config.json "
                    "keys>[#dotted.key] and has no --model-size preset")
            if self.mode not in ("serve",):
                raise ValueError(
                    f"{family} has no training path "
                    f"({untrained}): use --mode serve")
            if (self.serve.paged or self.serve.spec_tokens
                    or self.serve.mesh_model > 1
                    or self.serve.kv_dtype != "bf16"
                    or self.kv_cache_quant != "none"):
                raise ValueError(cache)
        if self.mode == "serve":
            if self.model not in ("gpt_lm", "moe_lm") + SOURCE_CONFIG_MODELS:
                raise ValueError(
                    f"mode=serve needs a causal LM with the decode "
                    f"cache (gpt_lm, moe_lm, glm_moe_dsa, axk1, "
                    f"minicpm_sala, granitemoehybrid, nemotron_h, "
                    f"exaone_moe or jamba), got {self.model!r}")
            if (self.mesh.model > 1 or self.mesh.seq > 1
                    or self.mesh.pipe > 1 or self.mesh.expert > 1):
                raise ValueError(
                    "mode=serve requires a pure data --mesh.* (model/"
                    "seq/pipe/expert == 1): the serve engine builds "
                    "its OWN tensor-parallel mesh — use "
                    "--serve.mesh-model N to shard the replica")
        if self.resilience.fault_plan:
            # Phase check: a fault keyed to a phase that never consults
            # it would sit silently unfired — reject at startup
            # (resilience/faults.py TRAIN_KINDS/SERVE_KINDS).
            from tensorflow_distributed_tpu.resilience.faults import (
                SERVE_KINDS, TRAIN_KINDS, parse_fault_plan)
            kinds = parse_fault_plan(self.resilience.fault_plan).kinds()
            if self.mode == "serve":
                bad = sorted(kinds - set(SERVE_KINDS))
                if bad:
                    raise ValueError(
                        f"fault kinds {bad} are train-phase only; "
                        f"mode=serve consults {sorted(SERVE_KINDS)} "
                        f"on the decode-step clock")
                if "reload" in kinds and not self.checkpoint_dir:
                    raise ValueError(
                        "fault kind 'reload' performs a live weight "
                        "swap from --checkpoint-dir; set one (serve "
                        "needs a swap source)")
            elif self.mode == "train":
                bad = sorted(kinds - set(TRAIN_KINDS))
                if bad:
                    raise ValueError(
                        f"fault kinds {bad} are serve-phase only; "
                        f"mode=train consults {sorted(TRAIN_KINDS)} "
                        f"on the train-step clock")
                if "device_loss" in kinds and not self.checkpoint_dir:
                    raise ValueError(
                        "fault kind 'device_loss' writes the device-"
                        "mask file under --checkpoint-dir (and the "
                        "elastic restart resumes from there); set "
                        "one")
            else:
                raise ValueError(
                    f"resilience.fault_plan has no injection points "
                    f"under mode={self.mode!r}; drop the flag")
        if self.serve.journal and self.mode != "serve":
            raise ValueError(
                "serve.journal is written by the mode=serve "
                "scheduler; drop the flag")
        if self.serve.mesh_model > 1 and self.mode != "serve":
            raise ValueError(
                "serve.mesh_model shards the mode=serve engine's "
                "mesh; drop the flag or add --mode serve")
        if self.serve.inbox:
            if self.mode != "serve":
                raise ValueError(
                    "serve.inbox is the mode=serve fleet-replica "
                    "intake; drop the flag or add --mode serve")
            if not self.seq_len:
                raise ValueError(
                    "serve.inbox has no workload to auto-size the "
                    "cache from — set an explicit --seq-len (the "
                    "fleet's per-request bound)")
        if self.mode != "serve":
            if self.observe.slo:
                raise ValueError(
                    "observe.slo declares SERVING latency targets "
                    "(mode=serve's live burn-rate monitor); drop the "
                    "flag or add --mode serve")
            if self.observe.export_every or self.observe.export_path:
                raise ValueError(
                    "observe.export_every/export_path dump the "
                    "mode=serve scheduler's rolling-metrics "
                    "snapshots; drop the flags or add --mode serve")
            if self.observe.slo_status_every:
                raise ValueError(
                    "observe.slo_status_every prints the mode=serve "
                    "scheduler's live status line; drop the flag or "
                    "add --mode serve")
        elif self.observe.slo:
            # Class names in targets must be real scheduler classes —
            # a typo'd class would silently never match a request.
            from tensorflow_distributed_tpu.observe.slo import parse_slo
            from tensorflow_distributed_tpu.serve.scheduler import (
                SLO_CLASSES)
            for tgt in parse_slo(self.observe.slo):
                if tgt.cls and tgt.cls not in SLO_CLASSES:
                    raise ValueError(
                        f"observe.slo names unknown class "
                        f"{tgt.cls!r}; have {SLO_CLASSES} (or no "
                        f"prefix for all requests)")
        if self.mode == "generate":
            if self.model not in ("gpt_lm", "moe_lm"):
                raise ValueError(
                    f"mode=generate needs a causal LM with the decode "
                    f"cache (gpt_lm or moe_lm), got {self.model!r}")
            if not self.checkpoint_dir:
                raise ValueError("mode=generate requires checkpoint_dir")
            if not self.prompt:
                raise ValueError(
                    "mode=generate requires --prompt (text for "
                    "dataset=text, else comma-separated token ids)")
            if self.mesh.seq != 1:
                raise ValueError(
                    "mode=generate requires mesh.seq == 1 (single-"
                    "token decode steps can't be seq-sharded)")
            if self.num_beams > 1 and (
                    self.gen_temperature > 0 or self.gen_top_k
                    or self.gen_top_p != 1.0):
                raise ValueError(
                    "num_beams > 1 is deterministic beam search; it "
                    "excludes the sampling knobs (gen_temperature / "
                    "gen_top_k / gen_top_p) — pick one")
        if self.gen_temperature < 0:
            raise ValueError(
                f"gen_temperature must be >= 0, got "
                f"{self.gen_temperature} (negative would sample the "
                f"inverted distribution)")
        if self.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {self.max_new_tokens}")
        if self.num_beams < 1:
            raise ValueError(
                f"num_beams must be >= 1, got {self.num_beams}")
        if self.pos_emb not in ("learned", "rope"):
            raise ValueError(f"unknown pos_emb {self.pos_emb!r}")
        if self.rope_theta <= 0:
            raise ValueError(
                f"rope_theta must be > 0, got {self.rope_theta}")
        if self.rope_theta != 10000.0 and self.pos_emb != "rope":
            raise ValueError(
                "rope_theta has no effect without pos_emb=rope; "
                "drop the flag or add --pos-emb rope")
        if self.n_kv_heads < 0:
            raise ValueError(
                f"n_kv_heads must be >= 0, got {self.n_kv_heads}")
        lm_families = ("bert_mlm", "gpt_lm", "moe_lm", "pipelined_lm")
        if self.shard_vocab and self.model not in lm_families:
            raise ValueError(
                f"shard_vocab has no effect on model={self.model!r} "
                f"(transformer families only); drop the flag")
        if self.shard_vocab and self.model == "pipelined_lm":
            raise ValueError(
                "shard_vocab is not available for pipelined_lm (the "
                "embedding shell carries no TP metadata; use mesh.pipe "
                "for memory)")
        if self.ce_chunk < 0:
            raise ValueError(
                f"ce_chunk must be >= 0, got {self.ce_chunk}")
        if self.ce_chunk and self.model not in lm_families:
            raise ValueError(
                f"ce_chunk has no effect on model={self.model!r} "
                f"(the fused head+loss exists for the LM families' "
                f"50k-row vocabs); drop the flag")
        if (self.ce_impl == "kernel" and self.model == "pipelined_lm"):
            raise ValueError(
                "ce_impl='kernel' is not available for pipelined_lm "
                "(the 1F1B schedule drives the fused loss through its "
                "own vjp at the last stage — the scan formulation "
                "composes there; the Mosaic kernel's shard_map wrap "
                "does not). Use the default ce_impl='scan'")
        if self.ce_impl == "kernel" and self.shard_vocab:
            raise ValueError(
                "ce_impl='kernel' does not compose with shard_vocab "
                "(the Mosaic kernel wants the whole head per device); "
                "the default ce_impl='scan' runs the vocab-parallel "
                "form instead")
        if self.ce_impl not in ("scan", "kernel"):
            raise ValueError(
                f"unknown ce_impl {self.ce_impl!r}; have "
                f"('scan', 'kernel')")
        if self.ce_impl != "scan" and not self.ce_chunk:
            raise ValueError(
                "ce_impl has no effect without ce_chunk > 0 (the fused "
                "head+loss master switch); add --ce-chunk")
        if self.ce_impl == "kernel" and self.mesh.model > 1:
            raise ValueError(
                "ce_impl='kernel' requires mesh.model == 1 (the "
                "Mosaic kernel wants the whole head per device); the "
                "default ce_impl='scan' runs the Megatron vocab-"
                "parallel form over the model axis instead")
        if self.seq_len < 0 or self.seq_len == 1:
            raise ValueError(
                f"seq_len must be 0 (family default) or >= 2, "
                f"got {self.seq_len}")
        if self.seq_len and self.model not in (lm_families
                                               + SOURCE_CONFIG_MODELS):
            raise ValueError(
                f"seq_len has no effect on model={self.model!r} "
                f"(LM families only); drop the flag")
        if self.seq_len and self.seq_len % self.mesh.seq:
            raise ValueError(
                f"seq_len {self.seq_len} not divisible by mesh.seq "
                f"{self.mesh.seq} (tokens shard the sequence dim over "
                f"the seq axis)")
        if self.synthetic_vocab < 0:
            raise ValueError(
                f"synthetic_vocab must be >= 0, got {self.synthetic_vocab}")
        if self.synthetic_vocab and self.model not in lm_families:
            raise ValueError(
                f"synthetic_vocab has no effect on model="
                f"{self.model!r} (LM families only); drop the flag")
        if self.synthetic_vocab and self.dataset == "text":
            raise ValueError(
                "synthetic_vocab has no effect with dataset='text' "
                "(the byte corpus pins vocab to 256); drop the flag")
        if self.mlp_variant not in ("gelu", "swiglu"):
            raise ValueError(f"unknown mlp_variant {self.mlp_variant!r}")
        if (self.mlp_variant != "gelu"
                and (self.moe_experts > 0 or self.model == "moe_lm")):
            raise ValueError(
                "mlp_variant has no effect with MoE (the block's MLP is "
                "replaced by MoeMlp, whose experts are gelu); drop the "
                "flag or use a dense family")
        if self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(f"unknown norm {self.norm!r}")
        if self.plan not in ("", "auto"):
            raise ValueError(
                f"unknown plan {self.plan!r}; have ('', 'auto')")
        if self.plan_hbm_budget_gb < 0:
            raise ValueError(
                f"plan_hbm_budget_gb must be >= 0, "
                f"got {self.plan_hbm_budget_gb}")
        if self.plan_hbm_budget_gb and self.plan != "auto":
            raise ValueError(
                "plan_hbm_budget_gb has no effect without --plan auto; "
                "drop the flag")
        if (self.plan_calibration and self.plan != "auto"
                and not self.profile_dir):
            # The profile feeds exactly two consumers: the planner's
            # roofline and the profiled device-time comparison.
            raise ValueError(
                "plan_calibration has no effect without --plan auto "
                "or --profile-dir; drop the flag")
        if self.plan == "auto":
            if self.mode != "train":
                raise ValueError(
                    f"--plan auto chooses a TRAINING layout; it has "
                    f"no effect under mode={self.mode!r} — drop the "
                    f"flag")
            if self.model not in ("gpt_lm", "moe_lm", "pipelined_lm"):
                raise ValueError(
                    f"--plan auto plans the LM training families "
                    f"(gpt_lm | moe_lm | pipelined_lm), got "
                    f"model={self.model!r}")
            if self.mesh != MeshConfig():
                raise ValueError(
                    "--plan auto owns the mesh shape; drop the "
                    "explicit --mesh.* flags (or drop --plan auto and "
                    "keep them)")
            if self.param_partition != "replicated":
                raise ValueError(
                    "--plan auto owns the partition choice "
                    "(replicated/fsdp/zero1 is part of the strategy "
                    "it ranks); drop --param-partition")
            if self.grad_sync != "implicit":
                raise ValueError(
                    "--plan auto owns the grad-sync choice (the "
                    "overlap strategy is one of the candidates it "
                    "ranks); drop --grad-sync")
            if self.param_sync_every > 1:
                raise ValueError(
                    "--plan auto does not compose with "
                    "param_sync_every > 1 (local SGD is not a "
                    "planner strategy)")
            if self.moe_experts > 0 and self.model != "moe_lm":
                # The planner scores the FAMILY's own program; a
                # dense family turned MoE via --moe-experts would be
                # scored as dense (wrong flops, wrong HBM, no expert
                # axis enumerated) — reject rather than emit a plan
                # that misdescribes the run.
                raise ValueError(
                    "--plan auto with --moe-experts needs "
                    "model=moe_lm (the planner scores the family's "
                    "own expert layout; a dense family with experts "
                    "bolted on would be scored as dense)")
        if self.mode == "eval" and not self.checkpoint_dir:
            raise ValueError("mode=eval requires checkpoint_dir")
        if self.resilience.nonfinite == "rewind" and not self.checkpoint_dir:
            raise ValueError(
                "resilience.nonfinite=rewind restores the newest "
                "verifiable checkpoint in-process; it requires "
                "checkpoint_dir")
        if self.resilience.nonfinite == "skip_batch":
            if (self.model == "pipelined_lm"
                    and self.pipeline_schedule == "1f1b"):
                raise ValueError(
                    "resilience.nonfinite=skip_batch is implemented in "
                    "the standard jitted step (the update is discarded "
                    "on device); the hand-scheduled 1F1B step has no "
                    "skip path — use nonfinite=rewind or halt")
            if self.param_sync_every > 1:
                raise ValueError(
                    "resilience.nonfinite=skip_batch does not compose "
                    "with param_sync_every > 1 (the local-SGD step has "
                    "no skip path); use nonfinite=rewind or halt")
        if self.observe.health and self.mode != "train":
            # Same explicitness rule as the taps check below: health
            # vitals are computed inside the TRAIN step — an observed
            # serve/eval/generate run would silently produce zero
            # health records.
            raise ValueError(
                f"observe.health is train-side telemetry (per-module "
                f"grad/update vitals inside the train step); it has "
                f"no effect under mode={self.mode!r} — drop the flag")
        if self.observe.health and self.mode == "train":
            if not self.log_every:
                raise ValueError(
                    "observe.health needs log_every > 0: the health "
                    "scalars ride the log-cadence metrics fetch")
            if (self.observe.health_every
                    and self.observe.health_every % self.log_every):
                raise ValueError(
                    f"observe.health_every {self.observe.health_every} "
                    f"must be a multiple of log_every {self.log_every} "
                    f"(the host only looks on the log cadence)")
            if self.param_sync_every > 1:
                raise ValueError(
                    "observe.health is implemented in the standard and "
                    "1F1B steps; the local-SGD step (param_sync_every "
                    "> 1) has no health path")
        if self.observe.health_taps and self.model not in (
                "bert_mlm", "gpt_lm", "moe_lm"):
            # Same explicitness rule as every other no-op knob: the
            # vision families have no tapped blocks, and pipelined_lm's
            # stage forwards run inside a manual shard_map with no sow
            # path out — a silently tap-less run would look like a
            # telemetry bug.
            raise ValueError(
                f"observe.health_taps needs a non-pipelined "
                f"transformer family (bert_mlm | gpt_lm | moe_lm), "
                f"got model={self.model!r} — per-module health still "
                f"works there, drop the taps flag")
        if self.halt_on_nonfinite and self.resilience.nonfinite != "off":
            raise ValueError(
                "halt_on_nonfinite=true and resilience.nonfinite are "
                "two handlers for the same event — drop "
                "halt_on_nonfinite (resilience.nonfinite=halt is its "
                "per-step superset)")
        self.mesh.validate()
        self.observe.validate()
        self.resilience.validate()
        self.serve.validate()


def _add_dataclass_args(parser: argparse.ArgumentParser, cls, prefix: str = "") -> None:
    # ``from __future__ import annotations`` makes f.type a string, so
    # resolve real types via get_type_hints before testing for nesting.
    import typing
    hints = typing.get_type_hints(cls)
    for f in dataclasses.fields(cls):
        ftype = hints.get(f.name, str)
        if dataclasses.is_dataclass(ftype):
            _add_dataclass_args(parser, ftype, prefix=f"{f.name}.")
            continue
        name = f"--{prefix}{f.name}".replace("_", "-")
        default = f.default if f.default is not dataclasses.MISSING else None
        if ftype is bool or isinstance(default, bool):
            parser.add_argument(name, type=lambda s: s.lower() in ("1", "true", "yes"),
                                default=default)
        elif default is None:
            parser.add_argument(name, type=float, default=None)
        else:
            parser.add_argument(name, type=type(default), default=default)


@functools.lru_cache(maxsize=None)
def known_flags() -> frozenset:
    """Every ``--flag`` spelling the CLI parses — THE flag namespace
    of the parent->child argv protocol. The supervisor and the fleet
    controller spell child flags through :func:`child_flag`, and the
    argv lint (``analysis/rules/argvproto.py``) verifies every flag
    literal they construct is in this set."""
    parser = argparse.ArgumentParser(add_help=False)
    _add_dataclass_args(parser, TrainConfig)
    return frozenset(parser._option_string_actions)


def child_flag(path: str) -> str:
    """The blessed child-argv spelling for a config field: dotted
    dataclass path in, ``--flag`` out (``"mesh.data"`` ->
    ``"--mesh.data"``, ``"checkpoint_dir"`` -> ``"--checkpoint-dir"``).
    Raises KeyError for a field the CLI does not parse, so a typo'd
    parent flag fails at construction, not as a child crash loop."""
    flag = "--" + path.replace("_", "-")
    if flag not in known_flags():
        raise KeyError(
            f"{flag!r} (from {path!r}) is not parsed by config.py")
    return flag


def parse_args(argv: Optional[Sequence[str]] = None) -> TrainConfig:
    """Build a TrainConfig from CLI args (one CLI for every role/mesh)."""
    parser = argparse.ArgumentParser(
        prog="tensorflow_distributed_tpu",
        description="TPU-native distributed trainer (single entrypoint; "
        "mesh shape replaces the reference's ps/worker roles)",
    )
    _add_dataclass_args(parser, TrainConfig)
    ns = parser.parse_args(argv)
    import typing
    hints = typing.get_type_hints(TrainConfig)
    kwargs = {}
    for f in dataclasses.fields(TrainConfig):
        ftype = hints[f.name]
        if dataclasses.is_dataclass(ftype):
            sub = {g.name: getattr(ns, f"{f.name}.{g.name}")
                   for g in dataclasses.fields(ftype)}
            kwargs[f.name] = ftype(**sub)
            continue
        v = getattr(ns, f.name)
        if f.name == "grad_clip_norm" and v is not None:
            v = float(v)
        kwargs[f.name] = v
    cfg = TrainConfig(**kwargs)
    cfg.validate()
    return cfg
