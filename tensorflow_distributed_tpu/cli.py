"""Single CLI entrypoint — ``python -m tensorflow_distributed_tpu.cli``.

Replaces all five reference entrypoints (mnist_python_m.py / _w1 / _w2 /
mnist_single.py / the notebook) and their ``tf.app.run`` dispatch
(mnist_python_m.py:323-324). Role selection by editing per-file flag
defaults is gone: every process runs this same module; mesh shape and
env-driven bootstrap decide the topology.

Examples:
    # single device (the mnist_single.py path):
    python -m tensorflow_distributed_tpu.cli --train-steps 200

    # 8-way data parallel on one host:
    python -m tensorflow_distributed_tpu.cli --mesh.data 8

    # reference-faithful hyperparameters (for apples-to-apples runs):
    python -m tensorflow_distributed_tpu.cli --init-scheme reference \
        --learning-rate 0.01 --log-every 1

    # continuous-batching inference (serve/; README "Serving"):
    python -m tensorflow_distributed_tpu.cli --mode serve \
        --model gpt_lm --serve.num-slots 8 --serve.num-requests 32

    # fast-path serving (README "Fast-path serving"): speculative
    # decoding (k-gram self-draft; token-identical by construction),
    # int8 KV cache (~2x slots per HBM at head dim 64), SLO classes
    # with per-tenant quotas + preempt-and-requeue
    python -m tensorflow_distributed_tpu.cli --mode serve \
        --model gpt_lm --serve.num-slots 4 --serve.num-requests 32 \
        --serve.spec-tokens 4 --serve.kv-dtype int8 \
        --serve.policy slo --serve.slo-mix "high:0.25,batch:0.25" \
        --serve.tenants 4 --serve.tenant-quota 512

    # tensor-parallel serving (README "Tensor-parallel serving"): the
    # replica itself sharded over a model=2 mesh — params AND every
    # slot-cache leaf head-sharded, per-device cache bytes / 2,
    # token-identical to the single-device engine; composes with the
    # spec/int8/paged flags above ("--family serve" on the planner
    # ranks the widths without executing)
    # (odd vocabs like GPT-2's 50257 need --shard-vocab true to pad)
    python -m tensorflow_distributed_tpu.cli --mode serve \
        --model gpt_lm --model-size tiny --serve.mesh-model 2 \
        --serve.num-slots 8 --serve.num-requests 32

    # paged KV + radix prefix reuse (serve/paging; README "Paged KV
    # + prefix reuse"): shared system prompts / few-shot headers /
    # multi-turn sessions attach cached pages instead of
    # re-prefilling, and slots hold pages for their actual
    # trajectory instead of reserving max_len rows
    python -m tensorflow_distributed_tpu.cli --mode serve \
        --model gpt_lm --serve.num-slots 8 --serve.num-requests 32 \
        --serve.paged true --serve.page-size 16 \
        --serve.session-turns 2

    # serve under fire (README "Serving under faults"): bursty
    # arrivals, slot-NaN containment + live weight swap drills, a
    # crash-durable request journal, decode watchdog; run under
    # resilience.supervisor for SIGKILL coverage
    python -m tensorflow_distributed_tpu.cli --mode serve \
        --model gpt_lm --checkpoint-dir /tmp/ckpt \
        --serve.trace bursty --serve.arrival-rate 8 \
        --serve.journal /tmp/serve.journal \
        --resilience.sync-timeout-s 60 \
        --resilience.fault-plan "slot_nan@6:1,reload@10,sigkill@14"

    # serve observatory (observe/serve_trace.py + observe/slo.py;
    # README "Serve tracing & SLO monitoring"): per-request Perfetto
    # trace (open at https://ui.perfetto.dev), live SLO burn-rate
    # monitor with slo_alert/slo_ok events + a periodic status line,
    # and atomic rolling-metrics snapshots a router can poll
    python -m tensorflow_distributed_tpu.cli --mode serve \
        --model gpt_lm --serve.num-slots 4 --serve.num-requests 32 \
        --serve.policy slo --serve.slo-mix "high:0.25" \
        --observe.metrics-jsonl serve.jsonl \
        --observe.trace serve.trace.json \
        --observe.slo "high:ttft_p95=100ms,tok_p50=30ms" \
        --observe.export-every 1 --observe.export-path serve.snap.json

    # autopilot (observe/autopilot.py; README "Autopilot"): the online
    # controller closing the calibrate→plan→act loop on the run's own
    # telemetry — SLO burn drives admission, page-pool pressure the
    # live slot cap, the rolling accept rate the speculation depth,
    # and plan drift a calibration refit; every decision is an
    # auditable `tune` record, every actuation token-identical (pin
    # knobs it must not touch with --observe.autopilot-pin)
    python -m tensorflow_distributed_tpu.cli --mode serve \
        --model gpt_lm --serve.num-slots 4 --serve.num-requests 64 \
        --serve.spec-tokens 4 --serve.policy slo \
        --observe.autopilot true --observe.autopilot-every 25 \
        --observe.autopilot-pin buckets \
        --observe.autopilot-calibration serve.calibration.json \
        --observe.metrics-jsonl serve.jsonl \
        --observe.slo "ttft_p95=250ms"

    # fleet serving (fleet/; README "Fleet serving"): a health-aware
    # router + lifecycle controller over N replica processes — each
    # an ordinary --mode serve command with a per-epoch inbox/journal/
    # snapshot workspace; replicas die, restart, hot-swap trainer
    # checkpoints (rolling, one at a time) while the fleet keeps
    # answering with zero lost requests
    python -m tensorflow_distributed_tpu.fleet.run \
        --replicas 3 --fleet-dir /tmp/fleet \
        --requests workload.jsonl --checkpoint-dir /tmp/ckpt \
        --kill r1@12.5 --hold-export r0@20:3 \
        -- --mode serve --model gpt_lm --seq-len 96 \
           --checkpoint-dir /tmp/ckpt --serve.num-slots 4 \
           --observe.anomaly true

    # fleet observatory (observe/fleet_trace.py + fleetview; README
    # "Fleet observatory"): one stitched Perfetto trace across router
    # + every replica (failover legs land on one timeline), fleet-level
    # SLO burn on client-perceived latency across retries, per-request
    # latency decomposition, and an atomically-rewritten control-plane
    # snapshot the fleetview CLI renders as a one-screen status page
    python -m tensorflow_distributed_tpu.fleet.run \
        --replicas 2 --fleet-dir /tmp/fleet \
        --requests workload.jsonl \
        --fleet.trace true \
        --fleet.slo "ttft_p95=200ms,tok_p99=80ms" \
        --fleet.export-path /tmp/fleet/fleet_snapshot.json \
        --fleet.export-every 1 \
        -- --mode serve --model gpt_lm --serve.num-slots 4
    python -m tensorflow_distributed_tpu.observe.fleetview /tmp/fleet

    # graftcheck runtime checks (analysis/runtime.py; README "Static
    # analysis"): transfer guard + sharding-contract assertion
    python -m tensorflow_distributed_tpu.cli --train-steps 100 --check true

    # elastic restarts (README "Elastic restarts"): supervise with
    # --elastic and a chip-loss drill — the restart probes the
    # surviving devices, degrades the mesh, and the resharded restore
    # continues training instead of crash-looping
    python -m tensorflow_distributed_tpu.resilience.supervisor \
        --elastic -- --mesh.data 8 --checkpoint-dir /tmp/ckpt \
        --checkpoint-every 50 \
        --resilience.fault-plan "device_loss@120:4"

    # device telemetry (observe/device.py + observe/health.py; README
    # "Device telemetry"): compiled-program cost/HBM records + per-layer
    # health vitals in the metrics JSONL
    python -m tensorflow_distributed_tpu.cli --model gpt_lm \
        --model-size tiny --observe.metrics-jsonl /tmp/m.jsonl \
        --observe.health true --observe.health-taps true

    # auto-layout planner (analysis/planner; README "Auto-layout
    # planner"): rank every valid mesh x strategy by AOT cost model,
    # or let the train CLI launch with the winner (--plan auto emits
    # an auditable "plan" record through observe)
    python -m tensorflow_distributed_tpu.analysis.planner \
        --family gpt --devices 8 --batch-size 128
    python -m tensorflow_distributed_tpu.cli --model gpt_lm \
        --model-size tiny --plan auto \
        --observe.metrics-jsonl /tmp/m.jsonl

    # overlap-aware gradient sync (parallel/overlap.py; README
    # "Gradient-sync overlap"): bucketed reduce-scatter + ZeRO-1
    # sharded update + bucketed all-gather, hidden under backward
    # compute; step records carry the exposed-vs-hidden comm estimate
    python -m tensorflow_distributed_tpu.cli --model gpt_lm \
        --mesh.data 8 --param-partition zero1 --grad-sync overlap \
        --grad-sync-bucket-mb 4 \
        --observe.metrics-jsonl /tmp/m.jsonl

    # ground-truth observatory (observe/xprof.py + planner/calibrate;
    # README "Ground-truth observatory"): the profiler window is
    # parsed into per-program device_time records beside the compile
    # records, --plan auto scores on measured effective rates, and a
    # plan_drift record closes predicted -> measured at run end
    python -m tensorflow_distributed_tpu.cli --model gpt_lm \
        --model-size tiny --plan auto \
        --plan-calibration calibration.json \
        --profile-dir /tmp/prof --observe.metrics-jsonl /tmp/m.jsonl

    # incident observatory (observe/anomaly.py + observe/flightrec.py;
    # README "Incident observatory"): online anomaly detection over
    # the already-fetched log-cadence values + a crash flight
    # recorder whose bundle survives even a SIGKILL — render it as a
    # human incident report with the postmortem CLI
    python -m tensorflow_distributed_tpu.cli --model mnist_cnn \\
        --dataset synthetic --train-steps 200 --log-every 1 \\
        --observe.metrics-jsonl m.jsonl --observe.anomaly true \\
        --observe.flightrec /tmp/flight \\
        --resilience.nonfinite skip_batch \\
        --resilience.fault-plan "nan_grad@60,sigkill@120"
    python -m tensorflow_distributed_tpu.observe.postmortem \\
        /tmp/flight/flight-<pid>.jsonl
"""

from __future__ import annotations

import sys
from typing import Optional, Sequence

from tensorflow_distributed_tpu.config import parse_args
from tensorflow_distributed_tpu.parallel.mesh import is_chief
from tensorflow_distributed_tpu.resilience.watchdog import StallError
from tensorflow_distributed_tpu.train.loop import (
    evaluate_only, generate_only, train)
from tensorflow_distributed_tpu.utils.compilecache import (
    enable_persistent_cache)

# Distinct exit codes for the failure classes a supervisor (e.g.
# resilience.supervisor) or scheduler wants to tell apart in logs:
# 2 = diverged (train: non-finite halt / recovery budget exhausted;
# serve: a request slot-quarantined past its retry budget — either
# way a restart re-diverges), 3 = stall watchdog fired (train data/
# sync or serve decode — a restart is exactly the remedy; serve legs
# resume from the request journal). Clean completion and graceful
# preemption both exit 0.
EXIT_DIVERGED = 2
EXIT_STALLED = 3


def main(argv: Optional[Sequence[str]] = None) -> int:
    enable_persistent_cache()
    cfg = parse_args(argv)
    if cfg.mode == "eval":
        evaluate_only(cfg)
        return 0
    if cfg.mode == "generate":
        generate_only(cfg)
        return 0
    if cfg.mode == "serve":
        # Continuous-batching inference over a request workload
        # (serve/run.py): slots join/leave one hot compiled decode
        # step, prompts prefill through a bounded bucket ladder.
        # Same exit-code contract as training, serve-shaped: a
        # request slot-quarantined past its retry budget is serve's
        # divergence (2 — deterministic decode would re-poison; the
        # supervisor must NOT hot-loop restarts), a decode watchdog
        # breach is a stall (3 — a restart + journal resume is
        # exactly the remedy).
        from tensorflow_distributed_tpu.serve.run import serve_run
        from tensorflow_distributed_tpu.serve.scheduler import (
            SlotRetryExhausted)
        try:
            serve_run(cfg)
        except SlotRetryExhausted as e:
            print(f"[resilience] serve diverged: {e}", file=sys.stderr,
                  flush=True)
            return EXIT_DIVERGED
        except StallError as e:
            print(f"[resilience] serve stalled: {e}", file=sys.stderr,
                  flush=True)
            return EXIT_STALLED
        return 0
    try:
        result = train(cfg)
    except FloatingPointError as e:
        print(f"[resilience] diverged: {e}", file=sys.stderr, flush=True)
        return EXIT_DIVERGED
    except StallError as e:
        print(f"[resilience] stalled: {e}", file=sys.stderr, flush=True)
        return EXIT_STALLED
    if is_chief():
        # Emit the reference's hand-maintained `performance` table
        # automatically (performance:1-6).
        table = result.logger.performance_table(cfg.learning_rate)
        if table.count("\n"):
            print(table)
        # Compiled-program HBM budget table (observe/device.py) —
        # printed when the run registered programs (a sink was
        # configured and --observe.programs wasn't turned off).
        from tensorflow_distributed_tpu.observe import (
            device as observe_device)
        budget = observe_device.budget_table()
        if budget and cfg.observe.programs:
            print(budget)
        # Point at the observe/ artifacts this run produced.
        if cfg.observe.metrics_jsonl:
            print(f"[observe] metrics: {cfg.observe.metrics_jsonl} "
                  f"(summarize: python -m "
                  f"tensorflow_distributed_tpu.observe.report "
                  f"{cfg.observe.metrics_jsonl})")
        if cfg.observe.trace:
            print(f"[observe] host trace: {cfg.observe.trace} "
                  f"(open at https://ui.perfetto.dev)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
