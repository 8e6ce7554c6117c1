"""Summarize metrics JSONLs: ``python -m tensorflow_distributed_tpu.observe.report <metrics.jsonl> [more.jsonl ...]``.

Regenerates the headline numbers a BENCH artifact wants — p50/p95 step
time, mean throughput and MFU, goodput % — from the raw JSONL the
:mod:`observe.registry` JSONL sink wrote, so bench records can always
be re-derived from (and audited against) the primary artifact.

Multiple paths merge into ONE report (each process of a multi-host
run writes its own host-tagged stream — registry.host_tags stamps
``process_index`` on every record); when records from more than one
host are present, a per-host section breaks the headline stats down
by origin.

``--json`` prints one machine-readable JSON object instead of the
human table.
"""

from __future__ import annotations

import argparse
import json
import sys

from tensorflow_distributed_tpu.observe import device as _device
from typing import Any, Dict, List


def load_records(path: str) -> List[Dict[str, Any]]:
    """Parse a metrics JSONL, SKIPPING malformed lines.

    A crashed or killed run leaves exactly the file this report exists
    for — and possibly a truncated final line (the sink flushes per
    record, but the OS can still cut a write mid-line at SIGKILL, and
    NFS appends can interleave). Raising on one bad line would make the
    report unavailable precisely when it matters: count-and-skip, note
    it on stderr, summarize the rest."""
    records = []
    bad, first_bad = 0, 0
    with open(path) as f:
        for i, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                records.append(json.loads(line))
            except json.JSONDecodeError:
                bad += 1
                first_bad = first_bad or i
    if bad:
        print(f"observe.report: {path}: skipped {bad} malformed "
              f"line(s) (first at line {first_bad}) — partial write "
              f"from a crashed run?", file=sys.stderr)
    return records


def _mean(values: List[float]) -> float:
    return sum(values) / len(values)


# THE percentile formula (observe/slo.py, stdlib-only): the live
# snapshot and this post-run report must agree exactly, so there is
# ONE definition (tests/test_serve_observe.py pins its values).
from tensorflow_distributed_tpu.observe.slo import (  # noqa: E402
    percentile as _percentile)


def _request_parts(serve_reqs: List[Dict[str, Any]]
                   ) -> Dict[str, Dict[str, float]]:
    """Where a request's time went, over the ``serve_request`` records
    that say (``wait_ms`` / ``decode_ms`` by kind of scheduler
    iteration, serve/scheduler.py): mean and p95 of each part of the
    wait for a first token, of the request's own ``prefill_ms``, of
    each part of ``decode_ms`` A TOKEN GAP, and of ``admits_endured``.
    Empty for records from before the fields."""
    cols: Dict[str, List[float]] = {}
    for r in serve_reqs:
        wait, dec = r.get("wait_ms"), r.get("decode_ms")
        if isinstance(wait, dict):
            for kind, ms in wait.items():
                cols.setdefault(f"wait.{kind}_ms", []).append(float(ms))
            if isinstance(r.get("prefill_ms"), (int, float)):
                cols.setdefault("prefill_ms", []).append(
                    float(r["prefill_ms"]))
        if isinstance(dec, dict) and int(r.get("new_tokens") or 0) > 1:
            gaps = int(r["new_tokens"]) - 1
            for kind, ms in dec.items():
                cols.setdefault(f"decode.{kind}_ms_per_token",
                                []).append(float(ms) / gaps)
            cols.setdefault("admits_endured", []).append(
                float(r.get("admits_endured") or 0))
    return {name: {"mean": round(_mean(vals), 3),
                   "p95": round(_percentile(sorted(vals), 95), 3),
                   "n": len(vals)}
            for name, vals in cols.items()}


def summarize(records: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Aggregate step/summary events into the report dict."""
    steps = [r for r in records if r.get("event") == "step"]
    summaries = [r for r in records if r.get("event") == "summary"]
    out: Dict[str, Any] = {"records": len(records),
                           "step_records": len(steps)}
    # Serve-mode records (serve/scheduler.py): per-request
    # serve_request rows + one serve_summary — reported alongside the
    # training summary so one JSONL tells the whole story.
    serve_reqs = [r for r in records if r.get("event") == "serve_request"]
    serve_sums = [r for r in records if r.get("event") == "serve_summary"]
    if serve_reqs:
        out["serve_requests"] = len(serve_reqs)
        ttfts = sorted(float(r["ttft_ms"]) for r in serve_reqs
                       if isinstance(r.get("ttft_ms"), (int, float)))
        if ttfts:
            out["serve_ttft_ms_p50"] = round(_percentile(ttfts, 50), 3)
            out["serve_ttft_ms_p95"] = round(_percentile(ttfts, 95), 3)
            out["serve_ttft_ms_p99"] = round(_percentile(ttfts, 99), 3)
        # Requests whose arrival->first-token window overlapped a
        # recovery event (slot quarantine / weight swap) — the
        # availability population of ``serve_ttft_ms_p99_recovery``.
        rec_ttfts = sorted(
            float(r["ttft_ms"]) for r in serve_reqs
            if r.get("recovery_window")
            and isinstance(r.get("ttft_ms"), (int, float)))
        if rec_ttfts:
            out["serve_recovery_requests"] = len(rec_ttfts)
            out["serve_ttft_ms_p99_recovery"] = round(
                _percentile(rec_ttfts, 99), 3)
        toks = [float(r["tok_ms"]) for r in serve_reqs
                if isinstance(r.get("tok_ms"), (int, float))]
        if toks:
            out["serve_tok_ms_mean"] = round(_mean(toks), 4)
        # Per-SLO-class TTFT p95 (serve/scheduler.py policy="slo"
        # tags every serve_request with its class): the split the SLO
        # scheduler exists to move — only emitted when a non-default
        # class actually appears, so plain FIFO reports are unchanged.
        by_class: Dict[str, List[float]] = {}
        for r in serve_reqs:
            if isinstance(r.get("ttft_ms"), (int, float)):
                by_class.setdefault(str(r.get("slo", "standard")),
                                    []).append(float(r["ttft_ms"]))
        if len(by_class) > 1 or set(by_class) - {"standard"}:
            for cls, vals in sorted(by_class.items()):
                out[f"serve_ttft_ms_p95_{cls}"] = round(
                    _percentile(sorted(vals), 95), 3)
        parts = _request_parts(serve_reqs)
        if parts:
            out["request_parts"] = parts
    if serve_sums:
        final = serve_sums[-1]
        for key in ("tokens_per_sec", "mean_slot_occupancy",
                    "total_new_tokens", "prefill_compiles", "retries",
                    "swaps", "swap_seconds", "seed", "trace",
                    "policy", "preemptions", "spec_tokens",
                    "verify_steps", "accept_rate", "tune_actions",
                    "spec_fallback_slots", "slo_alerts",
                    "slo_budget_remaining_min", "slo_targets",
                    # Paged KV + prefix reuse (serve/paging): pool
                    # occupancy, hit rate, evictions — present only
                    # when the run served paged (plain reports stay
                    # shape-stable).
                    "prefix_hit_rate", "prefix_hits",
                    "pool_occupancy", "pages_peak",
                    "slot_pages_peak", "page_evictions",
                    "cow_copies", "sessions"):
            if key in final:
                out[f"serve_{key}"] = final[key]
        if isinstance(final.get("phase_ms"), dict):
            # Where the serving wall went, by host phase (the span
            # seam's always-on totals): its own section below.
            out["phase_ms"] = final["phase_ms"]
            out["wall_s"] = final.get("wall_s")
        if isinstance(final.get("iter_ms"), dict):
            # The same wall by kind of iteration: one line under the
            # phase table.
            out["iter_ms"] = final["iter_ms"]
            out["admissions"] = final.get("admissions")
            out["admitted_at_once"] = final.get("admitted_at_once")
    # Live SLO monitor events (observe/slo.py): alert/clear
    # transitions per target plus the last reported budget state —
    # the burn-rate story beside the latency percentiles above.
    slo_events = [r for r in records
                  if r.get("event") in ("slo_alert", "slo_ok")]
    if slo_events:
        by_target: Dict[str, Dict[str, Any]] = {}
        for r in slo_events:
            entry = by_target.setdefault(str(r.get("target", "?")),
                                         {"alerts": 0, "clears": 0})
            if r["event"] == "slo_alert":
                entry["alerts"] += 1
                entry["worst_burn_fast"] = max(
                    entry.get("worst_burn_fast", 0.0),
                    float(r.get("burn_fast", 0.0)))
            else:
                entry["clears"] += 1
            if isinstance(r.get("budget_remaining"), (int, float)):
                entry["budget_remaining"] = r["budget_remaining"]
        out["slo"] = dict(sorted(by_target.items()))
    # Rolling metrics snapshots (scheduler.metrics_snapshot, dumped on
    # --observe.export-every): count + the final point-in-time view.
    # The last snapshot is forced at run end over every completion, so
    # its per-class p95s must AGREE with the serve_request-derived
    # numbers above.
    snapshots = [r for r in records
                 if r.get("event") == "metrics_snapshot"]
    if snapshots:
        out["snapshots"] = len(snapshots)
        last = snapshots[-1]
        keep = ("t_s", "decode_steps", "requests_done", "queue_depth",
                "slot_occupancy", "tokens_per_sec",
                "tokens_per_sec_window", "accept_rate",
                "accept_rate_window", "spec_tokens", "tune_actions",
                "retries", "preemptions", "swaps")
        entry = {k: last[k] for k in keep if k in last}
        for k in sorted(last):
            if k.startswith("ttft_ms_p"):
                entry[k] = last[k]
        out["snapshot_last"] = entry
    # Autopilot decision ledger (observe/autopilot.py): the run-end
    # tune_summary rollup plus the decision records folded per loop —
    # a quiet well-tuned run shows actions=0 here.
    tunes = [r for r in records if r.get("event") == "tune"]
    tune_sums = [r for r in records
                 if r.get("event") == "tune_summary"]
    if tunes or tune_sums:
        tentry: Dict[str, Any] = {}
        if tune_sums:
            tfin = tune_sums[-1]
            for k in ("evals", "actions", "advisories", "suppressed",
                      "by_knob", "quiet"):
                if k in tfin:
                    tentry[k] = tfin[k]
        by_loop: Dict[str, int] = {}
        for r in tunes:
            lp = str(r.get("loop", "?"))
            by_loop[lp] = by_loop.get(lp, 0) + 1
        if by_loop:
            tentry["decisions_by_loop"] = dict(sorted(
                by_loop.items()))
        out["tune"] = tentry
    # SLO preempt-and-requeue events (policy, not failure — reported
    # apart from the Recovery section).
    preempts = [r for r in records if r.get("event") == "preempt"]
    if preempts:
        out["serve_preempt_events"] = len(preempts)
    # Paged-KV events (serve/paging): per-admission prefix hits and
    # pressure evictions (RECORDS.md: prefix_hit / page_evict).
    hits = [r for r in records if r.get("event") == "prefix_hit"]
    if hits:
        out["serve_prefix_hit_events"] = len(hits)
        out["serve_prefix_hit_tokens"] = sum(
            int(r.get("hit_tokens", 0)) for r in hits)
    evicts = [r for r in records if r.get("event") == "page_evict"]
    if evicts:
        out["serve_page_evict_events"] = len(evicts)
        out["serve_pages_evicted"] = sum(
            int(r.get("evicted", 0)) for r in evicts)
    if steps:
        out["last_step"] = max(int(r.get("step", 0)) for r in steps)
        # The freshest rolling-window stats (each step record carries
        # the window's p50/p95 at that point; the last one covers the
        # run's tail — the steady state).
        for key in ("step_ms_p50", "step_ms_p95", "data_ms",
                    "dispatch_ms", "device_ms", "comm_ms_est",
                    "comm_exposed_ms_est"):
            vals = [r[key] for r in steps if key in r]
            if vals:
                out[key] = round(vals[-1], 3)
        for key in ("tokens_per_sec", "images_per_sec", "items_per_sec",
                    "model_tflops", "mfu", "hw_mfu"):
            vals = [float(r[key]) for r in steps
                    if isinstance(r.get(key), (int, float))]
            if vals:
                out[f"mean_{key}"] = round(_mean(vals), 4)
        losses = [float(r["loss"]) for r in steps
                  if isinstance(r.get("loss"), (int, float))]
        if losses:
            out["first_loss"], out["last_loss"] = (round(losses[0], 5),
                                                   round(losses[-1], 5))
    if summaries:
        final = summaries[-1]
        for key, val in final.items():
            if key.endswith("_seconds") or key == "goodput":
                out[key] = val
    # Recovery events (resilience/ + serve fire paths): count by kind
    # plus the rewind/swap time totals, so ONE report shows traffic
    # and faults together.
    recoveries = [r for r in records if r.get("event") == "recovery"]
    if recoveries:
        counts: Dict[str, int] = {}
        for r in recoveries:
            kind = str(r.get("kind", "?"))
            counts[kind] = counts.get(kind, 0) + 1
        out["recovery_counts"] = dict(sorted(counts.items()))
        swap_s = sum(float(r.get("seconds", 0.0)) for r in recoveries
                     if r.get("kind") == "weight_swap"
                     and isinstance(r.get("seconds"), (int, float)))
        if swap_s:
            out["swap_seconds_total"] = round(swap_s, 4)
        # Elastic restarts: mesh_change (the supervisor's resize
        # decision) and reshard_restore (the loop's resharded resume,
        # which carries the resize window's wall time). A supervised
        # resize emits both — the transition path prefers the
        # supervisor's records, the seconds come from the restores.
        mesh_moves = [r for r in recoveries
                      if r.get("kind") in ("mesh_change",
                                           "reshard_restore")]
        if mesh_moves:
            def _fmt(shape):
                if not isinstance(shape, dict):
                    return "?"
                parts = [f"{k}={v}" for k, v in shape.items()
                         if v != 1]
                return ",".join(parts) if parts else "single-device"

            changes = [r for r in mesh_moves
                       if r.get("kind") == "mesh_change"] or mesh_moves
            out["mesh_changes"] = len(changes)
            out["mesh_change_path"] = ", ".join(
                f"{_fmt(r.get('from_mesh'))} -> {_fmt(r.get('to_mesh'))}"
                for r in changes)
            reshard_s = sum(
                float(r["seconds"]) for r in mesh_moves
                if r.get("kind") == "reshard_restore"
                and isinstance(r.get("seconds"), (int, float)))
            if reshard_s:
                out["reshard_seconds_total"] = round(reshard_s, 4)
    # Fleet serving (fleet/router.py + fleet/controller.py records):
    # the front-end's fleet_summary headline (goodput inputs,
    # staleness, shed counts, the dispatch-retry histogram) plus a
    # per-replica breakdown assembled from the dispatch / lifecycle /
    # swap event streams — rendered beside the Recovery section.
    fl_sums = [r for r in records if r.get("event") == "fleet_summary"]
    fl_disp = [r for r in records
               if r.get("event") == "fleet_dispatch"]
    fl_shed = [r for r in records if r.get("event") == "fleet_shed"]
    fl_rep = [r for r in records if r.get("event") == "fleet_replica"]
    fl_swap = [r for r in records if r.get("event") == "fleet_swap"]
    if fl_sums or fl_disp or fl_rep:
        entry: Dict[str, Any] = {}
        if fl_sums:
            final = fl_sums[-1]
            for key in ("requests", "requests_done", "requests_shed",
                        "requests_lost", "dispatches", "redispatches",
                        "dispatch_retry_hist", "quarantines",
                        "rejoins", "deaths", "restarts",
                        "rolling_swaps", "staleness_max_steps",
                        "tokens_per_sec", "wall_s", "ttft_ms_p50",
                        "ttft_ms_p95", "ttft_ms_p99",
                        "recovery_requests", "ttft_ms_p99_recovery",
                        "shed_by_class", "shed_reasons"):
                if key in final:
                    entry[key] = final[key]
        if "dispatch_retry_hist" not in entry and fl_disp:
            # No summary landed (crashed front-end): re-derive the
            # histogram from the dispatch records' retry tags.
            worst: Dict[Any, int] = {}
            for r in fl_disp:
                rid = r.get("rid")
                worst[rid] = max(worst.get(rid, 0),
                                 int(r.get("retry", 0)))
            hist: Dict[str, int] = {}
            for n in worst.values():
                hist[str(n)] = hist.get(str(n), 0) + 1
            entry["dispatch_retry_hist"] = dict(
                sorted(hist.items(), key=lambda kv: int(kv[0])))
        if fl_shed:
            entry["shed_events"] = len(fl_shed)
        replicas: Dict[str, Dict[str, Any]] = {}

        def _rep_entry(name: Any) -> Dict[str, Any]:
            return replicas.setdefault(str(name), {})

        for r in fl_disp:
            e = _rep_entry(r.get("replica", "?"))
            e["dispatches"] = e.get("dispatches", 0) + 1
        for r in fl_rep:
            e = _rep_entry(r.get("replica", "?"))
            state = str(r.get("state", "?"))
            e[state] = e.get(state, 0) + 1
        for r in fl_swap:
            e = _rep_entry(r.get("replica", "?"))
            e["swaps"] = e.get("swaps", 0) + 1
        if replicas:
            entry["replicas"] = dict(sorted(replicas.items()))
        # Fleet observatory (PR 16): client-perceived end-to-end
        # latency per class from the router's fleet_request records —
        # the SAME population + nearest-rank percentile the router's
        # summary and the --fleet.export-path snapshot use, so all
        # three agree exactly (snapshot == report, fleet level).
        fl_req = [r for r in records
                  if r.get("event") == "fleet_request"]
        if fl_req:
            entry["e2e_requests"] = len(fl_req)
            by_cls: Dict[str, List[float]] = {}
            for r in fl_req:
                if isinstance(r.get("ttft_ms"), (int, float)):
                    by_cls.setdefault(
                        str(r.get("slo", "standard")), []).append(
                        float(r["ttft_ms"]))
            for cls, vals in sorted(by_cls.items()):
                vals.sort()
                entry[f"ttft_ms_p50_{cls}"] = round(
                    _percentile(vals, 50), 3)
                entry[f"ttft_ms_p95_{cls}"] = round(
                    _percentile(vals, 95), 3)
            e2es = sorted(float(r["e2e_ms"]) for r in fl_req
                          if isinstance(r.get("e2e_ms"), (int, float)))
            if e2es:
                entry["e2e_ms_p95"] = round(_percentile(e2es, 95), 3)
        # Fleet SLO transitions (the router-level monitor): alert /
        # all-clear counts per target + the final budget floor.
        fl_alerts = [r for r in records
                     if r.get("event") == "fleet_slo_alert"]
        fl_oks = [r for r in records
                  if r.get("event") == "fleet_slo_ok"]
        if fl_alerts or fl_oks:
            slo_entry: Dict[str, Any] = {
                "alerts": len(fl_alerts), "oks": len(fl_oks)}
            by_tgt: Dict[str, int] = {}
            for r in fl_alerts:
                t = str(r.get("target", "?"))
                by_tgt[t] = by_tgt.get(t, 0) + 1
            if by_tgt:
                slo_entry["alerts_by_target"] = dict(
                    sorted(by_tgt.items()))
            budgets = [r.get("budget_remaining")
                       for r in fl_alerts + fl_oks
                       if isinstance(r.get("budget_remaining"),
                                     (int, float))]
            if budgets:
                slo_entry["budget_remaining_min"] = min(budgets)
            entry["slo"] = slo_entry
        # Per-dispatch latency decomposition (stitched-trace derived):
        # mean component split + the residual fraction the bench
        # gates.
        fl_dec = [r for r in records
                  if r.get("event") == "fleet_decomp"]
        if fl_dec:
            comps = ("e2e_ms", "router_queue_ms", "inbox_lag_ms",
                     "replica_queue_ms", "prefill_ms", "decode_ms",
                     "absorb_ms", "residual_ms")
            dec_entry: Dict[str, Any] = {"requests": len(fl_dec)}
            for key in comps:
                vals = [float(r.get(key, 0.0)) for r in fl_dec]
                dec_entry[f"{key}_mean"] = round(
                    sum(vals) / len(vals), 3)
            fracs = [abs(float(r.get("residual_ms", 0.0)))
                     / float(r["e2e_ms"]) for r in fl_dec
                     if float(r.get("e2e_ms", 0.0)) > 0]
            if fracs:
                dec_entry["residual_frac_mean"] = round(
                    sum(fracs) / len(fracs), 4)
            entry["decomposition"] = dec_entry
        fl_snaps = [r for r in records
                    if r.get("event") == "fleet_snapshot"]
        if fl_snaps:
            entry["snapshots"] = len(fl_snaps)
        out["fleet"] = entry
    # Incident observatory (observe/anomaly.py "anomaly" records +
    # observe/flightrec.py "postmortem" records): per-detector counts,
    # the last anomaly, and any postmortem bundle the run dumped.
    anoms = [r for r in records if r.get("event") == "anomaly"]
    if anoms:
        by_det: Dict[str, int] = {}
        for r in anoms:
            det = str(r.get("detector", "?"))
            by_det[det] = by_det.get(det, 0) + 1
        last = anoms[-1]
        out["anomalies"] = {
            "count": len(anoms),
            "by_detector": dict(sorted(by_det.items())),
            "last": {k: last[k] for k in
                     ("detector", "severity", "step") if k in last},
        }
    posts = [r for r in records if r.get("event") == "postmortem"]
    if posts:
        out["postmortem_bundles"] = [
            r.get("bundle") for r in posts if r.get("bundle")]
    # Auto-layout planner (--plan auto, analysis/planner): the chosen
    # mesh/strategy and its predicted step time, reported beside the
    # MEASURED step time when the run got far enough to have one —
    # the audit trail for "why is this run on this mesh".
    plans = [r for r in records if r.get("event") == "plan"]
    if plans:
        p = plans[-1]
        entry: Dict[str, Any] = {
            "family": p.get("family"),
            "mesh": p.get("mesh"),
            "strategy": p.get("strategy"),
            "partition": p.get("partition"),
            "predicted_step_ms": p.get("predicted_step_ms"),
            "predicted_peak_hbm_bytes": p.get(
                "predicted_peak_hbm_bytes"),
            "candidates": p.get("candidates"),
            "feasible": p.get("feasible"),
            "infeasible": p.get("infeasible"),
        }
        if p.get("calibration_id"):
            entry["calibration_id"] = p["calibration_id"]
        if "step_ms_p50" in out:
            entry["measured_step_ms_p50"] = out["step_ms_p50"]
        # Predicted -> measured drift the loop emitted at run end
        # (train/loop.py "plan_drift"): the cost model's error on this
        # very run, the signal a calibration refit consumes.
        drifts = [r for r in records if r.get("event") == "plan_drift"]
        if drifts:
            d = drifts[-1]
            entry["drift_ratio"] = d.get("drift_ratio")
            entry["measured_step_ms_p50"] = d.get(
                "measured_step_ms_p50", entry.get(
                    "measured_step_ms_p50"))
        out["plan"] = entry
    # Device-time attribution (observe/xprof.py "device_time"
    # records): measured device wall per program beside its roofline
    # prediction — the ground-truth layer. Latest record per program
    # (or per module for unmatched ones); explicit-null parses are
    # counted, not rendered as rows.
    dts = [r for r in records if r.get("event") == "device_time"]
    if dts:
        by_prog: Dict[str, Dict[str, Any]] = {}
        nulls = 0
        for r in dts:
            key = r.get("program") or r.get("module")
            if key is None or r.get("device_ms") is None:
                nulls += 1
                continue
            by_prog[str(key)] = r
        entries = []
        for key, r in sorted(by_prog.items(),
                             key=lambda kv: -(kv[1].get("device_ms")
                                              or 0)):
            entries.append({k: r.get(k) for k in (
                "program", "module", "device_ms",
                "device_ms_per_call", "calls", "predicted_ms_per_call",
                "collective_ms", "exposed_collective_ms", "coarse",
                "calibration_id") if r.get(k) is not None})
        out["device_time"] = entries
        if nulls:
            out["device_time_null_records"] = nulls
    # Compiled-program registry (observe/device.py "compile" records):
    # latest record per program — name, flops, peak-HBM estimate,
    # compile seconds — the device-side cost/memory inventory.
    compiles = [r for r in records if r.get("event") == "compile"]
    if compiles:
        by_name: Dict[str, Dict[str, Any]] = {}
        for r in compiles:
            if r.get("program"):
                by_name[r["program"]] = r
        out["programs"] = [
            {"program": name,
             "flops": rec.get("flops"),
             "peak_hbm_bytes": rec.get("peak_hbm_bytes"),
             "donated_bytes": rec.get("donated_bytes"),
             "compile_s": rec.get("compile_s")}
            for name, rec in sorted(by_name.items())]
        budgets = [r for r in records if r.get("event") == "hbm_budget"]
        if budgets and "peak_hbm_bytes_sum" in budgets[-1]:
            out["peak_hbm_bytes_sum"] = budgets[-1]["peak_hbm_bytes_sum"]
    # Per-module health records (observe/health.py): worst update
    # ratio over the run plus first->last grad-norm trend per module.
    healths = [r for r in records if r.get("event") == "health"]
    if healths:
        by_module: Dict[str, List[Dict[str, Any]]] = {}
        for r in healths:
            if r.get("module"):
                by_module.setdefault(r["module"], []).append(r)
        health_out: Dict[str, Dict[str, Any]] = {}
        for module, recs in sorted(by_module.items()):
            entry: Dict[str, Any] = {"records": len(recs)}
            ratios = [(float(r["update_ratio"]), int(r.get("step", 0)))
                      for r in recs
                      if isinstance(r.get("update_ratio"), (int, float))]
            if ratios:
                worst, at = max(ratios)
                entry["worst_update_ratio"] = round(worst, 8)
                entry["worst_update_ratio_step"] = at
            gnorms = [float(r["grad_norm"]) for r in recs
                      if isinstance(r.get("grad_norm"), (int, float))]
            if gnorms:
                entry["grad_norm_first"] = round(gnorms[0], 8)
                entry["grad_norm_last"] = round(gnorms[-1], 8)
            for key in ("param_rms", "act_rms"):
                vals = [float(r[key]) for r in recs
                        if isinstance(r.get(key), (int, float))]
                if vals:
                    entry[f"{key}_last"] = round(vals[-1], 8)
            health_out[module] = entry
        out["health"] = health_out
    # Per-host breakdown, only when records from more than one host
    # tag are merged (multi-host runs: one JSONL per process, each
    # stamped with its process_index by registry.host_tags).
    hosts = sorted({r.get("process_index") for r in records
                    if r.get("process_index") is not None})
    if len(hosts) > 1:
        per_host: Dict[str, Dict[str, Any]] = {}
        for host in hosts:
            recs = [r for r in records
                    if r.get("process_index") == host]
            hsteps = [r for r in recs if r.get("event") == "step"]
            entry = {"records": len(recs)}
            if hsteps:
                entry["step_records"] = len(hsteps)
                entry["last_step"] = max(int(r.get("step", 0))
                                         for r in hsteps)
                p50s = [r["step_ms_p50"] for r in hsteps
                        if "step_ms_p50" in r]
                if p50s:
                    entry["step_ms_p50"] = round(p50s[-1], 3)
            hserve = [r for r in recs
                      if r.get("event") == "serve_request"]
            if hserve:
                entry["serve_requests"] = len(hserve)
            per_host[str(host)] = entry
        out["hosts"] = per_host
    return out





def render(summary: Dict[str, Any]) -> str:
    lines = ["observe.report"]
    order = ("records", "step_records", "last_step", "step_ms_p50",
             "step_ms_p95", "data_ms", "dispatch_ms", "device_ms",
             "mean_tokens_per_sec", "mean_images_per_sec",
             "mean_items_per_sec", "mean_model_tflops", "mean_mfu",
             "mean_hw_mfu", "first_loss", "last_loss", "goodput",
             "serve_requests", "serve_ttft_ms_p50", "serve_ttft_ms_p95",
             "serve_ttft_ms_p99", "serve_recovery_requests",
             "serve_ttft_ms_p99_recovery",
             "serve_tok_ms_mean", "serve_tokens_per_sec",
             "serve_mean_slot_occupancy", "serve_total_new_tokens",
             "serve_prefill_compiles", "serve_retries", "serve_swaps",
             "serve_swap_seconds", "serve_policy", "serve_preemptions",
             "serve_preempt_events", "serve_spec_tokens",
             "serve_verify_steps", "serve_accept_rate",
             "serve_spec_fallback_slots", "serve_tune_actions",
             "serve_slo_alerts",
             "serve_slo_budget_remaining_min", "serve_slo_targets",
             "serve_seed", "serve_trace", "snapshots")
    # plan/programs/health/recovery/slo render as their own sections
    # below; peak_hbm_bytes_sum renders as the Programs TOTAL row.
    sections = ("plan", "programs", "health", "peak_hbm_bytes_sum",
                "recovery_counts", "swap_seconds_total",
                "mesh_changes", "mesh_change_path",
                "reshard_seconds_total", "slo", "snapshot_last",
                "phase_ms", "iter_ms", "admissions", "admitted_at_once",
                "request_parts",
                "tune", "fleet", "anomalies", "postmortem_bundles",
                "device_time", "device_time_null_records", "hosts",
                # rendered inside the Device time section, not the
                # generic stats list (one print per number).
                "comm_ms_est", "comm_exposed_ms_est")
    for key in order:
        if key in summary:
            lines.append(f"  {key:<22} {summary[key]}")
    extras = [k for k in sorted(summary)
              if k not in order and k not in sections]
    for key in extras:
        lines.append(f"  {key:<22} {summary[key]}")
    if "plan" in summary:
        # Lazy, stdlib-only import: THE planner mesh formatter.
        from tensorflow_distributed_tpu.analysis.planner.candidates \
            import format_mesh
        p = summary["plan"]
        mesh = p.get("mesh") or {}
        mesh_s = format_mesh(mesh) if isinstance(mesh, dict) else "?"
        lines.append("Plan")
        lines.append(f"  {'chosen':<28} {mesh_s} "
                     f"[{p.get('strategy')}] "
                     f"partition={p.get('partition')}")
        pred = p.get("predicted_step_ms")
        meas = p.get("measured_step_ms_p50")
        step_line = (f"predicted={pred} ms"
                     if pred is not None else "predicted=-")
        if meas is not None:
            step_line += f" measured_p50={meas} ms"
        lines.append(f"  {'step_time':<28} {step_line}")
        lines.append(
            f"  {'peak_hbm':<28} "
            f"{_device.human_bytes(p.get('predicted_peak_hbm_bytes'))}")
        lines.append(f"  {'candidates':<28} {p.get('candidates')} "
                     f"({p.get('feasible')} feasible, "
                     f"{p.get('infeasible')} infeasible)")
        if p.get("drift_ratio") is not None:
            drift = (f"{p['drift_ratio']}x measured/predicted")
            if p.get("calibration_id"):
                drift += f" (calibration {p['calibration_id']})"
            lines.append(f"  {'drift':<28} {drift}")
    if "programs" in summary:
        lines.append("Programs")
        for p in summary["programs"]:
            flops = ("-" if p.get("flops") is None
                     else f"{p['flops']:.3g}")
            comp = ("-" if p.get("compile_s") is None
                    else f"{p['compile_s']:.3f}s")
            lines.append(
                f"  {p['program']:<28} flops={flops:<10} "
                f"peak_hbm={_device.human_bytes(p.get('peak_hbm_bytes')):<10} "
                f"compile={comp}")
        if "peak_hbm_bytes_sum" in summary:
            lines.append(f"  {'TOTAL (all resident)':<28} "
                         f"peak_hbm="
                         f"{_device.human_bytes(summary['peak_hbm_bytes_sum'])}")
    if "device_time" in summary:
        lines.append("Device time")
        for e in summary["device_time"]:
            name = e.get("program") or e.get("module") or "?"
            meas = e.get("device_ms_per_call")
            pred = e.get("predicted_ms_per_call")
            parts = []
            if meas is not None:
                parts.append(f"measured={meas}ms/call"
                             + (f" x{e['calls']}" if e.get("calls")
                                else ""))
            elif e.get("device_ms") is not None:
                parts.append(f"total={e['device_ms']}ms")
            if pred is not None:
                parts.append(f"predicted={pred}ms")
                if isinstance(meas, (int, float)) and pred:
                    parts.append(f"ratio={meas / pred:.2f}")
            if e.get("collective_ms"):
                parts.append(f"comm={e['collective_ms']}ms"
                             f"(exposed "
                             f"{e.get('exposed_collective_ms')}ms)")
            if e.get("coarse"):
                parts.append("[coarse]")
            lines.append(f"  {name:<28} " + " ".join(parts))
        for key in ("comm_ms_est", "comm_exposed_ms_est"):
            # The overlap grad-sync ESTIMATES next to the trace-derived
            # measurement above — predicted vs ground truth for the
            # exposed-comm story too.
            if key in summary:
                lines.append(f"  {key:<28} {summary[key]}ms "
                             f"(step-record estimate)")
        if "device_time_null_records" in summary:
            lines.append(f"  {'null_records':<28} "
                         f"{summary['device_time_null_records']} "
                         f"(absent/coarse profiler data)")
    if "hosts" in summary:
        lines.append("Hosts")
        for host, entry in summary["hosts"].items():
            parts = [f"records={entry.get('records')}"]
            for key in ("last_step", "step_ms_p50", "serve_requests"):
                if key in entry:
                    parts.append(f"{key}={entry[key]}")
            lines.append(f"  process {host:<20} " + " ".join(parts))
    if "recovery_counts" in summary:
        lines.append("Recovery")
        for kind, n in summary["recovery_counts"].items():
            lines.append(f"  {kind:<28} {n}")
        if "swap_seconds_total" in summary:
            lines.append(f"  {'swap_seconds_total':<28} "
                         f"{summary['swap_seconds_total']}")
        if "mesh_changes" in summary:
            lines.append(f"  {'mesh_changes':<28} "
                         f"{summary['mesh_changes']} "
                         f"({summary['mesh_change_path']})")
        if "reshard_seconds_total" in summary:
            lines.append(f"  {'reshard_seconds_total':<28} "
                         f"{summary['reshard_seconds_total']}")
    if "fleet" in summary:
        fl = summary["fleet"]
        lines.append("Fleet")
        head = []
        for key in ("requests", "requests_done", "requests_shed",
                    "requests_lost"):
            if key in fl:
                head.append(f"{key.replace('requests_', '')}="
                            f"{fl[key]}")
        if head:
            lines.append(f"  {'requests':<28} " + " ".join(head))
        avail = []
        for key in ("quarantines", "rejoins", "deaths", "restarts",
                    "shed_events"):
            if key in fl:
                avail.append(f"{key}={fl[key]}")
        if avail:
            lines.append(f"  {'availability':<28} " + " ".join(avail))
        loop_bits = []
        for key in ("rolling_swaps", "staleness_max_steps",
                    "tokens_per_sec", "wall_s"):
            if key in fl:
                loop_bits.append(f"{key}={fl[key]}")
        if loop_bits:
            lines.append(f"  {'train->serve':<28} "
                         + " ".join(loop_bits))
        rec_bits = []
        for key in ("recovery_requests", "ttft_ms_p99_recovery",
                    "ttft_ms_p50", "ttft_ms_p95", "ttft_ms_p99"):
            if key in fl:
                rec_bits.append(f"{key}={fl[key]}")
        if rec_bits:
            lines.append(f"  {'latency':<28} " + " ".join(rec_bits))
        if "dispatch_retry_hist" in fl:
            hist = " ".join(f"{k}x:{v}" for k, v in
                            fl["dispatch_retry_hist"].items())
            lines.append(f"  {'dispatch_retry_hist':<28} {hist}")
        if "shed_by_class" in fl and fl["shed_by_class"]:
            lines.append(f"  {'shed_by_class':<28} "
                         f"{fl['shed_by_class']}")
        e2e_bits = [f"{k}={v}" for k, v in sorted(fl.items())
                    if k.startswith("ttft_ms_p50_")
                    or k.startswith("ttft_ms_p95_")]
        if "e2e_ms_p95" in fl:
            e2e_bits.append(f"e2e_ms_p95={fl['e2e_ms_p95']}")
        if e2e_bits:
            lines.append(f"  {'e2e latency (per class)':<28} "
                         + " ".join(e2e_bits))
        if "slo" in fl:
            se = fl["slo"]
            bits = [f"alerts={se.get('alerts', 0)}",
                    f"oks={se.get('oks', 0)}"]
            if "budget_remaining_min" in se:
                bits.append(
                    f"budget_min={se['budget_remaining_min']}")
            if se.get("alerts_by_target"):
                bits.append(str(se["alerts_by_target"]))
            lines.append(f"  {'fleet slo':<28} " + " ".join(bits))
        if "decomposition" in fl:
            de = fl["decomposition"]
            lines.append(
                f"  {'decomposition (mean ms)':<28} "
                f"e2e={de.get('e2e_ms_mean', 0)} = "
                f"router_q {de.get('router_queue_ms_mean', 0)} + "
                f"inbox {de.get('inbox_lag_ms_mean', 0)} + "
                f"replica_q {de.get('replica_queue_ms_mean', 0)} + "
                f"prefill {de.get('prefill_ms_mean', 0)} + "
                f"decode {de.get('decode_ms_mean', 0)} + "
                f"absorb {de.get('absorb_ms_mean', 0)} + "
                f"residual {de.get('residual_ms_mean', 0)}"
                + (f" (frac={de['residual_frac_mean']})"
                   if "residual_frac_mean" in de else ""))
        for name, entry in (fl.get("replicas") or {}).items():
            bits = " ".join(f"{k}={v}" for k, v in
                            sorted(entry.items()))
            lines.append(f"  replica {name:<20} {bits}")
    if "phase_ms" in summary:
        wall_ms = 1e3 * float(summary.get("wall_s") or 0.0)
        lines.append("Serve host phases (self time; worst span at "
                     "step / run second)")
        for name, row in sorted(summary["phase_ms"].items(),
                                key=lambda kv: -kv[1]["sum_ms"]):
            share = (f"{100 * row['sum_ms'] / wall_ms:5.1f}%"
                     if wall_ms else "     -")
            lines.append(
                f"  {name:<30} {row['sum_ms']:>10.1f} ms {share} "
                f"n={row['count']:<6} max {row['max_ms']} ms @ step "
                f"{row['max_step']} / {row['max_at_s']}s")
        if "iter_ms" in summary:
            kinds = "  ".join(
                f"{kind} {ms:.1f} ms"
                + (f" ({100 * ms / wall_ms:.1f}%)" if wall_ms else "")
                for kind, ms in summary["iter_ms"].items())
            admits = summary.get("admissions")
            once = summary.get("admitted_at_once")
            lines.append(f"  by kind of iteration: {kinds}  "
                         f"admissions={admits}"
                         + (f" ({100 * once / admits:.1f}% at once)"
                            if admits and once is not None else ""))
    if "request_parts" in summary:
        lines.append("Where a request's time went (ms by kind of "
                     "scheduler iteration; mean / p95)")
        for name, row in summary["request_parts"].items():
            lines.append(f"  {name:<30} {row['mean']:>10.3f} / "
                         f"{row['p95']:<10.3f} n={row['n']}")
    if "slo" in summary:
        lines.append("SLO")
        for target, entry in summary["slo"].items():
            parts = [f"alerts={entry.get('alerts', 0)}"]
            if "worst_burn_fast" in entry:
                parts.append(
                    f"worst_burn_fast={entry['worst_burn_fast']:.2f}")
            if "budget_remaining" in entry:
                parts.append(
                    f"budget_remaining={entry['budget_remaining']}")
            lines.append(f"  {target:<28} " + " ".join(parts))
    if "snapshot_last" in summary:
        lines.append("Snapshot (final)")
        entry = summary["snapshot_last"]
        for key in sorted(entry):
            lines.append(f"  {key:<28} {entry[key]}")
    if "tune" in summary:
        lines.append("Autopilot")
        entry = summary["tune"]
        for key in sorted(entry):
            lines.append(f"  {key:<28} {entry[key]}")
    if "anomalies" in summary:
        lines.append("Anomalies")
        entry = summary["anomalies"]
        for det, n in entry.get("by_detector", {}).items():
            lines.append(f"  {det:<28} {n}")
        last = entry.get("last", {})
        if last:
            lines.append(
                f"  {'last':<28} {last.get('detector')} "
                f"severity={last.get('severity')} "
                f"step={last.get('step')}")
    if "postmortem_bundles" in summary:
        lines.append("Postmortem bundles")
        for path in summary["postmortem_bundles"]:
            lines.append(f"  {path} (render: python -m "
                         f"tensorflow_distributed_tpu.observe"
                         f".postmortem {path})")
    if "health" in summary:
        lines.append("Health")
        for module, entry in summary["health"].items():
            parts = []
            if "worst_update_ratio" in entry:
                parts.append(
                    f"worst_update_ratio={entry['worst_update_ratio']:.2e}"
                    f"@{entry['worst_update_ratio_step']}")
            if "grad_norm_first" in entry:
                parts.append(
                    f"grad_norm={entry['grad_norm_first']:.3g}->"
                    f"{entry['grad_norm_last']:.3g}")
            for key in ("param_rms_last", "act_rms_last"):
                if key in entry:
                    parts.append(f"{key}={entry[key]:.3g}")
            lines.append(f"  {module:<28} " + " ".join(parts))
    return "\n".join(lines)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="tensorflow_distributed_tpu.observe.report",
        description=__doc__)
    parser.add_argument("jsonl", nargs="+",
                        help="metrics JSONL(s) written by the observe "
                        "JSONL sink — multiple host-tagged streams "
                        "merge into one report")
    parser.add_argument("--json", action="store_true",
                        help="print one JSON object instead of text")
    args = parser.parse_args(argv)
    try:
        records = []
        for path in args.jsonl:
            records.extend(load_records(path))
    except (OSError, ValueError) as e:
        print(f"observe.report: {e}", file=sys.stderr)
        return 1
    summary = summarize(records)
    print(json.dumps(summary) if args.json else render(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
