"""Cross-run regression ledger over the committed bench artifacts.

Every perf PR in this repo gates on a committed artifact (GRADSYNC,
SERVEBENCH, SLOBENCH, FIREBENCH, ELASTICBENCH, PLANBENCH, CALIBBENCH,
...), but until now "did my change regress an OLD gate" meant eyeballing
JSON diffs. This module is the declarative alternative: a MANIFEST maps
each artifact to its headline metrics — where the number lives, which
direction is good, and how much noise a rerun is allowed — and the CLI
compares a fresh artifact (or the working tree's copy) against the
COMMITTED baseline (``git show <ref>:<name>``), printing a readable
table and exiting nonzero on any regression::

    # the working tree's artifacts vs HEAD (the t1 smoke — clean tree
    # must pass clean):
    python -m tensorflow_distributed_tpu.observe.regress

    # a freshly-regenerated artifact vs the committed one:
    python -m tensorflow_distributed_tpu.observe.regress \
        --artifact FIREBENCH.json --fresh /tmp/FIREBENCH.json

Check semantics (per fresh-vs-baseline pair):

- ``higher`` / ``lower``: the good direction; a move the BAD way
  beyond ``max(rtol*|baseline|, atol)`` is a REGRESSION, beyond it
  the GOOD way is reported IMPROVED, inside the band is OK. CPU
  timings carry generous rtols — the ledger flags real slides, not
  scheduler jitter.
- ``truthy``: a gate bool (or a must-be-nonzero count) that must stay
  truthy. A baseline that is ALREADY falsy skips the check (an
  expected-broken artifact — e.g. a TPU-probe snapshot recorded with
  rc!=0 — must not block unrelated PRs).
- ``equal``: exact (correctness counts like token_identical 32/32).

A metric missing from the fresh artifact while present in the baseline
is a regression (gates must not silently disappear); present only in
the fresh one is reported as new and passes. Artifacts not present in
the baseline ref are skipped with a note — the ledger audits committed
history, it doesn't invent it.

Stdlib-only (jax-free, fast): the manifest is data, the comparisons
are arithmetic, git is the only external dependency and only for
baseline loading (``--baseline`` sidesteps it).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


@dataclasses.dataclass(frozen=True)
class Check:
    """One ledger entry: where the number lives and what "worse"
    means. ``path`` is dotted; in a JSONL artifact the FIRST component
    selects the line by its ``metric`` field, the rest walks into the
    record (``fire_goodput.value``). ``rtol`` is relative to the
    baseline, ``atol`` absolute — the noise band is their max."""

    path: str
    direction: str  # higher | lower | truthy | equal
    rtol: float = 0.0
    atol: float = 0.0


def _jsonl_checks(*specs) -> List[Check]:
    return [Check(*s) for s in specs]


#: artifact name -> (format, checks). Formats: "jsonl" (one JSON per
#: line, "metric"-discriminated), "json" (one document).
MANIFEST: Dict[str, Tuple[str, List[Check]]] = {
    "GRADSYNC.json": ("json", _jsonl_checks(
        ("checks.identity", "truthy"),
        ("checks.overlap_not_slower", "truthy"),
        ("identity.params", "truthy"),
        ("steps.overlap.min_ms", "lower", 0.5),
        ("steps.serial.min_ms", "lower", 0.5),
        ("exposed_comm_ms.overlap", "lower", 0.6),
        ("ok", "truthy"),
    )),
    "SERVEBENCH.json": ("jsonl", _jsonl_checks(
        ("serve_speedup.value", "higher", 0.5),
        ("serve_continuous_tokens_per_sec.value", "higher", 0.5),
        ("serve_spec_tokens_per_sec.value", "higher", 0.5),
        ("serve_spec_tokens_per_sec.accept_rate", "higher", 0.0, 0.05),
        ("serve_spec_speedup.value", "higher", 0.3),
        ("serve_int8_slots_at_budget.ratio", "higher", 0.0, 0.05),
        ("serve_int8_greedy_divergence.value", "lower", 0.0, 0.0),
        ("serve_slo_p95_ttft_high.ratio", "lower", 1.0),
        ("serve_checks.speedup_ok", "truthy"),
        ("serve_checks.token_identical", "equal"),
        ("serve_tp_cache_bytes_per_slot.ratio", "higher", 0.0, 0.05),
        ("serve_checks.tp_cache_ratio_ok", "truthy"),
        ("serve_checks.tp_token_identical", "equal"),
    )),
    "SLOBENCH.json": ("jsonl", _jsonl_checks(
        ("slo_control_alerts.value", "lower", 0.0, 0.0),
        ("slo_fire_alerts.value", "truthy"),
        ("slo_instrumentation_tokens_per_sec.ratio",
         "higher", 0.0, 0.1),
        ("slo_checks.control_quiet", "truthy"),
        ("slo_checks.fire_alerted", "truthy"),
        ("slo_checks.traces_balanced", "truthy"),
        ("slo_checks.recovery_instants_ok", "truthy"),
        ("slo_checks.trace_spans_restart", "truthy"),
    )),
    "TUNEBENCH.json": ("jsonl", _jsonl_checks(
        ("tune_goodput.ratio", "higher", 0.1),
        ("tune_control.tune_actions", "lower", 0.0, 0.0),
        ("tune_autopilot_tokens_per_sec.ratio", "higher", 0.0, 0.1),
        ("tune_checks.converged", "truthy"),
        ("tune_checks.identity", "truthy"),
        ("tune_checks.quiet_control", "truthy"),
        ("tune_checks.spec_retuned", "truthy"),
        ("tune_checks.cli_wired", "truthy"),
        ("tune_checks.overhead_ok", "truthy"),
        ("tune_checks.evidence_ok", "truthy"),
    )),
    "FIREBENCH.json": ("jsonl", _jsonl_checks(
        ("fire_goodput.value", "higher", 0.15),
        ("fire_tokens_per_sec.value", "higher", 0.5),
        ("fire_checks.goodput_ok", "truthy"),
        ("fire_checks.lost_requests", "lower", 0.0, 0.0),
        ("fire_checks.token_identical", "equal"),
    )),
    "ELASTICBENCH.json": ("jsonl", _jsonl_checks(
        ("elastic_shrink_last_loss.delta_vs_baseline",
         "lower", 0.0, 1e-3),
        ("elastic_grow_last_loss.delta_vs_baseline",
         "lower", 0.0, 1e-3),
        ("elastic_shrink_reshard_seconds.value", "lower", 1.0),
        ("elastic_checks.shrink_loss_ok", "truthy"),
        ("elastic_checks.shrink_zero_lost_steps", "truthy"),
        ("elastic_checks.shrink_resharded_ok", "truthy"),
        ("elastic_checks.grow_loss_ok", "truthy"),
        ("elastic_checks.grow_zero_lost_steps", "truthy"),
        ("elastic_checks.grow_resharded_ok", "truthy"),
    )),
    "PLANBENCH.json": ("jsonl", _jsonl_checks(
        ("plan_checks.gpt.pick_ok", "truthy"),
        ("plan_checks.gpt.pick_vs_best", "lower", 0.0, 0.15),
        ("plan_checks.gpt.hbm_rank_ok", "truthy"),
        ("plan_checks.moe.pick_ok", "truthy"),
        ("plan_checks.moe.pick_vs_best", "lower", 0.0, 0.15),
        ("plan_checks.moe.hbm_rank_ok", "truthy"),
    )),
    "CALIBBENCH.json": ("jsonl", _jsonl_checks(
        ("calib_checks.calibrated_better", "truthy"),
        ("calib_checks.within_band", "truthy"),
        ("calib_checks.regress_flags_degraded", "truthy"),
        ("calib_checks.regress_clean_on_committed", "truthy"),
        ("calib_fit.calibrated_median_rel_err", "lower", 0.0, 0.25),
    )),
    "DETECTBENCH.json": ("jsonl", _jsonl_checks(
        # Recall/precision/bundle gates are exact (deterministic fault
        # plans are ground truth); the overhead ratio carries a
        # generous CPU band.
        ("detect_checks.recall_ok", "truthy"),
        ("detect_checks.precision_ok", "truthy"),
        ("detect_checks.bundle_ok", "truthy"),
        ("detect_checks.overhead_ok", "truthy"),
        ("detect_train_recall.flagged", "equal"),
        ("detect_serve_recall.flagged", "equal"),
        ("detect_train_precision.anomalies", "lower", 0.0, 0.0),
        ("detect_serve_precision.anomalies", "lower", 0.0, 0.0),
        ("detect_bundle.named_in_restart", "truthy"),
        ("detect_bundle.postmortem_cli_ok", "truthy"),
        ("detect_overhead.ratio", "higher", 0.0, 0.1),
    )),
    "PAGEBENCH.json": ("jsonl", _jsonl_checks(
        # Hit-rate / identity / lost are exact (seeded trace, greedy
        # determinism); the warm-TTFT ratio carries a generous CPU
        # band; FLOPs-saved and slots-at-budget are arithmetic over
        # engine counters — tight noise bands.
        ("page_checks.token_identical", "equal"),
        ("page_checks.dense_identical", "equal"),
        ("page_checks.lost", "lower", 0.0, 0.0),
        ("page_checks.flops_ok", "truthy"),
        ("page_checks.slots_ok", "truthy"),
        ("page_checks.ttft_ok", "truthy"),
        ("page_prefill_flops.saved_frac", "higher", 0.0, 0.05),
        ("page_hit.rate", "higher", 0.0, 0.1),
        ("page_hbm.slots_ratio", "higher", 0.0, 0.1),
        ("page_warm_ttft.ratio", "lower", 0.5),
    )),
    "FLEETBENCH.json": ("jsonl", _jsonl_checks(
        # Correctness gates are exact (token identity, zero lost/
        # shed, control quiet, drills fired); goodput carries a
        # generous CPU band and the recovery p99 is bounded by its
        # own gate bool rather than a noisy ms compare.
        ("fleet_checks.identity_token_identical", "equal"),
        ("fleet_checks.identity_lost", "lower", 0.0, 0.0),
        ("fleet_checks.identity_drills_ok", "truthy"),
        ("fleet_checks.goodput_ok", "truthy"),
        ("fleet_checks.loop_lost", "lower", 0.0, 0.0),
        ("fleet_checks.loop_shed", "lower", 0.0, 0.0),
        ("fleet_checks.control_quiet_ok", "truthy"),
        ("fleet_checks.recovery_p99_ok", "truthy"),
        ("fleet_checks.staleness_ok", "truthy"),
        ("fleet_checks.swaps_ok", "truthy"),
        ("fleet_checks.fault_drills_ok", "truthy"),
        ("fleet_goodput.value", "higher", 0.15),
        ("fleet_fault_staleness.rolling_swaps", "equal"),
    )),
    "FLEETOBSBENCH.json": ("jsonl", _jsonl_checks(
        # Observatory gates are bools the bench itself derives
        # (stitched-trace balance across a real SIGKILL failover,
        # alert-on-fault/quiet-on-control, decomposition residual,
        # snapshot==report parity, fleetview render); the only analog
        # metric is the tracing-overhead throughput ratio, banded
        # generously for CPU noise on top of its own >= gate.
        ("fleetobs_checks.control_quiet", "truthy"),
        ("fleetobs_checks.fault_alerted", "truthy"),
        ("fleetobs_checks.lost", "lower", 0.0, 0.0),
        ("fleetobs_checks.traces_balanced", "truthy"),
        ("fleetobs_checks.failover_legs_ok", "truthy"),
        ("fleetobs_checks.decomp_ok", "truthy"),
        ("fleetobs_checks.snapshot_agrees_with_report", "truthy"),
        ("fleetobs_checks.fleetview_ok", "truthy"),
        ("fleetobs_checks.overhead_ok", "truthy"),
        ("fleetobs_overhead.ratio", "higher", 0.1),
    )),
    "GENBENCH.json": ("jsonl", _jsonl_checks(
        ("gen_prefill_tokens_per_sec.value", "higher", 0.3),
        ("gen_decode_tokens_per_sec.value", "higher", 0.3),
        ("gen_decode_tokens_per_sec_gqa.value", "higher", 0.3),
    )),
    "MOEBENCH.json": ("jsonl", _jsonl_checks(
        ("moe_train_tokens_per_sec.value", "higher", 0.3),
        ("moe_train_active_mfu.value", "higher", 0.3),
    )),
    "RINGBENCH.json": ("jsonl", _jsonl_checks(
        ("ring_block_flash_vs_einsum_fwd_speedup.value",
         "higher", 0.3),
    )),
}


def manifest_for(name: str) -> Optional[Tuple[str, List[Check]]]:
    return MANIFEST.get(name)


def manifest_names() -> List[str]:
    """Every artifact the ledger covers that exists in the working
    tree."""
    return sorted(n for n in MANIFEST
                  if os.path.exists(os.path.join(REPO_ROOT, n)))


# --- artifact loading --------------------------------------------------

def parse_artifact(text: str, fmt: str) -> Dict[str, Any]:
    """Normalize to one navigable dict: JSON documents pass through;
    JSONL becomes ``{metric: record}`` (last line per metric wins —
    reruns replace)."""
    if fmt == "json":
        return json.loads(text)
    out: Dict[str, Any] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(rec, dict) and rec.get("metric"):
            out[str(rec["metric"])] = rec
    return out


_MISSING = object()


def resolve(doc: Any, path: str) -> Any:
    """Walk a dotted path; the sentinel ``_MISSING`` (is-checked by
    callers) when any component is absent."""
    cur = doc
    for part in path.split("."):
        if isinstance(cur, dict) and part in cur:
            cur = cur[part]
        else:
            return _MISSING
    return cur


def baseline_text(name: str, ref: str = "HEAD") -> Optional[str]:
    """The committed artifact's bytes at ``ref`` (None when untracked
    there, or git is unavailable)."""
    try:
        out = subprocess.run(["git", "show", f"{ref}:{name}"],
                             capture_output=True, text=True,
                             timeout=30, cwd=REPO_ROOT)
    except Exception:
        return None
    return out.stdout if out.returncode == 0 else None


# --- comparison --------------------------------------------------------

def compare_check(check: Check, base_doc: Dict[str, Any],
                  fresh_doc: Dict[str, Any]) -> Dict[str, Any]:
    """One check's finding: verdict in ok | improved | regression |
    skip (+ baseline/fresh/why)."""
    base = resolve(base_doc, check.path)
    fresh = resolve(fresh_doc, check.path)
    out: Dict[str, Any] = {"check": check.path,
                           "direction": check.direction,
                           "baseline": None if base is _MISSING else base,
                           "fresh": None if fresh is _MISSING else fresh}
    if base is _MISSING:
        out.update(verdict="skip", why="not in baseline (new metric)")
        return out
    if fresh is _MISSING:
        out.update(verdict="regression",
                   why="metric disappeared from the fresh artifact")
        return out
    if check.direction == "truthy":
        if not base:
            out.update(verdict="skip", why="baseline already failing")
        elif not fresh:
            out.update(verdict="regression", why="gate went falsy")
        else:
            out.update(verdict="ok")
        return out
    if check.direction == "equal":
        out.update(verdict="ok" if fresh == base else "regression",
                   why=None if fresh == base else "exact gate changed")
        return out
    if not isinstance(base, (int, float)) or not isinstance(
            fresh, (int, float)) or isinstance(base, bool) \
            or isinstance(fresh, bool):
        out.update(verdict="skip", why="non-numeric value")
        return out
    band = max(check.rtol * abs(float(base)), check.atol)
    delta = float(fresh) - float(base)
    worse = delta > band if check.direction == "lower" \
        else -delta > band
    better = -delta > band if check.direction == "lower" \
        else delta > band
    out["band"] = round(band, 6)
    if worse:
        out.update(verdict="regression",
                   why=f"moved {delta:+.6g} ({check.direction} is "
                       f"better; band ±{band:.6g})")
    elif better:
        out.update(verdict="improved")
    else:
        out.update(verdict="ok")
    return out


def compare_artifact(name: str, fresh_path: Optional[str] = None,
                     baseline_path: Optional[str] = None,
                     ref: str = "HEAD") -> List[Dict[str, Any]]:
    """Every manifest finding for one artifact. ``fresh_path``
    defaults to the working-tree copy, the baseline to
    ``git show <ref>:<name>`` (``baseline_path`` overrides for
    git-free use)."""
    spec = manifest_for(name)
    if spec is None:
        return [{"artifact": name, "verdict": "skip",
                 "why": "no manifest entry"}]
    fmt, checks = spec
    fresh_path = fresh_path or os.path.join(REPO_ROOT, name)
    if not os.path.exists(fresh_path):
        return [{"artifact": name, "verdict": "regression",
                 "why": f"fresh artifact missing: {fresh_path}"}]
    with open(fresh_path) as f:
        fresh_doc = parse_artifact(f.read(), fmt)
    if baseline_path is not None:
        with open(baseline_path) as f:
            base_text: Optional[str] = f.read()
    else:
        base_text = baseline_text(name, ref)
    if base_text is None:
        return [{"artifact": name, "verdict": "skip",
                 "why": f"not committed at {ref}"}]
    base_doc = parse_artifact(base_text, fmt)
    findings = []
    for check in checks:
        finding = compare_check(check, base_doc, fresh_doc)
        finding["artifact"] = name
        findings.append(finding)
    return findings


def render_table(findings: Sequence[Dict[str, Any]]) -> str:
    def fmt_val(v):
        if isinstance(v, float):
            return f"{v:.6g}"
        s = str(v)
        return s if len(s) <= 18 else s[:15] + "..."

    lines = [f"{'artifact':<18} {'check':<44} {'baseline':>12} "
             f"{'fresh':>12} verdict"]
    for f in findings:
        mark = {"ok": "ok", "improved": "OK+", "skip": "--",
                "regression": "REGRESSION"}[f["verdict"]]
        lines.append(
            f"{f.get('artifact', '?'):<18} {f.get('check', '-'):<44} "
            f"{fmt_val(f.get('baseline', '-')):>12} "
            f"{fmt_val(f.get('fresh', '-')):>12} {mark}")
        if f.get("why") and f["verdict"] != "ok":
            lines.append(f"{'':<18}   ^ {f['why']}")
    n_reg = sum(1 for f in findings if f["verdict"] == "regression")
    n_imp = sum(1 for f in findings if f["verdict"] == "improved")
    lines.append(f"regress: {len(findings)} checks, {n_reg} "
                 f"regression(s), {n_imp} improvement(s)")
    return "\n".join(lines)


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tensorflow_distributed_tpu.observe.regress",
        description="compare bench artifacts against the committed "
                    "baseline; exit 1 on any regression")
    parser.add_argument("--artifact", action="append", default=[],
                        help="artifact name(s) to check (default: "
                        "every manifest artifact present in the "
                        "working tree)")
    parser.add_argument("--fresh", default="",
                        help="path of a freshly-generated artifact "
                        "(requires exactly one --artifact; default: "
                        "the working-tree copy)")
    parser.add_argument("--baseline", default="",
                        help="explicit baseline file (default: git "
                        "show <ref>:<name>)")
    parser.add_argument("--ref", default="HEAD",
                        help="git ref the baseline is read from")
    parser.add_argument("--list", action="store_true",
                        help="print the manifest and exit")
    parser.add_argument("--json", action="store_true",
                        help="machine-readable findings")
    args = parser.parse_args(argv)
    if args.list:
        for name in sorted(MANIFEST):
            fmt, checks = MANIFEST[name]
            print(f"{name} ({fmt})")
            for c in checks:
                band = (f" rtol={c.rtol}" if c.rtol else "") + (
                    f" atol={c.atol}" if c.atol else "")
                print(f"  {c.path:<46} {c.direction}{band}")
        return 0
    names = args.artifact or manifest_names()
    if args.fresh and len(names) != 1:
        parser.error("--fresh needs exactly one --artifact")
    findings: List[Dict[str, Any]] = []
    for name in names:
        findings.extend(compare_artifact(
            name, fresh_path=args.fresh or None,
            baseline_path=args.baseline or None, ref=args.ref))
    print(json.dumps(findings, default=str) if args.json
          else render_table(findings))
    bad = [f for f in findings if f["verdict"] == "regression"]
    if bad:
        print(f"regress: FAILED — {len(bad)} regression(s)",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
