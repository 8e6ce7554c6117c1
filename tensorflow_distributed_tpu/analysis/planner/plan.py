"""Plan emission: the ranked table, ``plan.json``, and ``--plan auto``.

Standalone::

    python -m tensorflow_distributed_tpu.analysis.planner \
        --family gpt --devices 8 --batch-size 128

prints every candidate ranked by predicted step time (mesh, strategy,
predicted ms, peak-HBM, compile wall; infeasible candidates marked,
never dropped) and writes ``plan.json``. On a CPU host the requested
``--devices`` forces the virtual host-platform topology the same way
jaxprcheck's CLI does; on a TPU host the real devices are used.

Train-CLI integration: ``--plan auto`` (train.loop) calls
:func:`apply_auto` before the mesh is built — the winning candidate's
``--mesh.*`` axes, ``--param-partition``, and (pipelined) microbatch
count replace the defaults, and the choice is emitted as a ``plan``
JSONL record through observe so it is auditable next to the run's
step records (observe.report renders the "Plan" section from it).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from typing import Any, Dict, Optional, Sequence

from tensorflow_distributed_tpu.analysis.planner import candidates as cand_lib
from tensorflow_distributed_tpu.analysis.planner import score as score_lib

PLAN_VERSION = 1


def make_plan(family: str, devices: int, batch_size: int,
              size: str = "", seq_len: int = 0,
              strategies: Optional[Sequence[str]] = None,
              microbatches: int = 4, moe_experts: int = 0,
              dropout_rate: float = 0.0,
              compute_dtype: str = "bfloat16",
              hw: Optional[score_lib.Hardware] = None,
              hbm_budget: Optional[float] = None,
              overlap_conflict: Optional[str] = None,
              calibration: str = "") -> Dict[str, Any]:
    """Enumerate + score + rank: the whole planning pass, as a dict
    (the ``plan.json`` schema). ``chosen`` is the best feasible scored
    candidate, or None when nothing is feasible. ``overlap_conflict``
    prunes the overlap strategy with that reason (see
    enumerate_candidates — apply_auto threads the run's knob
    conflicts). ``calibration`` is a calibration.json path
    (calibrate.py): its measured effective rates replace the static
    roofline peaks (ignored when an explicit ``hw`` is passed)."""
    facts = cand_lib.model_facts(family, size, moe_experts=moe_experts)
    seq_len = seq_len or 128
    feasible, pruned = cand_lib.enumerate_candidates(
        facts, devices, batch_size, strategies=strategies,
        microbatches=microbatches, overlap_conflict=overlap_conflict)
    if hw is None:
        cal = None
        if calibration:
            from tensorflow_distributed_tpu.analysis.planner.calibrate \
                import load_calibration
            cal = load_calibration(calibration)
        hw = score_lib.detect_hardware(calibration=cal)
    rows = score_lib.score_candidates(
        feasible, facts, batch_size, hw, seq_len=seq_len, size=size,
        dropout_rate=dropout_rate, compute_dtype=compute_dtype,
        moe_experts=moe_experts, hbm_budget=hbm_budget)
    chosen = next((r for r in rows if r.get("feasible")
                   and isinstance(r.get("step_ms"), (int, float))),
                  None)
    return {
        "version": PLAN_VERSION,
        "family": family,
        "model": cand_lib.FAMILY_MODELS[family],
        "size": size or cand_lib.DEFAULT_SIZES[family],
        "devices": devices,
        "batch_size": batch_size,
        "seq_len": seq_len,
        "facts": dataclasses.asdict(facts),
        "hardware": hw.as_dict(),
        "hbm_budget_bytes": (hbm_budget if hbm_budget is not None
                             else hw.hbm_bytes),
        "candidates": rows,
        "pruned": [{"mesh": p.candidate.mesh,
                    "partition": p.candidate.partition,
                    "strategy": p.candidate.strategy,
                    "reason": p.reason} for p in pruned],
        "chosen": chosen,
    }


def render_table(plan: Dict[str, Any]) -> str:
    """The human table: one ranked row per candidate, the pruned
    shapes with reasons below it."""
    from tensorflow_distributed_tpu.observe.device import human_bytes

    lines = [f"plan: {plan['family']}/{plan['size']} on "
             f"{plan['devices']} device(s) "
             f"({plan['hardware']['device_kind']}), global batch "
             f"{plan['batch_size']}, seq {plan['seq_len']}"]
    lines.append(f"{'rank':<5} {'mesh':<24} {'strategy':<14} "
                 f"{'step_ms':>9} {'peak_hbm':>10} {'compile_s':>9} "
                 f"{'feasible':>9}")
    for i, row in enumerate(plan["candidates"], 1):
        ms = ("-" if row.get("step_ms") is None
              else f"{row['step_ms']:.3f}")
        comp = ("-" if row.get("compile_s") is None
                else f"{row['compile_s']:.2f}")
        feas = "yes" if row.get("feasible") else "NO"
        lines.append(
            f"{i:<5} {cand_lib.format_mesh(row['mesh']):<24} "
            f"{row['strategy']:<14} {ms:>9} "
            f"{human_bytes(row.get('peak_hbm_bytes')):>10} {comp:>9} "
            f"{feas:>9}")
        note = row.get("infeasible_reason") or row.get("error")
        if note:
            lines.append(f"      ^ {note}")
    if plan["pruned"]:
        lines.append("pruned (hard constraints):")
        for p in plan["pruned"]:
            lines.append(f"  {cand_lib.format_mesh(p['mesh']):<24} "
                         f"{p['strategy']:<14} {p['reason']}")
    if plan["chosen"] is not None:
        lines.append(
            f"chosen: {cand_lib.format_mesh(plan['chosen']['mesh'])} "
            f"[{plan['chosen']['strategy']}] predicted "
            f"{plan['chosen']['step_ms']} ms/step")
    else:
        lines.append("chosen: NONE (no feasible scored candidate)")
    return "\n".join(lines)


def write_plan(plan: Dict[str, Any], path: str) -> None:
    with open(path, "w") as f:
        json.dump(plan, f, indent=2, sort_keys=False)
        f.write("\n")


def load_plan(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def plan_record(plan: Dict[str, Any]) -> Dict[str, Any]:
    """The compact, auditable summary emitted as the ``plan`` JSONL
    record (and rendered by observe.report's "Plan" section)."""
    chosen = plan.get("chosen") or {}
    rows = plan.get("candidates", [])
    return {
        "family": plan["family"],
        "size": plan["size"],
        "devices": plan["devices"],
        "batch_size": plan["batch_size"],
        "mesh": chosen.get("mesh"),
        "strategy": chosen.get("strategy"),
        "partition": chosen.get("partition"),
        "predicted_step_ms": chosen.get("step_ms"),
        "predicted_peak_hbm_bytes": chosen.get("peak_hbm_bytes"),
        "candidates": len(rows),
        "feasible": sum(1 for r in rows if r.get("feasible")),
        "infeasible": sum(1 for r in rows if not r.get("feasible")),
        "pruned": len(plan.get("pruned", [])),
        # Which roofline predicted: None = static tables, else the
        # calibration profile's id (the train loop's plan_drift record
        # and the bench stamps carry the same id).
        "calibration_id": (plan.get("hardware") or {}).get(
            "calibration_id"),
    }


def apply_auto(cfg) -> Dict[str, Any]:
    """``--plan auto``: plan for the run's model/devices/batch and
    REWRITE ``cfg`` (mesh axes, param_partition, pipelined
    microbatches) to the winner. Called by train.loop before the mesh
    is built; config.validate has already vetted the combination.
    Returns the ``plan`` record for the run's sinks. Raises when no
    candidate is feasible — launching on a known-infeasible layout
    would just move the failure into XLA."""
    from tensorflow_distributed_tpu.config import MeshConfig
    from tensorflow_distributed_tpu.parallel.mesh import (
        alive_devices, is_chief)

    family = cand_lib.MODEL_FAMILIES[cfg.model]
    devices = len(alive_devices())
    plan = make_plan(
        family, devices, cfg.batch_size, size=cfg.model_size,
        seq_len=cfg.seq_len, microbatches=cfg.pipeline_microbatches,
        moe_experts=cfg.moe_experts, dropout_rate=cfg.dropout_rate,
        compute_dtype=cfg.compute_dtype,
        hbm_budget=(cfg.plan_hbm_budget_gb * 1e9
                    if cfg.plan_hbm_budget_gb else None),
        # Knobs the overlap launch would reject (non-elementwise
        # optimizer, grad clip, ce_chunk, ...) prune the strategy here
        # — picking it would just crash the re-validate after the plan.
        overlap_conflict=cfg.overlap_grad_sync_conflict(),
        calibration=cfg.plan_calibration)
    if is_chief():
        print(render_table(plan), flush=True)
    chosen = plan["chosen"]
    if chosen is None:
        raise ValueError(
            f"--plan auto: no feasible candidate for {family} on "
            f"{devices} device(s) with batch {cfg.batch_size} — see "
            f"the table above for per-candidate reasons")
    cfg.mesh = MeshConfig(**chosen["mesh"])
    if chosen["partition"] == "overlap":
        # The overlap strategy launches as zero1 slots + the explicit
        # bucketed grad sync (Candidate.cli_args says the same).
        cfg.param_partition = "zero1"
        cfg.grad_sync = "overlap"
    else:
        cfg.param_partition = chosen["partition"]
    if family == "pipelined" and chosen.get("microbatches"):
        cfg.pipeline_microbatches = chosen["microbatches"]
    return plan_record(plan)


def init_backend(n_devices: int = 0, tag: str = "planner") -> str:
    """Backend init for the planner-facing CLI (this module's main):
    force the
    virtual CPU host-platform device count to the requested size (the
    jaxprcheck CLI precedent — flags must land before the backend is
    first USED), and fall back to CPU when the configured accelerator
    can't come up. Returns the effective platform."""
    if n_devices and "--xla_force_host_platform_device_count" \
            not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    import jax
    try:
        jax.devices()
    except RuntimeError as e:
        print(f"[{tag}] accelerator backend unavailable "
              f"({str(e).splitlines()[0]}); retrying on CPU",
              file=sys.stderr, flush=True)
        try:
            jax.config.update("jax_platforms", "cpu")
        except RuntimeError:
            pass  # a backend initialized after all — use it
        jax.devices()
    return jax.default_backend()


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tensorflow_distributed_tpu.analysis.planner",
        description="cost-model-driven auto-layout: rank every valid "
                    "mesh x strategy for a model family and device "
                    "count, scored by AOT-compiling the real train "
                    "step (no execution)")
    parser.add_argument("--family", required=True,
                        choices=sorted(cand_lib.FAMILY_MODELS))
    parser.add_argument("--devices", type=int, default=0,
                        help="device count to plan for (default: all "
                        "visible; on CPU forces that many virtual "
                        "devices)")
    parser.add_argument("--batch-size", type=int, default=128,
                        help="global batch the plan must divide "
                        "(serve family: the decode slot count)")
    parser.add_argument("--size", default="",
                        help="family size preset (tiny or the GPT-2 "
                        "ladder; default: the family's factory "
                        "default)")
    parser.add_argument("--seq-len", type=int, default=0)
    parser.add_argument("--microbatches", type=int, default=4,
                        help="pipelined: microbatch floor (raised to "
                        "the pipe width when needed)")
    parser.add_argument("--moe-experts", type=int, default=0)
    parser.add_argument("--strategies", default="",
                        help="comma-separated strategy parts to allow "
                        "(data,fsdp,zero1,tensor,expert,pipe); "
                        "default all")
    parser.add_argument("--compute-dtype", default="bfloat16",
                        choices=("bfloat16", "float32"))
    parser.add_argument("--hbm-budget-gb", type=float, default=0.0,
                        help="per-device HBM budget (default: the "
                        "device's own memory_stats limit when it "
                        "reports one)")
    parser.add_argument("--peak-tflops", type=float, default=0.0)
    parser.add_argument("--hbm-gbps", type=float, default=0.0)
    parser.add_argument("--ici-gbps", type=float, default=0.0)
    parser.add_argument("--calibration", default="",
                        help="calibration.json (calibrate.py): "
                        "measured effective rates replace the static "
                        "table peaks; explicit --peak-tflops/"
                        "--hbm-gbps/--ici-gbps still win")
    parser.add_argument("--out", default="plan.json",
                        help="plan JSON path ('' = don't write)")
    args = parser.parse_args(argv)
    init_backend(args.devices)
    import jax
    devices = args.devices or len(jax.devices())
    if devices > len(jax.devices()):
        print(f"planner: asked to plan {devices} devices but only "
              f"{len(jax.devices())} are visible (backend initialized "
              f"before the CLI could force a CPU topology?)",
              file=sys.stderr)
        return 2
    cal = None
    if args.calibration:
        from tensorflow_distributed_tpu.analysis.planner.calibrate \
            import load_calibration
        cal = load_calibration(args.calibration)
    hw = score_lib.detect_hardware(
        peak_tflops=args.peak_tflops, hbm_gbps=args.hbm_gbps,
        ici_gbps=args.ici_gbps, hbm_budget_gb=args.hbm_budget_gb,
        calibration=cal)
    strategies = ([s.strip() for s in args.strategies.split(",")
                   if s.strip()] or None)
    plan = make_plan(
        args.family, devices, args.batch_size, size=args.size,
        seq_len=args.seq_len, strategies=strategies,
        microbatches=args.microbatches, moe_experts=args.moe_experts,
        compute_dtype=args.compute_dtype, hw=hw,
        hbm_budget=(args.hbm_budget_gb * 1e9 if args.hbm_budget_gb
                    else None))
    print(render_table(plan))
    if args.out:
        write_plan(plan, args.out)
        print(f"planner: wrote {args.out}")
    return 0 if plan["chosen"] is not None else 1


if __name__ == "__main__":
    sys.exit(main())
