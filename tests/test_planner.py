"""Auto-layout planner (analysis/planner) + the shared mesh rules.

Fast tier: enumeration completeness/pruning on stubbed constraints,
the roofline math on canned cost dicts, infeasible MARKING (never
dropping), plan.json round-trip, config validation, report folding —
no compiles, no device work. One default-tier e2e drives the real
thing: the standalone CLI plans tiny-gpt on the 8-device CPU mesh and
``--plan auto`` trains 2 steps on the chosen layout under ``--check``.
"""

import json

import pytest

from tensorflow_distributed_tpu.analysis.planner import candidates as C
from tensorflow_distributed_tpu.analysis.planner import plan as plan_lib
from tensorflow_distributed_tpu.analysis.planner import score as S


def _facts(family="gpt", heads=4, layers=2, experts=0):
    return C.ModelFacts(family=family, n_heads=heads, n_layers=layers,
                        n_experts=experts)


# --- enumeration -------------------------------------------------------

def test_enumeration_completeness_stubbed():
    # With the mesh rule stubbed permissive, every factorization x
    # partition that passes the family rules must appear exactly once.
    feasible, pruned = C.enumerate_candidates(
        _facts(), devices=8, batch=128,
        infeasible=lambda axes, d, b: None)
    keys = {(tuple(sorted(c.mesh.items())), c.partition)
            for c in feasible}
    assert len(keys) == len(feasible)  # no duplicates
    meshes = {frozenset((k, v) for k, v in c.mesh.items() if v != 1)
              for c in feasible}
    # (data=8), (4,2), (2,4) survive; model=8 is pruned on heads=4.
    assert frozenset({("data", 8)}) in meshes
    assert frozenset({("data", 4), ("model", 2)}) in meshes
    assert frozenset({("data", 2), ("model", 4)}) in meshes
    assert not any(c.mesh["model"] == 8 for c in feasible)
    reasons = {p.reason for p in pruned}
    assert any("n_heads" in r for r in reasons)
    # 3 factorizations x 3 partitions = 9 (model=8 pruned, and its
    # fsdp/zero1 variants pruned as degenerate-at-data-1), plus the
    # overlap strategy on the ONE pure-data shape (tensor-carrying
    # shapes prune it — the explicit grad-sync needs a pure data
    # mesh).
    assert len(feasible) == 10
    overlaps = [c for c in feasible if c.partition == "overlap"]
    assert len(overlaps) == 1 and overlaps[0].mesh["data"] == 8


def test_enumeration_prunes_all_on_stubbed_constraint():
    feasible, pruned = C.enumerate_candidates(
        _facts(), devices=8, batch=128,
        infeasible=lambda axes, d, b: "stubbed: no")
    assert feasible == []
    assert pruned and all(
        p.reason == "stubbed: no" or "identical to the plain" in p.reason
        or "n_heads" in p.reason or "pure data" in p.reason
        for p in pruned)


def test_enumeration_batch_divisibility_via_shared_rule():
    # The REAL shared rule (parallel.mesh.mesh_infeasible): batch 12
    # rejects data=8 (12 % 8 != 0) but keeps data=4 and data=2.
    feasible, pruned = C.enumerate_candidates(
        _facts(), devices=8, batch=12)
    assert not any(c.mesh["data"] == 8 for c in feasible)
    assert any("not divisible by data width 8" in p.reason
               for p in pruned)


def test_enumeration_strategy_filter():
    feasible, pruned = C.enumerate_candidates(
        _facts(), devices=8, batch=64,
        strategies=("data", "zero1"),
        infeasible=lambda axes, d, b: None)
    assert {c.strategy for c in feasible} == {"data", "zero1"}
    assert any("excluded by --strategies" in p.reason for p in pruned)


def test_enumeration_moe_expert_axis_and_pipelined():
    feasible, _ = C.enumerate_candidates(
        _facts("moe", experts=4), devices=8, batch=64,
        infeasible=lambda axes, d, b: None)
    assert any(c.mesh["expert"] == 4 for c in feasible)
    assert not any(c.mesh["expert"] == 8 for c in feasible)  # 4 experts
    feasible, pruned = C.enumerate_candidates(
        _facts("pipelined", layers=4), devices=8, batch=64,
        infeasible=lambda axes, d, b: None)
    assert any(c.mesh["pipe"] == 4 and c.microbatches == 4
               for c in feasible)
    # pipe=8 > 4 layers is pruned; fsdp never composes with pipelined.
    assert not any(c.mesh["pipe"] == 8 for c in feasible)
    assert not any(c.partition == "fsdp" for c in feasible)
    assert any("fsdp does not compose" in p.reason for p in pruned)


def test_strategy_names_and_cli_args():
    c = C.Candidate.make({"data": 4, "model": 2}, "fsdp")
    assert c.strategy == "fsdp+tensor"
    assert c.cli_args()[:2] == ["--mesh.data", "4"]
    assert "--param-partition" in c.cli_args()
    assert C.Candidate.make({"data": 8}).strategy == "data"
    assert C.Candidate.make({"data": 1}).strategy == "data"
    p = C.Candidate.make({"data": 2, "pipe": 4}, microbatches=4)
    assert p.strategy == "data+pipe"
    assert "--pipeline-microbatches" in p.cli_args()


# --- scoring math (canned dicts, no jax) -------------------------------

HW = S.Hardware(platform="test", device_kind="test",
                peak_flops=1e12, hbm_bw=1e11, ici_bw=2.5e10)


def test_table_peaks_unknown_tpu_kind_is_an_error():
    """The v5e as JAX names it is in the tables; a host that is not a
    TPU ranks on the generic ratios; a TPU the tables lack raises and
    names the kind instead of posting rates under its name."""
    assert S.table_peaks("tpu", "TPU v5 lite") == (
        197e12, 8.1e11, 1.6e11, 16e9)
    assert S.table_peaks("cpu", "cpu") == (
        (S.GENERIC_PEAK_FLOPS,) + S.GENERIC_HW)
    with pytest.raises(ValueError, match="TPU v9 mega"):
        S.table_peaks("tpu", "TPU v9 mega")


def test_roofline_compute_vs_memory_bound():
    compute_bound = S.roofline_ms(
        {"flops": 2e9, "bytes_accessed": 1e8}, 0.0, HW)
    assert compute_bound["compute_ms"] == pytest.approx(2.0)
    assert compute_bound["memory_ms"] == pytest.approx(1.0)
    assert compute_bound["step_ms"] == pytest.approx(2.0)
    memory_bound = S.roofline_ms(
        {"flops": 1e8, "bytes_accessed": 1e9}, 2.5e7, HW)
    assert memory_bound["step_ms"] == pytest.approx(10.0 + 1.0)
    assert memory_bound["collective_ms"] == pytest.approx(1.0)


def test_roofline_null_costs_stay_null():
    out = S.roofline_ms({"flops": None, "bytes_accessed": None},
                        0.0, HW)
    assert out == {"compute_ms": None, "memory_ms": None,
                   "collective_ms": None, "step_ms": None}


def test_mark_feasibility_marks_never_drops():
    rows = [{"peak_hbm_bytes": 100}, {"peak_hbm_bytes": 300},
            {"peak_hbm_bytes": None}, {"error": "boom"}]
    out = S.mark_feasibility(rows, hbm_budget=200)
    assert len(out) == 4                      # nothing dropped
    assert out[0]["feasible"] is True
    assert out[1]["feasible"] is False
    assert "exceeds" in out[1]["infeasible_reason"]
    assert out[2]["feasible"] is True         # unknown != overflow
    assert out[3]["feasible"] is False


def test_rank_orders_feasible_scored_first():
    rows = [{"strategy": "a", "feasible": False, "step_ms": 0.1},
            {"strategy": "b", "feasible": True, "step_ms": 3.0},
            {"strategy": "c", "feasible": True, "step_ms": 1.0},
            {"strategy": "d", "feasible": True, "step_ms": None}]
    ranked = S.rank(rows)
    assert [r["strategy"] for r in ranked] == ["c", "b", "d", "a"]


# --- plan.json round-trip ----------------------------------------------

def test_plan_json_round_trip(tmp_path):
    plan = {"version": 1, "family": "gpt", "devices": 8,
            "batch_size": 64,
            "candidates": [{"mesh": {"data": 8}, "strategy": "data",
                            "step_ms": 0.5, "feasible": True}],
            "pruned": [], "chosen": {"mesh": {"data": 8}}}
    path = str(tmp_path / "plan.json")
    plan_lib.write_plan(plan, path)
    assert plan_lib.load_plan(path) == plan


# --- shared mesh rules (parallel.mesh <-> supervisor) ------------------

def test_shared_mesh_rules_match_supervisor():
    from tensorflow_distributed_tpu.parallel import mesh as mesh_lib
    from tensorflow_distributed_tpu.resilience import supervisor as sup

    axes = {"data": -1, "model": 2, "seq": 1, "pipe": 1, "expert": 1}
    assert mesh_lib.pick_data_width(axes, 5, 64) == 2
    assert mesh_lib.pick_data_width(axes, 1, 64) is None
    picked = sup.pick_elastic_mesh(axes, 5, 64)
    assert picked["data"] == mesh_lib.pick_data_width(axes, 5, 64)
    assert mesh_lib.mesh_infeasible({"data": 4, "model": 2}, 8, 64) \
        is None
    assert "not divisible by data width" in mesh_lib.mesh_infeasible(
        {"data": 3}, 3, 64)
    assert "!=" in mesh_lib.mesh_infeasible({"data": 4}, 8, 64)
    assert "must be >= 1" in mesh_lib.mesh_infeasible({"data": 0}, 8,
                                                      64)


def test_model_facts_track_factory_constants():
    # The facts pruning runs on must be the factories' OWN numbers —
    # a tiny_config/factory-default change may not silently
    # desynchronize enumeration from the model the scorer builds.
    from tensorflow_distributed_tpu.models.pipelined import (
        PIPELINED_TINY_LAYERS)
    from tensorflow_distributed_tpu.models.transformer import (
        MOE_DEFAULT_EXPERTS, tiny_config)

    tiny = tiny_config()
    gpt = C.model_facts("gpt", "tiny")
    assert (gpt.n_heads, gpt.n_layers) == (tiny.n_heads, tiny.n_layers)
    assert C.model_facts("pipelined").n_layers == PIPELINED_TINY_LAYERS
    assert C.model_facts("moe").n_experts == MOE_DEFAULT_EXPERTS
    assert C.model_facts("moe", moe_experts=8).n_experts == 8


def test_supervisor_refuses_elastic_plus_plan_auto(capsys):
    # Two mesh owners: --elastic rewrites --mesh.* on every leg, which
    # the child's "--plan auto owns the mesh" guard rejects — the
    # supervisor must refuse up front (rc 2, no leg spawned), not
    # crash-loop the restart budget away.
    from tensorflow_distributed_tpu.resilience import supervisor as sup

    rc = sup.main(["--elastic", "--max-restarts", "1", "--",
                   "--model", "gpt_lm", "--plan", "auto",
                   "--checkpoint-dir", "/tmp/nope"])
    assert rc == 2
    assert "does not compose" in capsys.readouterr().err
    rc = sup.main(["--elastic", "--", "--plan=auto"])
    assert rc == 2


# --- config validation -------------------------------------------------

def test_plan_config_validation():
    from tensorflow_distributed_tpu.config import TrainConfig

    def cfg(**kw):
        c = TrainConfig(model="gpt_lm", dataset="synthetic", **kw)
        c.validate()
        return c

    cfg(plan="auto")                      # the valid combination
    with pytest.raises(ValueError, match="unknown plan"):
        cfg(plan="bogus")
    with pytest.raises(ValueError, match="no effect without"):
        cfg(plan_hbm_budget_gb=1.0)
    with pytest.raises(ValueError, match="owns the mesh"):
        from tensorflow_distributed_tpu.config import MeshConfig
        cfg(plan="auto", mesh=MeshConfig(data=8))
    with pytest.raises(ValueError, match="owns the partition"):
        cfg(plan="auto", param_partition="fsdp")
    with pytest.raises(ValueError, match="LM training families"):
        c = TrainConfig(model="mnist_cnn", plan="auto")
        c.validate()
    with pytest.raises(ValueError, match="mode="):
        cfg(plan="auto", mode="eval", checkpoint_dir="/tmp/x")
    cfg(plan="auto", plan_hbm_budget_gb=4.0)  # the budget composes
    with pytest.raises(ValueError, match="moe_lm"):
        # A dense family with experts bolted on would be scored as
        # dense — rejected rather than misplanned.
        cfg(plan="auto", moe_experts=8)
    c = TrainConfig(model="moe_lm", dataset="synthetic", plan="auto",
                    moe_experts=8)
    c.validate()  # experts on the moe family plan fine


# --- report folding ----------------------------------------------------

def test_report_plan_section():
    from tensorflow_distributed_tpu.observe.report import (
        render, summarize)

    records = [
        {"event": "plan", "family": "gpt",
         "mesh": {"data": 8, "model": 1}, "strategy": "data",
         "partition": "replicated", "predicted_step_ms": 0.17,
         "predicted_peak_hbm_bytes": 2406280, "candidates": 9,
         "feasible": 9, "infeasible": 0},
        {"event": "step", "step": 2, "loss": 4.0, "step_ms_p50": 34.6},
    ]
    out = summarize(records)
    assert out["plan"]["strategy"] == "data"
    assert out["plan"]["measured_step_ms_p50"] == 34.6
    text = render(out)
    assert "Plan" in text and "predicted=0.17" in text
    assert "data=8 [data]" in text


# --- the real thing (default-tier e2e; CPU 8-device mesh) --------------

def test_planner_cli_and_plan_auto_e2e(tmp_path):
    # 1. Standalone CLI: rank tiny-gpt candidates, write plan.json.
    out = str(tmp_path / "plan.json")
    rc = plan_lib.main(["--family", "gpt", "--devices", "8",
                        "--batch-size", "32", "--size", "tiny",
                        "--seq-len", "32", "--out", out])
    assert rc == 0
    plan = plan_lib.load_plan(out)
    rows = plan["candidates"]
    assert rows and plan["chosen"] == rows[0]
    scored = [r["step_ms"] for r in rows
              if r["feasible"] and r["step_ms"] is not None]
    assert scored == sorted(scored)          # ranked
    assert len(scored) >= 3                  # a real sweep, not one row
    assert plan["pruned"]                    # reasons reported
    assert all(p["reason"] for p in plan["pruned"])
    # The AOT pass really ran: every scored row carries compile wall.
    assert all(r["compile_s"] is not None for r in rows
               if r["step_ms"] is not None)

    # 2. An impossible budget MARKS everything infeasible (not drop).
    tight = plan_lib.make_plan("gpt", 8, 32, size="tiny", seq_len=32,
                               strategies=("data",), hbm_budget=1e3)
    assert tight["chosen"] is None
    assert tight["candidates"]
    assert all(not r["feasible"] for r in tight["candidates"])
    assert all("exceeds" in r["infeasible_reason"]
               for r in tight["candidates"])

    # 3. --plan auto: train 2 steps on the chosen layout under --check.
    from tensorflow_distributed_tpu.config import parse_args
    from tensorflow_distributed_tpu.train.loop import train

    jsonl = str(tmp_path / "m.jsonl")
    cfg = parse_args([
        "--model", "gpt_lm", "--model-size", "tiny",
        "--dataset", "synthetic", "--seq-len", "32",
        "--batch-size", "32", "--train-steps", "2",
        "--eval-every", "0", "--eval-batch-size", "32",
        "--log-every", "1", "--plan", "auto", "--check", "true",
        "--observe.metrics-jsonl", jsonl])
    result = train(cfg)
    assert int(result.state.step) == 2
    records = [json.loads(ln) for ln in open(jsonl)]
    plans = [r for r in records if r.get("event") == "plan"]
    assert len(plans) == 1
    # The run's mesh IS the plan's choice.
    starts = [r for r in records if r.get("event") == "start"]
    assert plans[0]["mesh"]["data"] == cfg.mesh.data
    assert starts and cfg.param_partition == plans[0]["partition"]
