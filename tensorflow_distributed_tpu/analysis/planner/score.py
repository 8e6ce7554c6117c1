"""AOT candidate scoring: the real compiler cost model, no execution.

For each candidate the scorer builds the REAL jitted train step (the
same ``train/step.py`` / ``train/pipeline_step.py`` builders the loop
dispatches) over an ABSTRACT sharded state
(train.state.abstract_train_state — zero bytes allocated, so shapes
too big or too broken to materialize here still score), then:

- ``lower()+compile()`` through observe.device.aot_lower_compile and
  reads flops / bytes / peak-HBM through observe.device.extract_costs
  — ONE extraction path shared with the compiled-program registry, so
  the jax-version key handling and the explicit-null degradation live
  in exactly one place. cost/memory analysis of the partitioned
  module is PER-DEVICE (verified on this container: an 8-way data
  mesh reports 1/8 the single-device flops), so parallelism shows up
  in the numbers without any hand-division.
- censuses the program's EXPLICIT collective traffic with
  analysis.jaxprcheck's walk (the pipeline's ppermute/psum schedule;
  GSPMD-inserted collectives never appear in a jaxpr — their cost
  rides the compiled module's bytes-accessed term instead).
- predicts step time with a roofline:
  ``max(flops/peak_flops, bytes/hbm_bw) + collective_bytes/ici_bw``.

Candidates whose peak-HBM estimate exceeds the budget are MARKED
infeasible (``feasible: false`` + reason) and ranked after the
feasible ones — never dropped. A candidate whose build/compile fails
degrades the same way: explicit-null cost fields plus the error.

The scoring math (:func:`roofline_ms`) and feasibility marking
(:func:`mark_feasibility`) are pure functions over plain dicts —
module import stays jax-free for the unit tier; everything jax lives
behind lazy imports in the build path.
"""

from __future__ import annotations

import dataclasses
import sys
from typing import Any, Dict, List, Optional, Sequence

from tensorflow_distributed_tpu.analysis.planner.candidates import (
    Candidate, ModelFacts)

#: per-device (hbm_bytes/s, ici_bytes/s, hbm_capacity_bytes) for the
#: chips observe.mfu.PEAK_BF16_FLOPS knows; the flops peak itself is
#: NOT duplicated here — it comes from that table. Hosts that are not
#: TPUs (what the tests rank on) get GENERIC_HW:
#: arbitrary but fixed ratios, fine for RANKING candidates against
#: each other, never to be read as wall-clock truth. A TPU the tables do not know is an error
#: (:func:`table_peaks`), never a default.
TPU_HW = {
    "TPU v4": (1.2e12, 3.0e11, 32e9),
    "TPU v5 lite": (8.1e11, 1.6e11, 16e9),
    "TPU v5e": (8.1e11, 1.6e11, 16e9),
    "TPU v5": (2.765e12, 6.0e11, 95e9),
    "TPU v6 lite": (1.64e12, 3.2e11, 32e9),
}
GENERIC_HW = (1.0e11, 2.5e10, None)
GENERIC_PEAK_FLOPS = 1.0e12


def table_peaks(platform: str, kind: str):
    """``(peak_flops, hbm_bytes/s, ici_bytes/s, hbm_capacity)`` from
    the static tables. On a TPU whose kind the tables lack this raises
    and names the kind — a generic rate under a chip's name would pass
    for a measurement; every other platform gets the generic ranking
    ratios."""
    from tensorflow_distributed_tpu.observe import mfu

    if kind in TPU_HW and kind in mfu.PEAK_BF16_FLOPS:
        return (mfu.PEAK_BF16_FLOPS[kind],) + TPU_HW[kind]
    if platform == "tpu":
        raise ValueError(
            f"unknown TPU device kind {kind!r}: add its peaks to "
            f"observe.mfu.PEAK_BF16_FLOPS and analysis.planner.score."
            f"TPU_HW (known: {sorted(TPU_HW)})")
    return (GENERIC_PEAK_FLOPS,) + GENERIC_HW


@dataclasses.dataclass(frozen=True)
class Hardware:
    """Per-device peaks the roofline divides by (plus the HBM budget
    candidates are marked infeasible against; None = unknown/no
    budget). ``calibration_id`` names the measured profile the rates
    came from (analysis/planner/calibrate.py) — None means the static
    tables."""

    platform: str
    device_kind: str
    peak_flops: float
    hbm_bw: float
    ici_bw: float
    hbm_bytes: Optional[float] = None
    calibration_id: Optional[str] = None
    # Fixed per-dispatch launch cost a calibration profile measured
    # (0 for the static tables): rank-neutral at fixed scale, but the
    # difference between a ranking device and a wall-clock predictor.
    overhead_ms: float = 0.0

    def as_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


def detect_hardware(peak_tflops: float = 0.0, hbm_gbps: float = 0.0,
                    ici_gbps: float = 0.0,
                    hbm_budget_gb: float = 0.0,
                    calibration: Optional[Dict[str, Any]] = None
                    ) -> Hardware:
    """Peaks for ``jax.devices()[0]``: the known-TPU tables
    (:func:`table_peaks` — an unknown TPU kind raises), the device's
    own ``memory_stats`` for capacity when it reports one, a
    CALIBRATION profile (calibrate.load_calibration) beating the
    tables — measured effective rates beat a fixed ratio every time,
    and on non-TPU hosts they replace GENERIC_HW's arbitrary ones —
    and explicit overrides beating everything. A profile whose platform or device
    kind doesn't match the live device is IGNORED with a stderr note
    (a CPU fit must never masquerade as TPU truth)."""
    import jax

    dev = jax.devices()[0]
    kind = getattr(dev, "device_kind", "unknown")
    platform = jax.default_backend()
    flops, hbm_bw, ici_bw, hbm = table_peaks(platform, kind)
    calibration_id = None
    overhead_ms = 0.0
    if calibration:
        cal_kind = calibration.get("device_kind")
        cal_platform = calibration.get("platform")
        if (cal_platform, cal_kind) != (platform, kind):
            print(f"planner: ignoring calibration profile for "
                  f"{cal_platform}/{cal_kind} on a live "
                  f"{platform}/{kind} device", file=sys.stderr)
        else:
            eff = calibration.get("effective", {})
            if isinstance(eff.get("peak_flops"), (int, float)):
                flops = float(eff["peak_flops"])
            if isinstance(eff.get("hbm_bw"), (int, float)):
                hbm_bw = float(eff["hbm_bw"])
            if isinstance(eff.get("ici_bw"), (int, float)):
                ici_bw = float(eff["ici_bw"])
            if isinstance(eff.get("overhead_ms"), (int, float)):
                overhead_ms = float(eff["overhead_ms"])
            calibration_id = calibration.get("calibration_id")
    try:
        stats = dev.memory_stats()
    except Exception:
        stats = None
    if stats and isinstance(stats.get("bytes_limit"), (int, float)):
        hbm = float(stats["bytes_limit"])
    if peak_tflops:
        flops = peak_tflops * 1e12
    if hbm_gbps:
        hbm_bw = hbm_gbps * 1e9
    if ici_gbps:
        ici_bw = ici_gbps * 1e9
    if hbm_budget_gb:
        hbm = hbm_budget_gb * 1e9
    return Hardware(platform=platform, device_kind=kind,
                    peak_flops=flops, hbm_bw=hbm_bw, ici_bw=ici_bw,
                    hbm_bytes=hbm, calibration_id=calibration_id,
                    overhead_ms=overhead_ms)


# --- the scoring math (pure; unit-tested on canned dicts) --------------

def roofline_ms(costs: Dict[str, Any], collective_bytes: float,
                hw: Hardware, overlap: bool = False
                ) -> Dict[str, Optional[float]]:
    """Predicted per-step milliseconds from one program's cost dict:
    ``max(compute, memory) + collectives``. Null costs (a backend
    exposing no analysis) yield explicitly-null predictions — the
    candidate stays in the table, unranked, never invents a number.

    ``overlap=True`` (the explicit bucketed grad-sync strategy,
    parallel/overlap.py) applies the overlap discount: the bucketed
    reduce-scatter/all-gather schedule hides under backward compute,
    so the collective term stops being additive —
    ``max(compute, memory, collectives)`` instead of
    ``max(compute, memory) + collectives``. That is exactly the edge
    the planner needs to rank overlap against plain data/zero1, whose
    GSPMD-implicit allreduce rides the bytes term serially."""
    flops, moved = costs.get("flops"), costs.get("bytes_accessed")
    if not isinstance(flops, (int, float)) or not isinstance(
            moved, (int, float)):
        return {"compute_ms": None, "memory_ms": None,
                "collective_ms": None, "step_ms": None}
    compute = 1e3 * float(flops) / hw.peak_flops
    memory = 1e3 * float(moved) / hw.hbm_bw
    collective = 1e3 * float(collective_bytes or 0.0) / hw.ici_bw
    step = (max(compute, memory, collective) if overlap
            else max(compute, memory) + collective)
    # Calibrated per-dispatch overhead (0 for table hardware).
    step += getattr(hw, "overhead_ms", 0.0)
    return {"compute_ms": round(compute, 6),
            "memory_ms": round(memory, 6),
            "collective_ms": round(collective, 6),
            "step_ms": round(step, 6)}


def mark_feasibility(rows: List[Dict[str, Any]],
                     hbm_budget: Optional[float]) -> List[Dict[str, Any]]:
    """Flag each scored row against the per-device HBM budget.

    MARKS, never drops: ``feasible`` False + ``infeasible_reason`` on
    rows whose peak-HBM estimate exceeds the budget (and on rows that
    failed to build/compile, whose ``error`` is already set). Rows
    with a null peak estimate stay feasible — an unknown is not an
    overflow. Returns the same list, mutated, for chaining."""
    for row in rows:
        if row.get("error"):
            row["feasible"] = False
            row.setdefault("infeasible_reason",
                           "build/compile failed (see error)")
            continue
        peak = row.get("peak_hbm_bytes")
        if (hbm_budget and isinstance(peak, (int, float))
                and peak > hbm_budget):
            row["feasible"] = False
            row["infeasible_reason"] = (
                f"predicted peak HBM {int(peak)} B exceeds the "
                f"per-device budget {int(hbm_budget)} B")
        else:
            row.setdefault("feasible", True)
    return rows


def rank(rows: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Feasible-and-scored first (by predicted step time), then
    feasible-but-unscored, then infeasible — nothing dropped."""
    def key(row):
        scored = isinstance(row.get("step_ms"), (int, float))
        return (0 if row.get("feasible") and scored else
                1 if row.get("feasible") else 2,
                row.get("step_ms") if scored else float("inf"),
                row.get("strategy", ""))
    return sorted(rows, key=key)


# --- candidate -> program -> costs (jax from here on) ------------------

def collective_traffic(closed_jaxpr) -> Dict[str, Any]:
    """{"counts": {prim: n}, "bytes": total} over every EXPLICIT
    collective equation (sub-jaxprs included — the jaxprcheck walk).
    Bytes are the per-shard result sizes, which is what actually
    crosses a link per ppermute hop / psum reduction operand."""
    import numpy as np

    from tensorflow_distributed_tpu.analysis.jaxprcheck import (
        COLLECTIVE_PREFIXES, iter_eqns)

    counts: Dict[str, int] = {}
    total = 0.0
    for eqn in iter_eqns(closed_jaxpr.jaxpr):
        name = eqn.primitive.name
        if not name.startswith(COLLECTIVE_PREFIXES):
            continue
        counts[name] = counts.get(name, 0) + 1
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                total += float(np.prod(aval.shape, dtype=np.float64)
                               * np.dtype(aval.dtype).itemsize)
    return {"counts": dict(sorted(counts.items())), "bytes": total}


def build_candidate_step(cand: Candidate, facts: ModelFacts,
                         batch: int, seq_len: int = 128,
                         size: str = "", dropout_rate: float = 0.0,
                         compute_dtype: str = "bfloat16",
                         moe_experts: int = 0,
                         abstract: bool = True):
    """(jitted step, state, abstract batch, mesh) for one candidate — the
    REAL builders on a real mesh over the first ``product(axes)``
    devices. ``abstract=True`` (scoring) keeps the state a
    sharding-annotated ShapeDtypeStruct tree — no allocation;
    ``abstract=False`` (an execution sweep) materializes it
    through create_train_state so the SAME construction backs both
    the prediction and the measurement. Raises on an unbuildable
    candidate; the scorer degrades it to an error row."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tensorflow_distributed_tpu.analysis.planner.candidates import (
        DEFAULT_SIZES)
    from tensorflow_distributed_tpu.config import MeshConfig
    from tensorflow_distributed_tpu.parallel.mesh import make_mesh
    from tensorflow_distributed_tpu.train.state import (
        abstract_train_state, create_train_state)
    from tensorflow_distributed_tpu.train.tasks import (
        make_mlm_loss, make_moe_loss, mlm_batch_shardings)

    make_state = (abstract_train_state if abstract
                  else create_train_state)

    axes = cand.mesh
    n = 1
    for _, v in cand.axes:
        n *= v
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"candidate needs {n} devices, have {len(devs)}")
    mesh = make_mesh(MeshConfig(**axes), devs[:n])
    size = size or DEFAULT_SIZES[facts.family]
    dtype = jnp.bfloat16 if compute_dtype == "bfloat16" else jnp.float32
    if facts.family == "serve":
        if not abstract:
            raise ValueError(
                "serve candidates score abstractly: the decode "
                "program is ranked by AOT costs, never executed by "
                "the planner (launch the pick via its cli_args)")
        return _build_serve_decode(cand, mesh, batch, seq_len, size,
                                   dtype)
    sample = np.zeros((2, seq_len), np.int32)
    kw: Dict[str, Any] = dict(dropout_rate=dropout_rate,
                              compute_dtype=dtype, max_len=seq_len)
    tx = optax.adam(1e-3)
    sh = mlm_batch_shardings(mesh)
    if facts.family == "pipelined":
        from tensorflow_distributed_tpu.models.pipelined import (
            pipelined_lm)
        from tensorflow_distributed_tpu.train.pipeline_step import (
            make_1f1b_train_step)
        model = pipelined_lm(mesh, size=size,
                             num_microbatches=cand.microbatches, **kw)
        state = make_state(model, tx, sample, mesh,
                           opt_fsdp=cand.partition == "zero1")
        params_out = (jax.tree_util.tree_map(lambda s: s.sharding,
                                             state.params)
                      if cand.partition == "zero1" else None)
        step = make_1f1b_train_step(model, mesh, batch_shardings=sh,
                                    params_out_shardings=params_out)
    else:
        from tensorflow_distributed_tpu.models import transformer
        from tensorflow_distributed_tpu.train.step import (
            make_train_step)
        if facts.family == "moe" and moe_experts:
            kw["moe_experts"] = moe_experts
        factory = (transformer.moe_lm if facts.family == "moe"
                   else transformer.gpt_lm)
        overlap = cand.partition == "overlap"
        if overlap:
            # The explicit step's forward runs inside its shard_map —
            # mesh-less model, no activation pins (the builder's
            # docstring; same construction train.loop uses for
            # --grad-sync).
            kw["tp_partitioning"] = False
        model = factory(mesh=None if overlap else mesh, size=size,
                        **kw)
        state = make_state(model, tx, sample, mesh,
                           fsdp=cand.partition == "fsdp",
                           opt_fsdp=cand.partition in ("zero1",
                                                       "overlap"))
        params_out = (jax.tree_util.tree_map(lambda s: s.sharding,
                                             state.params)
                      if cand.partition in ("zero1", "overlap")
                      else None)
        loss = (make_moe_loss() if facts.family == "moe"
                else make_mlm_loss())
        if overlap:
            from tensorflow_distributed_tpu.parallel.overlap import (
                make_explicit_train_step)
            step = make_explicit_train_step(
                mesh, state, loss=loss, batch_shardings=sh,
                grad_sync="overlap", params_out_shardings=params_out)
        else:
            step = make_train_step(mesh, loss=loss, batch_shardings=sh,
                                   params_out_shardings=params_out)
    abatch = {
        k: jax.ShapeDtypeStruct(
            (batch, seq_len),
            np.int32 if k != "mask" else np.float32, sharding=sh[k])
        for k in ("tokens", "targets", "mask")}
    return step, state, abatch, mesh


def _build_serve_decode(cand: Candidate, mesh, num_slots: int,
                        max_len: int, size: str, dtype):
    """(decode step, abstract (params, cache), abstract (tok, pos),
    mesh) for one serve-family candidate — THE program
    serve/engine.py dispatches every token, over the layout
    --serve.mesh-model would build: params placed by the partition
    metadata, the slot cache's head axis (dim 2 of every >= 3-d leaf,
    serve.engine.shard_cache's rule) sharded over "model". Everything
    is ShapeDtypeStructs: candidates rank by compiled AOT costs with
    zero bytes allocated. ``batch`` arrives as the SLOT count (decode
    batch == slots), ``seq_len`` as the cache depth."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from tensorflow_distributed_tpu.models import transformer
    from tensorflow_distributed_tpu.models.generate import decode_token
    from tensorflow_distributed_tpu.parallel.sharding import (
        param_sharding)

    model = transformer.gpt_lm(mesh, size=size, dropout_rate=0.0,
                               compute_dtype=dtype, max_len=max_len)
    abstract_vars = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)),
        jax.random.key(0))
    aparams = jax.tree_util.tree_map(
        lambda leaf, sd: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                              sharding=sd),
        nn.meta.unbox(abstract_vars)["params"],
        param_sharding(mesh, abstract_vars)["params"])
    tp = dict(mesh.shape).get("model", 1)
    tok = jnp.zeros((num_slots, 1), jnp.int32)
    cache_shapes = jax.eval_shape(
        lambda p, t, q: model.apply({"params": p}, t, decode=True,
                                    positions=q,
                                    mutable=["cache"])[1]["cache"],
        aparams, tok, tok)

    def cache_sds(leaf):
        spec = (PartitionSpec(None, None, "model")
                if tp > 1 and leaf.ndim >= 3 else PartitionSpec())
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                    sharding=NamedSharding(mesh, spec))

    acache = jax.tree_util.tree_map(cache_sds, cache_shapes)

    def step(state, batch):
        params, cache = state
        tok, pos = batch
        last, cache = decode_token(model, params, cache, tok, pos)
        ok = jnp.isfinite(last).all(axis=-1)
        return (cache, jnp.argmax(last, axis=-1).astype(jnp.int32),
                ok)

    rep = NamedSharding(mesh, PartitionSpec())
    slots = jax.ShapeDtypeStruct((num_slots,), np.int32, sharding=rep)
    # jit like the train-step builders do — aot_lower_compile wants a
    # lowerable callable.
    return jax.jit(step), (aparams, acache), (slots, slots), mesh


def score_candidate(cand: Candidate, facts: ModelFacts, batch: int,
                    hw: Hardware, seq_len: int = 128, size: str = "",
                    dropout_rate: float = 0.0,
                    compute_dtype: str = "bfloat16",
                    moe_experts: int = 0) -> Dict[str, Any]:
    """One candidate's score row: AOT costs + collective census +
    roofline prediction. Failures degrade to an explicit-null row
    with the error recorded — a broken candidate must not take down
    the plan (same contract as the program registry's registration)."""
    from tensorflow_distributed_tpu.observe.device import (
        COST_FIELDS, aot_lower_compile, extract_costs)

    row: Dict[str, Any] = {
        "mesh": cand.mesh, "strategy": cand.strategy,
        "partition": cand.partition,
        **{k: None for k in COST_FIELDS},
        "collectives": {}, "collective_bytes": 0.0,
        "lower_s": None, "compile_s": None,
    }
    if cand.microbatches:
        row["microbatches"] = cand.microbatches
    try:
        import jax

        step, state, abatch, _ = build_candidate_step(
            cand, facts, batch, seq_len=seq_len, size=size,
            dropout_rate=dropout_rate, compute_dtype=compute_dtype,
            moe_experts=moe_experts)
        traffic = collective_traffic(
            jax.make_jaxpr(step)(state, abatch))
        row["collectives"] = traffic["counts"]
        row["collective_bytes"] = traffic["bytes"]
        _, compiled, lower_s, compile_s = aot_lower_compile(
            step, (state, abatch))
        row.update(extract_costs(compiled))
        row["lower_s"] = round(lower_s, 4)
        row["compile_s"] = round(compile_s, 4)
    except Exception as e:  # degrade, never die: explicit-null row
        row["error"] = f"{type(e).__name__}: {e}"[:300]
    row.update(roofline_ms(row, row["collective_bytes"], hw,
                           overlap=cand.partition == "overlap"))
    return row


def score_candidates(cands: Sequence[Candidate], facts: ModelFacts,
                     batch: int, hw: Hardware, seq_len: int = 128,
                     size: str = "", dropout_rate: float = 0.0,
                     compute_dtype: str = "bfloat16",
                     moe_experts: int = 0,
                     hbm_budget: Optional[float] = None
                     ) -> List[Dict[str, Any]]:
    """Score every candidate, mark HBM feasibility, rank."""
    rows = [score_candidate(c, facts, batch, hw, seq_len=seq_len,
                            size=size, dropout_rate=dropout_rate,
                            compute_dtype=compute_dtype,
                            moe_experts=moe_experts)
            for c in cands]
    budget = hbm_budget if hbm_budget is not None else hw.hbm_bytes
    return rank(mark_feasibility(rows, budget))
