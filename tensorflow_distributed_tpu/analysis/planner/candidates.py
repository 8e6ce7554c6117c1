"""Candidate enumeration: mesh factorizations x parallelism strategies.

Pure and import-light (stdlib only at module load; the shared
divisibility rules and the model-size facts are imported lazily), so
the enumeration/pruning logic unit-tests with stubbed constraints and
zero jax machinery.

A candidate is a full mesh-axes assignment plus a parameter-partition
choice. Hard constraints prune UP FRONT, each pruned shape keeping its
reason — the planner's report distinguishes "never valid" (pruned
here) from "valid but over the HBM budget" (marked infeasible at
scoring time, score.mark_feasibility).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

# Same axis order as parallel.mesh.MESH_AXES (not imported: that
# module loads jax; this one must not).
MESH_AXES = ("data", "pipe", "seq", "model", "expert")

#: planner family -> the model registry name the train CLI uses.
#: "serve" plans the gpt family's DECODE program over the serve
#: replica's own tensor-parallel mesh (--serve.mesh-model), not a
#: train step — enumerate_candidates and the scorer branch on it.
FAMILY_MODELS = {"gpt": "gpt_lm", "moe": "moe_lm",
                 "pipelined": "pipelined_lm", "serve": "gpt_lm"}
#: registry name -> TRAIN family (serve excluded: gpt_lm's inverse is
#: the train family; serve is an explicit planner choice, never an
#: inference from a model name).
MODEL_FAMILIES = {v: k for k, v in FAMILY_MODELS.items()
                  if k != "serve"}

#: the factory-default size per family (models/transformer.py
#: gpt_lm(size="small"), moe_lm(size="tiny"), pipelined_lm("tiny")).
DEFAULT_SIZES = {"gpt": "small", "moe": "tiny", "pipelined": "tiny",
                 "serve": "small"}

#: the TP widths the serve family enumerates (ISSUE: rank
#: --serve.mesh-model without executing; width 1 is the single-device
#: engine the others are ranked against).
SERVE_TP_WIDTHS = (1, 2, 4)

#: Partition-like strategy choices. "overlap" = zero1 slot sharding +
#: the explicit bucketed reduce-scatter/all-gather grad sync
#: (parallel/overlap.py; launches as --param-partition zero1
#: --grad-sync overlap). Pure-data meshes only — the explicit
#: shard_map formulation doesn't reproduce tensor/expert/pipe
#: schedules.
PARTITIONS = ("replicated", "fsdp", "zero1", "overlap")


def format_mesh(mesh: Dict[str, int]) -> str:
    """"data=8" / "data=4,model=2" / "single-device" — THE mesh
    formatter for planner output. One copy on purpose: candidate
    keys are built from plan output, so two formatters drifting
    apart would silently break a lookup by key."""
    parts = [f"{k}={v}" for k, v in mesh.items() if v != 1]
    return ",".join(parts) if parts else "single-device"


@dataclasses.dataclass(frozen=True)
class ModelFacts:
    """What enumeration needs to know about a model family/size —
    nothing else (the scoring layer builds the real model)."""

    family: str                 # gpt | moe | pipelined
    n_heads: int
    n_layers: int
    n_experts: int = 0          # 0 = dense (no expert axis)
    vocab_size: int = 0         # factory base vocab; 0 = unknown
    #                             (only the serve family prunes on it:
    #                             the TP head is vocab-parallel)

    def validate(self) -> None:
        if self.family not in FAMILY_MODELS:
            raise ValueError(
                f"unknown planner family {self.family!r}; have "
                f"{sorted(FAMILY_MODELS)}")
        if self.n_heads < 1 or self.n_layers < 1 or self.n_experts < 0:
            raise ValueError(
                f"bad model facts: heads={self.n_heads} "
                f"layers={self.n_layers} experts={self.n_experts}")


def model_facts(family: str, size: str = "",
                moe_experts: int = 0) -> ModelFacts:
    """Facts for a named family/size preset, read from the model
    factories' OWN constants (lazy imports — the sizes live with the
    factories), so pruning can never desynchronize from the real
    model the scorer builds."""
    if family not in FAMILY_MODELS:
        raise ValueError(f"unknown planner family {family!r}; have "
                         f"{sorted(FAMILY_MODELS)}")
    size = size or DEFAULT_SIZES[family]
    from tensorflow_distributed_tpu.models.transformer import (
        GPT2_SIZES, MOE_DEFAULT_EXPERTS, tiny_config)
    if size == "tiny":
        tiny = tiny_config()
        heads, layers = tiny.n_heads, tiny.n_layers
        vocab = tiny.vocab_size
        if family == "pipelined":
            # pipelined_lm bumps tiny's layer count so common stage
            # counts divide it — the same constant the factory uses.
            from tensorflow_distributed_tpu.models.pipelined import (
                PIPELINED_TINY_LAYERS)
            layers = PIPELINED_TINY_LAYERS
    elif size in GPT2_SIZES:
        from tensorflow_distributed_tpu.models.transformer import (
            gpt2_small_config)
        heads = GPT2_SIZES[size]["n_heads"]
        layers = GPT2_SIZES[size]["n_layers"]
        vocab = gpt2_small_config().vocab_size
    else:
        raise ValueError(f"unknown size {size!r}; have "
                         f"(tiny, {', '.join(GPT2_SIZES)})")
    experts = ((moe_experts or MOE_DEFAULT_EXPERTS)
               if family == "moe" else 0)
    return ModelFacts(family=family, n_heads=heads, n_layers=layers,
                      n_experts=experts, vocab_size=vocab)


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One launch configuration: a full mesh-axes assignment plus the
    parameter-partition mode (and, pipelined, the microbatch count)."""

    axes: Tuple[Tuple[str, int], ...]   # hashable (axis, size) pairs
    partition: str = "replicated"       # replicated | fsdp | zero1
    microbatches: int = 0               # pipelined only (0 = n/a)
    serve: bool = False                 # serve family: the mesh is the
    #                                     ENGINE's (--serve.mesh-model),
    #                                     not the train --mesh.*

    @staticmethod
    def make(axes: Dict[str, int], partition: str = "replicated",
             microbatches: int = 0, serve: bool = False) -> "Candidate":
        full = {a: int(axes.get(a, 1)) for a in MESH_AXES}
        return Candidate(axes=tuple(full.items()), partition=partition,
                         microbatches=microbatches, serve=serve)

    @property
    def mesh(self) -> Dict[str, int]:
        return dict(self.axes)

    @property
    def strategy(self) -> str:
        """Human name, e.g. "data", "fsdp+tensor", "data+pipe". The
        partition contributes its name (fsdp/zero1) or "data" for
        plain replicated data parallelism; each non-unit non-data
        axis contributes tensor/seq/pipe/expert."""
        mesh = self.mesh
        parts: List[str] = []
        if self.partition != "replicated":
            parts.append(self.partition)
        elif mesh["data"] > 1:
            parts.append("data")
        for axis, name in (("model", "tensor"), ("seq", "seq"),
                           ("pipe", "pipe"), ("expert", "expert")):
            if mesh[axis] > 1:
                parts.append(name)
        return "+".join(parts) if parts else "data"

    def cli_args(self) -> List[str]:
        """The train-CLI flags that launch this candidate."""
        if self.serve:
            # The serve engine builds its OWN mesh from this one knob
            # (serve/run.py validates heads/devices at launch); the
            # train --mesh.* flags are rejected under mode=serve.
            return ["--serve.mesh-model", str(self.mesh["model"])]
        out: List[str] = []
        for axis, size in self.axes:
            out += [f"--mesh.{axis}", str(size)]
        if self.partition == "overlap":
            # The overlap strategy IS zero1 slot sharding plus the
            # explicit grad-sync flag.
            out += ["--param-partition", "zero1",
                    "--grad-sync", "overlap"]
        elif self.partition != "replicated":
            out += ["--param-partition", self.partition]
        if self.microbatches:
            out += ["--pipeline-microbatches", str(self.microbatches)]
        return out


@dataclasses.dataclass(frozen=True)
class Pruned:
    """A shape rejected by a hard constraint — kept, with its reason,
    so the plan reports what was ruled out and why."""

    candidate: Candidate
    reason: str


def _default_infeasible(axes: Dict[str, int], devices: int,
                        batch: Optional[int]) -> Optional[str]:
    # The shared rules (lazy import: parallel.mesh loads jax; the
    # enumeration itself must stay stdlib-importable for the jax-free
    # unit tier, which stubs this callable).
    from tensorflow_distributed_tpu.parallel.mesh import mesh_infeasible
    return mesh_infeasible(axes, devices, batch)


def _family_infeasible(facts: ModelFacts, axes: Dict[str, int],
                       batch: int, microbatches: int) -> Optional[str]:
    """Family/model divisibility the mesh layer can't know."""
    if axes.get("model", 1) > 1 and facts.n_heads % axes["model"]:
        return (f"n_heads {facts.n_heads} not divisible by tensor "
                f"axis {axes['model']} (heads shard over 'model')")
    if axes.get("expert", 1) > 1:
        if not facts.n_experts:
            return "expert axis needs an MoE family"
        if (facts.n_experts % axes["expert"]
                or axes["expert"] > facts.n_experts):
            return (f"{facts.n_experts} experts not divisible by "
                    f"expert axis {axes['expert']}")
    if axes.get("pipe", 1) > 1:
        if facts.n_layers % axes["pipe"]:
            return (f"n_layers {facts.n_layers} not divisible by pipe "
                    f"axis {axes['pipe']} (layers slice into stages)")
        if microbatches < axes["pipe"]:
            return (f"microbatches {microbatches} < pipe "
                    f"{axes['pipe']}: every stage needs a microbatch "
                    f"in flight")
    if facts.family == "pipelined" and batch % max(microbatches, 1):
        return (f"global batch {batch} not divisible by "
                f"pipeline microbatches {microbatches}")
    return None


def _second_axes(facts: ModelFacts) -> Sequence[str]:
    """Which non-data axis the family's factorizations spread over
    (seq stays 1 — ring attention is a long-context knob, not a
    throughput layout, and the planner doesn't model its windows)."""
    if facts.family == "pipelined":
        return ("pipe",)
    if facts.family == "moe":
        return ("model", "expert")
    return ("model",)


def enumerate_candidates(
        facts: ModelFacts, devices: int, batch: int,
        strategies: Optional[Sequence[str]] = None,
        microbatches: int = 4,
        infeasible: Optional[Callable[..., Optional[str]]] = None,
        overlap_conflict: Optional[str] = None,
) -> Tuple[List[Candidate], List[Pruned]]:
    """All (mesh factorization x partition) candidates for a family.

    Returns ``(feasible, pruned)`` — pruned shapes keep their reasons.
    ``strategies`` restricts by strategy PART (e.g. ("data", "fsdp",
    "zero1") excludes every tensor/expert/pipe shape); a candidate
    survives only when every part of its strategy name is allowed.
    ``infeasible`` is the shared mesh rule
    (parallel.mesh.mesh_infeasible), injectable for jax-free tests.
    ``overlap_conflict`` (a reason string, or None) prunes every
    "overlap" candidate — --plan auto passes the run's
    config.overlap_grad_sync_conflict() so the plan never picks a
    layout whose launch the config would then reject (the standalone
    planner CLI plans layouts, not runs, and passes nothing).
    """
    facts.validate()
    if devices < 1:
        raise ValueError(f"devices must be >= 1, got {devices}")
    if batch < 1:
        raise ValueError(f"batch must be >= 1, got {batch}")
    check = infeasible or _default_infeasible
    allowed = set(strategies) if strategies else None
    feasible: List[Candidate] = []
    pruned: List[Pruned] = []
    if facts.family == "serve":
        # The serve replica's OWN mesh: always [data=1, model=N] — the
        # engine serves one replica; data-scaling is the fleet
        # router's job, not this mesh's. ``batch`` is the slot count
        # (replicated), so the mesh rules' batch-divisibility checks
        # don't apply; what does: devices and head divisibility.
        for width in SERVE_TP_WIDTHS:
            cand = Candidate.make({"data": 1, "model": width},
                                  serve=True)
            if width > devices:
                pruned.append(Pruned(cand, (
                    f"model={width} needs {width} devices, have "
                    f"{devices}")))
                continue
            if width > 1 and facts.n_heads % width:
                pruned.append(Pruned(cand, (
                    f"n_heads {facts.n_heads} not divisible by model "
                    f"axis {width} (heads shard over 'model')")))
                continue
            if width > 1 and facts.vocab_size % width:
                # The TP LM head is vocab-parallel (column-split over
                # "model"); an odd vocab like GPT-2's 50257 only
                # shards padded (--shard-vocab), which the serve
                # scorer does not model — prune, don't error-row.
                pruned.append(Pruned(cand, (
                    f"vocab {facts.vocab_size} not divisible by model "
                    f"axis {width} (the LM head is vocab-parallel; "
                    f"--shard-vocab pads it)")))
                continue
            if allowed is not None and not (
                    set(cand.strategy.split("+")) <= allowed):
                pruned.append(Pruned(cand, (
                    f"strategy {cand.strategy!r} excluded by "
                    f"--strategies")))
                continue
            feasible.append(cand)
        return feasible, pruned
    second_axes = _second_axes(facts)
    for second in second_axes:
        for k in range(1, devices + 1):
            if devices % k:
                continue
            if k == 1 and second != second_axes[0]:
                continue  # the pure-data shape: keep one copy only
            data = devices // k
            axes = {"data": data, second: k}
            # Pipelined runs its schedule at any pipe >= 1; the
            # microbatch count never drops below the stage count.
            mb = (max(microbatches, k) if facts.family == "pipelined"
                  else 0)
            for partition in PARTITIONS:
                cand = Candidate.make(axes, partition, microbatches=mb)
                if partition == "fsdp" and facts.family == "pipelined":
                    pruned.append(Pruned(cand, (
                        "fsdp does not compose with pipelined_lm "
                        "(stage params are shard_map-managed; "
                        "config.validate rejects it)")))
                    continue
                if partition == "overlap":
                    if facts.family == "pipelined":
                        pruned.append(Pruned(cand, (
                            "overlap grad-sync applies to the "
                            "standard jitted step; the hand-scheduled "
                            "pipeline owns its own collective "
                            "schedule")))
                        continue
                    if k > 1:
                        pruned.append(Pruned(cand, (
                            f"overlap grad-sync needs a pure data "
                            f"mesh; {second}={k} > 1")))
                        continue
                    if overlap_conflict:
                        pruned.append(Pruned(cand, (
                            f"overlap grad-sync: {overlap_conflict}")))
                        continue
                if partition != "replicated" and data == 1:
                    pruned.append(Pruned(cand, (
                        f"{partition} shards over the data axis; "
                        f"data=1 replicates — identical to the "
                        f"plain candidate")))
                    continue
                reason = (check(axes, devices, batch)
                          or _family_infeasible(facts, axes, batch,
                                                mb))
                if reason:
                    pruned.append(Pruned(cand, reason))
                    continue
                if allowed is not None and not (
                        set(cand.strategy.split("+")) <= allowed):
                    pruned.append(Pruned(cand, (
                        f"strategy {cand.strategy!r} excluded by "
                        f"--strategies")))
                    continue
                feasible.append(cand)
    return feasible, pruned
