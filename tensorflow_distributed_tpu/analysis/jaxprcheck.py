"""Trace-level contract pass: collective & upcast census vs goldens.

The lint layer (analysis/lint.py) reads SOURCE; this layer reads the
PROGRAM. Each audited program — the LM / MoE / pipelined train steps
and the serve decode step — is traced with ``jax.make_jaxpr``
(precedent: parallel/pipeline.py's variant_residual_mask) and reduced
to a census of the two quantities that silently drift:

- **collectives**: psum / all_gather / ppermute / all_to_all /
  reduce_scatter equation counts, sub-jaxprs included. A PR that
  accidentally adds an all-gather to the decode step, or doubles the
  pipeline's ppermutes, changes a number here and fails loudly —
  instead of showing up as an ICI regression three sessions later.
- **upcasts**: ``convert_element_type`` equations widening a float
  (bfloat16→float32, float32→float64). bf16 paths legitimately upcast
  in a few places (loss accumulation, norm statistics, optimizer
  math); the census pins HOW MANY, so a silently-f32 matmul chain
  shows up as a count jump.

Budgets live in ``analysis/goldens/census.json`` (committed).
Regenerate after an INTENTIONAL change with::

    python -m tensorflow_distributed_tpu.analysis.jaxprcheck --update

and review the diff like any other golden. Plain runs compare and exit
nonzero on drift (wired into scripts/lint.sh; the same comparison
is a test in tests/test_analysis.py).

Census counts are pinned against THIS container's jax; a jax upgrade
that re-lowers a primitive is a legitimate regeneration, and the diff
shows exactly what moved.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from typing import Dict, Iterator, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def _force_cpu_topology() -> None:
    """The 8-device virtual CPU setup, exactly like tests/conftest.
    XLA_FLAGS is read when the backend first initializes, so setting
    it here is in time; jax itself is already imported (module top),
    so the platform goes through jax.config, not the environment.
    Called from main() ONLY: importing this module as a library must
    not re-platform the process (a TPU tool reusing census_of/
    iter_eqns keeps its devices). Under pytest, conftest already
    applied the same values; re-applying is a no-op.
    """
    if "--xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8").strip()
    try:
        jax.config.update("jax_platforms", "cpu")
    except RuntimeError:
        pass  # backend already initialized: use what the caller chose


GOLDEN_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "goldens", "census.json")

COLLECTIVE_PREFIXES = (
    "psum", "all_gather", "ppermute", "pmin", "pmax",
    "all_to_all", "reduce_scatter", "pgather",
)


# --- jaxpr walking -----------------------------------------------------

def _jaxprs_in(value) -> Iterator:
    """Yield any (Closed)Jaxpr reachable from an eqn param value."""
    if hasattr(value, "jaxpr") and hasattr(value, "consts"):
        yield value.jaxpr          # ClosedJaxpr
    elif hasattr(value, "eqns"):
        yield value                # Jaxpr
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _jaxprs_in(v)


def iter_eqns(jaxpr) -> Iterator:
    """Every equation, sub-jaxprs (pjit / scan / cond / shard_map /
    remat / custom_vjp bodies) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _jaxprs_in(v):
                yield from iter_eqns(sub)


def census_of(closed_jaxpr) -> Dict[str, Dict[str, int]]:
    """{"collectives": {prim: n}, "upcasts": {"bfloat16->float32": n}}"""
    collectives: Dict[str, int] = {}
    upcasts: Dict[str, int] = {}
    for eqn in iter_eqns(closed_jaxpr.jaxpr):
        name = eqn.primitive.name
        if name.startswith(COLLECTIVE_PREFIXES):
            collectives[name] = collectives.get(name, 0) + 1
        elif name == "convert_element_type":
            old = np.dtype(eqn.invars[0].aval.dtype)
            new = np.dtype(eqn.params["new_dtype"])
            if (jnp.issubdtype(old, jnp.floating)
                    and jnp.issubdtype(new, jnp.floating)
                    and new.itemsize > old.itemsize):
                key = f"{old.name}->{new.name}"
                upcasts[key] = upcasts.get(key, 0) + 1
    return {"collectives": dict(sorted(collectives.items())),
            "upcasts": dict(sorted(upcasts.items()))}


# --- the audited programs ----------------------------------------------

_B, _L, _V = 4, 16, 64  # toy shapes; the census tracks structure, not size


def _clm_batch():
    from tensorflow_distributed_tpu.data.lm import synthetic_clm
    ds = synthetic_clm(n=max(2 * _B, 32), seq_len=_L, vocab_size=_V)
    return ds.batch(np.arange(_B))


def _mesh(data: int = 1, pipe: int = 1, model: int = 1):
    from tensorflow_distributed_tpu.config import MeshConfig
    from tensorflow_distributed_tpu.parallel.mesh import make_mesh
    need = data * pipe * model
    devs = jax.devices()[:need]
    if len(devs) < need:
        raise RuntimeError(
            f"census needs {need} devices, have {len(devs)} — run via "
            f"the CLI (it forces an 8-device CPU topology) or under "
            f"tests/conftest.py")
    return make_mesh(MeshConfig(data=data, pipe=pipe, model=model), devs)


def _train_jaxpr(model_name: str, health_every: int = 0,
                 health_taps: bool = False):
    """The REAL jitted LM train step (same builders as train/loop.py),
    traced: bf16 compute so the upcast census watches the path that
    matters, dropout 0 so the trace is rng-schedule-free.

    ``health_every``/``health_taps`` build the health-instrumented
    variant (observe/health.py): its golden entry pins that enabling
    telemetry adds NO collectives — the vitals are local reductions,
    and a regression that sneaks an allreduce into the cadence branch
    fails here, not in an ICI profile three sessions later."""
    import optax

    from tensorflow_distributed_tpu.models import transformer
    from tensorflow_distributed_tpu.train.state import create_train_state
    from tensorflow_distributed_tpu.train.step import make_train_step
    from tensorflow_distributed_tpu.train.tasks import (
        make_mlm_loss, make_moe_loss, mlm_batch_shardings)

    mesh = _mesh()
    factory = (transformer.moe_lm if model_name == "moe_lm"
               else transformer.gpt_lm)
    model = factory(mesh=mesh, size="tiny", dropout_rate=0.0,
                    compute_dtype=jnp.bfloat16,
                    health_taps=health_taps)
    state = create_train_state(model, optax.adam(1e-3),
                               np.zeros((2, _L), np.int32), mesh, seed=0)
    loss = (make_moe_loss() if model_name == "moe_lm"
            else make_mlm_loss())
    step = make_train_step(mesh, loss=loss,
                           batch_shardings=mlm_batch_shardings(mesh),
                           health_every=health_every)
    return jax.make_jaxpr(step)(state, _clm_batch())


def _pipelined_jaxpr(health_every: int = 0):
    """The 1F1B pipelined step on a pipe=2 mesh — the program whose
    ppermute schedule the census exists to pin. The health variant
    proves the telemetry adds zero ppermutes/psums to the schedule."""
    import optax

    from tensorflow_distributed_tpu.models.pipelined import pipelined_lm
    from tensorflow_distributed_tpu.train.pipeline_step import (
        make_1f1b_train_step)
    from tensorflow_distributed_tpu.train.state import create_train_state

    mesh = _mesh(data=1, pipe=2)
    model = pipelined_lm(mesh, num_microbatches=2, dropout_rate=0.0,
                         compute_dtype=jnp.bfloat16, n_layers=2,
                         max_len=_L)
    state = create_train_state(model, optax.adam(1e-3),
                               np.zeros((2, _L), np.int32), mesh)
    step = make_1f1b_train_step(model, mesh, health_every=health_every)
    return jax.make_jaxpr(step)(state, _clm_batch())


#: The overlap census build: data=2 mesh, tiny model, a bucket bound
#: and scatter threshold small enough that the tiny tree splits into
#: SEVERAL scatter buckets — the golden pins one psum_scatter + one
#: all_gather PER BUCKET (plus the replicated-leaf psum and the metric
#: pmeans), so a refactor that fuses, drops, or doubles a bucket's
#: collectives fails here by count.
_OVERLAP_DATA = 2
_OVERLAP_BUCKET_BYTES = 8192
_OVERLAP_MIN_SIZE = 256


def _overlap_jaxpr(model_name: str):
    """The explicit overlap train step (parallel/overlap.py) on a
    data=2 mesh: bucketed psum_scatter -> ZeRO-1 sharded update ->
    bucketed all_gather, traced via the REAL builder. Model built
    mesh-less (the forward runs inside the step's shard_map; see the
    builder's docstring), state built with zero1 slots at the same
    scatter threshold the step plans with."""
    import optax

    from tensorflow_distributed_tpu.models import transformer
    from tensorflow_distributed_tpu.parallel.overlap import (
        make_explicit_train_step)
    from tensorflow_distributed_tpu.train.state import create_train_state
    from tensorflow_distributed_tpu.train.tasks import (
        make_mlm_loss, make_moe_loss, mlm_batch_shardings)

    mesh = _mesh(data=_OVERLAP_DATA)
    factory = (transformer.moe_lm if model_name == "moe_lm"
               else transformer.gpt_lm)
    model = factory(mesh=None, size="tiny", dropout_rate=0.0,
                    compute_dtype=jnp.bfloat16, tp_partitioning=False)
    state = create_train_state(model, optax.adam(1e-3),
                               np.zeros((2, _L), np.int32), mesh, seed=0,
                               opt_fsdp=True,
                               fsdp_min_size=_OVERLAP_MIN_SIZE)
    loss = (make_moe_loss() if model_name == "moe_lm"
            else make_mlm_loss())
    step = make_explicit_train_step(
        mesh, state, loss=loss,
        batch_shardings=mlm_batch_shardings(mesh), grad_sync="overlap",
        bucket_bytes=_OVERLAP_BUCKET_BYTES,
        fsdp_min_size=_OVERLAP_MIN_SIZE, jit=False)
    return jax.make_jaxpr(step)(state, _clm_batch())


def _serve_model(kv_cache_quant: str = "none"):
    """The tiny bf16 causal LM + zeroed slot cache the serve censuses
    trace against (kv_cache_quant="int8" produces the quantized cache
    layout — int8 K/V leaves with f32 scale leaves beside them)."""
    from tensorflow_distributed_tpu.models.transformer import (
        CausalLM, tiny_config)

    num_slots = 4
    model = CausalLM(tiny_config(causal=True,
                                 compute_dtype=jnp.bfloat16,
                                 kv_cache_quant=kv_cache_quant))
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    tok = jnp.zeros((num_slots, 1), jnp.int32)
    pos = jnp.zeros((num_slots, 1), jnp.int32)
    shapes = jax.eval_shape(
        lambda p, t, q: model.apply({"params": p}, t, decode=True,
                                    positions=q,
                                    mutable=["cache"])[1]["cache"],
        params, tok, pos)
    cache = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    return model, params, cache, num_slots


def _serve_decode_jaxpr(kv_cache_quant: str = "none"):
    """THE decode program serve/engine.py dispatches every step: one
    greedy token for every slot at its own depth. The int8 variant
    (``serve_decode_int8``) pins that KV-cache quantization adds NO
    collectives and only a bounded number of dtype converts — the
    quantize-on-write/scale-adjusted-attend math is entirely local."""
    from tensorflow_distributed_tpu.models.generate import decode_token
    from tensorflow_distributed_tpu.serve.engine import step_inputs

    model, params, cache, num_slots = _serve_model(kv_cache_quant)

    def run(params, cache, prev, host):
        # Mirrors serve/engine.py::_compiled_step: the token fed is
        # chosen on the device (step_inputs), greedy token + the
        # per-slot finiteness flag (NaN containment sensor) — the
        # golden pins that neither adds a collective.
        tok, pos = step_inputs(prev, host)
        last, cache = decode_token(model, params, cache, tok, pos)
        ok = jnp.isfinite(last).all(axis=-1)
        return (cache, jnp.argmax(last, axis=-1).astype(jnp.int32),
                ok)

    return jax.make_jaxpr(run)(params, cache,
                               jnp.zeros((num_slots,), jnp.int32),
                               jnp.zeros((3, num_slots), jnp.int32))


#: The verify census build: k proposals per slot, matching
#: serve/engine.py::_compiled_verify's shape discipline (toks
#: [S, k+1] = pending + proposals; one forward, argmax chain + ok).
_VERIFY_K = 4


def _serve_verify_jaxpr():
    """THE speculative verify program (serve/engine.py::
    _compiled_verify): all k proposals scored in one forward over the
    slot cache. The golden pins that speculation's verify adds ZERO
    collectives next to serve_decode — it is the same local attend
    over k + 1 positions."""
    model, params, cache, num_slots = _serve_model()
    k = _VERIFY_K

    def run(params, cache, toks, pos):
        positions = pos[:, None] + jnp.arange(k + 1)[None, :]
        logits, state = model.apply(
            {"params": params, "cache": cache}, toks, decode=True,
            positions=positions, mutable=["cache"])
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        ok = jnp.isfinite(logits).all(axis=(-1, -2))
        return state["cache"], nxt, ok

    return jax.make_jaxpr(run)(
        params, cache, jnp.zeros((num_slots, k + 1), jnp.int32),
        jnp.zeros((num_slots,), jnp.int32))


#: The paged-census page size (tiny max_len 128 -> 8 pages per slot).
_PAGE_SIZE = 16


def _serve_paged_model():
    """The tiny bf16 causal LM over a PAGED slot cache (serve/paging):
    [num_pages, page_size, ...] pool leaves + per-slot page tables."""
    from tensorflow_distributed_tpu.models.transformer import (
        CausalLM, tiny_config)

    num_slots = 4
    cfg = tiny_config(causal=True, compute_dtype=jnp.bfloat16)
    maxp = cfg.max_len // _PAGE_SIZE
    cfg = dataclasses.replace(cfg, kv_page_size=_PAGE_SIZE,
                              kv_num_pages=1 + num_slots * maxp)
    model = CausalLM(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    tables = jnp.zeros((num_slots, maxp), jnp.int32)
    tok = jnp.zeros((num_slots, 1), jnp.int32)
    pos = jnp.zeros((num_slots, 1), jnp.int32)
    shapes = jax.eval_shape(
        lambda p, t, q, g: model.apply({"params": p}, t, decode=True,
                                       positions=q, page_table=g,
                                       mutable=["cache"])[1]["cache"],
        params, tok, pos, tables)
    cache = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    return model, params, cache, tables, num_slots


def _serve_decode_paged_jaxpr():
    """THE paged decode program (serve/paging/engine.py::
    _compiled_step_paged): the dense decode plus the page-table gather
    — the golden pins that paging adds ZERO collectives (the gather is
    a local addressing change, not communication)."""
    from tensorflow_distributed_tpu.serve.engine import step_inputs

    model, params, cache, tables, num_slots = _serve_paged_model()

    def run(params, cache, prev, host, tables):
        tok, pos = step_inputs(prev, host)
        logits, state = model.apply(
            {"params": params, "cache": cache}, tok[:, None],
            decode=True, positions=pos[:, None], page_table=tables,
            mutable=["cache"])
        last = logits[:, -1, :]
        ok = jnp.isfinite(last).all(axis=-1)
        return (state["cache"],
                jnp.argmax(last, axis=-1).astype(jnp.int32), ok)

    return jax.make_jaxpr(run)(params, cache,
                               jnp.zeros((num_slots,), jnp.int32),
                               jnp.zeros((3, num_slots), jnp.int32),
                               tables)


def _serve_verify_paged_jaxpr():
    """THE paged speculative verify (serve/paging/engine.py::
    _compiled_verify_paged) — zero collectives, like the dense one."""
    model, params, cache, tables, num_slots = _serve_paged_model()
    k = _VERIFY_K

    def run(params, cache, toks, pos, tables):
        positions = pos[:, None] + jnp.arange(k + 1)[None, :]
        logits, state = model.apply(
            {"params": params, "cache": cache}, toks, decode=True,
            positions=positions, page_table=tables, mutable=["cache"])
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        ok = jnp.isfinite(logits).all(axis=(-1, -2))
        return state["cache"], nxt, ok

    return jax.make_jaxpr(run)(
        params, cache, jnp.zeros((num_slots, k + 1), jnp.int32),
        jnp.zeros((num_slots,), jnp.int32), tables)


def _serve_prefill_paged_jaxpr():
    """THE paged tail-prefill program (serve/paging/engine.py::
    _compiled_prefill_paged, bucket 16): writes the uncached tail
    through the slot's page table at an offset, attends the cached
    prefix pages, emits the greedy first token — zero collectives."""
    model, params, cache, tables, _num_slots = _serve_paged_model()
    bucket = 16

    def run(params, cache, prompt, positions, table, true_len):
        logits, state = model.apply(
            {"params": params, "cache": cache}, prompt, decode=True,
            positions=positions, page_table=table, mutable=["cache"])
        last = jax.lax.dynamic_index_in_dim(
            logits, true_len - 1, axis=1, keepdims=False)
        return (state["cache"],
                jnp.argmax(last, axis=-1).astype(jnp.int32))

    return jax.make_jaxpr(run)(
        params, cache, jnp.zeros((1, bucket), jnp.int32),
        jnp.zeros((1, bucket), jnp.int32), tables[:1],
        jnp.asarray(1, jnp.int32))


# --- tensor-parallel serve censuses ------------------------------------
#
# GSPMD inserts the TP collectives during PARTITIONING, after the jaxpr
# — jax.make_jaxpr sees none of them, so the TP entries census the
# COMPILED HLO text instead (the same artifact the AOT planner costs).
# The op names below are HLO's, not jaxpr primitives; the "-start"
# variants catch an async split, which counts the same program once.

HLO_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                   "collective-permute", "all-to-all")

_SERVE_TP = 2  # the model-axis width the TP censuses pin


def _hlo_collectives(hlo: str) -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for op in HLO_COLLECTIVES:
        n = hlo.count(f" {op}(") + hlo.count(f" {op}-start(")
        if n:
            counts[op] = n
    return dict(sorted(counts.items()))


def _serve_tp_model(kv_cache_quant: str = "none"):
    """The tiny bf16 causal LM over a [data=1, model=2] mesh — the
    layout ``--serve.mesh-model 2`` builds (serve/run.py): params
    placed via the partition metadata (heads/MLP width sharded over
    "model"), slot cache head-sharded by serve.engine.shard_cache."""
    import flax.linen as nn

    from tensorflow_distributed_tpu.models import transformer
    from tensorflow_distributed_tpu.parallel.sharding import (
        param_sharding)
    from tensorflow_distributed_tpu.serve.engine import zero_cache

    num_slots = 4
    mesh = _mesh(model=_SERVE_TP)
    model = transformer.gpt_lm(mesh, size="tiny",
                               compute_dtype=jnp.bfloat16,
                               kv_cache_quant=kv_cache_quant)
    sample = jnp.zeros((1, 8), jnp.int32)
    abstract = jax.eval_shape(lambda k: model.init(k, sample),
                              jax.random.key(0))
    variables = jax.jit(
        lambda k: nn.meta.unbox(model.init(k, sample)),
        out_shardings=param_sharding(mesh, abstract))(jax.random.key(0))
    params = variables["params"]
    cache = zero_cache(model, params, num_slots)
    return model, params, cache, num_slots


def _serve_decode_tp_census(kv_cache_quant: str = "none"):
    """THE tensor-parallel decode step: the golden pins the per-step
    collective schedule (attention out-proj + MLP down-proj psums and
    the logits gather land as all-reduce/all-gather here) — NONZERO by
    construction, and a count jump means a program change re-gathers
    the sharded cache or activations every token."""
    from tensorflow_distributed_tpu.models.generate import decode_token
    from tensorflow_distributed_tpu.serve.engine import step_inputs

    model, params, cache, num_slots = _serve_tp_model(kv_cache_quant)

    def run(params, cache, prev, host):
        tok, pos = step_inputs(prev, host)
        last, cache = decode_token(model, params, cache, tok, pos)
        ok = jnp.isfinite(last).all(axis=-1)
        return (cache, jnp.argmax(last, axis=-1).astype(jnp.int32),
                ok)

    args = (params, cache, jnp.zeros((num_slots,), jnp.int32),
            jnp.zeros((3, num_slots), jnp.int32))
    hlo = jax.jit(run).lower(*args).compile().as_text()
    return {"collectives": _hlo_collectives(hlo),
            "upcasts": census_of(jax.make_jaxpr(run)(*args))["upcasts"]}


def _serve_verify_tp_census():
    """THE tensor-parallel speculative verify — same sharded attend
    over k + 1 positions; its collective schedule must match the
    decode step's shape (per-dispatch, not per-token)."""
    model, params, cache, num_slots = _serve_tp_model()
    k = _VERIFY_K

    def run(params, cache, toks, pos):
        positions = pos[:, None] + jnp.arange(k + 1)[None, :]
        logits, state = model.apply(
            {"params": params, "cache": cache}, toks, decode=True,
            positions=positions, mutable=["cache"])
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        ok = jnp.isfinite(logits).all(axis=(-1, -2))
        return state["cache"], nxt, ok

    args = (params, cache, jnp.zeros((num_slots, k + 1), jnp.int32),
            jnp.zeros((num_slots,), jnp.int32))
    hlo = jax.jit(run).lower(*args).compile().as_text()
    return {"collectives": _hlo_collectives(hlo),
            "upcasts": census_of(jax.make_jaxpr(run)(*args))["upcasts"]}


PROGRAMS = {
    "gpt_train": lambda: _train_jaxpr("gpt_lm"),
    "moe_train": lambda: _train_jaxpr("moe_lm"),
    "pipelined_train": _pipelined_jaxpr,
    "serve_decode": _serve_decode_jaxpr,
    # Health-instrumented variants (observe.health: cadence 10, taps
    # on the dense family): the budgets pin that device telemetry
    # adds NO collectives next to the plain entries above.
    "gpt_train_health": lambda: _train_jaxpr(
        "gpt_lm", health_every=10, health_taps=True),
    "moe_train_health": lambda: _train_jaxpr(
        "moe_lm", health_every=10),
    "pipelined_train_health": lambda: _pipelined_jaxpr(health_every=10),
    # Explicit overlap grad-sync (parallel/overlap.py): the budgets
    # pin the bucketed reduce-scatter/all-gather schedule per bucket
    # count (see _overlap_jaxpr's constants).
    "gpt_train_overlap": lambda: _overlap_jaxpr("gpt_lm"),
    "moe_train_overlap": lambda: _overlap_jaxpr("moe_lm"),
    # Fast-path serving (speculative verify + int8 KV cache): both pin
    # ZERO collectives — per-token cost work must stay local — and the
    # int8 entry bounds the quantize/dequantize convert count.
    "serve_verify": _serve_verify_jaxpr,
    "serve_decode_int8": lambda: _serve_decode_jaxpr("int8"),
    # Paged KV serving (serve/paging): the paged decode/verify/prefill
    # executables pin ZERO collectives — page-table addressing is a
    # local gather/scatter, never communication.
    "serve_decode_paged": _serve_decode_paged_jaxpr,
    "serve_verify_paged": _serve_verify_paged_jaxpr,
    "serve_prefill_paged": _serve_prefill_paged_jaxpr,
    # Tensor-parallel serving (--serve.mesh-model 2): censused from
    # the compiled HLO (GSPMD inserts these collectives after the
    # jaxpr) — the ONLY entries whose collective budget is NONZERO,
    # pinning the per-step schedule the sharded replica pays.
    "serve_decode_tp": _serve_decode_tp_census,
    "serve_verify_tp": _serve_verify_tp_census,
}


def census(programs: Optional[Sequence[str]] = None
           ) -> Dict[str, Dict[str, Dict[str, int]]]:
    """Trace the named programs (default: all) and return their
    censuses, keyed like the golden file."""
    names = list(programs) if programs else sorted(PROGRAMS)
    out = {}
    for name in names:
        result = PROGRAMS[name]()
        # TP entries return a READY census (collectives counted from
        # compiled HLO — a jaxpr walk cannot see GSPMD's insertions);
        # everything else returns a jaxpr to walk here.
        out[name] = (result if isinstance(result, dict)
                     else census_of(result))
    return out


def load_golden() -> Dict:
    with open(GOLDEN_PATH) as f:
        return json.load(f)


def diff_censuses(golden: Dict, current: Dict,
                  required: Optional[Sequence[str]] = None) -> list:
    """Human-readable drift lines; empty when within budget.

    ``required`` names the programs this run was asked to trace
    (None = a full run, which must cover every golden entry): a
    golden program missing from a FULL run is drift — a deleted or
    renamed PROGRAMS entry must not silently disarm its budget.
    """
    lines = []
    req = set(golden) if required is None else set(required)
    for prog in sorted(set(golden) | set(current)):
        if prog not in golden:
            lines.append(f"{prog}: not in golden (new program? run "
                         f"--update)")
            continue
        if prog not in current:
            if prog in req:
                lines.append(
                    f"{prog}: in the golden but missing from the run "
                    f"(deleted/renamed in PROGRAMS? its budget is no "
                    f"longer checked)")
            continue  # partial run: only compare what was traced
        for section in ("collectives", "upcasts"):
            g = golden[prog].get(section, {})
            c = current[prog].get(section, {})
            for key in sorted(set(g) | set(c)):
                gv, cv = g.get(key, 0), c.get(key, 0)
                if gv != cv:
                    lines.append(
                        f"{prog}: {section}[{key}] {gv} -> {cv}")
    return lines


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tensorflow_distributed_tpu.analysis.jaxprcheck",
        description="collective/upcast census of the audited programs "
                    "vs the committed golden budgets")
    parser.add_argument("--update", action="store_true",
                        help="rewrite the golden file with the current "
                             "census (review the diff!)")
    parser.add_argument("--programs", default="",
                        help=f"comma-separated subset of "
                             f"{sorted(PROGRAMS)}")
    args = parser.parse_args(argv)
    _force_cpu_topology()
    names = ([n.strip() for n in args.programs.split(",") if n.strip()]
             if args.programs else None)
    unknown = set(names or ()) - set(PROGRAMS)
    if unknown:
        print(f"jaxprcheck: unknown programs {sorted(unknown)}; have "
              f"{sorted(PROGRAMS)}", file=sys.stderr)
        return 2
    current = census(names)
    for prog, c in current.items():
        print(f"{prog}: collectives={c['collectives']} "
              f"upcasts={c['upcasts']}")
    if args.update:
        if names:
            merged = load_golden() if os.path.exists(GOLDEN_PATH) else {}
            merged.update(current)
            current = dict(sorted(merged.items()))
        os.makedirs(os.path.dirname(GOLDEN_PATH), exist_ok=True)
        with open(GOLDEN_PATH, "w") as f:
            json.dump(current, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"jaxprcheck: wrote {GOLDEN_PATH}")
        return 0
    if not os.path.exists(GOLDEN_PATH):
        print(f"jaxprcheck: no golden at {GOLDEN_PATH}; run with "
              f"--update to create it", file=sys.stderr)
        return 1
    drift = diff_censuses(load_golden(), current, required=names)
    if drift:
        for line in drift:
            print(f"jaxprcheck: DRIFT {line}", file=sys.stderr)
        print("jaxprcheck: census drift — if intentional, regenerate "
              "with --update and commit the diff", file=sys.stderr)
        return 1
    print("jaxprcheck: within budget")
    return 0


if __name__ == "__main__":
    sys.exit(main())
