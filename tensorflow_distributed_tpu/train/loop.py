"""The training loop: the reference's five entrypoints as one function.

Covers mnist_python_m.py:285-320 (train loop + validation loop) and
mnist_single.py:104-134 (single-device loop + timing prints) with the
same code on any mesh shape and any task family. The loop body is thin
by design — the only per-step host work is feeding the next prefetched
batch, exactly the collapse SURVEY.md §3.5 prescribes.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Dict, Optional

import jax
import numpy as np
import optax

from tensorflow_distributed_tpu.analysis import runtime as graftcheck
from tensorflow_distributed_tpu.config import (
    SOURCE_CONFIG_MODELS, TrainConfig)
from tensorflow_distributed_tpu.data import prefetch_to_mesh
from tensorflow_distributed_tpu.models import (
    INFERENCE_ONLY_MODELS, build_model)
from tensorflow_distributed_tpu.models.transformer import train_flash_plan
from tensorflow_distributed_tpu.observe import Observatory
from tensorflow_distributed_tpu.observe import health as health_mod
from tensorflow_distributed_tpu.observe.registry import host_tags
from tensorflow_distributed_tpu.parallel import make_mesh
from tensorflow_distributed_tpu.parallel.mesh import (
    bootstrap, is_chief, mesh_shape_dict)
from tensorflow_distributed_tpu.parallel.sharding import (
    process_slice, shard_batch)
from tensorflow_distributed_tpu.resilience.faults import (
    FaultPlan, parse_fault_plan)
from tensorflow_distributed_tpu.resilience.policies import (
    LossSpikeDetector, NonFinitePolicy, RecoveryBudgetExceeded)
from tensorflow_distributed_tpu.resilience.watchdog import Watchdog
from tensorflow_distributed_tpu.train import checkpoint as ckpt
from tensorflow_distributed_tpu.train.optim import make_optimizer
from tensorflow_distributed_tpu.train.preemption import PreemptionGuard
from tensorflow_distributed_tpu.train.state import (
    TrainState, create_train_state, param_count)
from tensorflow_distributed_tpu.train.step import make_eval_step, make_train_step
from tensorflow_distributed_tpu.train.tasks import Task, make_task
from tensorflow_distributed_tpu.utils.logging import MetricLogger, Timer
from tensorflow_distributed_tpu.utils.profiling import StepProfiler


@dataclasses.dataclass
class TrainResult:
    state: TrainState
    train_seconds: float
    eval_seconds: float
    final_metrics: Dict[str, float]
    steps_per_sec: float
    images_per_sec: float
    logger: MetricLogger


def evaluate(state: TrainState, eval_fn, task: Task, mesh, batch: int
             ) -> Dict[str, float]:
    """Full-split eval in fixed-size SPMD batches (the reference's 5x1000
    validation loop, mnist_python_m.py:309-320, as jitted calls)."""
    data_size = mesh.shape["data"]
    # Clamp to the split size (rounded to a shardable multiple) so a
    # small validation split with a large eval_batch still evaluates.
    batch = min(batch, (task.eval_size // data_size) * data_size)
    if batch == 0:
        raise ValueError(
            f"validation split ({task.eval_size} rows) smaller than the "
            f"mesh data axis ({data_size})")
    totals: Dict[str, float] = {}
    count = 0
    for host_batch in task.eval_batches(batch):
        # eval_batches yields the same full batch on every process;
        # shard_batch wants process-local rows under multi-host (mesh-
        # aware: co-data-coordinate processes keep identical slices).
        b = shard_batch(mesh, process_slice(host_batch, mesh),
                        seq_axis=task.seq_axis)
        # The totals reduce on host per eval batch by design; this loop
        # runs only on the eval cadence (and at the end), never per
        # train step.
        # graftcheck: disable=host-sync-in-loop -- eval fetch, cadence-gated
        m = jax.device_get(eval_fn(state, b))
        for k, v in m.items():
            totals[k] = totals.get(k, 0.0) + float(v) * batch
        count += batch
    out = {k: v / max(count, 1) for k, v in totals.items()}
    if "loss" in out and task.name.endswith("clm"):
        # exp of the AVERAGED cross-entropy (not an average of
        # per-batch exponentials) — the standard LM eval number.
        # CLM only: its batches weight every token equally, so the
        # row-weighted batch average IS the token average; MLM's
        # per-batch masked-token counts vary, which would make this
        # a mean-of-means pseudo-perplexity — omitted rather than
        # reported subtly wrong.
        out["perplexity"] = float(np.exp(out["loss"]))
    if count < task.eval_size and is_chief():
        # Fixed-size SPMD batches truncate the split to a batch multiple
        # (exact for the reference's 5x1000 split) — surface the tail
        # drop instead of silently skewing small-split accuracy.
        print(f"[eval] split has {task.eval_size} rows; evaluated "
              f"{count} (remainder dropped by batch size {batch})")
    return out


def _build_model_and_state(cfg: TrainConfig, mesh, task):
    """Shared model/optimizer/state construction for train and eval."""
    size_kw = {"size": cfg.model_size} if cfg.model_size else {}
    if (cfg.remat != "none"
            and cfg.model in ("bert_mlm", "gpt_lm", "moe_lm",
                              "pipelined_lm")):
        size_kw.update(remat=True, remat_policy=cfg.remat)
    if cfg.moe_experts > 0:  # validated: transformer families only
        size_kw["moe_experts"] = cfg.moe_experts
    if cfg.model == "moe_lm" or cfg.moe_experts > 0:
        size_kw["moe_top_k"] = cfg.moe_top_k
        size_kw["moe_capacity_factor"] = cfg.moe_capacity_factor
        size_kw["moe_group_len"] = cfg.moe_group_len
        size_kw["moe_dispatch"] = cfg.moe_dispatch
    if cfg.model in ("bert_mlm", "gpt_lm", "moe_lm", "pipelined_lm"):
        # Transformer-family knobs, shared by the pipelined variant
        # (rope positions are derived inside its stage_fn; tying is
        # local to its embedding shell — models/pipelined.py).
        if cfg.pos_emb != "learned":
            size_kw["pos_emb"] = cfg.pos_emb
            size_kw["rope_theta"] = cfg.rope_theta
        if cfg.tie_embeddings:
            size_kw["tie_embeddings"] = cfg.tie_embeddings
        if cfg.shard_vocab:
            size_kw["shard_vocab"] = cfg.shard_vocab
        if cfg.n_kv_heads:
            size_kw["n_kv_heads"] = cfg.n_kv_heads
        if cfg.attn_window:
            size_kw["attn_window"] = cfg.attn_window
        if cfg.kv_cache_quant != "none":
            size_kw["kv_cache_quant"] = cfg.kv_cache_quant
        if cfg.mlp_variant != "gelu":
            size_kw["mlp_variant"] = cfg.mlp_variant
        if cfg.norm != "layernorm":
            size_kw["norm"] = cfg.norm
        if cfg.dataset == "text":
            # The model vocab follows the TOKENIZER: 256 byte values,
            # or whatever the corpus-trained BPE actually emitted
            # (task.vocab_size reads the built dataset — tiny corpora
            # can train fewer merges than requested).
            size_kw["vocab_size"] = task.vocab_size
        elif cfg.synthetic_vocab:
            size_kw["vocab_size"] = cfg.synthetic_vocab
        if cfg.seq_len:
            # The model's position budget tracks the training window —
            # the knob that makes long context trainable from the CLI
            # (ring attention engages via mesh.seq; the data stream
            # gets the same length through train.tasks).
            size_kw["max_len"] = cfg.seq_len
    if (cfg.observe.health and cfg.observe.health_taps
            and cfg.model in ("bert_mlm", "gpt_lm", "moe_lm")):
        # Activation-RMS taps in the transformer blocks (config
        # rejects the pipelined combination — no sow path out of its
        # manual shard_map).
        size_kw["health_taps"] = True
    if cfg.model in SOURCE_CONFIG_MODELS:
        # Sizes come from the source's own keys through ONE place.
        size_kw["source"] = cfg.model_config
        if cfg.seq_len:
            size_kw["max_len"] = cfg.seq_len
        if cfg.synthetic_vocab:
            size_kw["vocab_size"] = cfg.synthetic_vocab
    if cfg.model == "pipelined_lm":
        size_kw["num_microbatches"] = cfg.pipeline_microbatches
        if cfg.pipeline_virtual_stages > 1:
            size_kw["virtual_stages"] = cfg.pipeline_virtual_stages
    model_mesh = mesh
    if cfg.grad_sync != "implicit":
        # The explicit grad-sync step (parallel/overlap.py) runs the
        # forward INSIDE a shard_map over the whole mesh, where a
        # with_sharding_constraint on already-manual axes is an error:
        # build the model mesh-less (no activation pins, no TP
        # metadata — config.validate has already pinned the mesh to
        # pure-data, so both were no-ops anyway).
        model_mesh = None
        if cfg.model in ("bert_mlm", "gpt_lm", "moe_lm"):
            size_kw["tp_partitioning"] = False
    model = build_model(
        cfg.model, mesh=model_mesh, dropout_rate=cfg.dropout_rate,
        init_scheme=cfg.init_scheme,
        compute_dtype=jax.numpy.bfloat16
        if cfg.compute_dtype == "bfloat16" else jax.numpy.float32,
        **size_kw)
    if cfg.model in INFERENCE_ONLY_MODELS:
        # No training path: no optimizer slots beside bfloat16 weights.
        tx = optax.identity()
    else:
        tx = make_optimizer(cfg)
    state = create_train_state(model, tx, task.sample_input, mesh, cfg.seed,
                               fsdp=cfg.param_partition == "fsdp",
                               opt_fsdp=cfg.param_partition == "zero1",
                               ema=cfg.ema_decay > 0)
    return model, state


def evaluate_only(cfg: TrainConfig,
                  logger: Optional[MetricLogger] = None) -> Dict[str, float]:
    """mode=eval: restore a checkpoint, run the full validation pass,
    report. The reference could only reach its validation loop by
    training first (mnist_python_m.py:309-320 is the tail of main());
    here a saved run is re-validated — or validated on a different
    mesh shape — without a single training step.
    """
    cfg.validate()  # enforces checkpoint_dir for mode="eval"
    bootstrap()
    logger = logger or MetricLogger(enabled=is_chief(),
                                max_records=cfg.observe.max_records)
    mesh = make_mesh(cfg.mesh)
    task = make_task(cfg, mesh)
    _, state = _build_model_and_state(cfg, mesh, task)
    # mode=eval usually re-validates an EXISTING run: when the JSONL
    # already holds that run's records, append the eval record to the
    # artifact instead of truncating the training history away. A
    # fresh path still gets created (and reruns onto it replace).
    import os
    obs = Observatory(cfg.observe, chief=is_chief(),
                      tags=host_tags(mesh, cfg),
                      process_index=jax.process_index(),
                      append=bool(cfg.observe.metrics_jsonl
                                  and os.path.exists(
                                      cfg.observe.metrics_jsonl)))
    try:
        if cfg.param_sync_every > 1:
            # Local-SGD checkpoints persist the replica stack; average
            # it ON HOST into the plain template, so validation works on
            # ANY mesh shape regardless of the training replica count
            # (the documented eval-on-a-different-mesh capability).
            with obs.phase("restore"):
                state = ckpt.restore_averaged(cfg.checkpoint_dir, state)
        else:
            with obs.phase("restore"):
                state = ckpt.restore(cfg.checkpoint_dir, state)
        step = int(jax.device_get(state.step))
        eval_fn = make_eval_step(mesh, loss=task.eval_loss or task.loss,
                                 batch_shardings=task.batch_shardings)
        with obs.phase("eval"), Timer() as eval_t:
            metrics = evaluate(state, eval_fn, task, mesh,
                               cfg.eval_batch_size)
        logger.log_json({
            "event": "eval", "step": step,
            "eval_seconds": round(eval_t.elapsed, 3),
            **{f"val_{k}": round(v, 5) for k, v in metrics.items()},
        })
        obs.emit("eval", step=step,
                 eval_seconds=round(eval_t.elapsed, 3),
                 **{f"val_{k}": round(v, 5) for k, v in metrics.items()})
    finally:
        obs.close()
    return metrics


@dataclasses.dataclass
class _GenTask:
    """The two Task fields _build_model_and_state reads — enough to
    size the model for mode=generate without building (and paying, and
    being gated by) the full training data pipeline."""

    vocab_size: int
    sample_input: np.ndarray


def generate_only(cfg: TrainConfig,
                  logger: Optional[MetricLogger] = None) -> Dict:
    """mode=generate: restore a checkpoint and continue a prompt.

    The product surface over models/generate.py: greedy / sampled
    (gen_temperature, gen_top_k, gen_top_p) or beam search (num_beams),
    on the EMA weights when the checkpoint tracks them (the same
    Polyak preference eval applies). For dataset=text the prompt is a
    string run through the SAME tokenizer as training
    (data/lm.py::text_codec) and the continuation is decoded back;
    otherwise the prompt is comma-separated token ids. No reference
    counterpart (the reference has no sequence models, SURVEY.md §5).
    """
    cfg.validate()
    bootstrap()
    logger = logger or MetricLogger(enabled=is_chief(),
                                max_records=cfg.observe.max_records)
    mesh = make_mesh(cfg.mesh)

    # Tokenizer/vocab WITHOUT building the training task: make_task
    # would re-encode and window the whole corpus (and reject one
    # smaller than batch_size — a training-side check generation has
    # no use for). The checkpoint pins the model shapes, so the vocab
    # just has to match what training used.
    dec = None
    if cfg.dataset == "text":
        from tensorflow_distributed_tpu.data.lm import text_codec
        enc, dec, vocab = text_codec(cfg.data_dir, cfg.text_tokenizer,
                                     cfg.bpe_vocab_size)
        ids = enc(cfg.prompt)
        if not ids:
            raise ValueError(f"prompt {cfg.prompt!r} encoded to zero "
                             f"tokens")
    else:
        vocab = cfg.synthetic_vocab or 64
        try:
            ids = [int(t) for t in cfg.prompt.split(",")]
        except ValueError:
            raise ValueError(
                f"prompt {cfg.prompt!r} is not comma-separated token "
                f"ids (string prompts need dataset=text, whose "
                f"tokenizer defines a text vocabulary)") from None
        # Bound-checked below against the BUILT model's vocab — when
        # synthetic_vocab is unset, _build_model_and_state leaves the
        # family default (e.g. 50257 for gpt_lm small), so ids in
        # [synthetic default, family vocab) are legal model inputs.

    seq = cfg.seq_len or 128
    shim = _GenTask(vocab_size=vocab, sample_input=np.zeros(
        (max(2, dict(mesh.shape).get("data", 1)), seq), np.int32))
    model, state = _build_model_and_state(cfg, mesh, shim)
    if cfg.dataset != "text":
        bad = [t for t in ids if not 0 <= t < model.cfg.vocab_size]
        if bad:
            # The embedding gather would silently CLAMP these.
            raise ValueError(
                f"prompt ids {bad} outside the model vocabulary "
                f"[0, {model.cfg.vocab_size})")
    if cfg.param_sync_every > 1:
        state = ckpt.restore_averaged(cfg.checkpoint_dir, state)
    else:
        state = ckpt.restore(cfg.checkpoint_dir, state)
    params = state.params if state.ema is None else state.ema

    # Replicated global placement: every process holds the same
    # cfg.prompt, so this is multi-host-safe where a host-local numpy
    # array into the jitted prefill is not.
    from jax.sharding import NamedSharding, PartitionSpec as P
    prompt = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P()), np.asarray(ids, np.int32)[None, :])

    from tensorflow_distributed_tpu.models.generate import (
        beam_search, generate)
    if cfg.num_beams > 1:
        seqs, scores = beam_search(model, params, prompt,
                                   cfg.max_new_tokens,
                                   num_beams=cfg.num_beams)
        out = jax.device_get(seqs)[0, 0]          # best beam
        score = float(jax.device_get(scores)[0, 0])
    else:
        key = (jax.random.key(cfg.seed)
               if cfg.gen_temperature > 0 else None)
        out = jax.device_get(generate(
            model, params, prompt, cfg.max_new_tokens,
            temperature=cfg.gen_temperature, top_k=cfg.gen_top_k,
            top_p=cfg.gen_top_p, key=key))[0]
        score = None
    new_tokens = [int(i) for i in out]
    rec = {"event": "generate", "step": int(jax.device_get(state.step)),
           "prompt": cfg.prompt, "new_tokens": new_tokens}
    if score is not None:
        rec["beam_score"] = round(score, 5)
    if dec is not None:
        rec["text"] = dec(new_tokens)
    logger.log_json(rec)
    return rec


def train(cfg: TrainConfig, logger: Optional[MetricLogger] = None
          ) -> TrainResult:
    cfg.validate()
    bootstrap()
    plan_rec = None
    if cfg.plan == "auto":
        # Cost-model auto-layout (analysis/planner): score every valid
        # mesh x strategy by AOT-compiling the real step, then rewrite
        # cfg's mesh/partition to the winner BEFORE the mesh is built.
        # Re-validate after the rewrite so the chosen combination
        # passes the same rules an explicit one would; the record is
        # emitted once the Observatory exists below.
        from tensorflow_distributed_tpu.analysis.planner.plan import (
            apply_auto)
        plan_rec = apply_auto(cfg)
        # The plan is applied: cfg now IS an explicit config (the
        # "plan" record below keeps the audit trail), so clear the
        # planner flags before re-validating — the parse-time
        # "planner owns the mesh" guard must not reject its own
        # choice, and the budget knob is validated against plan=auto
        # (it already did its job inside apply_auto).
        cfg.plan = ""
        cfg.plan_hbm_budget_gb = 0.0
        if not cfg.profile_dir:
            # Consumed by apply_auto; with a profile window it also
            # feeds the device-time prediction join, so keep it then.
            cfg.plan_calibration = ""
        cfg.validate()
    logger = logger or MetricLogger(enabled=is_chief(),
                                max_records=cfg.observe.max_records)
    mesh = make_mesh(cfg.mesh)
    task = make_task(cfg, mesh)
    model, state = _build_model_and_state(cfg, mesh, task)
    n_params = param_count(state.params)  # before replica stacking
    # The run's observability hub: metrics registry (JSONL/CSV sinks),
    # host-phase Chrome trace, step-time breakdown, throughput/MFU
    # accounting, goodput ledger. Inert unless cfg.observe configures
    # an output. Constructing it installs the goodput counter that
    # train.checkpoint / train.preemption charge blocked time to.
    # Built BEFORE local-SGD replica stacking so the FLOPs estimate
    # counts the model once, not once per replica.
    obs = Observatory.for_training(cfg, mesh, task=task, model=model,
                                   params=state.params,
                                   chief=is_chief())
    # Everything below runs under the Observatory: close() must
    # fire on EVERY exit (normal, preemption, halt_on_nonfinite,
    # eval failure) so sinks flush (the CSV only writes on close),
    # file handles drop, and the process-global goodput counter is
    # uninstalled rather than left charging a dead run.
    try:
        if plan_rec is not None:
            # The auto-layout choice, durable next to the run's own
            # records: what was chosen, what it predicted, how many
            # candidates competed (observe.report's "Plan" section).
            logger.log_json({"event": "plan", **plan_rec})
            obs.emit("plan", **plan_rec)
        local_sgd = cfg.param_sync_every > 1
        if local_sgd:
            from tensorflow_distributed_tpu.train.local_sgd import (
                averaged_view, stack_state)
            # Replica-stacked state from here on; checkpoints persist
            # the stack (exact divergence survives resume), evals and
            # the returned result use the averaged view.
            state = stack_state(state, mesh)
            view = averaged_view
        else:
            view = lambda s: s  # noqa: E731

        start_step = 0
        if cfg.resume and ckpt.latest_step(cfg.checkpoint_dir) is not None:
            latest = ckpt.latest_step(cfg.checkpoint_dir)
            written = (ckpt.read_mesh_manifest(cfg.checkpoint_dir,
                                               latest)
                       or {}).get("mesh")
            current = mesh_shape_dict(mesh)
            resumed_extra = {}
            if written and written != current:
                # Elastic resume: the checkpoint was written on a
                # DIFFERENT mesh (a supervisor --elastic restart after
                # device loss, or an operator growing the run onto
                # returned capacity). Restore through the resharded
                # path — layout re-derived onto this mesh and verified
                # against the sharding contract — and charge the
                # resize window to its own goodput category. The
                # global batch is unchanged; the data layer re-derives
                # the per-device share from the new data-axis width,
                # so the loss trajectory stays comparable across the
                # resize.
                with obs.phase("reshard"):
                    state, rinfo = ckpt.restore_resharded(
                        cfg.checkpoint_dir, state)
                resumed_extra = {
                    "from_mesh": rinfo["from_mesh"],
                    "to_mesh": rinfo["to_mesh"],
                    "reshard_seconds": rinfo["seconds"],
                    "per_device_batch":
                        cfg.batch_size // current["data"]}
            else:
                with obs.phase("restore"):
                    state = ckpt.restore(cfg.checkpoint_dir, state)
            start_step = ckpt.host_step(state)
            logger.log_json({"event": "resumed", "step": start_step,
                             **resumed_extra})
            obs.emit("resumed", step=start_step, **resumed_extra)

        # Resilience wiring (all off by default — see config.
        # ResilienceConfig and the resilience/ package): fault plan,
        # non-finite policy, spike detector, watchdog, save-retry
        # policy. Built AFTER the Observatory so recovery events from
        # the library layers reach the run's sinks.
        res = cfg.resilience
        plan = (parse_fault_plan(res.fault_plan) if res.fault_plan
                else FaultPlan())
        plan.bind(start_step)
        policy = (NonFinitePolicy(res.nonfinite, res.max_skips,
                                  res.max_rewinds)
                  if res.nonfinite != "off" else None)
        spikes = (LossSpikeDetector(res.spike_window, res.spike_factor)
                  if res.spike_window else None)
        wdog = (Watchdog(res.data_timeout_s, res.sync_timeout_s)
                if (res.data_timeout_s or res.sync_timeout_s) else None)
        ckpt.set_io_policy(res.save_retries, res.save_retry_backoff_s)

        # ZeRO-1 needs new_params constrained back to the params' OWN
        # state-creation layout after the slot-sharded update — captured
        # from the live arrays so pipe/TP-sharded params keep those axes
        # (a blanket "replicated" would clobber them).
        params_out = (jax.tree_util.tree_map(lambda a: a.sharding,
                                             state.params)
                      if cfg.param_partition == "zero1" else None)
        # On-device health telemetry cadence (observe/health.py): the
        # vitals ride the log-cadence metrics fetch, so the default
        # cadence IS log_every (health_every must be a multiple —
        # config.validate enforces it).
        health_every = 0
        if cfg.observe.health:
            health_every = cfg.observe.health_every or cfg.log_every
        if cfg.model == "pipelined_lm" and cfg.pipeline_schedule == "1f1b":
            from tensorflow_distributed_tpu.train.pipeline_step import (
                make_1f1b_train_step)
            step_fn = make_1f1b_train_step(model, mesh, cfg.seed,
                                           batch_shardings=task.batch_shardings,
                                           moe_aux_weight=cfg.moe_aux_weight,
                                           moe_zloss_weight=cfg.moe_zloss_weight,
                                           grad_norm_metric=cfg.log_grad_norm,
                                           label_smoothing=cfg.label_smoothing,
                                           ema_decay=cfg.ema_decay,
                                           backward=cfg.pipeline_backward,
                                           ce_chunk=cfg.ce_chunk,
                                           params_out_shardings=params_out,
                                           health_every=health_every)
        elif local_sgd:
            from tensorflow_distributed_tpu.train.local_sgd import (
                make_local_sgd_train_step)
            step_fn = make_local_sgd_train_step(
                mesh, cfg.param_sync_every, cfg.seed, loss=task.loss,
                batch_shardings=task.batch_shardings,
                grad_norm_metric=cfg.log_grad_norm)
        else:
            step_fn = make_train_step(
                mesh, cfg.seed, loss=task.loss,
                batch_shardings=task.batch_shardings,
                accum_steps=cfg.grad_accum_steps,
                grad_norm_metric=cfg.log_grad_norm,
                ema_decay=cfg.ema_decay,
                params_out_shardings=params_out,
                skip_nonfinite=(policy is not None
                                and policy.mode == "skip_batch"),
                health_every=health_every,
                grad_sync=cfg.grad_sync,
                state_template=(state if cfg.grad_sync != "implicit"
                                else None),
                grad_sync_bucket_bytes=(
                    int(cfg.grad_sync_bucket_mb * 2 ** 20)
                    if cfg.grad_sync_bucket_mb else 0),
                grad_clip_norm=cfg.grad_clip_norm or 0.0)
            if cfg.grad_sync == "overlap":
                # Surface the per-step collective-traffic estimate so
                # the step records can split comm into exposed vs
                # hidden (observe/hub.py). The step carries the exact
                # plan its compiled program executes.
                from tensorflow_distributed_tpu.parallel import overlap
                plan_b = step_fn.bucket_plan
                obs.note_grad_sync(overlap.comm_bytes_per_step(plan_b),
                                   plan_b.describe())
        eval_fn = make_eval_step(mesh, loss=task.eval_loss or task.loss,
                                 batch_shardings=task.batch_shardings)
        # 1F1B-recompute steps advertise their extra executed FLOPs
        # (hw-MFU next to model MFU — train.pipeline_step).
        obs.note_step_fn(step_fn, params=state.params,
                         model_cfg=getattr(model, "cfg", None))
        # How often the flash kernels' causal skip engages is static:
        # the plan's tile counts ride the start record (the compile
        # record is off under --observe.programs false).
        flash = train_flash_plan(getattr(model, "cfg", None),
                                 task.sample_input.shape[-1], mesh)
        flash = {"flash_plan": flash} if flash else {}
        logger.log_json({
            "event": "start", "model": cfg.model, "task": task.name,
            "params": n_params, "mesh": dict(mesh.shape),
            "global_batch": cfg.batch_size, "start_step": start_step,
            **flash,
        })
        # Lifecycle events go to BOTH outputs on purpose: logger owns
        # the human stdout stream (and needs no observe config), obs
        # owns the tagged file sinks (mesh/config_hash ride its tags).
        obs.emit("start", model=cfg.model, task=task.name, params=n_params,
                 global_batch=cfg.batch_size, start_step=start_step,
                 **flash)

        def make_iterator(from_step: int):
            """Task stream -> fault wrapping -> prefetch; rebuilt on a
            rewind so the replayed steps consume the batches the
            uninterrupted run would have (fault events are one-shot,
            so an injected NaN is not re-injected on replay)."""
            return prefetch_to_mesh(
                plan.wrap_stream(task.train_stream(from_step),
                                 from_step),
                mesh, seq_axis=task.seq_axis)

        it = make_iterator(start_step)

        def _fetch(step_id: int):
            plan.maybe_stall(step_id)  # injected stalls happen INSIDE
            #                            the watched fetch
            return next(it)

        def cadence(step_now: int, state: TrainState, metrics) -> None:
            """Periodic log/eval/checkpoint — applied to EVERY step
            including the warm-up compile step."""
            if cfg.log_every and step_now % cfg.log_every == 0:
                # graftcheck: disable=host-sync-in-loop -- the log fetch,
                # gated on log_every by the line above
                host_metrics = jax.device_get(metrics)
                # Health scalars travel in the SAME fetch but are
                # per-module records, not step-log columns: split them
                # off so stdout stays readable, and emit them only
                # when the device's cadence flag says they're real
                # (observe/health.py).
                host_metrics, health, health_emitted = health_mod.split(
                    host_metrics)
                if health_emitted and health:
                    for module, fields in health_mod.group(health):
                        obs.emit("health", step=step_now, module=module,
                                 **{k: round(v, 8)
                                    for k, v in fields.items()})
                logger.log(step_now, **host_metrics)
                obs.log_step(step_now, host_metrics)
                if cfg.halt_on_nonfinite and not np.isfinite(
                        float(host_metrics["loss"])):
                    # Flush queued async saves first so the named resume
                    # point is the TRUE latest (metrics are replicated, so
                    # every process raises here and reaches wait()'s
                    # barrier).
                    ckpt.wait()
                    raise FloatingPointError(
                        f"non-finite loss {host_metrics['loss']} at step "
                        f"{step_now} (halt_on_nonfinite=true); last durable "
                        f"checkpoint: "
                        f"{ckpt.latest_step(cfg.checkpoint_dir) if cfg.checkpoint_dir else None}")
            if cfg.eval_every and step_now % cfg.eval_every == 0:
                with obs.phase("eval"):
                    em = evaluate(view(state), eval_fn, task, mesh,
                                  cfg.eval_batch_size)
                logger.log(step_now, **{f"val_{k}": v for k, v in em.items()})
                obs.emit("eval", step=step_now,
                         **{f"val_{k}": float(v) for k, v in em.items()})
            if (cfg.checkpoint_dir and cfg.checkpoint_every
                    and step_now % cfg.checkpoint_every == 0):
                if plan:
                    # An armed ckpt_io_fail@step_now fires inside this
                    # save's retry loop.
                    plan.arm_checkpoint_faults(step_now)
                with obs.phase("checkpoint"):
                    ckpt.save(cfg.checkpoint_dir, state, cfg.keep_checkpoints,
                              background=cfg.checkpoint_async,
                              backend=cfg.checkpoint_backend)

        def _inspect(step_id: int, step_metrics) -> Optional[int]:
            """Policy check on one RETIRED step's metrics (already
            device-synced — the host read costs nothing extra).
            Returns the bad step id when the policy orders a rewind;
            raises on halt / budget exhaustion; None otherwise. Inert
            (no host fetch at all) when no policy or detector is
            configured."""
            if policy is None and spikes is None:
                return None
            if policy is None and cfg.log_every \
                    and step_id % cfg.log_every:
                # Spike detection WITHOUT a recovery policy is advisory
                # telemetry: sample it on the log cadence instead of
                # paying a per-step host fetch in the hot path. The
                # trade is real and deliberate: a spike shorter than
                # log_every can fall between samples, and the rolling
                # window arms over window*log_every steps — acceptable
                # for an advisory signal. A run that ACTS on losses
                # (resilience.nonfinite != off) keeps full per-step
                # inspection; set log_every=1 to sample every step.
                return None
            # One transfer for both policy scalars (loss + the step's
            # skip flag) instead of two round trips. The jitted step
            # can skip on a non-finite GRAD NORM while the loss stays
            # finite (backward-only overflow); the skipped_nonfinite
            # metric it reports is the authority, so those skips charge
            # the budget exactly like NaN losses.
            # graftcheck: disable=host-sync-in-loop -- per-step by the
            # policy contract; _sync_retired already retired these
            # arrays, so this is a scalar D2H copy, not a device stall
            host_loss, host_skipped = map(float, jax.device_get(
                (step_metrics["loss"],
                 step_metrics.get("skipped_nonfinite", 0.0))))
            device_skipped = host_skipped > 0
            if not np.isfinite(host_loss) or device_skipped:
                if policy is None:
                    return None  # legacy path: cadence halt (or not)
                action = policy.on_nonfinite(step_id, host_loss)
                if action == "halt":
                    # Flush queued async saves first so the named
                    # resume point is the TRUE latest.
                    ckpt.wait()
                    raise RecoveryBudgetExceeded(policy.halt_message(
                        step_id, host_loss,
                        ckpt.latest_step(cfg.checkpoint_dir)
                        if cfg.checkpoint_dir else None))
                if action == "skip":
                    # The jitted step already discarded the update on
                    # device; here we only count it.
                    obs.goodput.incr("skip_nonfinite")
                    return None
                return step_id
            if spikes is not None:
                med = spikes.observe(host_loss)
                if med is not None:
                    if policy is not None:
                        action = policy.on_spike(step_id, host_loss,
                                                 med)
                        if action == "halt":
                            # Rewind budget exhausted on a spike:
                            # same ending as the nonfinite path —
                            # a swallowed halt would train on the
                            # diverged run unbounded.
                            ckpt.wait()
                            raise RecoveryBudgetExceeded(
                                policy.halt_message(
                                    step_id, host_loss,
                                    ckpt.latest_step(cfg.checkpoint_dir)
                                    if cfg.checkpoint_dir else None))
                        if action == "rewind":
                            return step_id
                    else:
                        obs.emit("recovery", kind="loss_spike",
                                 step=step_id,
                                 loss=round(host_loss, 6),
                                 window_median=round(med, 6))
            return None

        def _sync_retired(sid: int, m) -> None:
            """The one retirement sync protocol, shared by the main
            loop and the trailing drain (watchdog deadline when
            configured, plain block otherwise)."""
            if wdog is not None:
                wdog.sync(m, sid)
            else:
                # graftcheck: disable=host-sync-in-loop -- THE designed
                # retirement point: the bounded in-flight window blocks
                # on the oldest pending step on purpose (see the deque
                # comment below); everything else overlaps with it
                jax.block_until_ready(m)

        def _rewind(cur_state, bad_step: int):
            """In-process recovery: flush the writer, quarantine every
            checkpoint saved at/after the bad update (detection lags
            retirement by the in-flight window, so cadence saves in
            between hold the POISONED state — intact bytes, damaged
            values), restore the newest verifiable pre-damage
            checkpoint (corrupt candidates are quarantined by
            ckpt.restore itself), and hand back the step to re-enter
            the loop from. The poisoned live state is only a placement
            template for the restore."""
            # Drain the device FIRST: steps dispatched after the bad
            # one are still executing, and interleaving their
            # completion with the restore's device_puts + the replay's
            # fresh dispatch trips the container XLA:CPU runtime's
            # heap (same class as the async-ckpt SIGSEGV the repo
            # already documents). A rewind is off the hot path; a full
            # quiesce costs nothing that matters.
            # graftcheck: disable=host-sync-in-loop -- deliberate full
            # quiesce on the cold recovery path (see comment above)
            jax.block_until_ready(cur_state.params)
            ckpt.wait()
            ckpt.quarantine_from(
                cfg.checkpoint_dir, bad_step,
                reason=f"saved at/after non-finite step {bad_step} "
                       f"(rewind)")
            with obs.phase("rewind"):
                # The save at bad_step - 1 is usually clean (step K's
                # loss comes from the params ENTERING K, i.e. update
                # K-1's output — batch-caused NaNs never touch it),
                # but when the damage IS in the params (backward-only
                # overflow at K-1), that checkpoint holds intact
                # bytes around poisoned values. So verify each
                # candidate's params are finite after restoring and
                # walk back until one is — never quarantining a clean
                # sole checkpoint on a mere suspicion, never
                # restoring a poisoned one and burning the budget on
                # an instant re-NaN.
                # Hoisted OUT of the walk-back loop (graftcheck
                # jit-in-loop): one verify program, reused for every
                # candidate checkpoint instead of a fresh trace +
                # compile per iteration.
                params_finite = jax.jit(
                    lambda p: jax.numpy.all(jax.numpy.array(
                        [jax.numpy.all(jax.numpy.isfinite(x))
                         for x in jax.tree_util.tree_leaves(p)])))
                while True:
                    target = ckpt.latest_step(cfg.checkpoint_dir)
                    if target is None:
                        raise FloatingPointError(
                            "resilience.nonfinite=rewind: non-finite "
                            f"loss at step {bad_step} with no finite "
                            "durable checkpoint before it — nothing "
                            "to rewind to (checkpoint_dir="
                            f"{cfg.checkpoint_dir!r}, checkpoint_"
                            f"every={cfg.checkpoint_every})")
                    new_state = ckpt.restore(cfg.checkpoint_dir,
                                             cur_state)
                    # graftcheck: disable=host-sync-in-loop -- the
                    # walk-back must read each candidate's verdict on
                    # host; rewind is the cold recovery path
                    finite = bool(jax.device_get(
                        params_finite(new_state.params)))
                    if finite:
                        break
                    ckpt.quarantine_from(
                        cfg.checkpoint_dir, target,
                        reason=f"restored params non-finite (damage "
                               f"predates step {target})")
            rewound_to = ckpt.host_step(new_state)
            obs.goodput.incr("rewind")
            logger.log_json({"event": "rewound", "step": rewound_to})
            obs.emit("recovery", kind="rewind", from_step=bad_step,
                     to_step=rewound_to)
            if spikes is not None:
                spikes.reset()  # replayed steps re-approach the spike
            return new_state, rewound_to

        # --check (graftcheck's runtime layer): snapshot the layout the
        # state was CREATED with — the declared sharding contract the
        # first step must hand back (analysis/runtime.py).
        declared_shardings = (graftcheck.sharding_tree(state.params)
                              if cfg.check else None)

        # Warm-up compile outside the timed steady-state span (the
        # reference's timings conflated graph setup with steps; ours don't).
        # Goodput charges it as "compile" — setup, not forward progress.
        metrics = None
        want_rewind = None  # bad step id when a rewind is ordered
        with Timer() as compile_t:
            if cfg.train_steps > start_step:
                # Signal faults scheduled for the warm-up step fire
                # here like any other step's would (the guard isn't
                # armed yet, so a sigterm@first-step drill is a hard
                # first-leg crash — which is what it models).
                plan.maybe_device_loss(start_step + 1,
                                       cfg.checkpoint_dir)
                plan.maybe_signal(start_step + 1)
                with obs.phase("compile"):
                    # The first fetch is the one most likely to wedge
                    # (cold source, first NFS touch) — watch it too.
                    batch0 = (wdog.fetch(
                        lambda: _fetch(start_step + 1), start_step + 1)
                        if wdog is not None else _fetch(start_step + 1))
                    state, metrics = step_fn(state, batch0)
                    jax.block_until_ready(metrics)
                if declared_shardings is not None:
                    # The first step's output is where a missing
                    # with_sharding_constraint first shows: GSPMD
                    # propagating an input sharding into the params
                    # re-lays-out every later step silently.
                    graftcheck.assert_sharding_contract(
                        state.params, declared_shardings, what="params")
                cadence(start_step + 1, state, metrics)
                want_rewind = _inspect(start_step + 1, metrics)
        steps_done = 1 if cfg.train_steps > start_step else 0

        # Bounded async dispatch: block on the oldest pending step once more
        # than 2 ride in the deque, so at most 2 unconfirmed steps trail the
        # current dispatch (3 in flight at the dispatch instant). Unbounded
        # dispatch can queue dozens of SPMD programs whose collectives then
        # compete for the same worker threads (on oversubscribed hosts the
        # XLA:CPU rendezvous aborts after 40s); a shallow window preserves
        # the host/device overlap that hides dispatch latency.
        inflight = collections.deque()
        profiler = StepProfiler(
            log_dir=cfg.profile_dir if is_chief() else "",
            start_step=cfg.profile_start_step,
            num_steps=cfg.profile_num_steps)

        # SIGTERM (preemption notice) -> stop at a coordinated safe step,
        # fall through to the final durable save below, exit 0 for the
        # scheduler to restart with --resume. Only armed when there is a
        # checkpoint dir to save into.
        guard = PreemptionGuard(enabled=bool(cfg.checkpoint_dir))
        try:
            # --check: every transfer in the steady-state loop is
            # explicit by design (prefetch device_puts, cadence
            # device_gets); an IMPLICIT one is a bug the guard turns
            # into an error at its source line. Transparent when off.
            with graftcheck.transfer_guard(cfg.check), \
                    Timer() as train_t:
                # The outer while exists for ONE flow: a policy-ordered
                # rewind restores a checkpoint in-process and re-enters
                # the step loop from the restored step. Every other
                # exit (completion, preemption, halt) leaves it on the
                # first pass; without resilience configured the body is
                # the plain single-pass loop it always was.
                next_start = start_step + steps_done
                while True:
                    if want_rewind is not None:
                        # The restore inside _rewind does implicit
                        # transfers by design (checkpoint placement,
                        # the finite-params verdict) — exempt the cold
                        # recovery path from the steady-state --check
                        # guard or a rewind under --check would crash
                        # instead of recovering.
                        with graftcheck.transfer_allowed(cfg.check):
                            state, next_start = _rewind(state,
                                                        want_rewind)
                        it = make_iterator(next_start)
                        want_rewind = None
                    for i in range(next_start, cfg.train_steps):
                        if guard.should_stop(i):
                            logger.log_json({"event": "preempted",
                                             "step": i})
                            obs.instant("preempted", step=i)
                            obs.emit("preempted", step=i)
                            break
                        plan.maybe_device_loss(i + 1,
                                               cfg.checkpoint_dir)
                        plan.maybe_signal(i + 1)
                        profiler.observe(i + 1, pending=metrics)
                        with obs.data():
                            batch = (wdog.fetch(lambda: _fetch(i + 1),
                                                i + 1)
                                     if wdog is not None
                                     else _fetch(i + 1))
                        with obs.dispatch():
                            state, metrics = step_fn(state, batch)
                        inflight.append((i + 1, metrics))
                        if len(inflight) > 2:
                            sid, m = inflight.popleft()
                            with obs.device_wait():
                                _sync_retired(sid, m)
                            verdict = _inspect(sid, m)
                            if verdict is not None:
                                want_rewind = verdict
                                inflight.clear()
                                break
                        with obs.cadence():
                            cadence(i + 1, state, metrics)
                        obs.step_end()
                    if want_rewind is not None:
                        continue
                    if guard.fired is None:
                        # Retire the trailing in-flight steps through
                        # the same policy checks (a NaN on the final
                        # steps must not slip out unhandled); inert
                        # without a policy/detector.
                        while inflight:
                            sid, m = inflight.popleft()
                            _sync_retired(sid, m)
                            verdict = _inspect(sid, m)
                            if verdict is not None:
                                want_rewind = verdict
                                inflight.clear()
                                break
                        if want_rewind is not None:
                            continue
                    break
                jax.block_until_ready(state.params)
        finally:
            # Always restore the prior SIGTERM disposition — an exception
            # escaping the loop must not leave a handler that absorbs
            # future SIGTERMs into an Event nobody reads. The profiler
            # likewise: an open trace window must be finalized even when
            # the loop raises (halt_on_nonfinite fires mid-cadence — the
            # diverging run's trace is exactly the one worth keeping), and
            # the host-phase Chrome trace is flushed durable for the same
            # reason (the JSONL sink already flushes per record).
            guard.close()
            profiler.stop(pending=metrics)
            if profiler.captured:
                # Ground truth beside the predictions: parse the
                # closed window's Perfetto export and emit one
                # device_time record per attributed program
                # (observe/xprof.py; explicit-null on absent or
                # unusable profiler data).
                obs.emit_device_time(cfg.profile_dir,
                                     calibration=cfg.plan_calibration)
            obs.flush()
            if wdog is not None:
                wdog.close()

        preempted = guard.fired is not None
        if preempted and cfg.checkpoint_dir:
            # The eviction grace window exists for THIS save: take it
            # before eval, which on a real validation split could outlive
            # the grace period and void the whole feature. Goodput charges
            # the whole preempted flush as "drain" (the nested checkpoint
            # accounting suppresses itself inside an outer category).
            with obs.phase("drain"):
                ckpt.save(cfg.checkpoint_dir, state, cfg.keep_checkpoints,
                          background=cfg.checkpoint_async,
                          backend=cfg.checkpoint_backend)
                ckpt.wait()
        state_out = view(state)
        with Timer() as eval_t:
            if preempted:
                final = {}
            else:
                with obs.phase("eval"):
                    final = evaluate(state_out, eval_fn, task, mesh,
                                     cfg.eval_batch_size)
        if cfg.checkpoint_dir and not preempted:
            # The final save rides the SAME path as cadence saves: under
            # checkpoint_async a cadence save of this very step may still
            # sit in the writer queue, and the single writer serializes
            # them; a synchronous bypass here would race it on the tmp
            # dir. wait() then flushes the queue and barriers so
            # latest_step is coherent on return.
            with obs.phase("checkpoint"):
                ckpt.save(cfg.checkpoint_dir, state, cfg.keep_checkpoints,
                          background=cfg.checkpoint_async,
                          backend=cfg.checkpoint_backend)
                ckpt.wait()

        # Steps ACTUALLY executed in the timed span (a preemption break
        # runs fewer than the configured horizon; reporting the horizon
        # would inflate throughput).
        steady_steps = max(
            int(jax.device_get(state_out.step)) - start_step - steps_done, 0)
        sps = steady_steps / train_t.elapsed if train_t.elapsed > 0 else 0.0
        result = TrainResult(
            state=state_out,
            train_seconds=compile_t.elapsed + train_t.elapsed,
            eval_seconds=eval_t.elapsed, final_metrics=final,
            steps_per_sec=sps, images_per_sec=sps * cfg.batch_size,
            logger=logger)
        logger.log_json({
            "event": "done", "steps": int(jax.device_get(state_out.step)),
            "train_seconds": round(result.train_seconds, 3),
            "compile_seconds": round(compile_t.elapsed, 3),
            "steps_per_sec": round(sps, 3),
            "images_per_sec": round(result.images_per_sec, 1),
            **{f"val_{k}": round(v, 5) for k, v in final.items()},
        })
        if plan_rec is not None:
            # Predicted -> measured drift for the auto-layout choice:
            # the cost model's error on THIS run, durable next to the
            # plan record it audits (and the signal a calibration
            # refit consumes). Emitted only when the run measured a
            # steady-state p50.
            measured = obs.steptime.summary().get("step_ms_p50")
            pred = plan_rec.get("predicted_step_ms")
            if (isinstance(measured, (int, float))
                    and isinstance(pred, (int, float)) and pred > 0):
                obs.emit("plan_drift", predicted_step_ms=pred,
                         measured_step_ms_p50=round(measured, 4),
                         drift_ratio=round(measured / pred, 4),
                         calibration_id=plan_rec.get("calibration_id"))
        # Final rollup: rolling step-time stats + goodput ledger (counted
        # since the Observatory was built — restores, compile, eval and
        # checkpoint stalls all charged) + steady-state throughput/MFU.
        obs.summarize(
            steps=int(jax.device_get(state_out.step)),
            preempted=preempted,
            train_seconds=round(result.train_seconds, 3),
            compile_seconds=round(compile_t.elapsed, 3),
            steps_per_sec=round(sps, 3),
            **obs.accountant.rates(steady_steps * obs.items_per_step,
                                   train_t.elapsed),
            **{f"val_{k}": round(v, 5) for k, v in final.items()})
        return result
    finally:
        obs.close()
