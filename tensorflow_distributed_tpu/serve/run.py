"""``mode=serve`` driver: build/restore a causal LM, run a request
workload through the continuous-batching engine, report.

Workloads: ``--serve.requests file.jsonl`` (one JSON object per line:
``{"prompt": [ids...], "max_new_tokens": 32, "eos_id": 5,
"arrival_s": 0.25}`` — ``prompt`` may be a ``"text"`` string instead
when ``--dataset text`` supplies a tokenizer) or, with no file, a
synthetic open-loop workload: ``--serve.num-requests`` random prompts
with mixed lengths in [``--serve.prompt-len-min``,
``--serve.prompt-len-max``], arriving at ``--serve.arrival-rate``
req/s (0 = all queued at t=0). ``--serve.trace`` reshapes the
synthetic arrival process: ``poisson`` (exponential interarrivals),
``bursty`` (whole bursts land at once), ``diurnal`` (sinusoidally
modulated rate — a day compressed into the run), or a ``.jsonl`` file
of per-request ``{"arrival_s": t}`` offsets.

``--checkpoint-dir`` restores trained weights (EMA preferred, like
mode=eval/generate); without one the model serves FRESH-INIT params —
a load-testing/benchmarking mode, clearly labeled in the output.

Serve observatory (README "Serve tracing & SLO monitoring"):
``--observe.trace`` writes the per-request Perfetto span tree,
``--observe.slo`` arms the live burn-rate monitor (with a periodic
one-line status print), and ``--observe.export-every`` /
``--observe.export-path`` dump atomic rolling-metrics snapshots — all
bundled by :class:`observe.hub.ServeObservatory` and continued across
a journal resume (trace and JSONL both).

Serve-under-fire wiring (README "Serving under faults"): a
``--resilience.fault-plan`` with serve kinds drives the scheduler's
containment paths, ``--resilience.sync-timeout-s`` arms the decode
watchdog, ``--serve.journal`` makes progress crash-durable (an
existing non-empty journal means RESUME: finished requests skip,
in-flight ones re-admit as continuations), and ``--checkpoint-dir``
doubles as the live-weight-swap source (``reload@K`` faults, via
train.checkpoint.restore_params).
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, List

import jax
import numpy as np

from tensorflow_distributed_tpu.config import TrainConfig
from tensorflow_distributed_tpu.serve import journal as journal_mod
from tensorflow_distributed_tpu.serve.buckets import (
    default_buckets, parse_buckets)
from tensorflow_distributed_tpu.serve.engine import SlotDecodeEngine
from tensorflow_distributed_tpu.serve.params import serving_tree
from tensorflow_distributed_tpu.serve.scheduler import Request, Scheduler


def _arrivals(serve, n: int, rng) -> List[float]:
    """Arrival offsets for the synthetic workload, shaped by
    ``serve.trace`` (all deterministic under the run seed):

    - ``""``: uniformly spaced at ``arrival_rate`` (0 = all at t=0);
    - ``poisson``: exponential interarrivals at the same mean rate —
      the memoryless open-loop process real traffic approximates;
    - ``bursty``: bursts of ~4 requests landing TOGETHER, bursts
      spaced to keep the mean rate — the pathological arrival shape a
      starvation bound exists for;
    - ``diurnal``: rate modulated sinusoidally between 0.25x and
      1.75x over the workload span — a traffic day compressed into
      one run;
    - ``*.jsonl``: explicit per-request ``{"arrival_s": t}`` lines
      (row i feeds request i; the file must cover the workload).
    """
    rate = serve.arrival_rate
    trace = serve.trace
    if trace.endswith(".jsonl"):
        offs = []
        with open(trace) as f:
            for line in f:
                line = line.strip()
                if line:
                    offs.append(float(json.loads(line)["arrival_s"]))
        if len(offs) < n:
            raise ValueError(
                f"--serve.trace {trace}: {len(offs)} arrival rows < "
                f"{n} requests")
        return offs[:n]
    if not rate:
        return [0.0] * n
    if trace == "poisson":
        return list(np.cumsum(rng.exponential(1.0 / rate, size=n)))
    if trace == "bursty":
        burst = 4
        return [(i // burst) * (burst / rate) for i in range(n)]
    if trace == "diurnal":
        out, t = [], 0.0
        for i in range(n):
            # Instantaneous rate sweeps one full "day" over the
            # workload: 1.75x at the peak, 0.25x in the trough.
            lam = rate * (1.0 + 0.75 * np.sin(2 * np.pi * i / max(n, 1)))
            out.append(t)
            t += 1.0 / lam
        return out
    return [i / rate for i in range(n)]


def _workload(cfg: TrainConfig, vocab_size: int,
              encode=None) -> List[Request]:
    serve = cfg.serve
    if serve.requests:
        reqs = []
        with open(serve.requests) as f:
            for i, line in enumerate(f):
                line = line.strip()
                if not line:
                    continue
                obj = json.loads(line)
                if "text" in obj:
                    if encode is None:
                        raise ValueError(
                            f"{serve.requests}:{i + 1}: string prompts "
                            f"need --dataset text (its tokenizer "
                            f"defines the vocabulary)")
                    ids = encode(obj["text"])
                else:
                    ids = [int(t) for t in obj["prompt"]]
                if not ids:
                    raise ValueError(
                        f"{serve.requests}:{i + 1}: empty prompt")
                # Id bounds are checked against the BUILT model's
                # vocab in serve_run (like generate_only): with
                # synthetic_vocab unset the family default (e.g.
                # 50257 for gpt_lm small) is the real bound.
                slo = str(obj.get("slo", "standard"))
                from tensorflow_distributed_tpu.serve.scheduler import (
                    SLO_CLASSES)
                if slo not in SLO_CLASSES:
                    raise ValueError(
                        f"{serve.requests}:{i + 1}: unknown slo "
                        f"{slo!r}; have {SLO_CLASSES}")
                # graftcheck: disable=host-sync-in-loop -- request-file
                # parsing runs once, before the engine exists; this
                # materializes host JSON, not device buffers
                reqs.append(Request(
                    rid=len(reqs), prompt=np.asarray(ids, np.int32),
                    max_new_tokens=int(obj.get("max_new_tokens",
                                               serve.max_new_tokens)),
                    eos_id=int(obj.get("eos_id", serve.eos_id)),
                    arrival_s=float(obj.get("arrival_s", 0.0)),
                    slo=slo, tenant=str(obj.get("tenant", "")),
                    session=str(obj.get("session", ""))))
        if not reqs:
            raise ValueError(f"{serve.requests} names no requests")
        return reqs
    # Synthetic open-loop workload: mixed lengths, deterministic by
    # seed, arrivals shaped by the trace (prompt draws happen BEFORE
    # the arrival draws so the token content is identical across
    # traces — a trace A/B compares arrival shape, nothing else; the
    # class draws come after BOTH for the same reason).
    rng = np.random.default_rng(cfg.seed)
    prompts = []
    for _ in range(serve.num_requests):
        plen = int(rng.integers(serve.prompt_len_min,
                                serve.prompt_len_max + 1))
        prompts.append(
            rng.integers(0, vocab_size, size=plen).astype(np.int32))
    sessions = [""] * serve.num_requests
    if serve.session_turns > 1:
        # Multi-turn conversations: consecutive requests group into
        # sessions; each turn's prompt EXTENDS the previous turn's (a
        # client re-sending the conversation so far plus new text).
        # Drawn AFTER the base prompts so the first turns' content is
        # identical to the session-less workload at the same seed.
        k = serve.session_turns
        for g in range(0, serve.num_requests, k):
            sid = f"s{g // k}"
            for j in range(g, min(g + k, serve.num_requests)):
                sessions[j] = sid
                if j > g:
                    prompts[j] = np.concatenate(
                        [prompts[j - 1], prompts[j]])
    arrivals = _arrivals(serve, serve.num_requests, rng)
    slos = ["standard"] * serve.num_requests
    if serve.slo_mix:
        from tensorflow_distributed_tpu.serve.scheduler import (
            SLO_CLASSES, parse_slo_mix)
        mix = parse_slo_mix(serve.slo_mix)
        edges = np.cumsum([mix.get(c, 0.0) for c in SLO_CLASSES])
        draws = rng.random(serve.num_requests)
        slos = [SLO_CLASSES[int(np.searchsorted(edges, d,
                                                side="right").clip(
                                                    0, len(edges) - 1))]
                for d in draws]
    return [Request(rid=i, prompt=p,
                    max_new_tokens=serve.max_new_tokens,
                    eos_id=serve.eos_id, arrival_s=float(a),
                    slo=slos[i],
                    tenant=(f"t{i % serve.tenants}"
                            if serve.tenants > 1 else ""),
                    session=sessions[i])
            for i, (p, a) in enumerate(zip(prompts, arrivals))]


def serve_run(cfg: TrainConfig) -> Dict:
    """Run the serve workload; returns the summary dict (per-request
    records ride the observe JSONL)."""
    cfg.validate()
    from tensorflow_distributed_tpu.observe import (
        device as observe_device)
    from tensorflow_distributed_tpu.observe.hub import ServeObservatory
    from tensorflow_distributed_tpu.observe.registry import host_tags
    from tensorflow_distributed_tpu.parallel.mesh import (
        bootstrap, is_chief, make_mesh)
    from tensorflow_distributed_tpu.train import checkpoint as ckpt
    from tensorflow_distributed_tpu.train.loop import (
        _build_model_and_state, _GenTask)
    from tensorflow_distributed_tpu.train.state import param_count

    bootstrap()
    mesh = make_mesh(cfg.mesh)
    tp = cfg.serve.mesh_model
    if tp > 1:
        # Tensor-parallel replica: the engine's programs build over a
        # [data=1, model=tp] mesh of this replica's own — attention
        # heads / MLP width / the cache's head axis shard over
        # "model" (README "Tensor-parallel serving"). Validated here,
        # where devices and the model facts are both known; the
        # config layer only vets tp >= 1.
        from tensorflow_distributed_tpu.analysis.planner.candidates \
            import MODEL_FAMILIES, model_facts
        from tensorflow_distributed_tpu.config import MeshConfig
        devs = jax.devices()
        if len(devs) < tp:
            raise ValueError(
                f"--serve.mesh-model {tp} needs {tp} devices, have "
                f"{len(devs)}")
        facts = model_facts(MODEL_FAMILIES[cfg.model],
                            cfg.model_size or "")
        nk = cfg.n_kv_heads or facts.n_heads
        if facts.n_heads % tp or nk % tp:
            raise ValueError(
                f"--serve.mesh-model {tp} must divide n_heads "
                f"{facts.n_heads} and n_kv_heads {nk}: attention "
                f"heads and the KV cache's head axis shard over the "
                f"model axis")
        if (cfg.dataset != "text" and not cfg.shard_vocab
                and facts.vocab_size % tp):
            raise ValueError(
                f"--serve.mesh-model {tp} must divide the vocab "
                f"{facts.vocab_size}: the TP head is vocab-parallel. "
                f"Pass --shard-vocab true (pads the table to a "
                f"multiple of the model axis; the checkpoint must be "
                f"trained with the same flag) or pick a width that "
                f"divides")
        mesh = make_mesh(MeshConfig(data=1, model=tp), devs[:tp])
        if is_chief():
            print(f"[serve] tensor-parallel replica: model={tp} over "
                  f"{tp} device(s) (params + KV cache head-sharded)",
                  flush=True)

    encode = None
    if cfg.dataset == "text":
        from tensorflow_distributed_tpu.data.lm import text_codec
        encode, _, vocab = text_codec(cfg.data_dir, cfg.text_tokenizer,
                                      cfg.bpe_vocab_size)
        # The model vocab follows the tokenizer here, so the TP
        # head's divisibility is only checkable now.
        if tp > 1 and not cfg.shard_vocab and vocab % tp:
            raise ValueError(
                f"--serve.mesh-model {tp} must divide the tokenizer "
                f"vocab {vocab} (the TP head is vocab-parallel); "
                f"pass --shard-vocab true to pad it")
    else:
        vocab = cfg.synthetic_vocab or 64
    # Fleet-replica intake (--serve.inbox; fleet/replica.py): no
    # workload of our own — requests stream in from the router, and
    # the scheduler runs until a drain command lands. The journal/
    # snapshot paths are per-epoch (a restarted replica starts empty;
    # the router re-dispatched the dead epoch's work from its
    # journal), so there is no resume either.
    inbox_mode = bool(cfg.serve.inbox)
    requests = [] if inbox_mode else _workload(cfg, vocab, encode)

    # Journal resume: a non-empty journal at the configured path means
    # a previous leg died mid-traffic (the supervisor re-runs the SAME
    # command) — finished requests drop, in-flight ones re-admit as
    # continuations (prompt + journaled tokens, remaining budget), so
    # the kill cost is re-decoding at most the unflushed in-flight
    # tokens.
    resumed_journal = False
    if cfg.serve.journal and not inbox_mode:
        played = journal_mod.replay(cfg.serve.journal)
        if played:
            requests = journal_mod.apply_replay(requests, played)
            resumed_journal = True
            if is_chief():
                done_n = sum(1 for e in played.values() if e["done"])
                print(f"[serve] journal resume: {done_n} requests "
                      f"already complete, {len(requests)} to serve "
                      f"({cfg.serve.journal})", flush=True)
    if not requests and not inbox_mode:
        if is_chief():
            print("[serve] journal resume: every request already "
                  "complete — nothing to serve", flush=True)
        return {"requests": 0, "total_new_tokens": 0,
                "resumed": resumed_journal}

    from tensorflow_distributed_tpu.resilience.faults import (
        FaultPlan, parse_fault_plan)
    plan = (parse_fault_plan(cfg.resilience.fault_plan)
            if cfg.resilience.fault_plan else FaultPlan())
    if resumed_journal and plan:
        # The restarted leg IS the recovery under test: consume every
        # planned event (same contract as the train loop's
        # bind(start_step) — a resumed leg must terminate).
        plan.bind(1 << 30)

    # int8 KV-cache serving: --serve.kv-dtype is the serve-side
    # spelling of the model-level kv_cache_quant knob (the decode
    # cache quantizes on write, dequantizes inside attention via
    # exact scale-adjusted dots — models/transformer.py). An explicit
    # --kv-cache-quant int8 means the same thing and passes through.
    if (cfg.serve.kv_dtype == "int8"
            and cfg.kv_cache_quant == "none"):
        cfg = dataclasses.replace(cfg, kv_cache_quant="int8")

    if inbox_mode:
        # No workload to measure: the explicit --seq-len (validated
        # present) IS the per-request bound, and continuations can
        # re-prefill at any depth — cover the whole cache.
        max_prompt = need = cfg.seq_len
    else:
        max_prompt = max(len(r.prompt) for r in requests)
        # Per-request trajectory bound (what actually has to fit the
        # cache); bucket padding is prefill-only slack and is clamped
        # to the cache length by the ladder cap below.
        need = max(len(r.prompt) + r.max_new_tokens for r in requests)
    if cfg.seq_len and need > cfg.seq_len:
        raise ValueError(
            f"--seq-len {cfg.seq_len} cannot hold the workload: the "
            f"longest request (prompt + new tokens) needs a "
            f"{need}-token cache")
    if not cfg.seq_len:
        # Size the cache to the workload (fresh-init serving). A
        # checkpointed model's max_len is pinned by training — set
        # --seq-len to the trained length explicitly. Speculation gets
        # spec_tokens of verify write headroom past the last useful
        # position (a user-pinned tight seq_len instead falls back to
        # plain decode near each request's end — engine.can_verify).
        auto_len = max(need + cfg.serve.spec_tokens, 32)
        if cfg.serve.paged:
            # The paged cache is page-granular: round the auto-sized
            # length up to a whole page (an EXPLICIT --seq-len that
            # page_size does not divide is rejected by the engine —
            # a trained model's max_len is not ours to round).
            ps = cfg.serve.page_size
            auto_len = -(-auto_len // ps) * ps
        cfg = dataclasses.replace(cfg, seq_len=auto_len)
    # With a fault plan armed (or a resumed journal, or the SLO
    # scheduler's preemption), slot-retry / replay / preemption
    # continuations can carry prompts up to prompt+new-1 tokens —
    # size the default ladder to the full trajectory so a re-prefill
    # never outgrows the largest bucket.
    cover = (need if (plan or resumed_journal or inbox_mode
                      or cfg.serve.policy == "slo") else max_prompt)
    buckets = (parse_buckets(cfg.serve.buckets) if cfg.serve.buckets
               else default_buckets(cover, cap=cfg.seq_len))

    shim = _GenTask(vocab_size=vocab, sample_input=np.zeros(
        (max(2, dict(mesh.shape).get("data", 1)), cfg.seq_len),
        np.int32))
    model, state = _build_model_and_state(cfg, mesh, shim)
    if cfg.dataset != "text":
        # The embedding gather would silently CLAMP out-of-range ids —
        # bound-check against the BUILT model's vocabulary (the family
        # default when synthetic_vocab is unset), like generate_only.
        for r in requests:
            bad = [int(t) for t in r.prompt
                   if not 0 <= t < model.cfg.vocab_size]
            if bad:
                raise ValueError(
                    f"request {r.rid}: prompt ids {bad} outside the "
                    f"model vocabulary [0, {model.cfg.vocab_size})")
    restored = False
    ckpt_step0 = None
    if cfg.checkpoint_dir:
        # Same restore semantics as mode=generate: local-SGD
        # checkpoints persist the replica stack — average it into the
        # plain template (train/loop.py::generate_only).
        if cfg.param_sync_every > 1:
            state = ckpt.restore_averaged(cfg.checkpoint_dir, state)
        else:
            state = ckpt.restore(cfg.checkpoint_dir, state)
        restored = True
        # Which trained step these weights came from — rides
        # metrics_snapshot as ckpt_step (the fleet controller's
        # model-staleness feed; _swap keeps it current).
        ckpt_step0 = int(state.step)
    trained = state.params if state.ema is None else state.ema
    # The rest of the TrainState (step, optimizer slots) is training's:
    # dropped here, and with it the last reference to what is not served.
    del state
    n_params = param_count(trained)
    # What restore_params fills for a live swap: the TRAINING layout
    # (shapes, dtypes, shardings), without its float32 buffers.
    trained_layout = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=a.sharding), trained)
    # The engine holds a SERVING tree (serve/params.py): the leaves the
    # model only reads through a cast to its compute dtype, held in it.
    params, held = serving_tree(model, trained, donate=True)
    del trained

    # The serve observatory (observe/hub.py): metrics registry +
    # per-request tracer + SLO monitor + snapshot export, with the
    # process-level installs (active registry, compiled-program
    # registration) owned and torn down in obs.close(). Trace and
    # JSONL both continue across a journal resume.
    tags = host_tags(mesh, cfg)
    obs = ServeObservatory(cfg.observe, chief=is_chief(), tags=tags,
                           process_index=int(tags.get("process_index",
                                                      0)),
                           resumed=resumed_journal, run_config=cfg)
    registry = obs.registry
    on_token = None
    if cfg.serve.stream and is_chief():
        def on_token(rid: int, tok: int, done: bool) -> None:
            print(f"[serve] rid={rid} tok={tok}"
                  + (" <done>" if done else ""), flush=True)

    watchdog = None
    if cfg.resilience.sync_timeout_s > 0:
        from tensorflow_distributed_tpu.resilience.watchdog import (
            Watchdog)
        watchdog = Watchdog(sync_timeout_s=cfg.resilience.sync_timeout_s)
    if cfg.serve.paged:
        from tensorflow_distributed_tpu.serve.paging.engine import (
            PagedSlotEngine, auto_num_pages, page_bytes_estimate)
        num_pages = cfg.serve.num_pages
        if not num_pages:
            # Auto-size the page pool from the workload's trajectory
            # bound, a previous run's OBSERVED slot_pages_peak (read
            # from the still-standing --observe.export-path snapshot
            # when one exists), and the --serve.hbm-budget-gb cap
            # with the params' resident bytes subtracted — replacing
            # the old blind 2x heuristic (ROADMAP item-2 follow-up).
            ps = cfg.serve.page_size
            observed_peak = 0
            if cfg.observe.export_path and os.path.exists(
                    cfg.observe.export_path):
                try:
                    with open(cfg.observe.export_path) as f:
                        observed_peak = int(
                            json.load(f).get("slot_pages_peak", 0))
                except (OSError, ValueError):
                    observed_peak = 0
            num_pages, rationale = auto_num_pages(
                num_slots=cfg.serve.num_slots,
                need_pages=-(-need // ps),
                page_bytes=page_bytes_estimate(model.cfg, ps, tp=tp),
                budget_bytes=int(cfg.serve.hbm_budget_gb * 2 ** 30),
                reserved_bytes=held["bytes_held"],
                observed_peak=observed_peak)
            if is_chief():
                for line in rationale:
                    print(f"[serve] paged auto-size: {line}",
                          flush=True)
        engine = PagedSlotEngine(model, params, cfg.serve.num_slots,
                                 page_size=cfg.serve.page_size,
                                 num_pages=num_pages,
                                 radix=cfg.serve.radix,
                                 buckets=buckets, check=cfg.check,
                                 fault_plan=plan if plan else None,
                                 watchdog=watchdog,
                                 spec_tokens=cfg.serve.spec_tokens,
                                 tracer=obs.tracer)
        if obs.autopilot is not None:
            # Loop 2's advisory half: the autopilot re-runs the SAME
            # one-shot sizer against the peak it OBSERVED, via this
            # closure — the controller itself stays jax-free and
            # never re-derives page-bytes arithmetic.
            def _recommend_pages(observed_peak: int,
                                 _ps=cfg.serve.page_size):
                return auto_num_pages(
                    num_slots=cfg.serve.num_slots,
                    need_pages=-(-need // _ps),
                    page_bytes=page_bytes_estimate(model.cfg, _ps,
                                                   tp=tp),
                    budget_bytes=int(
                        cfg.serve.hbm_budget_gb * 2 ** 30),
                    reserved_bytes=held["bytes_held"],
                    observed_peak=int(observed_peak))
            obs.autopilot.bind_paging(num_pages=num_pages,
                                      recommend=_recommend_pages)
    else:
        engine = SlotDecodeEngine(model, params, cfg.serve.num_slots,
                                  buckets=buckets, check=cfg.check,
                                  fault_plan=plan if plan else None,
                                  watchdog=watchdog,
                                  spec_tokens=cfg.serve.spec_tokens,
                                  tracer=obs.tracer)
    # Speculative decoding: the proposer (k-gram self-draft, or a
    # draft model mirroring the slot cache — serve/speculate.py).
    from tensorflow_distributed_tpu.serve.speculate import (
        build_speculator)
    speculator = build_speculator(cfg, model, cfg.seed + 1,
                                  cfg.serve.num_slots, buckets)
    # Every program — the engine's AND a draft speculator's mirror —
    # dispatches once BEFORE the scheduler's clock starts:
    # first-request TTFT (and, on a supervised restart, the recovery
    # window) pays compute, not compile/cache-load, and the measured
    # serving wall (tokens/s) starts clean after warmup.
    engine.warmup(speculator)
    # Which form of the expanded prefill attend each bucket's program
    # traced, its blocks and the score tiles it computes, are static: a
    # family that has one (``model.prefill_attend_plan``) puts them on
    # the run's start record, as a training run's carries ``flash_plan``.
    attend_plan = getattr(model, "prefill_attend_plan", None)
    registry.emit("start", model=cfg.model, task="serve",
                  params=n_params, serving_params=held,
                  **({"prefill_attend_plan": attend_plan(buckets)}
                     if attend_plan else {}))
    if obs.autopilot is not None:
        # The bucket ladder the run booted with — the baseline the
        # prompt-distribution advisory compares against.
        obs.autopilot.bind_buckets(buckets)
    reload_fn = None
    if cfg.checkpoint_dir:
        def reload_fn():
            # Live weight swap source: newest VERIFIABLE checkpoint
            # (sha256 + finite-params walk-back), restored into the
            # training layout with its shardings and cast as the booted
            # tree was, so the engine's swap is a jit cache hit.
            fresh, step = ckpt.restore_params(cfg.checkpoint_dir,
                                              trained_layout)
            return serving_tree(model, fresh, donate=True)[0], step
    journal = (journal_mod.RequestJournal(cfg.serve.journal)
               if cfg.serve.journal else None)
    trace_name = cfg.serve.trace or (
        "file" if cfg.serve.requests else "uniform")
    status_fn = None
    if is_chief() and obs.status_every:
        def status_fn(line: str) -> None:
            print(line, flush=True)
    feed = None
    if inbox_mode:
        from tensorflow_distributed_tpu.fleet.replica import InboxFeed
        feed = InboxFeed(cfg.serve.inbox,
                         default_max_new=cfg.serve.max_new_tokens,
                         default_eos=cfg.serve.eos_id)
        if is_chief():
            print(f"[serve] fleet replica: inbox {cfg.serve.inbox} "
                  f"(serving until a drain command)", flush=True)
    sched = Scheduler(engine, decode_priority=cfg.serve.decode_priority,
                      on_token=on_token,
                      feed=feed, served_ckpt_step=ckpt_step0,
                      fault_plan=plan if plan else None,
                      journal=journal, reload_fn=reload_fn,
                      slot_retries=cfg.serve.slot_retries,
                      policy=cfg.serve.policy,
                      tenant_quota=cfg.serve.tenant_quota,
                      preempt=cfg.serve.preempt,
                      speculator=speculator,
                      status_fn=status_fn,
                      summary_extra={"seed": cfg.seed,
                                     "trace": trace_name,
                                     "resumed": resumed_journal},
                      **obs.scheduler_kwargs())
    try:
        if cfg.profile_dir and is_chief():
            # Whole-serving-window capture (warmup already dispatched
            # every program, so the trace is steady-state serving):
            # the Perfetto export is parsed below into device_time
            # records per engine program (decode/verify/prefill
            # buckets/insert) — observe/xprof.py.
            from tensorflow_distributed_tpu.utils.profiling import (
                trace as profile_trace)
            with profile_trace(cfg.profile_dir):
                done = sched.run(requests)
            obs.emit_device_time(cfg.profile_dir,
                                 calibration=cfg.plan_calibration)
        else:
            done = sched.run(requests)
        if obs.programs_armed:
            budget = observe_device.hbm_budget()
            if budget:
                registry.emit("hbm_budget", **budget)
    finally:
        if journal is not None:
            journal.close()
        if watchdog is not None:
            watchdog.close()
        obs.close()
    summary = dict(sched.summary)
    if done:
        # An inbox-mode replica can drain without ever serving a
        # request — the percentile math needs at least one.
        ttfts = np.asarray([c.ttft_s for c in done])
        summary["ttft_ms_p50"] = round(
            1e3 * float(np.percentile(ttfts, 50)), 3)
        summary["ttft_ms_p95"] = round(
            1e3 * float(np.percentile(ttfts, 95)), 3)
        summary["ttft_ms_p99"] = round(
            1e3 * float(np.percentile(ttfts, 99)), 3)
        summary["tok_ms_mean"] = round(
            float(np.mean([c.tok_ms for c in done])), 4)
    # Per-SLO-class TTFT p95: the number the SLO scheduler exists to
    # move. Emitted per class actually present, FIFO runs included —
    # a FIFO baseline with the same class mix is the A/B.
    by_class: Dict[str, list] = {}
    for c in done:
        by_class.setdefault(c.slo, []).append(c.ttft_s)
    for cls, vals in sorted(by_class.items()):
        # graftcheck: disable=host-sync-in-loop -- post-run summary
        # math over HOST completion floats; the engine is done
        summary[f"ttft_ms_p95_{cls}"] = round(
            1e3 * float(np.percentile(np.asarray(vals), 95)), 3)
    summary["params"] = "checkpoint" if restored else "fresh-init"
    if is_chief():
        print(f"[serve] {summary['requests']} requests, "
              f"{summary['total_new_tokens']} tokens in "
              f"{summary['wall_s']}s — "
              f"{summary['tokens_per_sec']} tok/s, occupancy "
              f"{summary['mean_slot_occupancy']}, ttft p50 "
              f"{summary.get('ttft_ms_p50')}ms / p95 "
              f"{summary.get('ttft_ms_p95')}ms, "
              f"{summary['prefill_compiles']} prefill programs "
              f"(buckets {summary['buckets']}), "
              f"{summary['params']} params", flush=True)
        if cfg.serve.spec_tokens:
            print(f"[serve] speculative: k={summary.get('spec_tokens')} "
                  f"accept_rate={summary.get('accept_rate')} "
                  f"verify_steps={summary.get('verify_steps')}",
                  flush=True)
        if cfg.serve.paged:
            print(f"[serve] paged: prefix_hit_rate="
                  f"{summary.get('prefix_hit_rate')} pool_occupancy="
                  f"{summary.get('pool_occupancy')} pages_peak="
                  f"{summary.get('pages_peak')}/"
                  f"{summary.get('num_pages')} evictions="
                  f"{summary.get('page_evictions')} cow="
                  f"{summary.get('cow_copies')} sessions="
                  f"{summary.get('sessions')}", flush=True)
        if cfg.serve.policy == "slo":
            cls_bits = " ".join(
                f"{k.rsplit('_', 1)[-1]}={summary[k]}ms"
                for k in sorted(summary)
                if k.startswith("ttft_ms_p95_"))
            print(f"[serve] slo: preemptions={summary['preemptions']} "
                  f"p95 ttft by class: {cls_bits}", flush=True)
        if plan or resumed_journal:
            print(f"[serve] fire: retries={summary['retries']} "
                  f"swaps={summary['swaps']} "
                  f"swap_s={summary['swap_seconds']} "
                  f"resumed={summary['resumed']} "
                  f"ttft p99 {summary.get('ttft_ms_p99')}ms",
                  flush=True)
        if cfg.observe.slo:
            print(f"[serve] slo monitor: "
                  f"alerts={summary.get('slo_alerts', 0)} "
                  f"budget_remaining_min="
                  f"{summary.get('slo_budget_remaining_min')} "
                  f"targets={summary.get('slo_targets')}", flush=True)
        if cfg.observe.trace:
            print(f"[observe] serve trace: {cfg.observe.trace} "
                  f"(open at https://ui.perfetto.dev)", flush=True)
        if cfg.observe.export_path:
            print(f"[observe] metrics snapshot: "
                  f"{cfg.observe.export_path} (atomic; rewritten "
                  f"every {cfg.observe.export_every or 'run-end'}"
                  f"{'s' if cfg.observe.export_every else ''})",
                  flush=True)
        if cfg.observe.metrics_jsonl:
            print(f"[observe] serve metrics: "
                  f"{cfg.observe.metrics_jsonl} (summarize: python -m "
                  f"tensorflow_distributed_tpu.observe.report "
                  f"{cfg.observe.metrics_jsonl})", flush=True)
    return summary
