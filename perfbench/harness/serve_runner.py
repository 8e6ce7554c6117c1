"""Runner for configurations of kind ``serve``: one ``cli.main --mode
serve`` run over a request file generated from the seed, every token
clocked by the benchmark, then a teacher-forced comparison of a sample of
the served requests with the plain reference."""

from __future__ import annotations

import gc
import json
import os
import time
from typing import Any, Dict, List, Optional

import numpy as np

from . import common, probes, stats, traffic
from .common import say
from .loader import Cell

SAMPLE_REQUESTS = 8          # compared with the reference after the window
REFERENCE_ROWS = 2           # sequences per reference call
TRACE_SECONDS = 2.0          # a capture's stop stalls serving for ~10x that


def served_gaps(sample: List[Dict[str, Any]], seed: int, model,
                sizes: Dict[str, int], precision: str = "f32"
                ) -> Dict[str, Any]:
    """Teacher-forced reference (``model``'s) over each sampled request's
    prompt plus served tokens. Returns every served token's gap (how far
    the f32 reference's logit of that token lies below the reference's best).
    With ``precision`` below f32 the tokens scored are not the served ones
    but those that precision would put first at each position: the
    control."""
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    params = jax.jit(lambda k: model.make_params(k, sizes, stacked=True))(
        jax.device_put(common.root_key(seed), dev))
    L = model.reference_positions(sizes, max(
        len(r["prompt"]) + len(r["tokens"]) for r in sample))
    gaps: List[float] = []
    for lo in range(0, len(sample), REFERENCE_ROWS):
        rows = sample[lo:lo + REFERENCE_ROWS]
        seqs = np.zeros((REFERENCE_ROWS, L), np.int32)
        for i, r in enumerate(rows):
            seq = list(r["prompt"]) + list(r["tokens"])
            seqs[i, :len(seq)] = seq
        seqs = jax.device_put(jnp.asarray(seqs), dev)
        gap, _ = model.served_token_gaps(params, seqs, "f32")
        if precision != "f32":
            _, low = model.served_token_gaps(params, seqs, precision)
            gap = model.gaps_of(params, seqs, low)
        gap = np.asarray(jax.device_get(gap))
        for i, r in enumerate(rows):
            p, n = len(r["prompt"]), len(r["tokens"])
            gaps.extend(float(x) for x in gap[i, p - 1:p - 1 + n])
    return {"gaps": gaps, "max": max(gaps), "mean": float(np.mean(gaps)),
            "tokens": len(gaps)}


def pick_sample(finished: List[Dict[str, Any]], seed: int, k: int
                ) -> List[Dict[str, Any]]:
    """k finished requests drawn from the seed, the longest among them."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    rest = [r for r in finished if r is not longest]
    rng = np.random.default_rng([seed % 2 ** 32, 0xc0de])
    idx = rng.permutation(len(rest))[:max(0, k - 1)]
    return [longest] + [rest[i] for i in idx]


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        rehearse: bool = False, fault: Optional[str] = None,
        require_tpu: bool = True, control: Optional[str] = None,
        mix_update: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """``mix_update`` overrides keys of the traffic mix (the knee sweep's
    rates); a run of the benchmark never passes it."""
    dev = common.find_devices(cell.chips, require_tpu and not rehearse)
    cfg = cell.config
    sizes = cell.sizes(rehearse)
    mix = dict(cell.traffic)
    if rehearse:
        mix.update(cfg["rehearsal"]["traffic"])
    mix.update(mix_update or {})
    requests = traffic.serve_requests(mix, seed, seconds,
                                      sizes["vocab_size"])
    out = common.work_dir(cell.name)
    req_file = os.path.join(out, "requests.jsonl")
    with open(req_file, "w") as f:
        for r in requests:
            f.write(json.dumps(r) + "\n")
    jsonl = os.path.join(out, "serve.jsonl")
    serve = cfg["rehearsal"]["serve"] if rehearse else cfg["serve"]
    argv = list(cfg["rehearsal"]["program_argv"] if rehearse
                else cfg["program_argv"])
    argv += ["--serve.requests", req_file,
             "--serve.num-slots", str(serve["num_slots"]),
             "--serve.buckets", serve["buckets"],
             "--seq-len", str(sizes["n_positions"]),
             # weights and requests come from the run's seed through the
             # benchmark; the program's own seed has nothing left to seed
             "--seed", "0", "--observe.metrics-jsonl", jsonl]
    tw = probes.TraceWindow(os.path.join(out, "trace"),
                            min(TRACE_SECONDS, seconds)) if trace else None
    probe = probes.ServeProbe(seed, cell.model, sizes, trace=tw,
                              trace_after_s=0.6 * seconds, fault=fault)
    say(f"devices found {time.perf_counter() - common.T_PROCESS_START:.2f}s "
        f"after process start")
    n_out = sum(r["max_new_tokens"] for r in requests)
    say(f"cell {cell.name}: {len(requests)} requests over "
        f"{requests[-1]['arrival_s']:.2f}s at {mix['rate_rps']} req/s, "
        f"{sum(len(r['prompt']) for r in requests)} prompt tokens, {n_out} "
        f"tokens asked; {serve['num_slots']} slots; seed {seed}; "
        f"cli.main {' '.join(argv)}")

    from tensorflow_distributed_tpu import cli
    with probes.serve_seams(probe):
        rc = cli.main(argv)
    if rc != 0 or probe.t0 is None:
        raise RuntimeError(f"cli.main --mode serve returned {rc}")
    setup_s = probe.t0 - common.T_PROCESS_START
    wall = probe.t1 - probe.t0
    mem_peak = common.memory_peak_bytes(cell.chips)
    slots = probe.engine.num_slots
    decode_steps, prefills = probe.engine.decode_steps, probe.engine.prefills
    import jax
    param_bytes = sum(x.nbytes for x in
                      jax.tree_util.tree_leaves(probe.engine.params))
    probe.engine = None
    gc.collect()              # parameters and cache went with serve_run

    toks: Dict[int, List[int]] = {}
    times: Dict[int, List[float]] = {}
    for rid, tok, t in probe.events:
        toks.setdefault(rid, []).append(tok)
        times.setdefault(rid, []).append(t)
    ttft, tpot, finished, failed = [], [], [], 0
    first_token_at: List[float] = []
    for rid, r in enumerate(requests):
        got = toks.get(rid, [])
        if len(got) != r["max_new_tokens"]:
            failed += 1       # refused, unfinished or wrong count: a miss
            continue
        ts = times[rid]
        ttft.append(1e3 * (ts[0] - (probe.t0 + r["arrival_s"])))
        first_token_at.append(ts[0])
        if len(ts) > 1:
            tpot.append(1e3 * (ts[-1] - ts[0]) / (len(ts) - 1))
        finished.append({"rid": rid, "prompt": r["prompt"], "tokens": got})
    done_tokens = sum(len(r["tokens"]) for r in finished)
    say(f"window: serving wall {wall:.4f}s for {len(requests)} requests "
        f"({failed} failed), {done_tokens} tokens completed, "
        f"{decode_steps} decode steps, {prefills} prefills; set-up "
        f"{setup_s:.2f}s")
    say("arrivals are the program's own open loop: a request becomes "
        "visible at the first scheduler iteration after it is due, and "
        "that lateness is inside its TTFT (clocked from the due time)")
    values: Dict[str, float] = {"setup_s": setup_s,
                                "serve_tokens_per_s": done_tokens / wall}
    if ttft:
        values["serve_ttft_p50_ms"] = stats.percentile(ttft, 50)
        values["serve_tpot_p95_ms"] = stats.percentile(tpot, 95)
        say(f"ttft ms: p50 {values['serve_ttft_p50_ms']:.3f} p95 "
            f"{stats.percentile(ttft, 95):.3f} max {max(ttft):.3f} over "
            f"{len(ttft)} requests (a failed request is a miss and has no "
            f"time); tpot ms: p50 {stats.percentile(tpot, 50):.3f} p95 "
            f"{values['serve_tpot_p95_ms']:.3f} over {len(tpot)} requests; "
            f"tokens/s completed {values['serve_tokens_per_s']:.2f}")

    compared = common.Compared()
    ok = compared("failed_requests", failed, 0, failed == 0)
    sample = pick_sample(finished, seed, SAMPLE_REQUESTS)
    check: Dict[str, Any] = {}
    if sample:
        t0 = time.perf_counter()
        check = served_gaps(sample, seed, cell.model, sizes)
        limits = (cfg["rehearsal"] if rehearse else cfg)["correct_limits"]
        ok = compared(
            "served_token_gap_max", check["max"],
            limits["served_token_gap_max"],
            check["max"] <= limits["served_token_gap_max"],
            f"(widest of {check['tokens']} served tokens in "
            f"{len(sample)} requests, longest "
            f"{len(sample[0]['prompt']) + len(sample[0]['tokens'])} tokens)"
        ) and ok
        ok = compared(
            "served_token_gap_mean", check["mean"],
            limits["served_token_gap_mean"],
            check["mean"] <= limits["served_token_gap_mean"]) and ok
        if control:
            c = served_gaps(sample, seed, cell.model, sizes,
                            precision=control)
            say(f"control at {control}: gap max {c['max']:.6g} mean "
                f"{c['mean']:.6g} over {c['tokens']} positions")
            check["control"] = {"max": c["max"], "mean": c["mean"]}
        say(f"correct: reference ran in {time.perf_counter() - t0:.1f}s")
    else:
        ok = False
    check.pop("gaps", None)

    device = dict(dev, memory_peak_bytes=mem_peak)
    metrics: Dict[str, Dict[str, Any]] = {}
    breakdown = None
    if trace:
        # Starting and stopping a capture stalls the scheduler for tens of
        # seconds; what the readers take from the run itself (not from
        # the trace) is taken from before the capture started.
        ctx = common.Ctx(
            cell=cell, model=cell.model, records=common.read_jsonl(jsonl),
            trace=common.read_capture(tw.log_dir), sizes=sizes, slots=slots,
            param_bytes=param_bytes, peaks=common.peaks_of(dev),
            chips=cell.chips, say=say, cut_s=tw.t_start - probe.t0,
            ttft_ms_before_capture=[t for t, at in zip(ttft, first_token_at)
                                    if at < tw.t_start])
        metrics, breakdown = common.traced_result(cell, ctx, device)
    else:
        for m in cell.end_to_end():
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    return {"correct": ok, "attempted": len(requests), "failed": failed,
            "metrics": metrics, "device": device, "breakdown": breakdown,
            "check": check, "compared": compared, "values": values,
            "wall_s": wall,
            "ttft_by_arrival": [(requests[r["rid"]]["arrival_s"], t)
                                for r, t in zip(finished, ttft)]}

