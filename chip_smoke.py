#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the repo's main path once, through the entry points a
user calls, at the full published width of GPT-2 small (12L x 768d x 12H,
d_ff 3072, vocab 50257, max_len 1024; bf16, random weights from a seed):

  device   JAX must report a TPU, else exit non-zero at once
  train    cli.main: 20 steps at B=8 L=1024, checkpoint + metrics JSONL;
           losses finite and falling, step records carry mfu, the Pallas
           flash kernel is in the compiled step (tpu_custom_call); then a
           short run through the fused-CE kernel whose first loss agrees
  serve    cli.main --mode serve on that checkpoint: 8 requests, prompts
           16-512 tokens, 32 new tokens each, 4 slots, 3 prefill buckets;
           every request completes, two are token-identical to one-shot
           greedy models/generate.py, and no more programs compile than
           buckets + the fixed ones

  --chips 4  runs ONLY the data-parallel phase: GPT-2-small training on
           --mesh.data 4 against the same seed and global batch on a
           one-device mesh in this process; per-step losses agree, and
           the state and batch really live on four distinct devices.

The last stdout line of a passing chip run is
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
Any failed phase is a non-zero exit. Without a TPU the script exits
non-zero before any phase. `--model-size tiny` is the rehearsal: it walks
the same control flow on whatever JAX finds (the CPU here) to catch wrong
paths before chip time is spent, checks the TPU-only assertions only on a
TPU, prints no result line and exits EXIT_REHEARSED — a rehearsal is never
a chip result.

Artifacts (metrics JSONL, request file, journal) land under
chiprun_out/chip_smoke/ next to this script; the 1.5 GB checkpoint the
train phase writes there is removed when the run ends.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
EXIT_NO_CHIP = 4
EXIT_REHEARSED = 5

# Workload sizes. "small" is the real thing; anything else is the CPU
# rehearsal's cut (same control flow, minutes -> seconds).
REAL = dict(seq_len=1024, train_steps=20, ce_chunk=8192,
            prompt_lens=(16, 24, 48, 100, 200, 300, 400, 512),
            buckets="32,128,512", new_tokens=32)
TINY = dict(seq_len=128, train_steps=20, ce_chunk=4096,
            prompt_lens=(4, 6, 8, 16, 24, 32, 48, 64),
            buckets="8,32,64", new_tokens=8)
BATCH = 8
NUM_SLOTS = 4
COMPARE_RIDS = (0, 3)        # two requests, two different buckets
# serve_decode_step + serve_insert_row; everything else is a bucket.
FIXED_SERVE_PROGRAMS = 2
# bf16 has 8 mantissa bits; a mean over >= 8192 tokens is far tighter,
# but the fused kernel / the 4-way psum reorder the reduction.
LOSS_RTOL = 2e-2


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(phase: str, msg: str) -> None:
    print(f"[smoke:{phase}] {msg}", flush=True)


def read_jsonl(path: str) -> list:
    from tensorflow_distributed_tpu.observe.report import load_records
    return load_records(path)


def rel_diff(a: float, b: float) -> float:
    return abs(a - b) / max(abs(a), abs(b))


# ----------------------------------------------------------------- device

def device_phase(args) -> dict:
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu" and args.model_size == "small":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{dev['platform']!r}); this script proves the chip path "
              f"and does not fall back", file=sys.stderr, flush=True)
        sys.exit(EXIT_NO_CHIP)
    check(len(devs) >= args.chips,
          f"--chips {args.chips} needs {args.chips} devices, JAX "
          f"reports {len(devs)}")

    import jaxlib
    from importlib import metadata
    from jax.extend import backend as jax_backend

    from tensorflow_distributed_tpu.utils.compilecache import (
        enable_persistent_cache)
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    say("device", f"platform={dev['platform']} kind={dev['kind']!r} "
        f"count={dev['count']} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu}")
    say("device", f"runtime: "
        f"{jax_backend.get_backend().platform_version.strip()}")
    say("device", f"compile cache: {enable_persistent_cache()} "
        f"(JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
    return dev


# ------------------------------------------------------------------ train

@contextlib.contextmanager
def kernel_call_counts():
    """Tee observe.device.register_compiled and count the Mosaic kernel
    calls (``tpu_custom_call``) in the compiled text of every program
    the run itself registers — the real train step, not a rebuilt
    look-alike. Yields {program: count}."""
    from tensorflow_distributed_tpu.observe import device as observe_device

    counts: dict = {}
    original = observe_device.register_compiled

    def tee(name, lowered=None, compiled=None, **kw):
        if compiled is not None:
            counts[name] = compiled.as_text().count("tpu_custom_call")
        return original(name, lowered, compiled, **kw)

    observe_device.register_compiled = tee
    try:
        yield counts
    finally:
        observe_device.register_compiled = original


def train_argv(args, sizes, steps, jsonl, extra=()):
    return ["--model", "gpt_lm", "--model-size", args.model_size,
            "--dataset", "synthetic", "--seq-len", str(sizes["seq_len"]),
            "--batch-size", str(BATCH), "--compute-dtype", "bfloat16",
            "--train-steps", str(steps), "--log-every", "1",
            "--eval-every", "0", "--eval-batch-size", str(BATCH),
            "--seed", str(args.seed),
            "--observe.metrics-jsonl", jsonl, *extra]


def step_losses(records, steps):
    by_step = {r["step"]: r for r in records if r.get("event") == "step"}
    check(sorted(by_step) == list(range(1, steps + 1)),
          f"expected step records 1..{steps}, got {sorted(by_step)}")
    losses = [by_step[s]["loss"] for s in range(1, steps + 1)]
    check(all(math.isfinite(x) for x in losses),
          f"non-finite loss in {losses}")
    return losses, by_step


def compile_seconds(records):
    """{program: compile_s} from the run's compile records, plus the
    loop's own first-step wall (trace + compile + run)."""
    out = {r["program"]: r.get("compile_s") for r in records
           if r.get("event") == "compile"}
    summary = [r for r in records if r.get("event") == "summary"]
    if summary:
        out["first_step_wall"] = summary[-1].get("compile_seconds")
    return out


def run_train(args, sizes, steps, jsonl, extra):
    """One cli.main training run -> (records, losses, last step record,
    kernel calls in its compiled train step)."""
    from tensorflow_distributed_tpu import cli

    with kernel_call_counts() as counts:
        rc = cli.main(train_argv(args, sizes, steps, jsonl, extra))
    check(rc == 0, f"train {extra}: cli.main returned {rc}")
    records = read_jsonl(jsonl)
    losses, by_step = step_losses(records, steps)
    check("train_step" in counts,
          "no compiled train_step was registered (observe/device.py "
          "swallowed a failure?) — compile records: "
          f"{[r for r in records if r.get('event') == 'compile']}")
    return records, losses, by_step[steps], counts["train_step"]


def train_phase(args, sizes, out, on_tpu) -> str:
    ckpt_dir = os.path.join(out, "ckpt")
    steps = sizes["train_steps"]
    t0 = time.perf_counter()
    records, losses, last, n_kernels = run_train(
        args, sizes, steps, os.path.join(out, "train.jsonl"),
        ["--checkpoint-dir", ckpt_dir, "--checkpoint-every", "0"])
    say("train", f"{steps} steps in {time.perf_counter() - t0:.1f}s "
        f"wall; losses " + " ".join(f"{x:.4f}" for x in losses))
    check(losses[-1] < losses[0],
          f"loss did not fall: {losses[0]} -> {losses[-1]}")
    say("train", f"compile seconds: {compile_seconds(records)}")
    say("train", "last step record: " + json.dumps(
        {k: last.get(k) for k in ("step_ms_p50", "tokens_per_sec",
                                  "model_tflops", "mfu")}))
    say("train", f"tpu_custom_call sites in the compiled train step: "
        f"{n_kernels}")
    if on_tpu:
        # Telemetry failures are swallowed by contract, so the records
        # themselves are the check: the chip's device_kind must be in
        # observe/mfu.py's table, and the dispatcher must not have
        # given way to the XLA oracle.
        check(isinstance(last.get("mfu"), (int, float)),
              f"step records carry no mfu: {last}")
        check(n_kernels > 0,
              "the compiled train step holds no tpu_custom_call: the "
              "flash dispatcher fell back to the XLA path")

    # The fused-loss kernel (ops/fused_ce_kernel.py): same seed, same
    # first batch, so its first loss must match the dense head's.
    records_ce, ce_losses, last_ce, n_ce = run_train(
        args, sizes, 4, os.path.join(out, "train_ce_kernel.jsonl"),
        ["--ce-chunk", str(sizes["ce_chunk"]), "--ce-impl", "kernel"])
    say("train", f"fused-CE kernel losses "
        + " ".join(f"{x:.4f}" for x in ce_losses)
        + f" (dense head: {' '.join(f'{x:.4f}' for x in losses[:4])}); "
        f"step_ms_p50={last_ce.get('step_ms_p50')}; {n_ce} "
        f"tpu_custom_call sites; compile seconds: "
        f"{compile_seconds(records_ce)}")
    check(rel_diff(ce_losses[0], losses[0]) <= LOSS_RTOL,
          f"fused-CE first loss {ce_losses[0]} != dense {losses[0]} "
          f"(rtol {LOSS_RTOL})")
    if on_tpu:
        check(n_ce > n_kernels,
              f"fused-CE step holds {n_ce} kernels, no more than the "
              f"dense step's {n_kernels}: the CE kernel is not in it")
    return ckpt_dir


# ------------------------------------------------------------------ serve

def serve_phase(args, sizes, out, ckpt_dir) -> None:
    import numpy as np

    from tensorflow_distributed_tpu import cli
    from tensorflow_distributed_tpu.config import parse_args
    from tensorflow_distributed_tpu.serve import journal as journal_mod
    from tensorflow_distributed_tpu.train.loop import generate_only

    # Prompts from the training distribution (data/lm.py's arithmetic
    # progressions over the synthetic 64-token alphabet), from --seed.
    rng = np.random.default_rng(args.seed)
    new = sizes["new_tokens"]
    prompts = []
    for n in sizes["prompt_lens"]:
        start, stride = int(rng.integers(0, 64)), int(rng.integers(1, 6))
        prompts.append([(start + stride * t) % 64 for t in range(n)])
    req_file = os.path.join(out, "requests.jsonl")
    with open(req_file, "w") as f:
        for p in prompts:
            f.write(json.dumps({"prompt": p, "max_new_tokens": new}) + "\n")
    journal = os.path.join(out, "serve.journal")
    jsonl = os.path.join(out, "serve.jsonl")
    if os.path.exists(journal):      # a non-empty journal means RESUME
        os.remove(journal)
    model_argv = ["--model", "gpt_lm", "--model-size", args.model_size,
                  "--seq-len", str(sizes["seq_len"]),
                  "--compute-dtype", "bfloat16",
                  "--checkpoint-dir", ckpt_dir, "--seed", str(args.seed)]
    t0 = time.perf_counter()
    rc = cli.main(["--mode", "serve", *model_argv,
                   "--serve.requests", req_file,
                   "--serve.num-slots", str(NUM_SLOTS),
                   "--serve.buckets", sizes["buckets"],
                   "--serve.journal", journal,
                   "--observe.metrics-jsonl", jsonl])
    wall = time.perf_counter() - t0
    check(rc == 0, f"serve: cli.main returned {rc}")

    records = read_jsonl(jsonl)
    served = journal_mod.replay(journal)
    done = {r["rid"]: r for r in records
            if r.get("event") == "serve_request"}
    check(sorted(done) == list(range(len(prompts))),
          f"serve_request records for rids {sorted(done)}, wanted "
          f"0..{len(prompts) - 1}")
    for rid, p in enumerate(prompts):
        ent = served.get(rid)
        check(ent is not None and ent["done"],
              f"request {rid} never completed: {ent}")
        check(len(ent["tokens"]) == new == done[rid]["new_tokens"],
              f"request {rid}: {len(ent['tokens'])} tokens journaled, "
              f"{done[rid]['new_tokens']} reported, {new} asked")
        check(done[rid]["prompt_len"] == len(p),
              f"request {rid}: prompt_len {done[rid]['prompt_len']} != "
              f"{len(p)}")
    summary = [r for r in records if r.get("event") == "serve_summary"][-1]
    say("serve", f"{len(prompts)} requests x {new} tokens in {wall:.1f}s "
        f"wall (compile included); summary: " + json.dumps(
            {k: summary.get(k) for k in (
                "wall_s", "tokens_per_sec", "mean_slot_occupancy",
                "decode_steps", "prefills", "prefill_compiles",
                "buckets")}))
    say("serve", "ttft_ms by rid: " + " ".join(
        f"{rid}:{done[rid]['ttft_ms']}" for rid in sorted(done)))

    # No more compiled programs than buckets + the fixed ones.
    n_buckets = len(sizes["buckets"].split(","))
    programs = sorted({r["program"] for r in records
                       if r.get("event") == "compile"})
    say("serve", f"compiled programs: {programs}; compile seconds: "
        f"{compile_seconds(records)}")
    check(summary["prefill_compiles"] <= n_buckets,
          f"{summary['prefill_compiles']} prefill programs > "
          f"{n_buckets} buckets")
    check(0 < len(programs) <= n_buckets + FIXED_SERVE_PROGRAMS,
          f"{len(programs)} serve programs compiled, bound is "
          f"{n_buckets} buckets + {FIXED_SERVE_PROGRAMS} fixed: "
          f"{programs}")
    failed = [r["program"] for r in records
              if r.get("event") == "compile" and r.get("error")]
    check(not failed, f"compile records with errors: {failed}")

    # The plain reference: one-shot greedy generate() of the same
    # prompt from the same checkpoint (mode=generate's entry point).
    mismatches = []
    for rid in COMPARE_RIDS:
        ref = generate_only(parse_args([
            "--mode", "generate", *model_argv,
            "--prompt", ",".join(str(t) for t in prompts[rid]),
            "--max-new-tokens", str(new)]))["new_tokens"]
        got = served[rid]["tokens"]
        same = got == ref
        say("serve", f"rid {rid} (prompt {len(prompts[rid])} tokens): "
            f"engine {'==' if same else '!='} one-shot greedy; "
            f"engine={got} reference={ref}")
        if not same:
            first = next(i for i, (a, b) in enumerate(zip(got, ref))
                         if a != b)
            mismatches.append(f"rid {rid} diverges at token {first}")
    check(not mismatches, "engine tokens differ from one-shot greedy "
          "generate(): " + "; ".join(mismatches))


# ---------------------------------------------------------- four chips

def four_chip_phase(args, sizes, out, on_tpu) -> None:
    """Synchronous data parallelism over a 4-device mesh against the
    same seed and global batch on a one-device mesh."""
    import jax

    from tensorflow_distributed_tpu.config import parse_args
    from tensorflow_distributed_tpu.data import prefetch_to_mesh
    from tensorflow_distributed_tpu.parallel.mesh import make_mesh
    from tensorflow_distributed_tpu.train.loop import train
    from tensorflow_distributed_tpu.train.tasks import make_task

    steps = 5

    def run(n_data, tag):
        jsonl = os.path.join(out, f"dp{n_data}.jsonl")
        cfg = parse_args(train_argv(
            args, sizes, steps, jsonl,
            ["--mesh.data", str(n_data), "--dropout-rate", "0.0"]))
        t0 = time.perf_counter()
        result = train(cfg)
        say("dp", f"{tag}: {steps} steps in "
            f"{time.perf_counter() - t0:.1f}s wall")
        records = read_jsonl(jsonl)
        losses, by_step = step_losses(records, steps)
        say("dp", f"{tag}: losses " + " ".join(f"{x:.5f}" for x in losses)
            + f"; step_ms_p50={by_step[steps].get('step_ms_p50')} "
            f"compile seconds: {compile_seconds(records)}")
        return cfg, result, losses

    cfg4, result4, losses4 = run(4, "mesh.data=4")

    # Placement, read off the arrays — a mesh of four having been built
    # proves nothing. Replicated params: four full copies, one a chip.
    devices = jax.devices()[:4]
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            result4.state.params):
        homes = {s.device for s in leaf.addressable_shards}
        check(homes == set(devices),
              f"param {jax.tree_util.keystr(path)} lives on {homes}, "
              f"not on the four devices")
    # The batch: the loop's own placement call on the loop's own stream.
    mesh = make_mesh(cfg4.mesh)
    task = make_task(cfg4, mesh)
    batch = next(prefetch_to_mesh(task.train_stream(0), mesh,
                                  seq_axis=task.seq_axis))
    for name, arr in batch.items():
        shards = arr.addressable_shards
        check({s.device for s in shards} == set(devices)
              and all(s.data.shape[0] == BATCH // 4 for s in shards)
              and len({s.index for s in shards}) == 4,
              f"batch[{name!r}] is not split four ways over four "
              f"devices: {[(s.device, s.index) for s in shards]}")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    say("dp", "params replicated on 4 distinct devices; batch rows "
        f"split {BATCH // 4}/device; peak bytes in use per device: "
        f"{peaks}")
    if on_tpu:
        replica = sum(x.nbytes for x in jax.tree_util.tree_leaves(
            result4.state.params))
        check(all(p is not None and p > replica for p in peaks),
              f"a device never held a {replica}-byte param replica: "
              f"{peaks}")
    del result4, batch

    # The one-device comparison, same process: hide three devices from
    # mesh construction (parallel/mesh.py's drill mask).
    os.environ["TFD_DEVICE_MASK"] = str(len(jax.devices()) - 1)
    try:
        _, result1, losses1 = run(1, "mesh.data=1")
    finally:
        del os.environ["TFD_DEVICE_MASK"]
    homes1 = {s.device for leaf in jax.tree_util.tree_leaves(
        result1.state.params) for s in leaf.addressable_shards}
    check(len(homes1) == 1, f"one-device run used {homes1}")
    diffs = [rel_diff(a, b) for a, b in zip(losses4, losses1)]
    say("dp", "relative loss differences 4-device vs 1-device: "
        + " ".join(f"{d:.2e}" for d in diffs))
    check(all(d <= LOSS_RTOL for d in diffs),
          f"per-step losses disagree beyond rtol {LOSS_RTOL}: "
          f"{losses4} vs {losses1}")


# ------------------------------------------------------------------- main

def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4 runs ONLY the data-parallel phase and its "
                   "one-device comparison (default 1: device, train, "
                   "serve)")
    p.add_argument("--seed", type=int, default=0,
                   help="weights, data and prompts all derive from it")
    p.add_argument("--model-size", choices=("small", "tiny"),
                   default="small",
                   help="'small' is GPT-2 small, the real run; 'tiny' "
                   "rehearses the control flow, TPU or not: it never "
                   "prints a result and exits %d" % EXIT_REHEARSED)
    args = p.parse_args(argv)
    sizes = REAL if args.model_size == "small" else TINY

    t_start = time.perf_counter()
    dev = device_phase(args)
    on_tpu = dev["platform"] == "tpu"
    if args.chips == 1 and dev["count"] > 1:
        # One chip's worth of work on a bigger host: hide the rest
        # from mesh construction instead of going data-parallel.
        os.environ["TFD_DEVICE_MASK"] = str(dev["count"] - 1)
    out = os.path.join(HERE, "chiprun_out", "chip_smoke")
    os.makedirs(out, exist_ok=True)
    try:
        if args.chips == 4:
            four_chip_phase(args, sizes, out, on_tpu)
        else:
            ckpt_dir = train_phase(args, sizes, out, on_tpu)
            serve_phase(args, sizes, out, ckpt_dir)
    finally:
        # chiprun_out/ brings back 64 MiB; the checkpoint is 1.5 GB.
        shutil.rmtree(os.path.join(out, "ckpt"), ignore_errors=True)
    say("done", f"all phases passed in "
        f"{time.perf_counter() - t_start:.1f}s")
    if args.model_size != "small":
        print(f"chip_smoke: rehearsal finished on {dev['platform']} — "
              f"not a chip result", flush=True)
        return EXIT_REHEARSED
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
