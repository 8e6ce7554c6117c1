"""Transformer-LM training benchmark: tokens/s, TFLOP/s, and MFU.

The reference's entire perf surface is its hand-recorded 6-line
``performance`` table for the MNIST CNN (/root/reference/performance:1-6,
SURVEY.md §6) — a host-bound workload that says nothing about the MXU.
This benchmark is its TPU-native successor for the sequence family this
framework showcases: train the GPT-family causal LM at a real size
(GPT-2-small: 12L x 768d x 12H, models/transformer.py gpt_lm) and report

- tokens/sec through the full jitted train step (fwd + bwd + Adam),
- achieved model TFLOP/s and MFU against the chip's bf16 peak,
- a flash-vs-XLA attention A/B on the SAME training step (the only
  change is TransformerConfig.use_flash), turning the kernel's claimed
  speedup into a measured number.

FLOP accounting (the PaLM/MFU convention, matmuls only):
  per token fwd = 2 * N_matmul  (every matmul param is one MAC/token)
  attention     = 4 * L * d_model per layer fwd (QK^T and PV), halved
                  for causal because the kernel skips masked blocks
  fwd + bwd     = 3x forward
MFU counts the causal-SKIPPED FLOPs — the useful work, not the work a
lazier kernel would have done.

The batch lives on device and is reused every step: this measures the
model/step path (the MXU story); the host->device data path is
bench.py's story. A loss-decrease assertion guards against benchmarking
a degenerate graph.

Timing ends on a host readback of the final step's loss, which depends
on every step before it — a barrier as good as block_until_ready, and
the value the loss-decrease assertion needs anyway.
"""

from __future__ import annotations

import argparse
import json
import time

# FLOP accounting and chip peaks live in observe.mfu (the unified
# observability subsystem) — re-exported here so the historical
# benchmark import surface keeps working.
from tensorflow_distributed_tpu.observe.mfu import (  # noqa: F401
    PEAK_BF16_FLOPS, attn_flops_per_token_fwd, flops_per_token,
    matmul_params, pipelined_hw_flops_per_token)


def _build(size: str, seq_len: int, use_flash: bool, remat: str,
           batch: int, mesh, seed: int = 0, pipeline_mb: int = 0,
           pipeline_backward: str = "recompute", attn_window: int = 0,
           ce_chunk: int = 0, ce_impl: str = "scan"):
    import jax
    import numpy as np
    import optax

    from tensorflow_distributed_tpu.data.lm import synthetic_clm
    from tensorflow_distributed_tpu.models.transformer import gpt_lm
    from tensorflow_distributed_tpu.parallel.sharding import shard_batch
    from tensorflow_distributed_tpu.train.state import create_train_state
    from tensorflow_distributed_tpu.train.step import make_train_step
    from tensorflow_distributed_tpu.train.tasks import (
        make_mlm_loss, mlm_batch_shardings, mlm_loss)

    kw = dict(max_len=seq_len, dropout_rate=0.0, use_flash=use_flash)
    if attn_window:
        kw["attn_window"] = attn_window
    if remat != "none":
        kw.update(remat=True, remat_policy=remat)
    if pipeline_mb > 0:
        # The flagship through the pipeline: pipelined_lm + the
        # hand-scheduled 1F1B step, flash kernel inside the pipe
        # shard_map (models/pipelined.py).
        from tensorflow_distributed_tpu.models.pipelined import (
            pipelined_lm)
        from tensorflow_distributed_tpu.train.pipeline_step import (
            make_1f1b_train_step)
        model = pipelined_lm(mesh, size=size,
                             num_microbatches=pipeline_mb, **kw)
    else:
        model = gpt_lm(mesh, size=size, **kw)
    state = create_train_state(
        model, optax.adam(3e-4), np.zeros((2, seq_len), np.int32), mesh,
        seed)
    if pipeline_mb > 0:
        step = make_1f1b_train_step(
            model, mesh, seed, batch_shardings=mlm_batch_shardings(mesh),
            backward=pipeline_backward, ce_chunk=ce_chunk)
    else:
        loss = (make_mlm_loss(ce_chunk=ce_chunk, ce_impl=ce_impl,
                              mesh=mesh) if ce_chunk else mlm_loss)
        step = make_train_step(mesh, seed, loss=loss,
                               batch_shardings=mlm_batch_shardings(mesh))
    ds = synthetic_clm(n=batch, seq_len=seq_len,
                       vocab_size=model.cfg.vocab_size, seed=seed)
    hb = ds.batch(np.arange(batch))
    dev_batch = shard_batch(mesh, hb, seq_axis=1)
    return model, state, step, dev_batch


def _timed_steps(step, state, batch, steps: int):
    """Steady-state steps/sec with async dispatch and an honest final
    readback barrier. Returns (dt_seconds, final_state, first, last)."""
    import jax

    state, metrics = step(state, batch)  # compile + step 1
    first_loss = float(jax.device_get(metrics["loss"]))
    for _ in range(2):                   # warm
        state, metrics = step(state, batch)
    float(jax.device_get(metrics["loss"]))

    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, batch)
    last_loss = float(jax.device_get(metrics["loss"]))
    dt = time.perf_counter() - t0
    return dt, state, first_loss, last_loss


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--size", default="small",
                        choices=["small", "medium", "large", "xl",
                                 "tiny"])
    parser.add_argument("--batch", type=int, default=8)
    parser.add_argument("--seq-len", type=int, default=1024)
    parser.add_argument("--steps", type=int, default=20)
    parser.add_argument("--remat", default="none",
                        choices=["none", "full", "dots"])
    parser.add_argument("--attn-window", type=int, default=0,
                        help="sliding-window attention width (0 = "
                        "full causal); the flash kernel skips "
                        "blocks outside the band, so tokens/s "
                        "should GROW as the window shrinks")
    parser.add_argument("--ce-chunk", type=int, default=0,
                        help="> 0: fused vocab-chunked head+loss (ops/"
                        "fused_ce.py) with this chunk width — the full "
                        "[B, L, V] logits are never materialized; "
                        "0 = dense path")
    parser.add_argument("--ce-impl", default="scan",
                        choices=["scan", "kernel"],
                        help="fused-loss formulation (with --ce-chunk): "
                        "lax.scan chunks or the Pallas flash-CE "
                        "kernels (ops/fused_ce_kernel.py)")
    parser.add_argument("--skip-ab", action="store_true",
                        help="skip the flash-vs-XLA attention A/B")
    parser.add_argument("--pipeline-backward", default="recompute",
                        choices=["recompute", "stash"],
                        help="1F1B backward strategy (see parallel."
                        "pipeline.pipeline_value_and_grad)")
    parser.add_argument("--pipeline-microbatches", type=int, default=0,
                        help="> 0: run the pipelined flagship instead "
                        "(1F1B schedule, flash inside the pipe "
                        "shard_map) with this many microbatches; the "
                        "mesh becomes (data=1, pipe=n_devices). The "
                        "flash-vs-XLA A/B is skipped in this mode")
    parser.add_argument("--out", default="",
                        help="also write the JSON lines to this file")
    args = parser.parse_args(argv)
    if args.pipeline_backward != "recompute" and not args.pipeline_microbatches:
        # Same convention as TrainConfig.validate: reject knobs that
        # would be silently ignored (the backward strategy only exists
        # in the pipelined 1F1B step).
        parser.error("--pipeline-backward requires "
                     "--pipeline-microbatches > 0")

    import jax
    import numpy as np

    from tensorflow_distributed_tpu.config import MeshConfig
    from tensorflow_distributed_tpu.parallel.mesh import make_mesh
    from tensorflow_distributed_tpu.train.state import param_count
    from tensorflow_distributed_tpu.utils.compilecache import (
        enable_persistent_cache)

    enable_persistent_cache()
    n_dev = len(jax.devices())
    pmb = args.pipeline_microbatches
    mesh = make_mesh(MeshConfig(data=1, pipe=n_dev) if pmb > 0
                     else MeshConfig(data=n_dev))
    kind = jax.devices()[0].device_kind
    peak = PEAK_BF16_FLOPS.get(kind)

    if args.ce_impl == "kernel" and pmb > 0:
        parser.error("--ce-impl kernel is not available in pipeline "
                     "mode (config.TrainConfig.validate has the why); "
                     "--ce-chunk with the default scan impl composes")
    if args.ce_impl != "scan" and not args.ce_chunk:
        # Same rule as TrainConfig.validate: refuse knobs that would
        # be silently ignored (and mislabel the benchmark record).
        parser.error("--ce-impl requires --ce-chunk > 0 (the fused "
                     "head+loss master switch)")
    model, state, step, batch = _build(
        args.size, args.seq_len, True, args.remat, args.batch, mesh,
        pipeline_mb=pmb, pipeline_backward=args.pipeline_backward,
        attn_window=args.attn_window, ce_chunk=args.ce_chunk,
        ce_impl=args.ce_impl)
    n_params = param_count(state.params)
    fpt = flops_per_token(state.params, model.cfg)

    dt, state, first, last = _timed_steps(step, state, batch, args.steps)
    assert np.isfinite(last), f"non-finite loss {last}"
    assert last < first, f"loss did not decrease: {first} -> {last}"

    tokens = args.steps * args.batch * args.seq_len
    tok_s = tokens / dt
    tflops = tok_s * fpt / 1e12
    mfu = tflops * 1e12 / (peak * n_dev) if peak else None

    family = ("pipelined_lm/1f1b" if pmb > 0 else "gpt_lm")
    meta = {"model": f"{family}/{args.size}", "params": n_params,
            "batch": args.batch, "seq_len": args.seq_len,
            "device": kind, "devices": n_dev, "remat": args.remat}
    if args.attn_window:
        meta["attn_window"] = args.attn_window
    if args.ce_chunk:
        meta["ce_chunk"] = args.ce_chunk
        meta["ce_impl"] = args.ce_impl
    if pmb > 0:
        meta["pipeline_microbatches"] = pmb
        meta["pipeline_backward"] = args.pipeline_backward
    lines = [
        {"metric": "lm_train_tokens_per_sec", "value": round(tok_s, 1),
         "unit": "tokens/sec", **meta},
        {"metric": "lm_train_model_tflops", "value": round(tflops, 2),
         "unit": "TFLOP/s", **meta},
        {"metric": "lm_train_mfu",
         "value": round(100 * mfu, 2) if mfu is not None else None,
         "unit": "%", **meta},
    ]
    if pmb > 0 and args.pipeline_backward == "recompute" and peak:
        # Model MFU charges 3x-forward per token, but 1F1B-recompute
        # EXECUTES 4x-forward for the block stack (each backward tick
        # re-runs the stage forward from the stashed input). Report the
        # hardware utilization too so the schedule's remat trade isn't
        # misread as MXU inefficiency; model MFU stays the headline
        # (useful work per second).
        hw_fpt = pipelined_hw_flops_per_token(state.params, model.cfg)
        hw_mfu = tok_s * hw_fpt / (peak * n_dev)
        lines.append({"metric": "lm_train_hw_mfu",
                      "value": round(100 * hw_mfu, 2), "unit": "%",
                      **meta})

    if not args.skip_ab and pmb > 0:
        import sys
        print("[lm_perf] flash-vs-XLA A/B skipped in pipeline mode "
              "(run without --pipeline-microbatches for it)",
              file=sys.stderr)
    if not args.skip_ab and pmb == 0:
        # STEP-LEVEL A/B, not a kernel microbenchmark: use_flash=False
        # re-jits the whole step (attention falls to the XLA path,
        # parallel.ring_attention.full_attention), so remat/fusion
        # differences elsewhere ride into the ratio too — the metric
        # name says "step_speedup" deliberately. Drop the flash run's
        # state/executable first — two resident GPT-2 train states
        # don't fit 16G HBM at batch 16.
        del state, step, batch
        _, state_x, step_x, batch_x = _build(
            args.size, args.seq_len, False, args.remat, args.batch, mesh,
            attn_window=args.attn_window, ce_chunk=args.ce_chunk,
            ce_impl=args.ce_impl)
        dt_x, _, _, last_x = _timed_steps(step_x, state_x, batch_x,
                                          args.steps)
        assert np.isfinite(last_x)
        lines.append({
            "metric": "flash_vs_xla_attention_step_speedup",
            "value": round(dt_x / dt, 3), "unit": "x",
            "xla_tokens_per_sec": round(tokens / dt_x, 1), **meta})

    print("\n".join(json.dumps(l) for l in lines))
    if args.out:
        from tensorflow_distributed_tpu.observe.registry import write_jsonl
        write_jsonl(args.out, lines)


if __name__ == "__main__":
    main()
