"""Autoregressive generation benchmark: prefill and decode throughput.

The training side's perf story lives in lm_perf.py (MFU) and bench.py
(data path); this covers the INFERENCE path the reference never had:
KV-cache generation (models/generate.py) as one jitted prefill+decode
program. Reports

- prefill tokens/sec (the batched, MXU-bound phase),
- decode tokens/sec and ms/token (the bandwidth-bound phase — each
  step reads every param and the KV cache once per token), and
- the same decode with grouped KV heads (--n-kv-heads), measuring
  what the narrower cache buys.

Prints one JSON line per metric; --out also writes them to a file
(overwritten per run, like the sibling benchmarks).

Timing ends on a host readback of the final tokens: the caller wants
the tokens on the host anyway, and the readback waits for the device
exactly as block_until_ready does.
"""

from __future__ import annotations

import argparse
import json
import time


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--size", default="small",
                        help="gpt_lm size preset (small | tiny)")
    parser.add_argument("--batch", type=int, default=8)
    parser.add_argument("--prompt-len", type=int, default=512)
    parser.add_argument("--new-tokens", type=int, default=256)
    parser.add_argument("--n-kv-heads", type=int, default=0,
                        help="also A/B decode with this many KV heads "
                        "(0 = skip the A/B)")
    parser.add_argument("--kv-cache-quant", default="none",
                        choices=["none", "int8"],
                        help="also A/B decode with this cache "
                        "storage (int8 halves the dominant decode "
                        "HBM read vs bf16)")
    parser.add_argument("--iters", type=int, default=5)
    parser.add_argument("--out", default="")
    args = parser.parse_args(argv)
    if args.new_tokens < 2:
        parser.error("--new-tokens must be >= 2 (decode is timed as "
                     "total minus the 1-token prefill run)")
    if args.iters < 1:
        parser.error("--iters must be >= 1")

    import jax
    import numpy as np

    from tensorflow_distributed_tpu.models.generate import generate
    from tensorflow_distributed_tpu.models.transformer import gpt_lm
    from tensorflow_distributed_tpu.parallel.mesh import single_device_mesh
    from tensorflow_distributed_tpu.train.state import (
        create_train_state, param_count)
    from tensorflow_distributed_tpu.utils.compilecache import (
        enable_persistent_cache)

    enable_persistent_cache()
    import optax

    dev = jax.devices()[0]
    mesh = single_device_mesh(dev)
    max_len = args.prompt_len + args.new_tokens
    rng = np.random.default_rng(0)

    def bench(label, **model_kw):
        model = gpt_lm(mesh, size=args.size, max_len=max_len,
                       dropout_rate=0.0, **model_kw)
        # Inference-only: optax.identity keeps the sharded-init path
        # without allocating Adam's 2x-param slot memory.
        state = create_train_state(
            model, optax.identity(),
            np.zeros((2, 16), np.int32), mesh, seed=0)
        params = state.params
        prompt = np.asarray(
            rng.integers(0, model.cfg.vocab_size,
                         size=(args.batch, args.prompt_len)), np.int32)

        def timed(n_tokens):
            """Warm-up compile, then the averaged timed loop with a
            host readback barrier — one methodology for both phases."""
            toks = generate(model, params, prompt, n_tokens)
            _ = np.asarray(toks)
            t0 = time.perf_counter()
            for _ in range(args.iters):
                toks = generate(model, params, prompt, n_tokens)
            _ = np.asarray(toks)
            return (time.perf_counter() - t0) / args.iters

        wall = timed(args.new_tokens)
        # Split phases: a 1-token run is (prefill + one pick).
        prefill = timed(1)

        decode = max(wall - prefill, 1e-9)
        n_decode = args.batch * (args.new_tokens - 1)
        lines = [
            {"metric": f"gen_prefill_tokens_per_sec{label}",
             "value": round(args.batch * args.prompt_len / prefill, 1),
             "unit": "tokens/sec"},
            {"metric": f"gen_decode_tokens_per_sec{label}",
             "value": round(n_decode / decode, 1), "unit": "tokens/sec"},
            {"metric": f"gen_decode_ms_per_token{label}",
             "value": round(1e3 * decode / (args.new_tokens - 1), 3),
             "unit": "ms/token"},
        ]
        common = {
            "model": f"gpt_lm/{args.size}",
            "params": param_count(params),
            "batch": args.batch, "prompt_len": args.prompt_len,
            "new_tokens": args.new_tokens,
            "device": dev.device_kind, "n_kv_heads": model_kw.get(
                "n_kv_heads", model.cfg.n_heads),
            "kv_cache_quant": model_kw.get("kv_cache_quant", "none"),
        }
        return [dict(ln, **common) for ln in lines]

    lines = bench("")
    if args.n_kv_heads:
        lines += bench("_gqa", n_kv_heads=args.n_kv_heads)
    if args.kv_cache_quant != "none":
        lines += bench("_kvq", kv_cache_quant=args.kv_cache_quant)
        if args.n_kv_heads:
            # The composed story: narrow (GQA) AND thin (int8) cache.
            lines += bench("_gqa_kvq", n_kv_heads=args.n_kv_heads,
                           kv_cache_quant=args.kv_cache_quant)

    print("\n".join(json.dumps(ln) for ln in lines))
    if args.out:
        # Overwrite like the sibling benchmarks: reruns replace, never
        # silently accumulate stale lines (observe.registry owns the
        # JSONL format).
        from tensorflow_distributed_tpu.observe.registry import write_jsonl
        write_jsonl(args.out, lines)


if __name__ == "__main__":
    main()
