"""MoE training benchmark: tokens/s, active-param MFU, dispatch cost.

EP is claimed first-class (PARITY.md parallelism checklist) — this
records what the GShard dense-dispatch formulation (models/moe.py)
actually delivers on chip, and documents its scale envelope. Reports

- tokens/sec through the full jitted moe_lm train step,
- MFU charged on ACTIVE FLOPs only (dense params + K/E of the expert
  params per token — the standard MoE accounting; the dropped-token
  fraction means real work can be slightly lower),
- the dispatch/combine einsum overhead as extra TFLOPs (2*S*E*C*M per
  group per tensor — work the dense formulation does that a ragged one
  would not),
- compiled memory: temp + argument bytes from XLA's memory analysis,
  alongside the closed-form dispatch-tensor bytes,
- the envelope: dispatch+combine bytes grow O(S^2 * E * c / E) = O(S^2)
  at fixed capacity factor (C = ceil(c*K*S/E)), printed for a seq
  sweep so the cliff is visible without running it.

The envelope conclusion lives in models/moe.py's docstring; this
benchmark is its measured backing (MOEBENCH.json).

Timing ends on a host readback of the final loss, like lm_perf.py.
"""

from __future__ import annotations

import argparse
import json
import math

from tensorflow_distributed_tpu.benchmarks.lm_perf import _timed_steps
from tensorflow_distributed_tpu.observe.mfu import (
    PEAK_BF16_FLOPS, flops_per_token)


def moe_active_flops_per_token(params, cfg) -> float:
    """fwd+bwd FLOPs per token with expert matmuls charged at K/E
    (each token visits top_k of num_experts experts). Thin alias over
    observe.mfu.flops_per_token, which owns the MoE active-FLOPs
    accounting (cfg carries moe_experts/moe_top_k)."""
    return flops_per_token(params, cfg)


def dispatch_bytes(seq: int, experts: int, top_k: int,
                   capacity_factor: float) -> int:
    """Closed-form f32 bytes for ONE group's dispatch + combine
    [S, E, C] tensors (models/moe.py builds both)."""
    cap = max(1, math.ceil(capacity_factor * top_k * seq / experts))
    return 2 * 4 * seq * experts * cap


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--batch", type=int, default=8)
    parser.add_argument("--seq-len", type=int, default=1024)
    parser.add_argument("--experts", type=int, default=8)
    parser.add_argument("--top-k", type=int, default=2)
    parser.add_argument("--d-model", type=int, default=768)
    parser.add_argument("--n-layers", type=int, default=12)
    parser.add_argument("--moe-group-len", type=int, default=0,
                        dest="group_len",
                        help="MoE routing-group length (0 = whole "
                        "sequence); the dispatch-envelope knob — same "
                        "name as the train CLI's flag")
    parser.add_argument("--moe-dispatch", default="dense",
                        choices=["dense", "scatter"], dest="dispatch",
                        help="token-movement formulation (models/"
                        "moe.py); scatter skips the one-hot einsums")
    parser.add_argument("--remat", default="none",
                        choices=["none", "full", "dots"])
    parser.add_argument("--steps", type=int, default=20)
    parser.add_argument("--out", default="")
    args = parser.parse_args(argv)

    import jax
    import numpy as np
    import optax

    from tensorflow_distributed_tpu.config import MeshConfig
    from tensorflow_distributed_tpu.data.lm import synthetic_clm
    from tensorflow_distributed_tpu.models.transformer import moe_lm
    from tensorflow_distributed_tpu.parallel.mesh import make_mesh
    from tensorflow_distributed_tpu.parallel.sharding import shard_batch
    from tensorflow_distributed_tpu.train.state import (
        create_train_state, param_count)
    from tensorflow_distributed_tpu.train.step import make_train_step
    from tensorflow_distributed_tpu.train.tasks import (
        make_moe_loss, mlm_batch_shardings)
    from tensorflow_distributed_tpu.utils.compilecache import (
        enable_persistent_cache)

    enable_persistent_cache()
    n_dev = len(jax.devices())
    mesh = make_mesh(MeshConfig(data=n_dev))
    kind = jax.devices()[0].device_kind
    peak = PEAK_BF16_FLOPS.get(kind)

    model = moe_lm(mesh, size="small", moe_experts=args.experts,
                   moe_top_k=args.top_k, d_model=args.d_model,
                   n_layers=args.n_layers, max_len=args.seq_len,
                   moe_group_len=args.group_len,
                   moe_dispatch=args.dispatch, dropout_rate=0.0,
                   **({"remat": True, "remat_policy": args.remat}
                      if args.remat != "none" else {}))
    state = create_train_state(
        model, optax.adam(3e-4), np.zeros((2, args.seq_len), np.int32),
        mesh)
    step = make_train_step(mesh, loss=make_moe_loss(0.01, 0.0),
                           batch_shardings=mlm_batch_shardings(mesh))
    ds = synthetic_clm(n=args.batch, seq_len=args.seq_len,
                       vocab_size=model.cfg.vocab_size)
    batch = shard_batch(mesh, ds.batch(np.arange(args.batch)), seq_axis=1)

    mem = {}
    try:
        ana = step.lower(state, batch).compile().memory_analysis()
        mem = {"temp_bytes": int(ana.temp_size_in_bytes),
               "argument_bytes": int(ana.argument_size_in_bytes)}
    except Exception as e:  # not every backend exposes it
        mem = {"memory_analysis_unavailable": str(e)}

    dt, state, first, last = _timed_steps(step, state, batch, args.steps)
    assert np.isfinite(last), f"non-finite loss {last}"
    assert last < first, f"loss did not decrease: {first} -> {last}"

    tokens = args.steps * args.batch * args.seq_len
    tok_s = tokens / dt
    fpt = moe_active_flops_per_token(state.params, model.cfg)
    tflops = tok_s * fpt / 1e12
    mfu = tflops * 1e12 / (peak * n_dev) if peak else None

    # Dispatch/combine einsum work per token, fwd (+2x for bwd), PER
    # LAYER x n_layers (every block's MLP is a MoE): each einsum costs
    # 2*E*C*M MACs per token-position. Capacity follows the ROUTING
    # GROUP length (= --group-len when set) — which is why group_len
    # is also a FLOPs knob, not just a memory knob: C (hence dispatch
    # work) scales with the group.
    # min(): MoeMlp routes the whole sequence as ONE group when
    # group_len >= seq, so capacity follows the smaller of the two.
    grp = min(args.group_len or args.seq_len, args.seq_len)
    cf = model.cfg.moe_capacity_factor
    cap = max(1, math.ceil(cf * args.top_k * grp / args.experts))
    disp_fpt = (3.0 * 2.0 * (2.0 * args.experts * cap * args.d_model)
                * args.n_layers)
    disp_tflops = tok_s * disp_fpt / 1e12

    cfg = model.cfg
    meta = {"model": "moe_lm", "params": param_count(state.params),
            "experts": args.experts, "top_k": args.top_k,
            "capacity": cap, "group_len": args.group_len,
            "dispatch": args.dispatch,
            "remat": args.remat, "batch": args.batch,
            "seq_len": args.seq_len, "d_model": args.d_model,
            "n_layers": args.n_layers, "device": kind, "devices": n_dev}
    lines = [
        {"metric": "moe_train_tokens_per_sec", "value": round(tok_s, 1),
         "unit": "tokens/sec", **meta},
        {"metric": "moe_train_active_tflops",
         "value": round(tflops, 2), "unit": "TFLOP/s", **meta},
        {"metric": "moe_train_active_mfu",
         "value": round(100 * mfu, 2) if mfu is not None else None,
         "unit": "%", **meta},
        {"metric": "moe_dispatch_overhead_tflops",
         "value": round(disp_tflops, 2), "unit": "TFLOP/s", **meta},
        {"metric": "moe_step_memory", "value": mem, "unit": "bytes",
         **meta},
        {"metric": "moe_dispatch_bytes_per_group_envelope",
         "value": {str(s): dispatch_bytes(s, args.experts, args.top_k,
                                          cfg.moe_capacity_factor)
                   for s in (1024, 4096, 8192, 16384, 32768)},
         "unit": "f32 bytes (dispatch+combine, one group)", **meta},
    ]
    out = "\n".join(json.dumps(l) for l in lines)
    print(out)
    if args.out:
        from tensorflow_distributed_tpu.observe.registry import (
            write_jsonl)
        write_jsonl(args.out, lines)  # git-sha/calibration stamped


if __name__ == "__main__":
    main()
