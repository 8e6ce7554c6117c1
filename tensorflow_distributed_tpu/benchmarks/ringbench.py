"""Ring-attention local-compute A/B: Pallas partial kernel vs einsum.

The zigzag causal ring's per-step work is half-block partial attends
(parallel.ring_attention._partial_attend). This measures that building
block on the chip at the shapes an L=8192, S=8 ring actually runs
(local block 1024 -> half-blocks nh=512), einsum oracle vs the Pallas
partial-softmax kernel (ops.flash_attention.flash_attention_partial),
forward and forward+backward-through-merge. A single chip cannot run
an S>1 ring (no second device for the ppermutes), so this is the
honest single-chip form of the ring speedup: the collective schedule
is pinned by the CPU-mesh parity tests; the arithmetic is measured
here. Prints one JSON line per metric.
"""

from __future__ import annotations

import argparse
import json
import time


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--batch", type=int, default=4)
    parser.add_argument("--heads", type=int, default=8)
    parser.add_argument("--head-dim", type=int, default=64)
    parser.add_argument("--half-block", type=int, default=512,
                        help="nh = L / (2S); 512 = L 8192 over S 8")
    parser.add_argument("--ring-size", type=int, default=8,
                        help="S: ring steps simulated per timed call")
    parser.add_argument("--iters", type=int, default=50)
    parser.add_argument("--out", default="")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorflow_distributed_tpu.ops.flash_attention import (
        flash_attention_partial)
    from tensorflow_distributed_tpu.parallel.ring_attention import (
        _block_attend, _merge, causal_bias)
    from tensorflow_distributed_tpu.utils.compilecache import (
        enable_persistent_cache)

    enable_persistent_cache()
    B, H, D, nh = args.batch, args.heads, args.head_dim, args.half_block
    S = args.ring_size
    rng = np.random.default_rng(0)
    mk = lambda *shape: jnp.asarray(  # noqa: E731
        rng.normal(size=shape), jnp.bfloat16) * 0.5
    q, q2 = mk(B, nh, H, D), mk(B, nh, H, D)
    # DISTINCT K,V per simulated ring step — the rotated blocks a real
    # ring receives; identical operands would let XLA CSE the repeated
    # attends down to one.
    ks, vs = mk(S, B, nh, H, D), mk(S, B, nh, H, D)
    ks2, vs2 = mk(S, B, nh, H, D), mk(S, B, nh, H, D)
    tri = causal_bias(nh, nh)

    def einsum_partial(q, k, v, causal):
        return _block_attend(q, k, v, tri if causal else None)

    def flash_partial(q, k, v, causal):
        return flash_attention_partial(q, k, v, causal=causal)

    def ring_step(attend):
        # The FULL per-device zigzag arithmetic for an S-way ring:
        # step 0 does the two triangular diagonals + one full attend,
        # every later step two full attends — 2S + 1 half-attends and
        # the accumulator merges (parallel.ring_attention
        # _zigzag_causal_shard), minus only the ppermutes a single
        # chip cannot run. The S-1 later steps ride a lax.scan with
        # DISTINCT K,V per step (the ring's rotated blocks): no CSE,
        # one compiled kernel instance.
        def f(q, q2, ks, vs, ks2, vs2):
            acc1 = attend(q, ks[0], vs[0], True)
            acc2 = _merge(*attend(q2, ks2[0], vs2[0], True),
                          *attend(q2, ks[0], vs[0], False))

            def tick(carry, xs):
                a1, a2 = carry
                k1, v1, k2, v2 = xs
                a2 = _merge(*a2, *attend(q2, k1, v1, False))
                a1 = _merge(*a1, *attend(q, k2, v2, False))
                return (a1, a2), None

            (acc1, acc2), _ = jax.lax.scan(
                tick, (acc1, acc2),
                (ks[1:], vs[1:], ks2[1:], vs2[1:]))
            outs = []
            for m, l, o in (acc1, acc2):
                outs.append(o / l.transpose(0, 2, 1)[..., None])
            out = jnp.concatenate(outs, axis=1)
            return jnp.sum(out.astype(jnp.float32) ** 2)
        return f

    import statistics

    def timed(fn, grad: bool):
        # Differentiate wrt ALL inputs — grads wrt only q/q2 would let
        # XLA dead-code-eliminate the whole dk/dv backward (verified:
        # 5 vs 9 dots in optimized HLO) and under-measure fwd_bwd.
        f = jax.jit(jax.grad(fn, argnums=tuple(range(6))) if grad
                    else fn)
        args6 = (q, q2, ks, vs, ks2, vs2)
        r = f(*args6)  # compile
        jax.block_until_ready(r)
        times = []
        for _ in range(args.iters):
            t0 = time.perf_counter()
            r = f(*args6)
            jax.block_until_ready(r)
            times.append(time.perf_counter() - t0)
        return statistics.median(times) * 1e3

    meta = {"batch": B, "heads": H, "head_dim": D, "half_block": nh,
            "ring_size": S, "seq_len": 2 * S * nh,
            "device": jax.devices()[0].device_kind}
    lines = []
    for grad, tag in ((False, "fwd"), (True, "fwd_bwd")):
        t_e = timed(ring_step(einsum_partial), grad)
        t_f = timed(ring_step(flash_partial), grad)
        lines.append({
            "metric": f"ring_block_flash_vs_einsum_{tag}_speedup",
            "value": round(t_e / t_f, 3), "unit": "x",
            "einsum_ms": round(t_e, 3), "flash_ms": round(t_f, 3),
            **meta})

    out = "\n".join(json.dumps(ln) for ln in lines)
    print(out)
    if args.out:
        from tensorflow_distributed_tpu.observe.registry import (
            write_jsonl)
        write_jsonl(args.out, lines)  # git-sha/calibration stamped


if __name__ == "__main__":
    main()
