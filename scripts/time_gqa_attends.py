#!/usr/bin/env python3
"""Time the two attends models/exaone_moe.py brought, ALONE on the chip, at
K-EXAONE's shapes (64 query heads over 8 key-value heads of 128, bf16): the
median DEVICE time of ten calls from a profiler capture.

    chiprun --chips 1 -- python3 scripts/time_gqa_attends.py \
        [band:<windows a block>,...] [gqa:<positions a block>,...]

``band``: ``ops/latent_attention.py::prefill_attend`` under the window of
128 at the 3,072 and 12,288 buckets, a block of 1, 2, 4 or 8 windows (8 is
the causal plan's 1,024), beside the causal call of the same bucket.
``gqa``: ``ops/hybrid_attention.py::gqa_decode_attend`` over 32 slots of
16,384 positions, 25 of them live at depths 128 to 14,000, in blocks of 256,
512 or 1,024 positions, beside the slot-blind ``dense_decode_attend`` it
replaces and the roofline of the live rows' positions (4,096 B a position
at 819 GB/s). Prints one JSON line a form and writes them all to
``chiprun_out/time_gqa_attends.json``.
"""

import json
import os
import statistics
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "perfbench"))

PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9
H, G, D, WINDOW, SLOTS, T = 64, 8, 128, 128, 32, 16384
CALLS = 10


def device_ms(jitted, name, args):
    import jax

    from harness import trace as tr
    jax.block_until_ready(jitted(*args))
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        for _ in range(CALLS):
            jax.block_until_ready(jitted(*args))
        jax.profiler.stop_trace()
        secs = tr.module_calls(tr.load_xplane(tr.find_xplane(tmp)),
                               lambda n: n.startswith("jit_" + name))
    return 1e3 * statistics.median(secs), len(secs)


def main(argv) -> int:
    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.ops import flash_attention as flash
    from tensorflow_distributed_tpu.ops import hybrid_attention as hyb
    from tensorflow_distributed_tpu.ops import latent_attention as lat

    if jax.default_backend() != "tpu":
        print("no TPU: a device time comes only from the chip",
              file=sys.stderr)
        return 4
    asked = dict(a.split(":") for a in argv[1:]) or {
        "band": "1,2,4,8", "gqa": "256,512,1024"}
    rows = []

    def say(row):
        rows.append(row)
        print(json.dumps(row), flush=True)

    scale = D ** -0.5
    for L in (3072, 12288) if "band" in asked else ():
        q, k, v = (jax.random.normal(kk, (H, L, D), jnp.bfloat16)
                   for kk in jax.random.split(jax.random.PRNGKey(L), 3))
        want = None
        for n in [0] + [int(x) for x in asked["band"].split(",")]:
            window = WINDOW if n else 0
            # n windows a block (8 is the plan's own 1,024); 0: the causal
            # call, under the plan's blocks
            plan = flash.flash_plan(
                L, L, D, jnp.bfloat16, causal=True, window=window, Dv=D,
                **({"block_q": n * WINDOW, "block_k": n * WINDOW}
                   if n else {}))

            def call(q, k, v, window=window, plan=plan):
                return flash._fwd(q, k, v, causal=True, plan=plan,
                                  interpret=False, window=window,
                                  scale=scale, stats=False,
                                  name="mla_prefill_attend")[0]

            call.__name__ = f"band_{L}_{n}"
            try:
                ms, calls = device_ms(jax.jit(call), call.__name__,
                                      (q, k, v))
            except Exception as e:      # blocks the compiler refuses
                say({"form": "band", "L": L, "windows": n,
                     "refused": str(e)[:300]})
                continue
            keys = plan.tiles_computed * plan.tile_q * plan.tile_k / L
            row = {"form": "band" if n else "causal", "L": L,
                   "block": plan.block_q, "device_ms_median": ms,
                   "calls": calls, "tiles_computed": plan.tiles_computed,
                   "keys_per_query": keys,
                   "ms_at_peak": 1e3 * 4 * H * D * L * keys / PEAK_FLOPS}
            if n:
                got = jax.jit(call)(q, k, v).astype(jnp.float32)
                if want is None:
                    want = lat.prefill_attend_xla(
                        q, k, v, None, scale, WINDOW).astype(jnp.float32)
                row["minus_xla_max"] = float(jnp.max(jnp.abs(got - want)))
            say(row)
    if "gqa" in asked:
        keys = jax.random.split(jax.random.PRNGKey(7), 3)
        q = jax.random.normal(keys[0], (SLOTS, G, H // G, D), jnp.bfloat16)
        kv = jax.random.normal(keys[1], (SLOTS, T, 2 * G * D), jnp.bfloat16)
        depth = jax.random.randint(keys[2], (SLOTS,), 128, 14000)
        pos = jnp.where(jnp.arange(SLOTS) % 32 < 25, depth, 0)  # 25 live
        live = int(jnp.sum(jnp.where(pos > 0, pos + 1, 0)))
        floor_ms = 1e3 * live * 2 * G * D * 2 / PEAK_BYTES

        def blind(q, kv, pos):
            return hyb.dense_decode_attend(q, kv, pos, T, scale)

        ms, calls = device_ms(jax.jit(blind), "blind", (q, kv, pos))
        want = jax.jit(blind)(q, kv, pos)
        say({"form": "dense_decode_attend", "device_ms_median": ms,
             "calls": calls, "live_positions": live, "floor_ms": floor_ms})
        for bt in (int(x) for x in asked["gqa"].split(",")):
            hyb.GQA_BLOCK_T = bt

            def call(q, kv, pos):
                return hyb.gqa_decode_attend(q, kv, pos, scale)

            call.__name__ = f"gqa_{bt}"
            try:
                ms, calls = device_ms(jax.jit(call), call.__name__,
                                      (q, kv, pos))
            except Exception as e:
                say({"form": "gqa_dense_attend", "block": bt,
                     "refused": str(e)[:300]})
                continue
            got = jax.jit(call)(q, kv, pos)
            gap = jnp.max(jnp.abs(jnp.where((pos > 0)[:, None, None, None],
                                            got - want, 0.0)))
            say({"form": "gqa_dense_attend", "block": bt,
                 "device_ms_median": ms, "calls": calls,
                 "live_positions": live,
                 "visited": int(hyb.gqa_attend_visits(pos, T)),
                 "floor_ms": floor_ms, "roofline_share": floor_ms / ms,
                 "minus_dense_decode_attend_max": float(gap)})
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "time_gqa_attends.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
