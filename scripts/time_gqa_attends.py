#!/usr/bin/env python3
"""Time the decode attends ALONE on the chip: the median DEVICE time of ten
calls from a profiler capture.

    chiprun --chips 1 -- python3 scripts/time_gqa_attends.py \
        [band:<windows a block>,...] [gqa:<positions a block>,...] \
        [kv:<positions a block>,...]

``band``: ``ops/latent_attention.py::prefill_attend`` at K-EXAONE's shapes
(64 query heads over 8 key-value heads of 128, bf16) under the window of
128 at the 3,072 and 12,288 buckets, a block of 1, 2, 4 or 8 windows (8 is
the causal plan's 1,024), beside the causal call of the same bucket.
``gqa``: ``ops/hybrid_attention.py::gqa_decode_attend`` at the three shapes
that run it (``GQA_SHAPES``: K-EXAONE's 32 slots of 16,384, granite's 64 of
4,096 with 4 queries a group, Nemotron's 128 of 6,144), in blocks of 256 to
2,048 positions, beside the slot-blind ``dense_decode_attend`` and the
roofline of the live rows' positions at 819 GB/s.
``kv``: ``ops/kv_attend.py::decode_attend`` over GPT-2 large's leaves (16
slots of 1,024, 20 heads of 64, T-minor as the TPU keeps them) with 5 and
with 16 live rows at depths drawn from the steady cell's traffic file, in
blocks of 128 to 1,024 positions, beside XLA's ``full_attention`` over the
whole leaves. Prints one JSON line a form and writes them all to
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
H, G, D, WINDOW = 64, 8, 128, 128
#: name: (slots, positions a slot, query heads, key-value heads, live rows,
#: shallowest and deepest live row)
GQA_SHAPES = {"kexaone": (32, 16384, 64, 8, 25, 128, 14000),
              "granite": (64, 4096, 32, 8, 27, 300, 1800),
              "nemotron": (128, 6144, 32, 2, 40, 400, 2400)}
KV_SHAPE = (16, 1024, 20, 64)       # GPT-2 large: slots, positions, heads
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
        "band": "1,2,4,8", "gqa": "256,512,1024,2048",
        "kv": "128,256,512,1024"}
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
    for name, (S, T, heads, groups, n_live, lo, hi) in (
            GQA_SHAPES.items() if "gqa" in asked else ()):
        keys = jax.random.split(jax.random.PRNGKey(7), 3)
        q = jax.random.normal(keys[0], (S, groups, heads // groups, D),
                              jnp.bfloat16)
        kv = jax.random.normal(keys[1], (S, T, 2 * groups * D), jnp.bfloat16)
        depth = jax.random.randint(keys[2], (S,), lo, hi)
        # live rows among free ones, as a run's slots are
        pos = jnp.where((jnp.arange(S) * n_live) % S < n_live, depth, 0)
        live = int(jnp.sum(jnp.where(pos > 0, pos + 1, 0)))
        floor_ms = 1e3 * live * 2 * groups * D * 2 / PEAK_BYTES

        def blind(q, kv, pos, T=T):
            return hyb.dense_decode_attend(q, kv, pos, T, scale)

        ms, calls = device_ms(jax.jit(blind), "blind", (q, kv, pos))
        want = jax.jit(blind)(q, kv, pos)
        say({"form": "dense_decode_attend", "shape": name,
             "device_ms_median": ms, "calls": calls,
             "live_rows": int(jnp.sum(pos > 0)), "live_positions": live,
             "floor_ms": floor_ms})
        for bt in (int(x) for x in asked["gqa"].split(",")):
            hyb.GQA_BLOCK_T = bt

            def call(q, kv, pos):
                return hyb.gqa_decode_attend(q, kv, pos, scale)

            call.__name__ = f"gqa_{name}_{bt}"
            try:
                ms, calls = device_ms(jax.jit(call), call.__name__,
                                      (q, kv, pos))
            except Exception as e:
                say({"form": "gqa_dense_attend", "shape": name, "block": bt,
                     "refused": str(e)[:300]})
                continue
            got = jax.jit(call)(q, kv, pos)
            gap = jnp.max(jnp.abs(jnp.where((pos > 0)[:, None, None, None],
                                            got - want, 0.0)))
            say({"form": "gqa_dense_attend", "shape": name, "block": bt,
                 "device_ms_median": ms, "calls": calls,
                 "live_positions": live,
                 "visited": int(hyb.gqa_attend_visits(pos, T)),
                 "floor_ms": floor_ms, "roofline_share": floor_ms / ms,
                 "minus_dense_decode_attend_max": float(gap)})
    if "kv" in asked:
        from harness import traffic

        from tensorflow_distributed_tpu.ops import kv_attend
        from tensorflow_distributed_tpu.parallel.ring_attention import (
            full_attention)

        S, T, nk, dh = KV_SHAPE
        with open(os.path.join(ROOT, "perfbench", "traffic",
                               "chat-lognormal-0.8knee.json")) as f:
            mix = json.load(f)
        keys = jax.random.split(jax.random.PRNGKey(11), 4)
        q = jax.random.normal(keys[0], (S, 1, nk, dh), jnp.bfloat16)
        # the leaves as the TPU keeps them: T minor
        kt, vt = (jax.random.normal(k, (S, nk, dh, T), jnp.bfloat16)
                  for k in keys[1:3])
        for n_live in (5, 16):
            # a live row's depth: its prompt and half its answer
            p, o = mix["prompt_len"], mix["output_len"]
            depth = [a + b // 2 for a, b in zip(
                traffic.lognormal_quantiles(n_live, p["median"], p["sigma"],
                                            p["min"], p["max"]),
                traffic.lognormal_quantiles(n_live, o["median"], o["sigma"],
                                            o["min"], o["max"])[::-1])]
            at = [(i * S) // n_live for i in range(n_live)]
            pos = jnp.zeros((S,), jnp.int32).at[jnp.asarray(at)].set(
                jnp.asarray(depth, jnp.int32))
            live = int(jnp.sum(jnp.where(pos > 0, pos + 1, 0)))
            floor_ms = 1e3 * live * 2 * nk * dh * 2 / PEAK_BYTES

            def leaves(kt, vt):
                return (jnp.transpose(x, (0, 3, 1, 2)) for x in (kt, vt))

            # what the step wrote at each row's position: an input, as in
            # the decode program (the gather is not the attend's)
            new = jnp.take_along_axis(
                jnp.transpose(vt, (0, 3, 1, 2)), pos[:, None, None, None], 1)

            def xla(q, kt, vt, pos):
                kc, vc = leaves(kt, vt)
                bias = jnp.where(jnp.arange(T)[None, None, :]
                                 <= pos[:, None, None], 0.0, -1e30)
                return full_attention(q, kc, vc, bias)

            ms, calls = device_ms(jax.jit(xla), "xla", (q, kt, vt, pos))
            want = jax.jit(xla)(q, kt, vt, pos).astype(jnp.float32)
            say({"form": "full_attention", "live_rows": n_live,
                 "device_ms_median": ms, "calls": calls,
                 "live_positions": live, "floor_ms": floor_ms})
            for bt in (int(x) for x in asked["kv"].split(",")):
                kv_attend.BLOCK_T = bt
                kv_attend._MAX_BLOCK_BYTES = 2 * nk * dh * bt

                def call(q, kt, vt, pos, new):
                    kc, vc = leaves(kt, vt)
                    return kv_attend._decode_attend.__wrapped__(
                        q, kc, vc, pos, new, False)

                call.__name__ = f"kv_{n_live}_{bt}"
                try:
                    ms, calls = device_ms(jax.jit(call), call.__name__,
                                          (q, kt, vt, pos, new))
                except Exception as e:
                    say({"form": "kv_decode_attend", "live_rows": n_live,
                         "block": bt, "refused": str(e)[:300]})
                    continue
                got = jax.jit(call)(q, kt, vt, pos, new).astype(jnp.float32)
                say({"form": "kv_decode_attend", "live_rows": n_live,
                     "block": bt, "device_ms_median": ms, "calls": calls,
                     "live_positions": live,
                     "visited": int(kv_attend.visits(
                         pos, (S, T, nk, dh), jnp.bfloat16)),
                     "floor_ms": floor_ms, "roofline_share": floor_ms / ms,
                     "minus_full_attention_max": float(
                         jnp.max(jnp.abs(got - want)))})
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "time_gqa_attends.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
