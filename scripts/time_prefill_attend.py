#!/usr/bin/env python3
"""Time the latent family's prefill attend ALONE on the chip: each form
of ``ops/latent_attention.py::prefill_attend`` jitted, bf16, at the two
benchmark models' shapes, the median DEVICE time of ten calls from a
profiler capture beside the causal FLOPs at the chip's peak.

    chiprun --chips 1 -- python3 scripts/time_prefill_attend.py \
        [xla] [kernel] [kernel:<block_q>x<block_k> ...]

A.X-K1: q, k [64, L, 192], v [64, L, 128], no mask, L = 4,096 and 8,192.
GLM:    q, k [64, L, 256], v [64, L, 256], a [L, L] selection mask that
        keeps 2,048 causal keys a query, L = 6,144 and 14,336.
Prints one JSON line a (shape, form) and writes them all to
``chiprun_out/time_prefill_attend.json``.
"""

import json
import os
import statistics
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "perfbench"))

PEAK_FLOPS = 197e12
SHAPES = [("axk1", 64, 4096, 192, 128, False),
          ("axk1", 64, 8192, 192, 128, False),
          ("glm", 64, 6144, 256, 256, True),
          ("glm", 64, 14336, 256, 256, True)]
TOPK = 2048
CALLS = 10


def _with_blocks(lat, blocks):
    """The kernel form, under the grid blocks the plan picks or under
    ``<block_q>x<block_k>`` pinned in their place (a sweep)."""
    kernel = lat.prefill_attend_kernel
    if not blocks:
        return kernel
    bq, bk = (int(b) for b in blocks.split("x"))
    from tensorflow_distributed_tpu.ops import flash_attention as flash

    def pinned(*args):
        plan = flash.flash_plan
        flash.flash_plan = lambda *a, **kw: plan(*a, **kw, block_q=bq,
                                                 block_k=bk)
        try:
            return kernel(*args)
        finally:
            flash.flash_plan = plan

    return pinned


def main(argv) -> int:
    import jax
    import jax.numpy as jnp

    from harness import trace as T
    from tensorflow_distributed_tpu.ops import latent_attention as lat

    forms = argv[1:] or ["xla", "kernel"]
    if jax.default_backend() != "tpu":
        print("no TPU: a device time comes only from the chip",
              file=sys.stderr)
        return 4
    fns = {"xla": lat.prefill_attend_xla}
    for form in forms:
        if form.startswith("kernel"):
            fns[form] = _with_blocks(lat, form.partition(":")[2])
    rows = []
    for model, H, L, dq, dv, masked in SHAPES:
        keys = jax.random.split(jax.random.PRNGKey(L), 4)
        q, k = (jax.random.normal(kk, (H, L, dq), jnp.bfloat16)
                for kk in keys[:2])
        v = jax.random.normal(keys[2], (H, L, dv), jnp.bfloat16)
        keep = None
        if masked:
            t = jnp.arange(L)
            share = jnp.minimum(1.0, TOPK / (t[:, None] + 1.0))
            keep = jax.jit(lambda key: (
                jax.random.uniform(key, (L, L)) < share)
                & (t[None, :] <= t[:, None]))(keys[3])
        scale = 1.0 / dq ** 0.5
        flops = 2 * H * L * L / 2 * (dq + dv)
        outs = {}
        for form in forms:
            fn = fns[form]

            def call(q, k, v, keep, fn=fn):
                return fn(q, k, v, keep, scale)

            call.__name__ = f"attend_{form}_{model}_{L}".replace(":", "_")
            jitted = jax.jit(call)
            try:
                outs[form] = jax.block_until_ready(jitted(q, k, v, keep))
            except Exception as e:      # blocks the compiler refuses
                print(json.dumps({"model": model, "L": L, "form": form,
                                  "refused": str(e)[:300]}), flush=True)
                continue
            with tempfile.TemporaryDirectory() as tmp:
                jax.profiler.start_trace(tmp)
                for _ in range(CALLS):
                    jax.block_until_ready(jitted(q, k, v, keep))
                jax.profiler.stop_trace()
                tr = T.load_xplane(T.find_xplane(tmp))
            secs = T.module_calls(
                tr, lambda n: n.startswith("jit_" + call.__name__))
            row = {"model": model, "form": form, "H": H, "L": L, "dq": dq,
                   "dv": dv, "masked": masked, "calls": len(secs),
                   "device_ms_median": (1e3 * statistics.median(secs)
                                        if secs else None),
                   "device_ms_min": 1e3 * min(secs) if secs else None,
                   "device_ms_max": 1e3 * max(secs) if secs else None,
                   "causal_flops": flops,
                   "ms_at_peak": 1e3 * flops / PEAK_FLOPS,
                   "device": jax.devices()[0].device_kind}
            if secs:
                row["share_of_peak"] = row["ms_at_peak"] \
                    / row["device_ms_median"]
            rows.append(row)
            print(json.dumps(row), flush=True)
        for form in outs:
            if form != "xla" and "xla" in outs:
                gap = jnp.max(jnp.abs(outs["xla"].astype(jnp.float32)
                                      - outs[form].astype(jnp.float32)))
                print(json.dumps({"model": model, "L": L, "form": form,
                                  "minus_xla_max": float(gap)}), flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "time_prefill_attend.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
