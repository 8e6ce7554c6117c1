#!/usr/bin/env python3
"""Time ``ops/latent_attention.py::held_experts`` ALONE on the chip, at
the three routed configurations' shapes: the whole call and the ``%gmm``
ops inside it, the median DEVICE time of ten calls from a profiler
capture, under the plan ``moe_plan`` makes or under one pinned in its
place (a sweep).

    chiprun --chips 1 -- python3 scripts/time_held_experts.py \
        [--rehearse] [--cases granite:512,granite:64,...] [plan] [parent] \
        [tm=256,in=1024x768,out=768x1024,M=3200 ...]

A case is ``<config>:<tokens>[:<load>]``: ``granite`` (36 of 72 experts
[4096, 768], 10 a token), ``glm`` (8 of 256 [6144, 2048], 8 a token),
``axk1`` (12 of 192 [7168, 2048], 8 a token); ``load`` multiplies the
share of the pairs that land here (the seeded weights' routing is
skewed: 1.8 read for GLM, up to 4 for A.X-K1, PERF.md section 5). At or
under ONE_HOT_TOKENS tokens the case is a decode step: the second half
of the rows are free slots, whose pairs all land on the first experts.
A variant is ``plan`` (the function's own), ``parent`` (blocks of N / 2
rows in 512s, tiles (512 or 128, 512, 1024): what stood before PR 42)
or a list of ``tm=``, ``in=<tk>x<tn>``, ``out=<tk>x<tn>``, ``M=``, ``C=``
(tokens a turn of the combine) that replace those parts of the plan. Prints one JSON line a (case, variant)
and appends them to ``chiprun_out/time_held_experts.jsonl``.
``--rehearse`` runs the same control flow off the chip at an eighth of
the widths and prints no time.
"""

import json
import os
import statistics
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "perfbench"))

# config -> (held, routed, D, F, k)
CONFIGS = {"granite": (36, 72, 4096, 768, 10),
           "glm": (8, 256, 6144, 2048, 8),
           "axk1": (12, 192, 7168, 2048, 8)}
DEFAULT_CASES = "granite:64,granite:256,granite:512,glm:5120:1.8,axk1:8192:2"
CALLS = 10


def parent_plan(lat, N, k, E, D, F, share):
    """The constants that stood before the plan was derived."""
    P = N * k
    small = N <= lat.ONE_HOT_TOKENS
    M = P if small else min(P, -(-(N // 2) // 512) * 512)
    tm = 512 if M % 512 == 0 else 128
    return lat.MoePlan(small, M, -(-P // M), max(1, -(-int(P * share) // M)),
                       (tm, 512, 1024), (tm, 512, 1024), N)


def pinned_plan(lat, spec):
    """``moe_plan`` with the parts ``spec`` names replaced."""
    own = lat.moe_plan
    if spec == "plan":
        return own
    if spec == "parent":
        return lambda *a: parent_plan(lat, *a)
    pins = dict(p.split("=") for p in spec.split(","))

    def plan(N, k, E, D, F, share):
        p = own(N, k, E, D, F, share)
        tm = int(pins.get("tm", p.tiles_in[0]))
        t_in = tuple(int(x) for x in pins["in"].split("x")) \
            if "in" in pins else p.tiles_in[1:]
        t_out = tuple(int(x) for x in pins["out"].split("x")) \
            if "out" in pins else p.tiles_out[1:]
        M = p.block_rows
        if not p.one_hot:
            M = -(-int(pins.get("M", M)) // tm) * tm
        return p._replace(block_rows=M, max_trips=-(-N * k // M),
                          tiles_in=(tm,) + t_in, tiles_out=(tm,) + t_out,
                          combine_tokens=min(N, int(pins.get(
                              "C", p.combine_tokens))))

    return plan


def routing(key, N, k, held, routed, load, decode):
    """(local [N, k], weights [N, k]): the k largest of Gumbel noise over
    the routed experts, the held ones lifted by log(load)."""
    import jax
    import jax.numpy as jnp

    lift = jnp.where(jnp.arange(routed) < held, jnp.log(load), 0.0)
    noise = jax.random.gumbel(key, (N, routed)) + lift[None]
    top, ids = jax.lax.top_k(noise, k)
    if decode:      # free slots: equal logits pick the first k experts
        ids = jnp.where(jnp.arange(N)[:, None] < N // 2, ids,
                        jnp.arange(k)[None])
    local = jnp.where(ids < held, ids, -1).astype(jnp.int32)
    return local, jax.nn.softmax(top, axis=-1)


def main(argv) -> int:
    import jax
    import jax.numpy as jnp

    from harness import trace as T
    from tensorflow_distributed_tpu.ops import latent_attention as lat

    args = argv[1:]
    cases = DEFAULT_CASES
    rehearse = bool(args) and args[0] == "--rehearse"
    args = args[rehearse:]
    if args and args[0] == "--cases":
        cases, args = args[1], args[2:]
    variants = args or ["plan", "parent"]
    if jax.default_backend() != "tpu" and not rehearse:
        print("no TPU: a device time comes only from the chip",
              file=sys.stderr)
        return 4
    rows = []
    own = lat.moe_plan
    for case in cases.split(","):
        name, tokens, *rest = case.split(":")
        N, load = int(tokens), float(rest[0]) if rest else 1.0
        held, routed, D, F, k = CONFIGS[name]
        if rehearse:
            D, F = D // 8, F // 8
        keys = jax.random.split(jax.random.PRNGKey(N), 6)
        xs = jax.random.normal(keys[0], (N, D), jnp.bfloat16)
        gate, up = (jax.random.normal(kk, (held, D, F), jnp.bfloat16) * 0.02
                    for kk in keys[1:3])
        down = jax.random.normal(keys[3], (held, F, D), jnp.bfloat16) * 0.02
        local, weights = routing(keys[4], N, k, held, routed,
                                 load, N <= lat.ONE_HOT_TOKENS)
        n_held = int(jnp.sum(local >= 0))
        first = None
        for spec in variants:
            lat.moe_plan = pinned_plan(lat, spec)
            plan = lat.moe_plan(N, k, held, D, F, held / routed)

            def call(xs, local, weights, gate, up, down):
                return lat.held_experts(xs, local, weights, gate, up, down,
                                        jnp.bfloat16, held / routed)

            call.__name__ = "held_" + "".join(
                c if c.isalnum() else "_" for c in f"{case}_{spec}")
            jitted = jax.jit(call)
            row = {"case": case, "variant": spec, "n_held": n_held,
                   "rows_per_expert": n_held / held, **plan._asdict()}
            try:
                out = jax.block_until_ready(
                    jitted(xs, local, weights, gate, up, down))
            except Exception as e:      # tiles the compiler refuses
                row["refused"] = str(e)[:300]
                print(json.dumps(row), flush=True)
                continue
            finally:
                lat.moe_plan = own
            if first is None:
                first = out
            row["minus_first_max"] = float(jnp.max(jnp.abs(out - first)))
            with tempfile.TemporaryDirectory() as tmp:
                jax.profiler.start_trace(tmp)
                for _ in range(CALLS):
                    jax.block_until_ready(
                        jitted(xs, local, weights, gate, up, down))
                jax.profiler.stop_trace()
                tr = T.load_xplane(T.find_xplane(tmp))
            secs = T.module_calls(
                tr, lambda n: n.startswith("jit_" + call.__name__))
            gmm_s, gmm_n = T.op_time(tr, lambda n: n.startswith("%gmm"))
            if secs:
                row.update(calls=len(secs),
                           device_ms_median=1e3 * statistics.median(secs),
                           device_ms_min=1e3 * min(secs),
                           gmm_ms_a_call=1e3 * gmm_s / len(secs),
                           gmm_ops_a_call=gmm_n / len(secs),
                           device=jax.devices()[0].device_kind)
            rows.append(row)
            print(json.dumps(row), flush=True)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "time_held_experts.jsonl"), "a") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
