#!/usr/bin/env python3
"""Time ``ops/latent_attention.py::held_experts`` ALONE on the chip, at
the four routed configurations' shapes: the whole call with the ``%gmm``
and ``%moe_combine_held`` ops inside it, and the COMBINE alone (the
blocks' loop with the grouped matmuls taken out: one ``[M, D]`` float32
result stands for every block's) under each candidate form. Median
DEVICE time of ten calls from a profiler capture.

    chiprun --chips 1 -- python3 scripts/time_held_experts.py \
        [--rehearse] [--parent bench_checkout/parent] \
        [--cases nemotron:1024,granite:512,...] \
        [plan] [tm=256,in=1024x768,out=768x1024,M=3200,td=512 ...] \
        [combine:gathers] [combine:rows] [combine:rows,td=512,tr=128] \
        [combine:tokens] [combine:tokens,td=256,tn=128]

A case is ``<config>:<tokens>[:<load>]``: ``nemotron`` (128 of 512
ungated experts [1024, 2688], 22 a token), ``granite`` (36 of 72 experts
[4096, 768], 10 a token), ``glm`` (8 of 256 [6144, 2048], 8 a token),
``axk1`` (12 of 192 [7168, 2048], 8 a token); ``load`` multiplies the
share of the pairs that land here (the seeded weights' routing is
skewed: 1.8 read for GLM, up to 4 for A.X-K1, PERF.md section 5). At or
under ONE_HOT_TOKENS tokens the case is a decode step: the second half
of the rows are free slots, whose pairs all land on the first experts
(no combine candidate runs there: the one-hot branch has none).

A variant of the whole call is ``plan`` (the function's own), ``parent``
(``held_experts`` of the checkout ``--parent`` names, a ``git archive``
of the commit to compare with, its module loaded beside this one) or a
list of ``tm=``, ``in=<tk>x<tn>``, ``out=<tk>x<tn>``, ``M=``, ``td=`` (the
combine's D tile) that replace those parts of the plan. A candidate of
the combine is ``combine:gathers`` (what stood before PR 44: ``k`` row
gathers over all ``N k`` pair slots every trip, in turns of as many
tokens as 80 MiB of gathered rows hold, the slots outside the block
selected away), ``combine:rows`` (``lat.combine_held``: the kernel
``moe_combine_held`` over the block's held rows, under the plan's tiles
or ``td=`` / ``tr=``) or ``combine:tokens`` (the token-major form, here
only: a block's live rows sorted by token, a ``[M, td]`` tile of the
block's result resident, each token's rows summed in registers and its
row of the result written once; ``td=`` lanes, ``tn=`` tokens a grid
step). Prints one JSON line a (case, variant) and
appends them to ``chiprun_out/time_held_experts.jsonl``. ``--rehearse``
runs the same control flow off the chip at an eighth of the widths (the
kernel in interpret mode) and prints no time.
"""

import importlib.util
import json
import os
import statistics
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "perfbench"))

# config -> (held, routed, D, F, k, gated)
CONFIGS = {"nemotron": (128, 512, 1024, 2688, 22, False),
           "granite": (36, 72, 4096, 768, 10, True),
           "glm": (8, 256, 6144, 2048, 8, True),
           "axk1": (12, 192, 7168, 2048, 8, True)}
DEFAULT_CASES = ("nemotron:512,nemotron:1024,nemotron:1536,nemotron:2048,"
                 "nemotron:4096,granite:512,granite:1024,granite:3072,"
                 "axk1:4096:2,axk1:8192:2,axk1:4096:4,axk1:8192:4,"
                 "glm:5120:1.8,glm:14336:1.8")
DEFAULT_VARIANTS = ["plan", "combine:gathers", "combine:rows",
                    "combine:tokens"]
CALLS = 10
GATHER_BYTES = 80 * 2 ** 20     # the gathered rows a turn held, PR 42
TOKENS_TILE = 4 * 1024 * 1024   # numbers of the [M, td] tile a pass holds


def pinned_plan(lat, spec):
    """``moe_plan`` with the parts ``spec`` names replaced."""
    own = lat.moe_plan
    if spec == "plan":
        return own
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
                          combine_tile=int(pins.get("td", p.combine_tile)))

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


def gather_turn_tokens(N, k, D):
    """Tokens a turn of the gathers' combine took (PR 42's plan)."""
    turn = max(1, GATHER_BYTES // (k * D * 4))
    turn = 1 << (turn.bit_length() - 1)
    while turn > 128 and N % turn:
        turn //= 2
    return N if turn >= N or N % turn else turn


def tokens_combine(y, out, tok, w, n_rows, tn, td, interpret):
    """The token-major candidate of one block's combine: the live rows
    sorted by token (stable: a token's rows stay in row order, so the
    sums are the row-major form's), grid (D tiles, tiles of ``tn``
    tokens), the block's ``[M, td]`` tile resident; a token's rows are
    read at dynamic sublane indices and summed in registers."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, D = y.shape
    M = out.shape[0]
    key = jnp.where(jnp.arange(M) < n_rows, tok, N)
    key, rows, w = jax.lax.sort((key, jnp.arange(M, dtype=jnp.int32), w),
                                num_keys=1, is_stable=True)
    starts = jnp.searchsorted(key, jnp.arange(N + 1, dtype=key.dtype),
                              method="sort").astype(jnp.int32)

    def body(starts_ref, rows_ref, w_ref, out_ref, y_in_ref, y_ref):
        t0 = pl.program_id(1) * tn

        def token(n, _):
            def add(i, acc):
                return acc + w_ref[i] * out_ref[pl.ds(rows_ref[i], 1), :]

            y_ref[pl.ds(n, 1), :] = jax.lax.fori_loop(
                starts_ref[t0 + n], starts_ref[t0 + n + 1], add,
                y_in_ref[pl.ds(n, 1), :])
            return 0

        jax.lax.fori_loop(0, tn, token, 0)

    mine = pl.BlockSpec((tn, td), lambda d, t, *_: (t, d))
    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(D // td, N // tn),
            in_specs=[pl.BlockSpec((M, td), lambda d, t, *_: (0, d)), mine],
            out_specs=mine),
        out_shape=jax.ShapeDtypeStruct(y.shape, jnp.float32),
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=2 * (M + 2 * tn) * td * 4 + (4 << 20)),
        interpret=interpret, name="moe_combine_tokens",
    )(starts, rows, w, out, y)


def combine_alone(lat, spec, plan, E, D, interpret):
    """``(out [M, D], local, weights) -> y [N, D]``: the blocks' loop of
    ``held_experts`` with ``out`` standing for every block's grouped
    matmuls, under the candidate ``spec`` names."""
    import jax
    import jax.numpy as jnp

    form, *pins = spec.split(",")
    pins = dict(p.split("=") for p in pins)
    M = plan.block_rows
    tr = int(pins.get("tr", plan.tiles_in[0]))
    tn = int(pins.get("tn", 256))
    td = plan.combine_tile
    if form == "tokens" and D % 128 == 0:
        td = lat._tile(D, max(128, TOKENS_TILE // M))
    td = int(pins.get("td", td))

    def call(out, local, weights):
        N, k = local.shape
        P = N * k
        pad = plan.max_trips * M - P
        flat_e = jnp.where(local >= 0, local, E).reshape(P)
        w_held = jnp.where(local >= 0, weights, 0.0)
        n_held = jnp.sum(local >= 0)
        y0 = jnp.zeros((N, D), jnp.float32)
        trips = -(-n_held // M)
        if form in ("rows", "tokens"):
            _, order, w_row = jax.lax.sort(
                (flat_e, jnp.arange(P, dtype=jnp.int32), w_held.reshape(P)),
                num_keys=1, is_stable=True)
            tok, w_row = jnp.pad(order // k, (0, pad)), jnp.pad(w_row, (0, pad))

            def block(b, y):
                lo = b * M
                args = (y, out, jax.lax.dynamic_slice_in_dim(tok, lo, M),
                        jax.lax.dynamic_slice_in_dim(w_row, lo, M),
                        jnp.clip(n_held - lo, 0, M))
                if form == "tokens":
                    return tokens_combine(*args, min(tn, N), td, interpret)
                return lat.combine_held(*args, tr, td, True)

            return jax.lax.fori_loop(0, trips, block, y0)
        assert form == "gathers", spec
        order = jnp.argsort(flat_e, stable=True).astype(jnp.int32)
        rank = jnp.argsort(order).astype(jnp.int32).reshape(N, k)
        tokens = gather_turn_tokens(N, k, D)

        def block(b, y):
            at = rank - b * M
            here = (at >= 0) & (at < jnp.minimum(M, n_held - b * M))

            def turn(c):
                at_c, here_c, w_c = c
                return sum(jnp.where(
                    here_c[:, j, None],
                    w_c[:, j, None] * out[jnp.clip(at_c[:, j], 0, M - 1)],
                    0.0) for j in range(k))

            if tokens == N:
                return y + turn((at, here, w_held))
            turns = jax.lax.map(turn, jax.tree_util.tree_map(
                lambda a: a.reshape(-1, tokens, k), (at, here, w_held)))
            return y + turns.reshape(N, D)

        return jax.lax.fori_loop(0, trips, block, y0)

    return call


def timed(T, jitted, args, name):
    """(device seconds of each of CALLS calls, the capture)."""
    import jax

    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        for _ in range(CALLS):
            jax.block_until_ready(jitted(*args))
        jax.profiler.stop_trace()
        tr = T.load_xplane(T.find_xplane(tmp))
    return T.module_calls(tr, lambda n: n.startswith("jit_" + name)), tr


def main(argv) -> int:
    import jax
    import jax.numpy as jnp

    from harness import trace as T
    from tensorflow_distributed_tpu.ops import latent_attention as lat

    args = argv[1:]
    cases = DEFAULT_CASES
    rehearse = bool(args) and args[0] == "--rehearse"
    args = args[rehearse:]
    parent = None
    if args and args[0] == "--parent":
        spec = importlib.util.spec_from_file_location(
            "parent_latent_attention", os.path.join(
                args[1], "tensorflow_distributed_tpu", "ops",
                "latent_attention.py"))
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
        args = args[2:]
    if args and args[0] == "--cases":
        cases, args = args[1], args[2:]
    variants = args or DEFAULT_VARIANTS
    if jax.default_backend() != "tpu" and not rehearse:
        print("no TPU: a device time comes only from the chip",
              file=sys.stderr)
        return 4
    rows = []
    own = lat.moe_plan
    for case in cases.split(","):
        name, tokens, *rest = case.split(":")
        N, load = int(tokens), float(rest[0]) if rest else 1.0
        held, routed, D, F, k, gated = CONFIGS[name]
        if rehearse:
            D, F = D // 8, F // 8
        keys = jax.random.split(jax.random.PRNGKey(N), 7)
        xs = jax.random.normal(keys[0], (N, D), jnp.bfloat16)
        gate, up = (jax.random.normal(kk, (held, D, F), jnp.bfloat16) * 0.02
                    for kk in keys[1:3])
        down = jax.random.normal(keys[3], (held, F, D), jnp.bfloat16) * 0.02
        local, weights = routing(keys[4], N, k, held, routed,
                                 load, N <= lat.ONE_HOT_TOKENS)
        n_held = int(jnp.sum(local >= 0))
        first = {}
        for spec in variants:
            alone = spec.startswith("combine:")
            mod = parent if spec == "parent" else lat
            lat.moe_plan = own if alone or mod is parent \
                else pinned_plan(lat, spec)
            plan = mod.moe_plan(N, k, held, D, F, held / routed)
            if alone and plan.one_hot:
                continue
            if alone:
                call = combine_alone(lat, spec[len("combine:"):], plan, held,
                                     D, rehearse)
                operands = (jax.random.normal(
                    keys[5], (plan.block_rows, D), jnp.float32),
                    local, weights)
            else:
                def call(xs, local, weights, gate, up, down, mod=mod):
                    return mod.held_experts(
                        xs, local, weights, gate if gated else None, up, down,
                        jnp.bfloat16, held / routed,
                        act=jax.nn.silu if gated else
                        lambda x: jnp.square(jax.nn.relu(x)))

                operands = (xs, local, weights, gate, up, down)
            call.__name__ = "held_" + "".join(
                c if c.isalnum() else "_" for c in f"{case}_{spec}")
            jitted = jax.jit(call)
            row = {"case": case, "variant": spec, "n_held": n_held,
                   "held_share": n_held / (N * k),
                   "trips": -(-n_held // plan.block_rows),
                   "rows_per_expert": n_held / held, **plan._asdict()}
            try:
                out = jax.block_until_ready(jitted(*operands))
            except Exception as e:      # tiles the compiler refuses
                row["refused"] = str(e)[:300]
                print(json.dumps(row), flush=True)
                continue
            finally:
                lat.moe_plan = own
            same = first.setdefault(alone, out)
            row["minus_first_max"] = float(jnp.max(jnp.abs(out - same)))
            if not rehearse:
                secs, tr = timed(T, jitted, operands, call.__name__)
                gmm_s, gmm_n = T.op_time(tr, lambda n: n.startswith("%gmm"))
                comb_s, comb_n = T.op_time(
                    tr, lambda n: n.startswith("%moe_combine_held"))
                row.update(calls=len(secs),
                           device_ms_median=1e3 * statistics.median(secs),
                           device_ms_min=1e3 * min(secs),
                           gmm_ms_a_call=1e3 * gmm_s / len(secs),
                           gmm_ops_a_call=gmm_n / len(secs),
                           combine_kernel_ms_a_call=1e3 * comb_s / len(secs),
                           combine_kernel_ops_a_call=comb_n / len(secs),
                           device=jax.devices()[0].device_kind)
            rows.append(row)
            print(json.dumps(row), flush=True)
    if rehearse:
        return 0
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "time_held_experts.jsonl"), "a") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
