#!/usr/bin/env python3
"""Time the per-channel state-space kernels ALONE on the chip: the median
DEVICE time of ten calls from a profiler capture.

    chiprun --chips 1 -- python3 scripts/time_s6_kernels.py \
        [scan:<positions a chunk>x<channels a block>,...] \
        [step:<channels a block>,...] [lengths:512,8192]

``scan``: ``ops/state_space.py::s6_chunk_scan`` at Jamba2-3B's shapes (one
row, 5,120 channels, 16 state numbers, bf16 ``x``, float32 ``dt``) at each
of ``lengths`` with ``true_len`` at the bucket's end, under each pair of
chunk and channel block, beside the ``lax.scan`` form at the shortest
length; a pair the compiler refuses is reported as refused. ``step``:
``s6_state_step`` over 192 slots of which 120 are live, in blocks of
channels, beside the slot-blind XLA form and the roofline of the live
rows' states read and written at 819 GB/s. The kernel's own device time is
``kernel_ms`` (the ``%s6_*`` op); ``device_ms`` is the jitted call's, with
the wrapper's mask of ``dt`` and the columns of ``B`` and ``C``. Prints one
JSON line a form and writes them all to
``chiprun_out/time_s6_kernels.json``.
"""

import json
import os
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "perfbench"))

PEAK_BYTES = 819e9
C, N, SLOTS, LIVE = 5120, 16, 192, 120
CALLS = 10


def device_ms(jitted, name, kernel, args):
    """(median device ms of the jitted call, median ms of the ops inside it
    whose name starts with ``kernel``)."""
    import jax

    from harness import trace as tr
    jax.block_until_ready(jitted(*args))
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        for _ in range(CALLS):
            jax.block_until_ready(jitted(*args))
        jax.profiler.stop_trace()
        trace = tr.load_xplane(tr.find_xplane(tmp))
    secs = tr.module_calls(trace, lambda n: n.startswith("jit_" + name))
    t, n = tr.op_time(trace, lambda n: n.startswith("%" + kernel))
    return (1e3 * statistics.median(secs) if secs else None,
            1e3 * t / n if n else None)


def main(argv) -> int:
    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.ops import state_space as ss

    rehearse = "--rehearse" in argv
    argv = [a for a in argv if a != "--rehearse"]
    if jax.default_backend() != "tpu" and not rehearse:
        print("no TPU: a device time comes only from the chip",
              file=sys.stderr)
        return 4
    asked = dict(a.split(":") for a in argv[1:]) or {
        "scan": "128x640,128x1280,256x1280,256x2560,512x1280,256x5120",
        "step": "1280,2560,5120", "lengths": "512,8192"}
    rows = []

    def say(row):
        rows.append(row)
        print(json.dumps(row), flush=True)

    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    A = -jnp.broadcast_to(jnp.arange(1.0, N + 1)[:, None], (N, C))
    skip = jnp.ones((C,), jnp.float32)
    lengths = [int(x) for x in asked.get("lengths", "512,8192").split(",")]
    for L in lengths if "scan" in asked else ():
        x = jax.random.normal(ks[0], (1, L, C), jnp.bfloat16)
        dt = 0.05 * jax.nn.softplus(jax.random.normal(ks[1], (1, L, C)))
        Bm, Cm = (jax.random.normal(k, (1, L, N)) for k in ks[2:4])
        n = jnp.asarray([L], jnp.int32)
        want = None
        if L == min(lengths):
            def xla(x, dt, Bm, Cm, n):
                return ss._s6_scan_xla(x, dt, A, Bm, Cm, skip)

            ms, _ = device_ms(jax.jit(xla), "xla", "none", (x, dt, Bm, Cm, n))
            want = jax.jit(xla)(x, dt, Bm, Cm, n)
            say({"form": "lax.scan", "L": L, "device_ms": ms})
        for pair in asked["scan"].split(","):
            T, bc = (int(v) for v in pair.split("x"))
            ss.S6_CHUNK, ss.S6_CHANNELS = T, bc

            def call(x, dt, Bm, Cm, n):
                return ss.s6_chunk_scan(x, dt, A, Bm, Cm, skip, n,
                                        interpret=True if rehearse else None)

            call.__name__ = f"scan_{L}_{T}_{bc}"
            try:
                ms, kernel = device_ms(jax.jit(call), call.__name__,
                                       "s6_chunk_scan", (x, dt, Bm, Cm, n))
            except Exception as e:      # blocks the compiler refuses
                say({"form": "s6_chunk_scan", "L": L, "chunk": T,
                     "channels": bc, "refused": str(e)[-300:]})
                continue
            row = {"form": "s6_chunk_scan", "L": L, "chunk": T,
                   "channels": bc, "device_ms": ms, "kernel_ms": kernel,
                   "kernel_us_per_position": 1e3 * kernel / L
                   if kernel else None}
            if want is not None:
                got = jax.jit(call)(x, dt, Bm, Cm, n)
                row["y_minus_lax_scan_max"] = float(
                    jnp.max(jnp.abs(got[0] - want[0])))
                row["h_minus_lax_scan_max"] = float(
                    jnp.max(jnp.abs(got[1] - want[1])))
            say(row)
    if "step" in asked:
        S = jax.random.normal(ks[4], (SLOTS, N, C), jnp.float32)
        x = jax.random.normal(ks[5], (SLOTS, C), jnp.float32)
        dt = 0.05 * jax.nn.softplus(jax.random.normal(ks[6], (SLOTS, C)))
        Bm, Cm = (jax.random.normal(k, (SLOTS, N)) for k in ks[2:4])
        # live rows among free ones, as a run's slots are
        pos = jnp.where((jnp.arange(SLOTS) * LIVE) % SLOTS < LIVE, 500, 0)
        fold = pos > 0
        live = int(jnp.sum(pos > 0))
        floor_ms = 1e3 * live * 2 * N * C * 4 / PEAK_BYTES

        def blind(S, x, dt, Bm, Cm, fold, pos):
            return ss._s6_step_xla(S, x, dt, A, Bm, Cm, skip, fold, pos)

        ms, _ = device_ms(jax.jit(blind), "blind", "none",
                          (S, x, dt, Bm, Cm, fold, pos))
        want = jax.jit(blind)(S, x, dt, Bm, Cm, fold, pos)
        say({"form": "xla_slot_blind", "device_ms": ms, "live_rows": live,
             "floor_ms": floor_ms})
        for W in (int(v) for v in asked["step"].split(",")):
            ss.S6_STEP_LANES = W

            def call(S, x, dt, Bm, Cm, fold, pos):
                return ss.s6_state_step(S, x, dt, A, Bm, Cm, skip, fold, pos,
                                        interpret=True if rehearse else None)

            call.__name__ = f"step_{W}"
            try:
                ms, kernel = device_ms(jax.jit(call), call.__name__,
                                       "s6_state_step",
                                       (S, x, dt, Bm, Cm, fold, pos))
            except Exception as e:
                say({"form": "s6_state_step", "channels": W,
                     "refused": str(e)[-300:]})
                continue
            got = jax.jit(call)(S, x, dt, Bm, Cm, fold, pos)
            # the host's clock around 200 calls that hand the state on
            # (donated, as the engine's step does): no less than the
            # device's time a call
            chain = jax.jit(lambda S: call(S, x, dt, Bm, Cm, fold, pos)[0],
                            donate_argnums=(0,))
            state = chain(S + 0.0)
            jax.block_until_ready(state)
            t0 = time.perf_counter()
            for _ in range(200):
                state = chain(state)
            jax.block_until_ready(state)
            wall = 1e3 * (time.perf_counter() - t0) / 200
            say({"form": "s6_state_step", "channels": W, "device_ms": ms,
                 "wall_ms_donated": wall,
                 "kernel_ms": kernel, "live_rows": live,
                 "floor_ms": floor_ms,
                 "roofline_share": floor_ms / kernel if kernel else None,
                 "S_minus_xla_max": float(jnp.max(jnp.abs(got[0] - want[0]))),
                 "y_minus_xla_max": float(jnp.max(jnp.abs(got[1] - want[1])))})
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "time_s6_kernels.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
