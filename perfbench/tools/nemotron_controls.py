#!/usr/bin/env python3
"""Show that ``correct`` SEES each mechanism of the ``nemotron_h`` cell: run
the cell through the runner with one mechanism of the PROGRAM broken
underneath, and print whether ``correct`` came out false and by which limit.

    python3 perfbench/tools/nemotron_controls.py --seed 2147483777 \\
        --seconds 10 --buckets 1024,2048,4096 \\
        --breaks sound,state_never_read,routed_part_left_out,group0_for_every_head

``sound`` runs the program as it is, with the runner's lower-precision
control (the reference's fp8 operands in the program's place) beside it.
The breaks are made where the tests make them (:func:`broken`): names of the
package rebound for the length of one run, nothing in the program knows of
them. ``--buckets`` narrows the prefill ladder so that a broken program
compiles fewer prefills (a break that changes the prefill compiles every
bucket anew). Sets nothing: the limits are written by hand into the
configuration file, with these readings in PERF.md.
"""

import argparse
import contextlib
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (ROOT, os.path.dirname(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

CELL = "nemotron3s-serve-agentic"
BREAKS = ("fp8_operands", "state_never_read", "routed_part_left_out",
          "group0_for_every_head")


def fresh_programs() -> None:
    """The engine keeps its compiled programs by model VALUE and the held
    experts are traced once a shape: a program broken underneath must not
    be handed what a sound run left behind, nor leave its own."""
    from tensorflow_distributed_tpu.ops import latent_attention as lat_ops
    from tensorflow_distributed_tpu.serve import engine
    for name in ("_compiled_prefill", "_compiled_step", "_compiled_verify"):
        getattr(engine, name).cache_clear()
    lat_ops._held_experts_jit.clear_cache()


@contextlib.contextmanager
def broken(how):
    """The program with one mechanism broken for the length of the block
    (None: as it is):

    - ``fp8_operands``: every product of the two model files' ``_mm``
      takes its operands rounded to fp8 (the precision below the stated);
    - ``state_never_read``: the decode step moves the state and reads
      nothing out of it (``S C`` is 0);
    - ``routed_part_left_out``: the held experts' part of every expert
      layer is 0 (the shared expert and the rest stay);
    - ``group0_for_every_head``: every head of the state-space layers
      reads the ``B`` and ``C`` of group 0, in the scan and in the step."""
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.models import granitemoehybrid, nemotron_h
    from tensorflow_distributed_tpu.ops import latent_attention as lat_ops
    from tensorflow_distributed_tpu.ops import state_space as ops
    if how is not None and how not in BREAKS:
        raise ValueError(f"break {how!r}; have {BREAKS}")
    kept = []

    def rebind(module, name, new):
        kept.append((module, name, getattr(module, name)))
        setattr(module, name, new)

    def group0(m):
        return jnp.broadcast_to(m[..., :1, :], m.shape)

    if how == "fp8_operands":
        def rounded(x, dtype):
            x = x.astype(jnp.float32)
            s = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
            return ((x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32)
                    * s).astype(dtype)

        real_mm = nemotron_h._mm
        for module in (nemotron_h, granitemoehybrid):
            rebind(module, "_mm", lambda spec, a, w, dtype: real_mm(
                spec, rounded(a, dtype), rounded(w, dtype), dtype))
    elif how == "state_never_read":
        real_step = ops.ssd_state_step

        def never_read(S, *args, **kw):
            S, y = real_step(S, *args, **kw)
            return S, 0.0 * y

        rebind(ops, "ssd_state_step", never_read)
    elif how == "routed_part_left_out":
        real_once = lat_ops.held_experts_once
        rebind(lat_ops, "held_experts_once",
               lambda xs, *args, **kw: 0.0 * real_once(xs, *args, **kw))
    elif how == "group0_for_every_head":
        real_scan, real_step = ops.ssd_chunk_scan, ops.ssd_state_step
        rebind(ops, "ssd_chunk_scan", lambda x, dt, A, Bm, Cm, *a, **kw:
               real_scan(x, dt, A, group0(Bm), group0(Cm), *a, **kw))
        rebind(ops, "ssd_state_step", lambda S, x, dt, A, Bm, Cm, *a, **kw:
               real_step(S, x, dt, A, group0(Bm), group0(Cm), *a, **kw))
    fresh_programs()
    try:
        yield
    finally:
        for module, name, real in kept:
            setattr(module, name, real)
        fresh_programs()


def main(argv=None) -> int:
    from harness import serve_runner
    from harness.loader import Cell

    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--breaks", default="sound," + ",".join(BREAKS[1:]))
    ap.add_argument("--buckets", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = Cell(CELL)
    block = cell.config["rehearsal"] if args.rehearse else cell.config
    if args.buckets:
        block["serve"] = dict(block["serve"], buckets=args.buckets)
    limits = block["correct_limits"]
    for how in args.breaks.split(","):
        with broken(None if how == "sound" else how):
            res = serve_runner.run(
                cell, args.seed, args.seconds, False, rehearse=args.rehearse,
                control="fp8" if how == "sound" else None)
        check = res["check"]
        print(f"[controls] {how}: correct={res['correct']} failed="
              f"{res['failed']} gap max {check['max']:.6g} (limit "
              f"{limits['served_token_gap_max']:g}) mean {check['mean']:.6g}"
              f" (limit {limits['served_token_gap_mean']:g}) over "
              f"{check['tokens']} tokens"
              + (f"; reference at fp8 in the program's place: max "
                 f"{check['control']['max']:.6g} mean "
                 f"{check['control']['mean']:.6g}"
                 if "control" in check else ""), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
