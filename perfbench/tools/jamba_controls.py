#!/usr/bin/env python3
"""Show that ``correct`` SEES each mechanism of the ``jamba`` cell.

    python3 perfbench/tools/jamba_controls.py --seed 2147483777 \\
        --seconds 10 --buckets 512,2048,8192 \\
        --breaks sound,state_zeroed,state_at_bucket_end,ring_one_tap_off

``sound`` runs the program as it is and then, over the SAME sample of served
requests, scores what the reference would have served with one mechanism of
the reference changed in the program's place (the runner's control, one
reference pass each): ``fp8`` (every product's operands rounded to fp8, the
precision below the stated), ``no_bc_norm`` (the inner norms on ``B`` and
``C`` left out), ``no_attention`` (the attention layers left out). The other
breaks are made in the PROGRAM, where the tests make them (:func:`broken`):
names of the package rebound for the length of one run, nothing in the
program knows of them. ``--buckets`` narrows the prefill ladder so that a run
compiles fewer prefills. Sets nothing: the limits are written by hand into
the configuration file, with these readings in PERF.md.
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

CELL = "jamba2-serve-highrate"
REFERENCE_CONTROLS = ("fp8", "no_bc_norm", "no_attention")
BREAKS = ("state_zeroed", "state_at_bucket_end", "ring_one_tap_off")


def fresh_programs() -> None:
    """The engine keeps its compiled programs by model VALUE: a program
    broken underneath must not be handed what a sound run left behind, nor
    leave its own."""
    from tensorflow_distributed_tpu.serve import engine
    for name in ("_compiled_prefill", "_compiled_step", "_compiled_verify"):
        getattr(engine, name).cache_clear()


@contextlib.contextmanager
def broken(how):
    """The program with one mechanism broken for the length of the block
    (None: as it is):

    - ``state_zeroed``: the prefill hands the decode steps a state of
      zeros (the scan's ``y`` is sound, so the first token is);
    - ``state_at_bucket_end``: the scan is not told ``true_len``, so the
      state it leaves is the one at the END of the padded bucket;
    - ``ring_one_tap_off``: a decode step writes its input one row off in
      the convolution's ring (row ``(p + 1) mod 4``), so every window of
      the convolution reads its taps one position off."""
    from tensorflow_distributed_tpu.ops import state_space as ops
    if how is not None and how not in BREAKS:
        raise ValueError(f"break {how!r}; have {BREAKS}")
    kept = []

    def rebind(name, new):
        kept.append((name, getattr(ops, name)))
        setattr(ops, name, new)

    if how == "state_zeroed":
        real_scan = ops.s6_chunk_scan

        def zeroed(*args, **kw):
            y, h = real_scan(*args, **kw)
            return y, 0.0 * h

        rebind("s6_chunk_scan", zeroed)
    elif how == "state_at_bucket_end":
        real_scan = ops.s6_chunk_scan
        rebind("s6_chunk_scan",
               lambda x, dt, A, Bm, Cm, D, true_len=None, **kw: real_scan(
                   x, dt, A, Bm, Cm, D, None, **kw))
    elif how == "ring_one_tap_off":
        real_step = ops.ssd_conv_step
        rebind("ssd_conv_step", lambda ring, new, w, b, pos: real_step(
            ring, new, w, b, pos + 1))
    fresh_programs()
    try:
        yield
    finally:
        for name, real in kept:
            setattr(ops, name, real)
        fresh_programs()


def main(argv=None) -> int:
    from harness import serve_runner
    from harness.loader import Cell

    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--breaks", default="sound," + ",".join(BREAKS))
    ap.add_argument("--buckets", default="")
    ap.add_argument("--rate", type=float, default=0.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = Cell(CELL)
    block = cell.config["rehearsal"] if args.rehearse else cell.config
    if args.buckets:
        block["serve"] = dict(block["serve"], buckets=args.buckets)
    limits = block["correct_limits"]
    sizes = cell.sizes(args.rehearse)
    mix = {"rate_rps": args.rate} if args.rate else None

    def verdict(name, check):
        fails = [k for k, v in (("max", "served_token_gap_max"),
                                ("mean", "served_token_gap_mean"))
                 if check[k] > limits[v]]
        print(f"[controls] {name}: gap max {check['max']:.6g} (limit "
              f"{limits['served_token_gap_max']:g}) mean {check['mean']:.6g}"
              f" (limit {limits['served_token_gap_mean']:g}) over "
              f"{check['tokens']} tokens: "
              f"{'NOT correct by ' + ' and '.join(fails) if fails else 'correct'}",
              flush=True)

    # the sample the runner compared, for the reference's own controls
    samples = []
    real_pick = serve_runner.pick_sample

    def pick(*a, **kw):
        samples.append(real_pick(*a, **kw))
        return samples[-1]

    serve_runner.pick_sample = pick
    try:
        for how in args.breaks.split(","):
            with broken(None if how == "sound" else how):
                res = serve_runner.run(cell, args.seed, args.seconds, False,
                                       rehearse=args.rehearse,
                                       mix_update=mix)
            print(f"[controls] {how}: correct={res['correct']} failed="
                  f"{res['failed']}", flush=True)
            verdict(how, res["check"])
            if how == "sound":
                for ctl in REFERENCE_CONTROLS:
                    verdict(f"reference with {ctl} in the program's place",
                            serve_runner.served_gaps(
                                samples[-1], args.seed, cell.model, sizes,
                                precision=ctl))
    finally:
        serve_runner.pick_sample = real_pick
    return 0


if __name__ == "__main__":
    sys.exit(main())
