#!/usr/bin/env python3
"""Show that ``correct`` SEES each mechanism of the ``exaone_moe`` cell.

    python3 perfbench/tools/kexaone_controls.py --seed 2147483777 \\
        --seconds 10 --buckets 1024,4096,12288 \\
        --breaks sound,ring_one_off,routed_part_left_out

``sound`` runs the program as it is and then, over the SAME sample of served
requests, scores what the reference would have served with one mechanism of
the reference changed in the program's place (the runner's control, one
reference pass each): ``fp8`` (every product's operands rounded to fp8, the
precision below the stated), ``window_ignored`` (the window layers attend
the whole depth), ``rope_everywhere`` (the rotation on the full layers
too). The other breaks are made in the PROGRAM, where the tests make them
(:func:`broken`): names of the package rebound for the length of one run,
nothing in the program knows of them. ``--buckets`` narrows the prefill
ladder so that a run compiles fewer prefills. Sets nothing: the limits are
written by hand into the configuration file, with these readings in
PERF.md.
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

CELL = "kexaone-serve-mixedlen"
REFERENCE_CONTROLS = ("fp8", "window_ignored", "rope_everywhere")
BREAKS = ("ring_one_off", "ring_read_unmasked", "routed_part_left_out")


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
def broken(how, window: int):
    """The program with one mechanism broken for the length of the block
    (None: as it is); ``window`` the configuration's ``sliding_window``,
    by which a ring is told from a full layer's rows:

    - ``ring_one_off``: a decode step writes its row of K and V one slot
      off in the ring (row ``(p + 1) mod window``), so the oldest row
      still needed is lost and the newest is missing;
    - ``ring_read_unmasked``: a decode step attends all ``window`` rows of
      a ring whatever the depth (what a ring that has not wrapped must
      not: the rows past the depth are a bucket's padding or the slot's
      last tenant; a cell whose every prompt is a window long never shows
      it, a prompt shorter than the window does);
    - ``routed_part_left_out``: the held experts' part of every routed
      layer is 0 (the shared expert and the rest stay)."""
    from tensorflow_distributed_tpu.ops import hybrid_attention as hyb_ops
    from tensorflow_distributed_tpu.ops import latent_attention as lat_ops
    if how is not None and how not in BREAKS:
        raise ValueError(f"break {how!r}; have {BREAKS}")
    kept = []

    def rebind(module, name, new):
        kept.append((module, name, getattr(module, name)))
        setattr(module, name, new)

    if how == "ring_one_off":
        real_write = lat_ops.write_rows

        def one_off(buf, new, start):
            if buf.shape[1] == window and new.shape[1] == 1:
                start = (start + 1) % window
            return real_write(buf, new, start)

        rebind(lat_ops, "write_rows", one_off)
    elif how == "ring_read_unmasked":
        real_attend = hyb_ops.dense_decode_attend

        def unmasked(q, kv, pos, limit, scale):
            if kv.shape[1] == window:
                pos = 0 * pos + window - 1
            return real_attend(q, kv, pos, limit, scale)

        rebind(hyb_ops, "dense_decode_attend", unmasked)
    elif how == "routed_part_left_out":
        real_once = lat_ops.held_experts_once
        rebind(lat_ops, "held_experts_once",
               lambda xs, *args, **kw: 0.0 * real_once(xs, *args, **kw))
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
    ap.add_argument("--breaks", default="sound,ring_one_off,"
                    "routed_part_left_out")
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
            with broken(None if how == "sound" else how,
                        sizes["sliding_window"]):
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
