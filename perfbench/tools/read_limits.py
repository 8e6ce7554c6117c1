#!/usr/bin/env python3
"""Read, on the chip and in one process, the numbers the limits of
``correct`` are set from: for each seed a short run of the cell through
the runner (the program's numbers against the reference), and for the
first ``--controls`` seeds the control too (the reference in the program's
place at the precision below the stated one).

    python3 perfbench/tools/read_limits.py --workload gpt2m-train-dp1 \\
        --seeds 1001,1002,1003 --controls 3 --seconds 2 --out chiprun_out/limits.json

Prints, per number, the largest the sound runs gave and the smallest the
control gave. Sets nothing: the limits are written by hand into the
configuration file, with these readings in PERF.md.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (ROOT, os.path.dirname(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> int:
    from harness.loader import Cell

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--control-precision", default="fp8")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    cell = Cell(args.workload)
    if cell.kind == "train":
        from harness import train_runner as runner
    else:
        from harness import serve_runner as runner
    rows = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        res = runner.run(cell, seed, args.seconds, False,
                         rehearse=args.rehearse,
                         control=(args.control_precision
                                  if i < args.controls else None))
        check = res["check"]
        sound = check.get("numbers") or {
            "served_token_gap_max": check["max"],
            "served_token_gap_mean": check["mean"]}
        control = check.get("control")
        if control and "max" in control:
            control = {"served_token_gap_max": control["max"],
                       "served_token_gap_mean": control["mean"]}
        rows.append({"seed": seed, "correct": res["correct"],
                     "sound": sound, "control": control})
        print(f"[limits] seed {seed}: correct={res['correct']} "
              f"sound={sound} control={control}", flush=True)
    names = list(rows[0]["sound"])
    print("[limits] number: largest sound / smallest control / ratio")
    for n in names:
        hi = max(r["sound"][n] for r in rows)
        lows = [r["control"][n] for r in rows if r["control"]]
        lo = min(lows) if lows else float("nan")
        print(f"[limits] {n}: {hi:.6g} / {lo:.6g} / "
              f"{lo / hi if hi else float('inf'):.3g}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
