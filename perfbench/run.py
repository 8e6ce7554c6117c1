#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result: one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device``
(``--trace 1``: the per-layer metrics, ``busy_s``/``window_s`` and a
``breakdown``), then ``compared``: every number ``correct`` compared,
beside its limit; the same are the last lines of standard error. Without a TPU, or with fewer chips than the cell asks
for, the run exits non-zero and prints no result. ``--rehearse`` walks the
same control flow at a tiny size on whatever JAX finds (the CPU in the
sandbox), prints no result and exits 5: a rehearsal is never a result.

Everything about a cell is data: see perfbench/README.md.
"""

from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> int:
    from harness import common             # starts the set-up clock
    from harness.loader import BenchmarkError, Cell

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, any platform, no result, exit 5")
    args = ap.parse_args(argv)
    try:
        cell = Cell(args.workload)
    except BenchmarkError as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    try:
        import tensorflow_distributed_tpu  # noqa: F401
    except ImportError as e:
        print(f"perfbench: the system under test is not here: {e}",
              file=sys.stderr)
        return 3
    if cell.kind == "train":
        from harness import train_runner as runner
    else:
        from harness import serve_runner as runner
    res = runner.run(cell, args.seed, args.seconds, bool(args.trace),
                     rehearse=args.rehearse)
    if args.rehearse:
        print(f"perfbench: rehearsal of {cell.name} finished (correct="
              f"{res['correct']}) — not a chip result", flush=True)
    else:
        print(common.result_line(res["correct"], res["attempted"],
                                 res["failed"], res["metrics"], res["device"],
                                 res["breakdown"], res["compared"].rows),
              flush=True)
    # what a record of a run that is not correct keeps: the end of this
    print("\n".join(res["compared"].lines), file=sys.stderr, flush=True)
    return common.EXIT_REHEARSED if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
