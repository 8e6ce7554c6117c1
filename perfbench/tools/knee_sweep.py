#!/usr/bin/env python3
"""Find the knee of a serve cell once, by one sweep in one process: the
highest offered rate at which the backlog does not grow through the window.

    python3 perfbench/tools/knee_sweep.py --workload gpt2l-serve-steady \\
        --rates 3,4,5,6,7,8 --seconds 20 --seed 11

For each rate it prints the tails, the tokens completed a second, how long
the system needed after the last arrival to drain, and the mean TTFT of the
requests due in the second half of the window over that of the first half
(a backlog that grows shows as a ratio well above 1 and a long drain). The
fixed rates of the cells are then written by hand into the traffic files.
"""

import argparse
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (ROOT, os.path.dirname(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> int:
    from harness import serve_runner
    from harness.loader import Cell

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = Cell(args.workload)
    for rate in (float(r) for r in args.rates.split(",")):
        res = serve_runner.run(
            cell, args.seed, args.seconds, False, rehearse=args.rehearse,
            mix_update={"rate_rps": rate, "stop_fraction": 1.0})
        pts = sorted(res["ttft_by_arrival"])
        half = args.seconds / 2
        first = [t for a, t in pts if a <= half] or [float("nan")]
        second = [t for a, t in pts if a > half] or [float("nan")]
        v = res["values"]
        print(f"[knee] rate {rate:g}/s: {res['attempted']} requests "
              f"{res['failed']} failed; ttft p50 "
              f"{v.get('serve_ttft_p50_ms', float('nan')):.1f} ms, tpot p95 "
              f"{v.get('serve_tpot_p95_ms', float('nan')):.2f} ms, "
              f"{v['serve_tokens_per_s']:.1f} tokens/s; drain "
              f"{res['wall_s'] - pts[-1][0]:.2f}s after the last arrival; "
              f"mean ttft second half / first half "
              f"{statistics.mean(second) / statistics.mean(first):.2f} "
              f"({statistics.mean(first):.1f} -> "
              f"{statistics.mean(second):.1f} ms); correct "
              f"{res['correct']}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
