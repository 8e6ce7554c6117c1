#!/usr/bin/env python3
"""Look at the device ops INSIDE the serve programs of a capture: one
decode step's ops in order (which kernel follows which), and the ops of
the decode steps and of the prefills summed by name without the
instruction number. What ``harness/decode_parts.py`` attributes by order
was first read here by hand.

    python3 perfbench/tools/step_look.py <trace dir or .xplane.pb> [module prefix]
"""

import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main(argv) -> int:
    from harness import decode_parts as D
    from harness import trace as T

    path = argv[1]
    if os.path.isdir(path):
        path = T.find_xplane(path)
    prefix = argv[2] if len(argv) > 2 else D.DECODE_MODULE
    tr = T.load_xplane(path)
    dev = tr.devices[min(tr.devices)]
    calls = [(n, s, d) for n, s, d in dev["modules"] if n.startswith(prefix)]
    if not calls:
        print("no module", prefix, "in", sorted(
            {m[0].split("(")[0] for m in dev["modules"]}))
        return 1
    calls.sort(key=lambda c: c[2])
    name, s, dur = calls[len(calls) // 2]
    print(f"{len(calls)} calls of {prefix}; the median one: {name} "
          f"{dur / 1e6:.3f} ms")
    for o_name, o_s, o_dur in D.ops_inside(dev, s, dur):
        print(f"  +{(o_s - s) / 1e3:9.1f} us {o_dur / 1e3:9.1f} us  {o_name}")
    for pre in (D.DECODE_MODULE, "jit_serve_prefill_b"):
        acc, n_calls = {}, 0
        for m_name, m_s, m_dur in dev["modules"]:
            if not m_name.startswith(pre):
                continue
            n_calls += 1
            for o_name, _, o_dur in D.ops_inside(dev, m_s, m_dur):
                key = re.sub(r"\.\d+( |$)", r"\1", o_name)
                a = acc.setdefault(key, [0, 0])
                a[0] += o_dur
                a[1] += 1
        print(f"{pre}: {n_calls} calls, ops by name (ms a call, ops a call):")
        for key, (t, n) in sorted(acc.items(), key=lambda kv: -kv[1][0])[:40]:
            print(f"  {t / 1e6 / max(n_calls, 1):9.3f} ms "
                  f"{n / max(n_calls, 1):7.1f}  {key}")
    parts = D.decode_parts(tr)
    print("decode_parts:", parts)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
