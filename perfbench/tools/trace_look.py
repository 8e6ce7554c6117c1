#!/usr/bin/env python3
"""Look at a profiler capture by hand: planes, lines, the first events of
each line with their stats, and the names that take most device time.

    python3 perfbench/tools/trace_look.py <trace dir or .xplane.pb> [fixture.json.gz [max_ops]]

With a second argument it also writes the reduced trace (its first
``max_ops`` device ops) in the fixture format of harness/trace.py.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main(argv) -> int:
    from jax.profiler import ProfileData

    from harness import trace as T

    path = argv[1]
    if os.path.isdir(path):
        path = T.find_xplane(path)
    data = ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events")
            if plane.name.startswith("/device:"):
                for e in events[:4]:
                    stats = {k: (str(v)[:60]) for k, v in e.stats}
                    print(f"    {e.name[:70]!r} start={e.start_ns} "
                          f"dur={e.duration_ns} stats={stats}")
    tr = T.load_xplane(path)
    print(f"window {tr.window_s:.4f}s busy {T.busy_s(tr):.4f}s "
          f"idle {T.idle_share(tr)}%")
    print("top ops:", *T.top_ops(tr, 25), sep="\n  ")
    mods = {}
    for d in tr.devices.values():
        for name, _, dur in d["modules"]:
            k = mods.setdefault(name.split("(")[0], [0, 0])
            k[0] += 1
            k[1] += dur
    print("modules:", *sorted(mods.items()), sep="\n  ")
    print("idle gaps:", *T.idle_gaps(tr), sep="\n  ")
    if len(argv) > 2:
        T.dump_json(tr, argv[2], int(argv[3]) if len(argv) > 3 else 0)
        print("wrote", argv[2], os.path.getsize(argv[2]), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
