#!/usr/bin/env python3
"""Look at the program's own spans in a profiler capture, beside the
device's idle time, and cut a fixture for tests/benchmark.

    python3 perfbench/tools/spans_look.py <trace dir or .xplane.pb> \
        [fixture.json.gz [steps [merge_ns]]] [find=<text>]

Prints every ``tfd.*`` span name with its count and total, the split of
the first device's idle time by innermost span
(harness/program_spans.py), how much of the idle time lies inside XLA
module events (gaps between the ops of a running program, which no host
code fills), and with ``find=`` the first device op whose name holds the
text, with its stats (where a kernel's ``name`` arrives). With a second
argument it writes ``steps`` decode steps from the middle of the capture
in the fixture format of harness/trace.py plus a ``spans`` list; device
ops are stored as busy intervals, merged where they lie ``merge_ns`` or
less apart (0: only where they touch).
"""

import gzip
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def busy_intervals(ops, merge_ns):
    out = []
    for s, e in sorted((s, s + d) for _, s, d in ops):
        if out and s <= out[-1][1] + merge_ns:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def first_device_event(path, text):
    """The first device event whose name holds ``text``: where it is,
    its name and its stats."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if text in e.name:
                    stats = {k: str(v)[:200] for k, v in e.stats}
                    return (f"plane {plane.name} line {line.name!r} name "
                            f"{e.name[:300]!r} stats {stats}")
    return None


def cut_fixture(tr, spans, path, steps, merge_ns):
    from harness import program_spans as P

    dev = min(tr.devices)
    d = tr.devices[dev]
    decode = [m for m in d["modules"] if m[0].startswith(P.DECODE_MODULE)]
    first = max(0, len(decode) // 2 - steps // 2)
    chosen = decode[first:first + steps]
    lo = chosen[0][1] - 1_000_000          # 1 ms of lead-in
    hi = chosen[-1][1] + chosen[-1][2] + 1_000_000

    def inside(evs):
        return [list(e) for e in evs if e[1] >= lo and e[1] + e[2] <= hi]

    ops = [["busy", s, e - s] for s, e in busy_intervals(
        [o for o in d["ops"] if o[1] >= lo and o[1] + o[2] <= hi],
        merge_ns)]
    obj = {"devices": {str(dev): {"ops": ops, "async": [],
                                  "modules": inside(d["modules"])}},
           "host": inside(tr.host), "spans": inside(spans),
           "start_ns": lo, "end_ns": hi, "merged_gap_ns": merge_ns,
           "note": "device ops are busy intervals, not single ops"}
    with gzip.open(path, "wt") as f:
        json.dump(obj, f)
    print(f"wrote {path}: {os.path.getsize(path)} bytes, {len(chosen)} "
          f"decode steps, {len(ops)} busy intervals (merge {merge_ns} ns), "
          f"{len(obj['spans'])} spans, window {(hi - lo) / 1e6:.3f} ms")


def main(argv) -> int:
    from harness import program_spans as P
    from harness import trace as T

    find = [a[5:] for a in argv[1:] if a.startswith("find=")]
    args = [a for a in argv[1:] if not a.startswith("find=")]
    path = args[0]
    if os.path.isdir(path):
        path = T.find_xplane(path)
    print(f"capture {path}: {os.path.getsize(path)} bytes")
    tr = T.load_xplane(path)
    spans = P.load_spans(path)
    by = {}
    for name, _, dur in spans:
        n, t = by.get(name, (0, 0))
        by[name] = (n + 1, t + dur)
    print(f"{len(spans)} program spans on the host planes:")
    for name, (n, t) in sorted(by.items()):
        print(f"  {name}: {n} spans, {t / 1e6:.3f} ms in all, "
              f"{t / n / 1e3:.1f} us each")
    if tr.devices:
        idle = P.idle_by_span(tr, spans)
        total = sum(idle.values())
        print(f"window {tr.window_s:.6f}s, idle {total:.6f}s "
              f"({T.idle_share(tr):.3f}%), "
              f"{P.module_count(tr, P.DECODE_MODULE)} decode steps, "
              f"{P.module_count(tr, P.PREFILL_MODULE)} prefills")
        for name in sorted(idle, key=lambda n: -idle[n]):
            print(f"  {name}: {idle[name]:.6f}s "
                  f"{100 * idle[name] / max(total, 1e-12):.2f}%")
        d = tr.devices[min(tr.devices)]
        gaps = P.idle_intervals(tr)
        mods = T.union((s, s + dur) for _, s, dur in d["modules"])
        outside = T.total(T.subtract(gaps, mods))
        print(f"idle inside XLA module events {total - outside / 1e9:.6f}s,"
              f" between them {outside / 1e9:.6f}s")
        for m in (0, 200, 1000):
            print(f"  busy intervals at merge {m} ns: "
                  f"{len(busy_intervals(d['ops'], m))} of "
                  f"{len(d['ops'])} ops")
    for text in find:
        print(f"find {text!r}: "
              + (first_device_event(path, text)
                 or "no device event holds it"))
    if len(args) > 1:
        cut_fixture(tr, spans, args[1],
                    int(args[2]) if len(args) > 2 else 30,
                    int(args[3]) if len(args) > 3 else 0)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
