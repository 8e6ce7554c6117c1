"""Reduction of a profiler trace to the per-layer numbers.

The benchmark's own code (the program has ``observe/xprof.py``, which has
only ever parsed CPU captures). Written after looking at a chip trace by
hand; what the trace of a TPU v5e looks like (jax 0.9.0, libtpu 0.0.34) is
recorded in PERF.md section 3 and pinned by the fixture under
``tests/benchmark/fixtures``.

A trace is reduced to plain tuples first (``load_xplane`` or
``load_json``), so that the arithmetic below runs the same on the recorded
fixture and on a fresh capture:

    Trace.devices   {device id: {"ops": [Ev], "async": [Ev], "modules": [Ev]}}
                    ("XLA Ops", "Async XLA Ops", "XLA Modules" lines)
    Trace.host      [Ev]   the harness's own spans (names "bench.*")
    Ev = (name, start_ns, duration_ns)

All results are in seconds unless a name says otherwise.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import json
import os
import re
from typing import Callable, Dict, Iterable, List, Optional, Tuple

Ev = Tuple[str, int, int]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
LINES = {OPS_LINE: "ops", ASYNC_LINE: "async", MODULES_LINE: "modules"}
_INSTR = re.compile(r"^(%[^ ]+) = (.*?) ([a-z][a-z0-9\-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
HOST_SPAN_PREFIX = "bench."
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)")


@dataclasses.dataclass
class Trace:
    devices: Dict[int, Dict[str, List[Ev]]]
    host: List[Ev]
    start_ns: int
    end_ns: int

    @property
    def window_s(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


def short_name(text: str) -> str:
    """The trace of a v5e names a device op by its whole HLO instruction
    (hundreds of characters). Keep the instruction's name; for a custom
    call (a Pallas kernel is one, target ``tpu_custom_call``) also the
    target and the result's type without layouts, which is what tells the
    flash kernels apart: ``%attn.97 tpu_custom_call (bf16[128,1024,64],
    bf16[128,1024,64])``."""
    m = _INSTR.match(text)
    if not m:
        return text[:120]
    instr, out, opcode = m.groups()
    if opcode != "custom-call":
        return instr
    target = _TARGET.search(text)
    return (f"{instr} {target.group(1) if target else 'custom-call'} "
            f"{_LAYOUT.sub('', out)}")[:160]


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[int, Dict[str, List[Ev]]] = {}
    host: List[Ev] = []
    lo, hi = None, None
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in LINES:
                key = LINES[line.name]
                evs = devices.setdefault(
                    int(m.group(1)),
                    {"ops": [], "async": [], "modules": []})[key]
                names: Dict[str, str] = {}
                for e in line.events:
                    name = e.name
                    if key != "modules":
                        name = names.get(name) or names.setdefault(
                            name, short_name(name))
                    evs.append((name, int(e.start_ns),
                                int(e.duration_ns)))
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    if e.name.startswith(HOST_SPAN_PREFIX):
                        host.append((e.name, int(e.start_ns),
                                     int(e.duration_ns)))
    for evs in [host] + [d[k] for d in devices.values() for k in d]:
        for _, s, d in evs:
            lo = s if lo is None else min(lo, s)
            hi = s + d if hi is None else max(hi, s + d)
    if lo is None:
        lo = hi = 0
    for d in devices.values():
        for evs in d.values():
            evs.sort(key=lambda e: e[1])
    host.sort(key=lambda e: e[1])
    return Trace(devices, host, lo, hi)


def dump_json(trace: Trace, path: str, max_ops_per_device: int = 0) -> None:
    """Write the reduced trace (optionally its first ops only) as
    gzipped JSON: the fixture format."""
    devices = {}
    end = trace.end_ns
    for dev, d in trace.devices.items():
        ops = d["ops"]
        if max_ops_per_device and len(ops) > max_ops_per_device:
            ops = ops[:max_ops_per_device]
            end = min(end, ops[-1][1] + ops[-1][2])
        devices[str(dev)] = {"ops": ops, "async": d.get("async", []),
                             "modules": d["modules"]}
    for d in devices.values():
        for key in ("async", "modules"):
            d[key] = [m for m in d[key] if m[1] + m[2] <= end]
    obj = {"devices": devices,
           "host": [h for h in trace.host if h[1] + h[2] <= end],
           "start_ns": trace.start_ns, "end_ns": end}
    with gzip.open(path, "wt") as f:
        json.dump(obj, f)


def load_json(path: str) -> Trace:
    with gzip.open(path, "rt") as f:
        obj = json.load(f)
    devices = {int(k): {key: [tuple(e) for e in v.get(key, [])]
                        for key in ("ops", "async", "modules")}
               for k, v in obj["devices"].items()}
    return Trace(devices, [tuple(e) for e in obj["host"]],
                 obj["start_ns"], obj["end_ns"])


# ---------------------------------------------------------------- arithmetic

def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge (start, end) intervals."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Iterable[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Tuple[int, int]], b: List[Tuple[int, int]]
             ) -> List[Tuple[int, int]]:
    """The parts of merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _spans(evs: Iterable[Ev]) -> List[Tuple[int, int]]:
    return [(s, s + d) for _, s, d in evs]


def busy_s(trace: Trace) -> float:
    """Seconds in which an operation ran on the device: the union of the
    op intervals, averaged over the devices in the trace."""
    if not trace.devices:
        return 0.0
    per = [total(union(_spans(d["ops"]))) for d in trace.devices.values()]
    return sum(per) / len(per) / 1e9


def idle_share(trace: Trace) -> Optional[float]:
    """1 - busy over the traced window, in percent."""
    if not trace.devices or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - busy_s(trace) / trace.window_s)


def module_calls(trace: Trace, match: Callable[[str], bool]
                 ) -> List[float]:
    """Device seconds of every execution of the modules ``match`` picks:
    the union of the op intervals inside each module event, on every
    device (a four-chip step gives four calls)."""
    out = []
    for d in trace.devices.values():
        ops = d["ops"]
        starts = [o[1] for o in ops]
        for name, s, dur in d["modules"]:
            if not match(name):
                continue
            i = bisect.bisect_left(starts, s)
            inside = []
            while i < len(ops) and ops[i][1] < s + dur:
                inside.append((ops[i][1], min(ops[i][1] + ops[i][2],
                                              s + dur)))
                i += 1
            out.append(total(union(inside)) / 1e9)
    return out


def op_time(trace: Trace, match: Callable[[str], bool]
            ) -> Tuple[float, int]:
    """(device seconds summed over devices, number of events) of the ops
    ``match`` picks."""
    t, n = 0, 0
    for d in trace.devices.values():
        for name, _, dur in d["ops"]:
            if match(name):
                t += dur
                n += 1
    return t / 1e9, n


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.match(name.lstrip("%")))


def exposed_collective_s(trace: Trace) -> Optional[float]:
    """Collective op time (synchronous ones on the "XLA Ops" line and
    asynchronous start-to-done spans on "Async XLA Ops") during which no
    other op runs on that device, averaged over the devices. None where
    the trace holds no collective."""
    per, seen = [], False
    for d in trace.devices.values():
        coll = union(_spans(o for o in d["ops"] + d.get("async", [])
                            if is_collective(o[0])))
        rest = union(_spans(o for o in d["ops"] if not is_collective(o[0])))
        seen = seen or bool(coll)
        per.append(total(subtract(coll, rest)))
    if not seen:
        return None
    return sum(per) / len(per) / 1e9


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """The device operations that took most time, by name, summed over
    devices and divided by the number of devices."""
    acc: Dict[str, int] = {}
    for d in trace.devices.values():
        for name, _, dur in d["ops"]:
            acc[name] = acc.get(name, 0) + dur
    k = max(1, len(trace.devices))
    return [[name, t / 1e9 / k] for name, t in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, n: int = 10) -> List[List]:
    """The longest idle gaps of the first device, each named by the
    harness span the host was in at the gap's midpoint (the spans do not
    overlap: the last one to start before the midpoint is looked at) ("outside harness
    spans" where it was in none: the program writes no spans of its own
    on the profiler's clock yet), summed by name."""
    if not trace.devices:
        return []
    d = trace.devices[min(trace.devices)]
    busy = union(_spans(d["ops"]))
    gaps = subtract([(trace.start_ns, trace.end_ns)], busy)
    starts = [h[1] for h in trace.host]          # sorted by load_*
    acc: Dict[str, int] = {}
    for s, e in gaps:
        mid = (s + e) // 2
        name = "outside harness spans"
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and mid < trace.host[i][1] + trace.host[i][2]:
            name = trace.host[i][0]
        acc[name] = acc.get(name, 0) + (e - s)
    return [[name, t / 1e9] for name, t in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]
