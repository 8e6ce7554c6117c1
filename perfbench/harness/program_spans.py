"""Device idle time laid against the program's own host spans.

The program writes ``tfd.*`` spans on the profiler's clock
(``tensorflow_distributed_tpu/observe/trace.py::HostSpans``): they land
on the ``/host:`` planes of the same ``.xplane.pb`` as the device's
``XLA Ops``. ``harness/trace.py`` keeps only the harness's own
``bench.*`` spans and names an idle gap by the span at its midpoint;
this module reads the program's spans and splits every idle interval
EXACTLY among the innermost spans that cover it, so that "the chip sat
idle 30% of the window" becomes "so many ms a step while the host
fetched tokens, so many while it launched, so many in the scheduler".

A program without such spans (the parent of the PR that added them)
gives nothing to read: every reader returns None.

    Ev = (name, start_ns, duration_ns), as in harness/trace.py
"""

from __future__ import annotations

import gzip
import json
import os
from typing import Any, Dict, Iterable, List, Optional, Tuple

from . import trace as T
from .loader import ROOT

Ev = T.Ev
Seg = Tuple[int, int, str]

SPAN_PREFIX = "tfd."
UNATTRIBUTED = "unattributed"
DECODE_MODULE = "jit_serve_decode_step"
PREFILL_MODULE = "jit_serve_prefill_b"

FETCH = ("tfd.serve.token_fetch",)
LAUNCH = ("tfd.serve.step_upload", "tfd.serve.step_dispatch")
SCHED = ("tfd.serve.retire", "tfd.serve.tail", "tfd.serve.poll")
ADMIT = ("tfd.serve.admit", "tfd.serve.prefill_launch",
         "tfd.serve.first_token_fetch")

_PARSED: Dict[str, List[Ev]] = {}


def load_spans(xplane_path: str) -> List[Ev]:
    """The ``tfd.*`` events of the ``/host:`` planes of one capture,
    sorted by start. Parsed once per path: five readers share it."""
    if xplane_path not in _PARSED:
        from jax.profiler import ProfileData

        spans: List[Ev] = []
        for plane in ProfileData.from_file(xplane_path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, int(e.start_ns),
                                      int(e.duration_ns)))
        spans.sort(key=lambda e: e[1])
        _PARSED[xplane_path] = spans
    return _PARSED[xplane_path]


def load_spans_json(path: str) -> List[Ev]:
    """The ``spans`` list of a fixture (``harness/trace.py``'s format
    with that one key more)."""
    with gzip.open(path, "rt") as f:
        return [tuple(e) for e in json.load(f).get("spans", [])]


def innermost(spans: Iterable[Ev]) -> List[Seg]:
    """Disjoint ``(start, end, name)`` segments in time order, each
    named by the innermost span that covers it. Spans of one thread
    nest; a child that outlasts its parent is cut at the parent's end."""
    out: List[Seg] = []
    stack: List[Tuple[str, int]] = []          # (name, end)
    cur = 0

    def close_until(t: Optional[int]) -> None:
        nonlocal cur
        while stack and (t is None or stack[-1][1] <= t):
            name, end = stack.pop()
            if end > cur:
                out.append((cur, end, name))
                cur = end

    for name, start, dur in sorted(spans, key=lambda e: (e[1], -e[2])):
        close_until(start)
        end = start + dur
        if stack:
            if start > cur:
                out.append((cur, start, stack[-1][0]))
            end = min(end, stack[-1][1])
        cur = max(cur, start) if stack else start
        stack.append((name, end))
    close_until(None)
    return out


def idle_intervals(trace: T.Trace) -> List[Tuple[int, int]]:
    """The first device's idle intervals inside the traced window, as
    ``harness/trace.py::idle_gaps`` has them."""
    if not trace.devices:
        return []
    d = trace.devices[min(trace.devices)]
    busy = T.union((s, s + dur) for _, s, dur in d["ops"])
    return T.subtract([(trace.start_ns, trace.end_ns)], busy)


def idle_by_span(trace: T.Trace, spans: Iterable[Ev]) -> Dict[str, float]:
    """Seconds of device idle time inside each program span (innermost
    span at every instant, no midpoint rule), and ``unattributed`` for
    idle time no span covers. The values add up to the idle time of
    the window."""
    gaps = idle_intervals(trace)
    segs = innermost(spans)
    acc: Dict[str, int] = {}
    j = 0
    for s, e in gaps:
        while j < len(segs) and segs[j][1] <= s:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < e:
            lo, hi = max(s, segs[k][0]), min(e, segs[k][1])
            if hi > lo:
                acc[segs[k][2]] = acc.get(segs[k][2], 0) + (hi - lo)
            k += 1
    out = {name: t / 1e9 for name, t in acc.items()}
    out[UNATTRIBUTED] = (T.total(gaps) - sum(acc.values())) / 1e9
    return out


def module_count(trace: T.Trace, prefix: str) -> int:
    """Executions of the modules named ``prefix*`` on the first device."""
    if not trace.devices:
        return 0
    mods = trace.devices[min(trace.devices)]["modules"]
    return sum(1 for name, _, _ in mods if name.startswith(prefix))


def _spans_of(ctx: Any) -> Optional[List[Ev]]:
    """The program spans of the run ``ctx`` describes: what a test put
    there, else the run's own capture. None where there is none."""
    given = getattr(ctx, "program_spans", None)
    if given is not None:
        return given or None
    try:
        path = T.find_xplane(os.path.join(
            ROOT, ".cache", "perfbench", ctx.cell.name, "trace"))
    except FileNotFoundError:
        return None
    return load_spans(path) or None


def split(ctx: Any) -> Optional[Dict[str, Any]]:
    """``{"idle": idle_by_span, "steps": decode steps, "admissions":
    prefills, "idle_s": sum}`` of the run's capture, computed and said
    once per run; None without a capture or without program spans."""
    if "_idle_split" in ctx.__dict__:
        return ctx._idle_split
    ctx._idle_split = None
    spans = None if ctx.trace is None else _spans_of(ctx)
    if not spans:
        ctx.say("program spans: none in this capture (the program "
                "writes no tfd.* span), nothing to read")
        return None
    idle = idle_by_span(ctx.trace, spans)
    idle_s = sum(idle.values())
    res = {"idle": idle, "idle_s": idle_s,
           "steps": module_count(ctx.trace, DECODE_MODULE),
           "admissions": module_count(ctx.trace, PREFILL_MODULE)}
    ctx._idle_split = res
    counts: Dict[str, int] = {}
    for name, _, _ in spans:
        counts[name] = counts.get(name, 0) + 1
    ctx.say(f"device idle by program span: {idle_s:.6f}s idle in a "
            f"window of {ctx.trace.window_s:.6f}s, {res['steps']} decode "
            f"steps, {res['admissions']} admissions, {len(spans)} spans")
    for name in sorted(idle, key=lambda n: -idle[n]):
        ctx.say(f"  {name}: {idle[name]:.6f}s "
                f"({100 * idle[name] / max(idle_s, 1e-12):.2f}% of idle, "
                f"{counts.get(name, 0)} spans)")
    return res


def idle_ms_per(ctx: Any, names: Iterable[str], per: str
                ) -> Optional[float]:
    """Idle milliseconds inside the spans ``names`` per decode step
    (``per="steps"``) or per admission (``per="admissions"``)."""
    res = split(ctx)
    if res is None or not res[per]:
        return None
    return 1e3 * sum(res["idle"].get(n, 0.0) for n in names) / res[per]
