"""The state-space parts of a decode step and of a prefill, from the device
ops inside each execution of the serve programs.

The program's two Pallas kernels appear in a capture under their names
(``%ssd_state_step.N``, ``%ssd_chunk_scan.N``); the convolution is
anonymous fusions and stays in the remainder. A program without these
kernels (the parent of the PR that added them, any other model) gives
nothing to read.
"""

from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

from . import decode_parts as D
from . import hybrid_parts as H

STATE_STEP = re.compile(r"^%ssd_state_step")
CHUNK_SCAN = re.compile(r"^%ssd_chunk_scan")


def _kernel_calls(trace, is_program, kernel):
    """(what ``is_program`` made of the module's name, the ops inside that
    execution whose name ``kernel`` matches) for every execution in the
    trace of a program ``is_program`` accepts that ran the kernel."""
    if trace is None:
        return
    for dev in trace.devices.values():
        for name, s, dur in dev["modules"]:
            found = is_program(name)
            if not found:
                continue
            mine = [o for o in D.ops_inside(dev, s, dur)
                    if kernel.match(o[0])]
            if mine:
                yield found, mine


def decode_kernels(trace) -> Optional[Dict[str, float]]:
    """Over the decode steps of the trace that ran the state step:
    ``steps``, and the kernel's device seconds and calls (``state_s``,
    ``state_calls``)."""
    out = {"steps": 0.0, "state_s": 0.0, "state_calls": 0.0}
    for _, mine in _kernel_calls(
            trace, lambda n: n.startswith(D.DECODE_MODULE), STATE_STEP):
        out["steps"] += 1
        out["state_calls"] += len(mine)
        out["state_s"] += sum(o[2] for o in mine) / 1e9
    return out if out["steps"] else None


def prefill_scans(trace) -> Tuple[float, list]:
    """(device seconds of the chunk-scan kernel over the prefills in the
    trace, [(bucket, calls)] of the prefills that ran it)."""
    seconds, found = 0.0, []
    for m, mine in _kernel_calls(trace, D.PREFILL_MODULE.match, CHUNK_SCAN):
        seconds += sum(o[2] for o in mine) / 1e9
        found.append((int(m.group(1)), len(mine)))
    return seconds, found


def counts(ctx) -> Optional[Dict]:
    """What a decode step of the CAPTURE needed, from the program's
    counters: ``live`` rows a step (the capture's own, from its fetch
    spans), and of the run's averages a live row: the cached positions its
    attention layers attend (``keys``) and, a step, the held experts the
    routed pairs reached (``experts_hit``, summed over the layers). None
    where the run's ``serve_summary`` lacks this family's counters."""
    s = D.summary_of(ctx.records)
    if not s or not s.get("state_rows_stepped") \
            or not s.get("decode_live_rows") \
            or s.get("conv_bytes_per_slot") is None:
        return None
    live = H.capture_live_rows(ctx)
    if live is None:
        return None
    return {"live": live, "summary": s,
            "keys": live * s.get("attend_keys", 0) / s["decode_live_rows"],
            "experts_hit": (s["moe_experts_hit"] / s["decode_steps"]
                            if s.get("moe_experts_hit") is not None
                            and s.get("decode_steps") else None)}
