"""The per-channel state-space parts (Mamba-1's selective scan) of a decode
step and of a prefill, from the device ops inside each execution of the
serve programs.

The program's two Pallas kernels appear in a capture under their names
(``%s6_state_step.N``, ``%s6_chunk_scan.N``); the convolution, the
projections and the three inner norms are anonymous fusions and stay in the
remainder. A program without these kernels (the parent of the PR that added
them, any other model) gives nothing to read, and neither does a model file
without the counts the roofline readers divide by.
"""

from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

from . import decode_parts as D
from . import hybrid_parts as H
from . import ssm_parts as S

STATE_STEP = re.compile(r"^%s6_state_step")
CHUNK_SCAN = re.compile(r"^%s6_chunk_scan")


def decode_kernels(trace) -> Optional[Dict[str, float]]:
    """Over the decode steps of the trace that ran the state step:
    ``steps``, and the kernel's device seconds and calls (``state_s``,
    ``state_calls``)."""
    out = {"steps": 0.0, "state_s": 0.0, "state_calls": 0.0}
    for _, mine in S._kernel_calls(
            trace, lambda n: n.startswith(D.DECODE_MODULE), STATE_STEP):
        out["steps"] += 1
        out["state_calls"] += len(mine)
        out["state_s"] += sum(o[2] for o in mine) / 1e9
    return out if out["steps"] else None


def prefill_scans(trace) -> Tuple[float, list]:
    """(device seconds of the scan kernel over the prefills in the trace,
    [(bucket, calls)] of the prefills that ran it)."""
    seconds, found = 0.0, []
    for m, mine in S._kernel_calls(trace, D.PREFILL_MODULE.match,
                                   CHUNK_SCAN):
        seconds += sum(o[2] for o in mine) / 1e9
        found.append((int(m.group(1)), len(mine)))
    return seconds, found


def live_rows(ctx) -> Optional[float]:
    """Live rows a decode step of the CAPTURE (from its fetch spans), where
    the run's ``serve_summary`` carries the state step's counters; None
    otherwise."""
    s = D.summary_of(ctx.records)
    if not s or not s.get("state_rows_stepped") \
            or not s.get("decode_live_rows"):
        return None
    return H.capture_live_rows(ctx)
