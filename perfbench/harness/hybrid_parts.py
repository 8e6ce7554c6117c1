"""The parts of a decode step and of a prefill of a hybrid of linear and
block-sparse attention layers, from the device ops inside each execution
of the serve programs.

The program's four Pallas kernels appear in a capture under their names
(``%lightning_state_step.N``, ``%lightning_chunk_scan.N``,
``%sparse_block_scores.N``, ``%sparse_block_attend.N``); everything else
is anonymous fusions and sorts. A sparse layer's SELECTION (block maxima,
forced blocks, the top-k, the attend's mask) is therefore read by ORDER,
as ``decode_parts`` reads GLM's: the ops between a layer's score kernel
and its attend kernel can only be that layer's own. A program without
these kernels (the parent of the PR that added them, any other model)
gives nothing to read.
"""

from __future__ import annotations

import os
import re
from typing import Dict, Optional, Tuple

from . import decode_parts as D
from . import trace as T
from .loader import ROOT

STATE_STEP = re.compile(r"^%lightning_state_step")
CHUNK_SCAN = re.compile(r"^%lightning_chunk_scan")
BLOCK_SCORES = re.compile(r"^%sparse_block_scores")
BLOCK_ATTEND = re.compile(r"^%sparse_block_attend")


def decode_kernels(trace) -> Optional[Dict[str, float]]:
    """Over the decode steps of the trace that ran any of the kernels:
    ``steps``, and device seconds and calls of the state step
    (``state_s``, ``state_calls``), the score kernel, the attend kernel,
    and ``select_s``: from each score kernel to its attend kernel, both
    included."""
    if trace is None:
        return None
    out = dict.fromkeys(("steps", "state_s", "state_calls", "scores_s",
                         "scores_calls", "attend_s", "attend_calls",
                         "select_s"), 0.0)
    for dev in trace.devices.values():
        for name, s, dur in dev["modules"]:
            if not name.startswith(D.DECODE_MODULE):
                continue
            ops = D.ops_inside(dev, s, dur)
            if not any(STATE_STEP.match(o[0]) or BLOCK_SCORES.match(o[0])
                       for o in ops):
                continue
            out["steps"] += 1
            selecting = False
            for o_name, _, o_dur in ops:
                for key, pattern in (("state", STATE_STEP),
                                     ("scores", BLOCK_SCORES),
                                     ("attend", BLOCK_ATTEND)):
                    if pattern.match(o_name):
                        out[key + "_s"] += o_dur / 1e9
                        out[key + "_calls"] += 1
                selecting = selecting or bool(BLOCK_SCORES.match(o_name))
                if selecting:
                    out["select_s"] += o_dur / 1e9
                if BLOCK_ATTEND.match(o_name):
                    selecting = False
    return out if out["steps"] else None


def prefill_scans(trace) -> Tuple[float, list]:
    """(device seconds of the chunk-scan kernel over the prefills in the
    trace, [(bucket, calls)] of the prefills that ran it)."""
    seconds, found = 0.0, []
    if trace is None:
        return seconds, found
    for dev in trace.devices.values():
        for name, s, dur in dev["modules"]:
            m = D.PREFILL_MODULE.match(name)
            if not m:
                continue
            mine = [o for o in D.ops_inside(dev, s, dur)
                    if CHUNK_SCAN.match(o[0])]
            if mine:
                seconds += sum(o[2] for o in mine) / 1e9
                found.append((int(m.group(1)), len(mine)))
    return seconds, found


def roofline(ctx, name: str, ops: float, byts: float, seconds: float,
             calls: float, what: str) -> float:
    """Percent of the roofline: the larger of ``ops`` at the MXU's peak
    and ``byts`` at the memory's (both of ONE call) over a call's device
    time."""
    t_ops, t_bytes = (ops / ctx.peaks.bf16_flops,
                      byts / ctx.peaks.hbm_bytes_per_s)
    ctx.say(f"{name}: {calls:.0f} calls, {1e6 * seconds / calls:.1f} us "
            f"each; a call needs {what}: {1e6 * t_ops:.2f} us of "
            f"operations, {1e6 * t_bytes:.2f} us of bytes "
            f"({'operations' if t_ops > t_bytes else 'bytes'} bound)")
    return 100.0 * max(t_ops, t_bytes) / (seconds / calls)


def capture_live_rows(ctx) -> Optional[float]:
    """Live rows a decode step of the CAPTURE, from the program's own
    ``tfd.serve.token_fetch`` spans (each carries the ``live`` rows of the
    step it fetched). A capture is two seconds of one schedule and holds
    fewer or more live rows than the run's average (PERF.md section 6, PR
    34): a kernel's time in the capture is held against the rows the
    capture's steps had, not the run's. A test sets
    ``ctx.capture_live_rows``."""
    given = getattr(ctx, "capture_live_rows", None)
    if given is not None:
        return given
    try:
        path = T.find_xplane(os.path.join(
            ROOT, ".cache", "perfbench", ctx.cell.name, "trace"))
    except FileNotFoundError:
        return None
    from jax.profiler import ProfileData
    live = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == "tfd.serve.token_fetch":
                    live += [float(v) for k, v in e.stats if k == "live"]
    ctx.capture_live_rows = sum(live) / len(live) if live else None
    if live:
        ctx.say(f"capture: {len(live)} decode steps fetched, "
                f"{ctx.capture_live_rows:.2f} live rows a step")
    return ctx.capture_live_rows


def counts(ctx) -> Optional[Dict]:
    """What a decode step of the CAPTURE needed, from the program's
    counters: ``live`` rows a step (the capture's own), and of each the
    run's average a live row: ``kept`` positions a key-value group,
    ``available`` positions (its depth). None where the run's
    ``serve_summary`` lacks this family's counters."""
    s = D.summary_of(ctx.records)
    if not s or not s.get("state_rows_stepped") \
            or not s.get("decode_live_rows"):
        return None
    live = capture_live_rows(ctx)
    if live is None:
        return None
    rows = s["decode_live_rows"]
    return {"live": live, "summary": s,
            "kept": live * s.get("select_keys_kept", 0) / rows,
            "available": live * s.get("select_keys_available", 0) / rows}
