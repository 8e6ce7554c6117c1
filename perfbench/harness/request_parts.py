"""Where a served request's milliseconds went, from the program's own
``serve_request`` records (PR 39).

Since PR 39 a record splits the wait for a first token (``wait_ms``, from
the time the request was due to the start of its own first admission) and
the time between its first token and its last (``decode_ms``) by the KIND
of scheduler iteration that was running: ``admit`` (another request's
prompt held the chip and every live row), ``step`` (decode iterations) and
``other`` (poll, tail, a sleeping engine). ``wait_ms`` plus the record's
``prefill_ms`` is its ``ttft_ms``; ``decode_ms`` is its ``tok_ms`` times
its token gaps. The program reads them off the span seam's running
self-time totals (``observe/trace.py::HostSpans.elapsed_by``) on the
host's clock: a token is the client's when ``tfd.serve.retire`` hands it
over.

The readers here take two sets of requests, both served BEFORE the
profiler capture started (its start and stop stall the scheduler, as
``serve.queue_steps_p95`` says):

- the MIDDLE FIFTH by ``ttft_ms``, nearest-rank p40 to p60: the band the
  judged median (``serve_ttft_p50_ms``) sits in;
- the SLOWEST TENTH by ``tok_ms``, at or above nearest-rank p90: the
  judged p95 (``serve_tpot_p95_ms``) sits in the middle of it.

A program without the fields (the parent of PR 39) gives nothing to read.
"""

from __future__ import annotations

import math
from statistics import fmean
from typing import Any, Dict, List, Optional

from .decode_parts import summary_of

KINDS = ("admit", "step", "other")


def _gaps(r: Dict[str, Any]) -> int:
    return max(1, int(r["new_tokens"]) - 1)


def split_requests(ctx, decoded: bool = False) -> List[Dict[str, Any]]:
    """The ``serve_request`` records that carry the split and got their
    first token before the capture; ``decoded``: more than one token,
    the last of them before the capture too."""
    cut = getattr(ctx, "cut_s", math.inf)
    out = []
    for r in ctx.records:
        if (r.get("event") != "serve_request"
                or not isinstance(r.get("wait_ms"), dict)
                or not isinstance(r.get("decode_ms"), dict)
                or r.get("ttft_ms") is None
                or r.get("t_first_s", 0.0) > cut):
            continue
        if decoded and (int(r.get("new_tokens") or 0) < 2
                        or r["t_first_s"] + 1e-3 * r["tok_ms"] * _gaps(r)
                        > cut):
            continue
        out.append(r)
    return out


def ttft_mid(ctx, metric: str) -> Optional[Dict[str, float]]:
    """Means over the middle fifth by ``ttft_ms``: ``admit``, ``step``,
    ``other`` (the three of ``wait_ms``), ``prefill`` and ``ttft``."""
    reqs = sorted(split_requests(ctx), key=lambda r: r["ttft_ms"])
    reqs = [r for r in reqs if r.get("prefill_ms") is not None]
    if not reqs:
        return None
    lo = max(1, math.ceil(0.4 * len(reqs)))
    hi = max(lo, math.ceil(0.6 * len(reqs)))
    band = reqs[lo - 1:hi]
    out = {k: fmean(r["wait_ms"][k] for r in band) for k in KINDS}
    out["prefill"] = fmean(r["prefill_ms"] for r in band)
    out["ttft"] = fmean(r["ttft_ms"] for r in band)
    parts = sum(out[k] for k in KINDS) + out["prefill"]
    ctx.say(f"{metric}: the middle fifth by ttft_ms is {len(band)} of "
            f"{len(reqs)} requests served before the capture "
            f"({band[0]['ttft_ms']:.1f}-{band[-1]['ttft_ms']:.1f} ms): "
            f"waiting behind admissions {out['admit']:.3f} + behind decode "
            f"iterations {out['step']:.3f} + other {out['other']:.3f} + own "
            f"prefill {out['prefill']:.3f} = {parts:.3f} ms against a mean "
            f"ttft_ms of {out['ttft']:.3f}")
    return out


def tpot_tail(ctx, metric: str) -> Optional[Dict[str, float]]:
    """Means over the slowest tenth by ``tok_ms`` of a token gap's ms by
    kind (``decode_ms`` over the request's gaps), and ``tok``."""
    reqs = sorted(split_requests(ctx, decoded=True),
                  key=lambda r: r["tok_ms"])
    if not reqs:
        return None
    tail = reqs[max(1, math.ceil(0.9 * len(reqs))) - 1:]
    out = {k: fmean(r["decode_ms"][k] / _gaps(r) for r in tail)
           for k in KINDS}
    out["tok"] = fmean(r["tok_ms"] for r in tail)
    out["endured"] = fmean(r.get("admits_endured") or 0 for r in tail)
    ctx.say(f"{metric}: the slowest tenth by tok_ms is {len(tail)} of "
            f"{len(reqs)} requests decoded before the capture "
            f"({tail[0]['tok_ms']:.2f}-{tail[-1]['tok_ms']:.2f} ms a token, "
            f"{out['endured']:.1f} admissions endured each): a token gap "
            f"spent {out['admit']:.3f} behind admissions + {out['step']:.3f} "
            f"in decode iterations + other {out['other']:.3f} = "
            f"{sum(out[k] for k in KINDS):.3f} ms against a mean tok_ms of "
            f"{out['tok']:.3f}")
    return out


def admit_wall_share(ctx, metric: str) -> Optional[float]:
    """The share of the serving wall spent inside admissions, in percent.
    An untraced run: ``serve_summary.iter_ms.admit`` over the wall the
    three kinds tile (``wall_s`` to the 2% the program's tests hold). A
    TRACED run cannot be read that way: the capture's start and stop
    stall the host for seconds, between two spans (``probes.py`` starts
    and stops it before an engine call: inside ``wall_s``, inside no
    kind), and the requests that queue up meanwhile are then admitted back
    to back and decoded in fewer, fuller steps, so the run's ``iter_ms``
    has every admission and too few steps. There the share is taken of the
    wall BEFORE the capture: the ``prefill_ms`` (the wall of its own
    ``tfd.serve.admit``) of every request whose first token came before it,
    over ``cut_s``. Both are printed."""
    summary = summary_of(ctx.records)
    if not summary or not isinstance(summary.get("iter_ms"), dict):
        return None
    by = summary["iter_ms"]
    covered = sum(by.values())
    if covered <= 0:
        return None
    whole = 100.0 * by.get("admit", 0.0) / covered
    wall_s = float(summary.get("wall_s") or 0.0)
    said = (f"{metric}: of the run's {covered:.0f} ms inside spans (wall_s "
            f"{1e3 * wall_s:.0f}) admissions {by.get('admit', 0.0):.0f} "
            f"({summary.get('admissions')} of them), decode iterations "
            f"{by.get('step', 0.0):.0f}, other {by.get('other', 0.0):.0f}: "
            f"{whole:.2f}% of the run")
    cut = getattr(ctx, "cut_s", math.inf)
    if not 0.0 < cut < wall_s:
        ctx.say(said)
        return whole
    first = [r["prefill_ms"] for r in split_requests(ctx)
             if r.get("prefill_ms") is not None]
    before = 100.0 * sum(first) / (1e3 * cut)
    ctx.say(f"{said}, which a capture distorts; before the capture started "
            f"at {cut:.1f}s {len(first)} admissions took "
            f"{sum(first):.0f} ms: {before:.2f}%")
    return before
