"""The held experts' grouped matmuls of a decode step of a model whose
expert layers stand alone (no attention kernel beside them to find the step
by), from the device ops inside each execution of the decode program, and
what such a step needed, from the program's counters.

The grouped matmul appears in a capture under megablox's name (``%gmm.N``).
A program without it in its decode step (the parent of the PR that added
this, a model without routed experts) gives nothing to read.
"""

from __future__ import annotations

from typing import Dict, Optional

from . import decode_parts as D
from . import hybrid_parts as H
from . import ssm_parts as S


def decode_gmm(trace) -> Optional[Dict[str, float]]:
    """Over the decode steps of the trace that ran a grouped matmul:
    ``steps``, and the kernel's device seconds and calls (``gmm_s``,
    ``gmm_calls``)."""
    out = {"steps": 0.0, "gmm_s": 0.0, "gmm_calls": 0.0}
    for _, mine in S._kernel_calls(
            trace, lambda n: n.startswith(D.DECODE_MODULE), D.EXPERTS):
        out["steps"] += 1
        out["gmm_calls"] += len(mine)
        out["gmm_s"] += sum(o[2] for o in mine) / 1e9
    return out if out["steps"] else None


def step_counts(ctx) -> Optional[Dict[str, float]]:
    """What a decode step of the CAPTURE gave its held experts, from the
    program's counters (all expert layers together): ``live`` rows a step
    (the capture's own, from its fetch spans), ``held_pairs`` (the run's
    pairs a live row, times those rows) and ``experts_hit``. The run
    counts the experts a step reached; a step of ``r`` rows reaches an
    expert unless none of its ``r k`` picks is that expert, so the run's
    count a step is brought from the run's mean rows to the capture's by
    ``1 - (1 - k / R)^r`` (``k`` picked of ``R`` published). None where the
    run's ``serve_summary`` lacks the counters."""
    s = D.summary_of(ctx.records)
    if not s or not s.get("decode_live_rows") or not s.get("decode_steps") \
            or s.get("moe_held_pairs") is None \
            or s.get("moe_experts_hit") is None \
            or not s.get("moe_pairs_routed") or not s.get("moe_layers"):
        return None
    live = H.capture_live_rows(ctx)
    if live is None:
        return None
    mean_rows = s["decode_live_rows"] / s["decode_steps"]
    k = s["moe_pairs_routed"] / (s["decode_live_rows"] * s["moe_layers"])
    miss = 1.0 - k / ctx.sizes["router_experts"]

    def reached(rows):                   # share of the experts, by chance
        return 1.0 - miss ** rows

    hit = s["moe_experts_hit"] / s["decode_steps"]
    return {"live": live,
            "held_pairs": live * s["moe_held_pairs"] / s["decode_live_rows"],
            "experts_hit": hit * reached(live) / reached(mean_rows)}
