"""The parts of one decode step of a latent-attention, sparse-selection,
routed-expert model, from the device ops inside each execution of the
decode program.

What a capture of a v5e shows of such a step (looked at by hand with
``tools/trace_look.py``, PERF.md section 3): the program's named Pallas
kernels appear under their names (``%dsa_index_scores.N``,
``%mla_latent_attend.N``, the grouped matmul ``%gmm.N``), everything
else as anonymous fusions and sorts (``%fusion.N``, ``%sort.N``). A part
is therefore its named kernels plus what can be tied to them by ORDER:
the ops of a ``full`` layer between its index-score kernel and its
latent attend can only be that layer's own (the next op of another part
needs the attend's result, the previous layer's ops feed the index
scores), so the ``%sort`` ops in that interval are the selection's exact
top-k and not the expert layers' argsorts. The ROW GATHER that feeds each
attend is an anonymous ``%fusion.N`` (0.91 ms a layer, the largest single
op of a step; my chip run, PR 28): nothing in the reduced trace tells it
from a matmul fusion, so it stays in the step's remainder (PERF.md
section 7). A program without these kernels (any other model) gives
nothing to read.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional

from . import trace as T

DECODE_MODULE = "jit_serve_decode_step"
PREFILL_MODULE = re.compile(r"^jit_serve_prefill_b(\d+)")
INDEX = re.compile(r"^%dsa_index_scores")
ATTEND = re.compile(r"^%mla_latent_attend")
EXPERTS = re.compile(r"^%gmm")
# Between a layer's index scores and its attend: the exact top-k (XLA
# lowers lax.top_k to sort ops).
TOPK_OP = re.compile(r"^%sort")


def ops_inside(dev: Dict[str, List[T.Ev]], start: int, dur: int
               ) -> List[T.Ev]:
    ops = dev["ops"]
    i = bisect.bisect_left([o[1] for o in ops], start)
    out = []
    while i < len(ops) and ops[i][1] < start + dur:
        out.append(ops[i])
        i += 1
    return out


def decode_parts(trace) -> Optional[Dict[str, float]]:
    """Mean device ms a decode step spends in ``select`` (index scores
    and top-k), ``attend`` (the latent attend kernels), ``experts`` (the
    held experts' grouped matmuls) and the whole ``step``; None where
    the trace holds no such step."""
    if trace is None:
        return None
    tot = {"select": 0, "attend": 0, "experts": 0, "step": 0}
    kernels = {"index": 0, "attend": 0, "experts": 0}
    steps = 0
    for dev in trace.devices.values():
        for name, s, dur in dev["modules"]:
            if not name.startswith(DECODE_MODULE):
                continue
            ops = ops_inside(dev, s, dur)
            if not any(INDEX.match(o[0]) or ATTEND.match(o[0])
                       for o in ops):
                continue
            steps += 1
            tot["step"] += T.total(T.union(
                [(o[1], min(o[1] + o[2], s + dur)) for o in ops]))
            selecting = False
            for o_name, _, o_dur in ops:
                if INDEX.match(o_name):
                    selecting = True
                    tot["select"] += o_dur
                    kernels["index"] += 1
                elif ATTEND.match(o_name):
                    selecting = False
                    tot["attend"] += o_dur
                    kernels["attend"] += 1
                elif EXPERTS.match(o_name):
                    tot["experts"] += o_dur
                    kernels["experts"] += 1
                elif selecting and TOPK_OP.match(o_name):
                    tot["select"] += o_dur
    if not steps:
        return None
    out = {k + "_ms": v / 1e6 / steps for k, v in tot.items()}
    out["steps"] = steps
    out.update({k + "_kernels_per_step": v / steps
                for k, v in kernels.items()})
    return out


def kernel_time(trace, pattern) -> tuple:
    """(device seconds, calls) of the ops whose name ``pattern``
    matches (the decode step's kernels run in no other program)."""
    return T.op_time(trace, lambda n: bool(pattern.match(n)))


def summary_of(records) -> Optional[Dict]:
    """The run's last ``serve_summary`` record."""
    found = [r for r in records if r.get("event") == "serve_summary"]
    return found[-1] if found else None
