"""Layer: kernels. Device ms of the fused prefill attend
(``%mla_prefill_attend``, every layer's call) per 1,000 bucket tokens of
the prefills in the trace that ran it. A program whose prefill attends by
an XLA loop (an anonymous ``%while.N``: the parent of the PR that added
the kernel, or a model the kernel does not serve) gives nothing to
read."""

import re

from harness import decode_parts as D

PREFILL_ATTEND = re.compile(r"^%mla_prefill_attend")


def read(ctx):
    if ctx.trace is None:
        return None
    ns, tokens = 0, 0
    for dev in ctx.trace.devices.values():
        for name, s, dur in dev["modules"]:
            m = D.PREFILL_MODULE.match(name)
            if not m:
                continue
            mine = [o for o in D.ops_inside(dev, s, dur)
                    if PREFILL_ATTEND.match(o[0])]
            if mine:
                ns += sum(o[2] for o in mine)
                tokens += int(m.group(1))
    return (ns / 1e6) / (tokens / 1e3) if tokens else None
