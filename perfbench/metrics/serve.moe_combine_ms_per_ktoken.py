"""Layer: kernels. Device ms of the held experts' combine
(``%moe_combine_held``, every expert layer's and every block trip's) per
1,000 bucket tokens of the prefills in the trace that ran it. Nothing
where the combine is anonymous gathers (a program before the kernel) or
the one-hot matmuls (the shortest bucket, the decode step)."""

import re

from harness import decode_parts as D

COMBINE = re.compile(r"^%moe_combine_held")


def read(ctx):
    if ctx.trace is None:
        return None
    seconds, tokens = 0.0, 0
    for dev in ctx.trace.devices.values():
        for name, start, dur in dev["modules"]:
            prefill = D.PREFILL_MODULE.match(name)
            if not prefill:
                continue
            mine = [op[2] for op in D.ops_inside(dev, start, dur)
                    if COMBINE.match(op[0])]
            if mine:
                seconds += sum(mine) / 1e9
                tokens += int(prefill.group(1))
    return 1e6 * seconds / tokens if tokens else None
