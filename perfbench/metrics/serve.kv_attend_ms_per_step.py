"""Layer: kernels. Device ms a decode step spends in the dense slot
engine's decode attends (``%kv_decode_attend``: one step's queries a live
row over that row's K and V leaves in place, blocks up to the row's depth,
one call a layer), over the executions of the decode program in the trace
that ran it. A program without the kernel (the parent of the PR that added
it, whose attends are anonymous XLA reductions over the whole leaves; any
other model) gives nothing to read."""

import re

from harness import decode_parts as D
from harness import ssm_parts as S

KERNEL = re.compile(r"^%kv_decode_attend")


def read(ctx):
    steps, ns = 0, 0
    for _, mine in S._kernel_calls(
            ctx.trace, lambda n: n.startswith(D.DECODE_MODULE), KERNEL):
        steps += 1
        ns += sum(o[2] for o in mine)
    return ns / 1e6 / steps if steps else None
