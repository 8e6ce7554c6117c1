"""Layer: kernels. Device ms a decode step spends in the full-attention
layers' decode attend (``%gqa_dense_attend``: one step's queries a live row
over that row's K and V in place, blocks up to the row's depth), over the
executions of the decode program in the trace that ran it. The window
layers' attends over their rings are anonymous XLA fusions and stay in the
step's remainder. A program without the kernel (the parent of the PR that
added it, any other model) gives nothing to read."""

import re

from harness import decode_parts as D
from harness import ssm_parts as S

KERNEL = re.compile(r"^%gqa_dense_attend")


def read(ctx):
    steps, ns = 0, 0
    for _, mine in S._kernel_calls(
            ctx.trace, lambda n: n.startswith(D.DECODE_MODULE), KERNEL):
        steps += 1
        ns += sum(o[2] for o in mine)
    return ns / 1e6 / steps if steps else None
