"""Layer: kernels. The full-attention layers' decode attend's share of its
roofline (``%gqa_dense_attend``): the least time the chip could take for the
positions the LIVE rows needed a call (the program's own count,
``serve_summary.full_attend_keys`` a live row, times the live rows the
CAPTURE's steps had, a full layer, through the model's
``gqa_attend_cost``), the larger of operations at the MXU's peak and bytes
at the memory's, over the kernel's device time a call, in percent. The
numerator is the live rows' positions to their depth, not the blocks the
kernel walks: whatever implements the attend the same work is priced, and
blocks past a row's depth or in free slots lower the share, they do not
raise it. A program without the kernel or the counter gives nothing to
read."""

import re

from harness import decode_parts as D
from harness import hybrid_parts as H

KERNEL = re.compile(r"^%gqa_dense_attend")


def read(ctx):
    if ctx.trace is None or ctx.peaks is None \
            or not hasattr(ctx.model, "gqa_attend_cost"):
        return None
    t, n = D.kernel_time(ctx.trace, KERNEL)
    s = D.summary_of(ctx.records)
    if not n or not s or not s.get("full_attend_keys") \
            or not s.get("decode_live_rows"):
        return None
    live = H.capture_live_rows(ctx)
    if live is None:
        return None
    full = ctx.model.layer_counts(ctx.sizes)[0]
    positions = live * s["full_attend_keys"] / s["decode_live_rows"] / full
    ops, byts = ctx.model.gqa_attend_cost(ctx.sizes, positions)
    return H.roofline(
        ctx, "gqa_dense_attend_roofline", ops, byts, t, n,
        f"{positions:.0f} live positions ({live:.2f} live rows, {full} "
        f"call(s) a step)")
