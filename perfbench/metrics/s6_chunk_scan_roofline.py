"""Layer: kernels. The per-channel scan's share of its roofline
(``%s6_chunk_scan``): the larger of the recurrence's operations at the
chip's one published compute peak and its operands' bytes at the memory's,
at each traced prefill's bucket length (``s6_scan_cost``: ``x``, ``dt``,
``z``, ``B`` and ``C`` in, ``y`` out; the carried state stays in VMEM and is
not priced), over the kernel's device time, in percent. The recurrence runs
on the vector and transcendental units, for which the table of peaks has no
entry: held against the MXU's peak a kernel bound there reads low, and
that is what the number says."""

from harness import hybrid_parts as H
from harness import s6_parts as S6


def read(ctx):
    seconds, found = S6.prefill_scans(ctx.trace)
    if not found or ctx.peaks is None \
            or not hasattr(ctx.model, "s6_scan_cost"):
        return None
    ops = byts = calls = 0.0
    for bucket, n in found:
        o, b = ctx.model.s6_scan_cost(ctx.sizes, bucket)
        ops, byts, calls = ops + n * o, byts + n * b, calls + n
    return H.roofline(ctx, "s6_chunk_scan_roofline", ops / calls,
                      byts / calls, seconds, calls,
                      f"a bucket of {sum(b for b, _ in found) / len(found):.0f}"
                      f" positions")
