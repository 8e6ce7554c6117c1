"""Layer: kernels. The chunked scan's share of its roofline
(``%ssd_chunk_scan``): the larger of the chunked form's operations at the
MXU's peak and its bytes at the memory's, at each traced prefill's bucket
length (``chunk_scan_cost``; the carried state stays in VMEM and is not
priced as traffic), over the kernel's device time."""

from harness import hybrid_parts as H
from harness import ssm_parts as S


def read(ctx):
    seconds, found = S.prefill_scans(ctx.trace)
    if not found or ctx.peaks is None \
            or not hasattr(ctx.model, "chunk_scan_cost"):
        return None
    ops = byts = calls = 0.0
    for bucket, n in found:
        o, b = ctx.model.chunk_scan_cost(ctx.sizes, bucket,
                                         ctx.model.SCAN_CHUNK)
        ops, byts, calls = ops + n * o, byts + n * b, calls + n
    return H.roofline(ctx, "ssd_chunk_scan_roofline", ops / calls,
                      byts / calls, seconds, calls,
                      f"a bucket of {sum(b for b, _ in found) / len(found):.0f}"
                      f" positions in chunks of {ctx.model.SCAN_CHUNK}")
