"""Layer: kernels. Device ms of the per-channel state-space layers' prefill
scan (``%s6_chunk_scan``, every layer's) per 1,000 bucket positions of the
prefills in the trace."""

from harness import s6_parts as S6


def read(ctx):
    seconds, found = S6.prefill_scans(ctx.trace)
    tokens = sum(bucket for bucket, _ in found)
    return 1e6 * seconds / tokens if tokens else None
