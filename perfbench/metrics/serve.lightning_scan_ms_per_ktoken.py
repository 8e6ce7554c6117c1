"""Layer: kernels. Device ms of the linear layers' chunked scan
(``%lightning_chunk_scan``, every layer's) per 1,000 bucket tokens of the
prefills in the trace."""

from harness import hybrid_parts as H


def read(ctx):
    seconds, found = H.prefill_scans(ctx.trace)
    tokens = sum(bucket for bucket, _ in found)
    return 1e6 * seconds / tokens if tokens else None
