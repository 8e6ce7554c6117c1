"""Layer: kernels. Device ms of the state-space layers' chunked scan
(``%ssd_chunk_scan``, every layer's) per 1,000 bucket tokens of the
prefills in the trace."""

from harness import ssm_parts as S


def read(ctx):
    seconds, found = S.prefill_scans(ctx.trace)
    tokens = sum(bucket for bucket, _ in found)
    return 1e6 * seconds / tokens if tokens else None
