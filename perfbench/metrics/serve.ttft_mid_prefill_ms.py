"""Layer: serve driver. Over the middle fifth of requests by ``ttft_ms``
(nearest-rank p40 to p60), served before the capture started, the mean of
``serve_request.prefill_ms``: the wall of the request's OWN first admission
(its ``tfd.serve.admit`` span), the part of the median first-token time
that a faster prefill shortens directly; the two ``serve.ttft_mid_wait_*``
metrics are the rest. Read only from records that carry the split (a
program without ``wait_ms``, the parent of PR 39, gives nothing to read, so
the three stand or fall together)."""

from harness import request_parts as R


def read(ctx):
    got = R.ttft_mid(ctx, "serve.ttft_mid_prefill_ms")
    return None if got is None else got["prefill"]
