"""Layer: serve driver. Over the middle fifth of requests by ``ttft_ms``
(nearest-rank p40 to p60), served before the capture started, the mean of
``serve_request.wait_ms.step``: ms of the wait for an admission that went
to decode iterations (the starvation clock's ``--serve.decode-priority``
steps with a slot free, or no slot free). A program without the field (the
parent of PR 39) gives nothing to read."""

from harness import request_parts as R


def read(ctx):
    got = R.ttft_mid(ctx, "serve.ttft_mid_wait_step_ms")
    return None if got is None else got["step"]
