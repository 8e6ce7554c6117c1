"""Layer: serve driver. Over the slowest tenth of requests by ``tok_ms`` (at or
above nearest-rank p90), first to last token before the capture started, the
mean of ``serve_request.decode_ms.step`` over the request's token gaps: ms a
token gap spent in decode iterations (the step on the device with the
host's iteration around it). A program without the field (the parent of PR
39) gives nothing to read."""

from harness import request_parts as R


def read(ctx):
    got = R.tpot_tail(ctx, "serve.tpot_tail_step_ms")
    return None if got is None else got["step"]
