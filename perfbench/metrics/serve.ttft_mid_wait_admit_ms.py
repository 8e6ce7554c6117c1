"""Layer: serve driver. Over the middle fifth of requests by ``ttft_ms``
(nearest-rank p40 to p60: the band the judged median sits in), served
before the capture started, the mean of the program's own
``serve_request.wait_ms.admit``: ms between the time a request was due and
the start of its own admission that went to OTHER requests' admissions (a
prefill holds the chip, every live row and every waiting request). A
program without the field (the parent of PR 39) gives nothing to read."""

from harness import request_parts as R


def read(ctx):
    got = R.ttft_mid(ctx, "serve.ttft_mid_wait_admit_ms")
    return None if got is None else got["admit"]
