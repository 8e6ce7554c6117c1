"""Layer: serve driver. Over the slowest tenth of requests by ``tok_ms`` (at
or above nearest-rank p90: the judged p95 sits in the middle of it), first
to last token before the capture started, the mean of
``serve_request.decode_ms.admit`` over the request's token gaps: ms a token
gap spent behind admissions (other requests' prompts; a re-prefill of its
own continuation counts too). A program without the field (the parent of
PR 39) gives nothing to read."""

from harness import request_parts as R


def read(ctx):
    got = R.tpot_tail(ctx, "serve.tpot_tail_admit_ms")
    return None if got is None else got["admit"]
