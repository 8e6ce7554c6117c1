"""Layer: serve driver. The share of the serving wall in which no live row
could advance because an admission ran, in percent, from the program's own
records: in a traced run the walls of the ``tfd.serve.admit`` spans
(``serve_request.prefill_ms``) of the requests served before the capture
started over the wall up to it; in an untraced one
``serve_summary.iter_ms.admit`` over the wall the three kinds of iteration
tile (``harness/request_parts.py::admit_wall_share`` says why a traced run's
own ``iter_ms`` cannot be used). A program without ``iter_ms`` (the parent
of PR 39) gives nothing to read."""

from harness import request_parts as R


def read(ctx):
    return R.admit_wall_share(ctx, "serve.admit_wall_share")
