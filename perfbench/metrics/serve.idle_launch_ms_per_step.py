"""Layer: serve engine. Device idle time inside ``tfd.serve.step_upload``
and ``tfd.serve.step_dispatch`` (the upload of the slot scalars and the
launch of the decode program), per decode step in the capture."""

from harness import program_spans as P


def read(ctx):
    return P.idle_ms_per(ctx, P.LAUNCH, "steps")
