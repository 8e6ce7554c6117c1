"""Layer: serve driver. Device idle time inside the scheduler's own
phases (``tfd.serve.retire``, ``.tail``, ``.poll``: everything between
the engine's return and the next admission or dispatch), per decode step
in the capture."""

from harness import program_spans as P


def read(ctx):
    return P.idle_ms_per(ctx, P.SCHED, "steps")
