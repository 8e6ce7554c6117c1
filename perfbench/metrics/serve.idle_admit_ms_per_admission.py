"""Layer: serve driver. Device idle time inside ``tfd.serve.admit`` and
its children (``.prefill_launch``, ``.first_token_fetch``), per prefill
program execution in the capture: what an admission leaves the chip
waiting for besides the prefill and the row insert themselves."""

from harness import program_spans as P


def read(ctx):
    return P.idle_ms_per(ctx, P.ADMIT, "admissions")
