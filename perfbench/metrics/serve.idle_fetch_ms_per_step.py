"""Layer: serve engine. Device idle time inside the program's
``tfd.serve.token_fetch`` spans (the blocking fetch of a decode step's
tokens), per decode step in the capture: what is left of the launch
latency when the asynchronous dispatch has returned, and how long after
the device finished the host had the tokens."""

from harness import program_spans as P


def read(ctx):
    return P.idle_ms_per(ctx, P.FETCH, "steps")
