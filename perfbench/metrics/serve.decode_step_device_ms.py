"""Layer: serve engine. Device time of one execution of the decode
program (module ``serve_decode_step``), mean over the calls in the trace."""

from harness import trace as T


def read(ctx):
    if ctx.trace is None:
        return None
    calls = T.module_calls(
        ctx.trace, lambda n: n.startswith("jit_serve_decode_step"))
    return 1e3 * sum(calls) / len(calls) if calls else None
