"""Layer: serve engine. Device time of one execution of a bucketed
prefill program (modules ``serve_prefill_b*``), mean over the calls in
the trace, whatever their bucket."""

from harness import trace as T


def read(ctx):
    if ctx.trace is None:
        return None
    calls = T.module_calls(
        ctx.trace, lambda n: n.startswith("jit_serve_prefill_b"))
    return 1e3 * sum(calls) / len(calls) if calls else None
