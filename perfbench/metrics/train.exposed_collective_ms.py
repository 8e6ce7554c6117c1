"""Layer: mesh. Per step, collective op time during which no other op
runs on that device (mean over devices). Nothing to read on one chip."""

from harness import trace as T


def read(ctx):
    if ctx.trace is None or ctx.chips < 2:
        return None
    exposed = T.exposed_collective_s(ctx.trace)
    steps = len(T.module_calls(
        ctx.trace, lambda n: n.startswith("jit_train_step")))
    if exposed is None or not steps:
        return None
    return 1e3 * exposed / (steps / len(ctx.trace.devices))
