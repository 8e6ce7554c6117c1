"""Layer: step program. Device time of one execution of the module the
program names ``train_step``: the union of the op intervals inside each
module event of the trace, mean over calls and devices."""

from harness import trace as T


def read(ctx):
    if ctx.trace is None:
        return None
    calls = T.module_calls(ctx.trace, lambda n: n.startswith("jit_train_step"))
    return 1e3 * sum(calls) / len(calls) if calls else None
