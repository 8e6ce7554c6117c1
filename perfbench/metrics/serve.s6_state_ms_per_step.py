"""Layer: kernels. Device ms a decode step spends in the per-channel
state-space layers' state step (``%s6_state_step``: every live row's state
``[16, channels]`` read, decayed a number at a time, added to and written
back in place, and read out), all layers of the step."""

from harness import s6_parts as S6


def read(ctx):
    k = S6.decode_kernels(ctx.trace)
    return 1e3 * k["state_s"] / k["steps"] if k else None
