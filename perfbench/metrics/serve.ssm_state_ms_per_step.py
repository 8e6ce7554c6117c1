"""Layer: kernels. Device ms a decode step spends in the state-space
layers' state step (``%ssd_state_step``: every live row's state read,
decayed, added to and written back in place, and read out), all layers of
the step."""

from harness import ssm_parts as S


def read(ctx):
    k = S.decode_kernels(ctx.trace)
    return 1e3 * k["state_s"] / k["steps"] if k else None
