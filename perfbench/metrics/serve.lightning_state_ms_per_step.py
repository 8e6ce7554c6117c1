"""Layer: kernels. Device ms a decode step spends in the linear layers'
state step (``%lightning_state_step``: every live row's recurrent state
read, decayed, added to and written back in place, and read out), all
layers of the step."""

from harness import hybrid_parts as H


def read(ctx):
    k = H.decode_kernels(ctx.trace)
    return 1e3 * k["state_s"] / k["steps"] if k and k["state_calls"] \
        else None
