"""Layer: kernels. The per-channel state step's share of its roofline
(``%s6_state_step``): the least time for the states of the LIVE rows a step
of the capture (the program's ``live`` count on its fetch spans), each read
once and written once from HBM (``state_step_cost``), over the kernel's
device time a call, in percent. The numerator is the live rows' bytes, not
what the kernel walks: whatever implements the step the same work is
priced, and a kernel that moved every slot's state would read the live
share of the slots."""

from harness import hybrid_parts as H
from harness import s6_parts as S6


def read(ctx):
    k = S6.decode_kernels(ctx.trace)
    if not k or ctx.peaks is None \
            or not hasattr(ctx.model, "state_step_cost"):
        return None
    live = S6.live_rows(ctx)
    if live is None:
        return None
    ops, byts = ctx.model.state_step_cost(ctx.sizes, live)
    return H.roofline(ctx, "s6_state_step_roofline", ops, byts,
                      k["state_s"], k["state_calls"],
                      f"{live:.2f} live rows' states")
