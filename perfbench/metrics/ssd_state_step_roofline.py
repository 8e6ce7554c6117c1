"""Layer: kernels. The state step's share of its roofline
(``%ssd_state_step``): the least time for the states of the LIVE rows a
step of the capture (the program's ``live`` count on its fetch spans),
each read once and written once from HBM (``state_step_cost``), over the
kernel's device time a call. A kernel that moved every slot's state would
read the live share of the slots."""

from harness import hybrid_parts as H
from harness import ssm_parts as S


def read(ctx):
    k, s = S.decode_kernels(ctx.trace), S.counts(ctx)
    if not k or not s or ctx.peaks is None:
        return None
    ops, byts = ctx.model.state_step_cost(ctx.sizes, s["live"])
    return H.roofline(ctx, "ssd_state_step_roofline", ops, byts,
                      k["state_s"], k["state_calls"],
                      f"{s['live']:.2f} live rows' states")
